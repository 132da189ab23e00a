"""Qwen3-Next's decoder (Qwen, 2025-09, ``model_type: qwen3_next``) in the
netconfig DSL: a pre-norm residual block whose mixer is a gated
delta-rule linear-attention layer (``gated_delta``) on three layers of
four and gated grouped-query attention with QK norm and RoPE on part of
a head (``gqa_attention`` with ``rope_dim``) on every
``full_attention_interval``-th, and whose other half is an expert layer
routed by a softmax over all experts with one gated shared expert (``moe``
with ``score_func = softmax`` and ``shared_gate = 1``); a final RMSNorm
and an untied head.

``qwen3_next_lm`` writes the netconfig for any sizes (the tests' tiny
twin); ``qwen3_next`` fills in the published widths of
Qwen3-Next-80B-A3B-Instruct
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json).
The multi-token-prediction module is not built.

A chip's share of a deployment is asked for by arguments, never by a
width: ``num_layers`` (depth), ``experts_held`` / ``expert_first`` (the
experts that live here; the router keeps all ``num_experts``) and
``vocab`` (the rows of the vocabulary slice held here).
"""

from typing import List


def qwen3_next_lm(*, vocab: int, hidden: int, num_layers: int,
                  full_attention_interval: int, nhead: int, nkvhead: int,
                  head_dim: int, rope_dim: int, rope_theta: float,
                  linear_nkhead: int, linear_nvhead: int,
                  linear_key_dim: int, linear_value_dim: int,
                  linear_conv_kernel: int, linear_chunk: int,
                  rms_norm_eps: float, expert_width: int, num_experts: int,
                  experts_per_tok: int, shared_width: int, experts_held: int,
                  expert_first: int, seq_len: int, batch_size: int,
                  q_block: int, expert_block: int, loss_chunk: int,
                  init_sigma: float, lr: float, remat: str = "block") -> str:
    """The netconfig text (with the global keys a training conf needs)
    of a decoder-only language model of Qwen3-Next's block. Layer ``i``
    is a full-attention layer iff ``(i + 1) % full_attention_interval ==
    0``; the others are linear-attention layers. ``shared_width`` must
    be a whole number of expert widths (the published one is one)."""
    if shared_width % expert_width:
        raise ValueError("qwen3_next_lm: shared_width %d is not a multiple "
                         "of expert_width %d" % (shared_width, expert_width))
    out: List[str] = ["netconfig=start",
                      "layer[0->1] = embed:embed",
                      "  nvocab = %d" % vocab,
                      "  nhidden = %d" % hidden]
    node = 1

    def new() -> int:
        nonlocal node
        node += 1
        return node

    def norm(src: int, dst: int, key: str) -> List[str]:
        return ["layer[%d->%d] = rmsnorm:%s" % (src, dst, key),
                "  eps = %g" % rms_norm_eps]

    for i in range(num_layers):
        x = node
        a, b, h = new(), new(), new()
        out += norm(x, a, "l%d_attn_norm" % i)
        if (i + 1) % full_attention_interval:
            out += ["layer[%d->%d] = gated_delta:l%d_delta" % (a, b, i),
                    "  nkhead = %d" % linear_nkhead,
                    "  nvhead = %d" % linear_nvhead,
                    "  key_dim = %d" % linear_key_dim,
                    "  value_dim = %d" % linear_value_dim,
                    "  conv_kernel = %d" % linear_conv_kernel,
                    "  chunk = %d" % linear_chunk,
                    "  eps = %g" % rms_norm_eps]
        else:
            out += ["layer[%d->%d] = gqa_attention:l%d_attn" % (a, b, i),
                    "  nhead = %d" % nhead,
                    "  nkvhead = %d" % nkvhead,
                    "  head_dim = %d" % head_dim,
                    "  window = 0",
                    "  rope = 1",
                    "  rope_dim = %d" % rope_dim,
                    "  rope_theta = %g" % rope_theta,
                    "  eps = %g" % rms_norm_eps,
                    "  q_block = %d" % q_block]
        out += ["layer[%d,%d->%d] = add:l%d_attn_add" % (x, b, h, i)]
        c, d, y = new(), new(), new()
        out += norm(h, c, "l%d_ffn_norm" % i)
        out += ["layer[%d->%d] = moe:l%d_moe" % (c, d, i),
                "  nexpert = %d" % num_experts,
                "  topk = %d" % experts_per_tok,
                "  nhidden = %d" % expert_width,
                "  nshared = %d" % (shared_width // expert_width),
                "  score_func = softmax",
                "  shared_gate = 1",
                "  routed_scaling_factor = 1",
                "  norm_topk_prob = 1",
                "  expert_first = %d" % expert_first,
                "  expert_count = %d" % experts_held,
                "  expert_block = %d" % expert_block,
                "  bias_sigma = 0",
                "layer[%d,%d->%d] = add:l%d_ffn_add" % (h, d, y, i)]
    last = node
    n, o = new(), new()
    out += norm(last, n, "final_norm")
    out += ["layer[%d->%d] = fullc:head" % (n, o),
            "  nhidden = %d" % vocab,
            "  no_bias = 1",
            "layer[%d->%d] = softmax" % (o, o),
            "  loss_chunk = %d" % loss_chunk,
            "netconfig=end",
            "input_shape = 1,1,%d" % seq_len,
            "label_vec[0,%d) = label" % seq_len,
            "batch_size = %d" % batch_size,
            "random_type = gaussian",
            "init_sigma = %g" % init_sigma,
            "updater = adam",
            "eta = %g" % lr,
            "beta1 = 0.1",
            "beta2 = 0.05",
            "wd = 0.0",
            "remat = %s" % remat,
            "eval_train = 0"]
    return "\n".join(out) + "\n"


def qwen3_next(num_layers: int = 48, vocab: int = 151936,
               experts_held: int = 512, expert_first: int = 0,
               seq_len: int = 8192, batch_size: int = 2, q_block: int = 1024,
               expert_block: int = 512, loss_chunk: int = 1024,
               lr: float = 1e-4) -> str:
    """Qwen3-Next-80B-A3B-Instruct's decoder at its published widths. The
    defaults are the uncut model; a chip's share passes fewer layers, the
    experts it holds and its vocabulary slice. Not in the published config
    and set here: ``init_sigma`` 0.02; the delta rule in chunks of 64
    positions (the published kernels' chunk); Adam's ``lr`` 1e-4 with
    betas 0.9 / 0.95 (this updater's ``beta1`` / ``beta2`` keys are 1 -
    beta) and no weight decay, ``remat = block``, the block sizes. The
    rotated part of a head is ``partial_rotary_factor`` 0.25 x
    ``head_dim`` 256 = 64 features."""
    return qwen3_next_lm(
        vocab=vocab, hidden=2048, num_layers=num_layers,
        full_attention_interval=4, nhead=16, nkvhead=2, head_dim=256,
        rope_dim=64, rope_theta=1e7, linear_nkhead=16, linear_nvhead=32,
        linear_key_dim=128, linear_value_dim=128, linear_conv_kernel=4,
        linear_chunk=64, rms_norm_eps=1e-6, expert_width=512,
        num_experts=512, experts_per_tok=10, shared_width=512,
        experts_held=experts_held, expert_first=expert_first,
        seq_len=seq_len, batch_size=batch_size, q_block=q_block,
        expert_block=expert_block, loss_chunk=loss_chunk, init_sigma=0.02,
        lr=lr)


def qwen3_next_tiny(seq_len: int = 16, batch_size: int = 2,
                    experts_held: int = 8, expert_first: int = 0,
                    vocab: int = 64, num_layers: int = 4) -> str:
    """The same block at toy widths, for the CPU tests only: 2 key heads
    serving 4 value heads in chunks of 4 positions on three layers of
    four, 4 query heads on 2 key/value heads with 4 of a head's 8
    features rotated on the fourth."""
    return qwen3_next_lm(
        vocab=vocab, hidden=32, num_layers=num_layers,
        full_attention_interval=4, nhead=4, nkvhead=2, head_dim=8,
        rope_dim=4, rope_theta=1e7, linear_nkhead=2, linear_nvhead=4,
        linear_key_dim=8, linear_value_dim=6, linear_conv_kernel=4,
        linear_chunk=4, rms_norm_eps=1e-6, expert_width=24, num_experts=8,
        experts_per_tok=3, shared_width=24, experts_held=experts_held,
        expert_first=expert_first, seq_len=seq_len, batch_size=batch_size,
        q_block=8, expert_block=4, loss_chunk=8, init_sigma=0.3, lr=0.01)
