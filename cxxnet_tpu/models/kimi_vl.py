"""The language model of Kimi-VL-A3B-Instruct (moonshotai, 2025-04) in the
netconfig DSL: DeepSeek-V3's decoder block (pre-norm residual, multi-head
latent attention, one dense SwiGLU layer, then sigmoid-routed expert
layers with shared experts), a final RMSNorm and an untied head.

``decoder_lm`` writes the netconfig for any sizes (the tests' tiny twin);
``kimi_vl_a3b`` fills in the published widths
(https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json).
The vision tower and projector are not built: text goes in as ids.

A chip's share of a deployment is asked for by arguments, never by a
width: ``num_layers`` (depth), ``experts_held`` / ``expert_first`` (the
experts that live here; the router keeps all ``n_routed_experts``) and
``vocab`` (the rows of the vocabulary slice held here).
"""

from typing import List


def decoder_lm(*, vocab: int, hidden: int, num_layers: int, first_k_dense: int,
               nhead: int, qk_nope_head_dim: int, qk_rope_head_dim: int,
               v_head_dim: int, kv_lora_rank: int, rope_theta: float,
               rms_norm_eps: float, dense_width: int, expert_width: int,
               n_routed_experts: int, experts_per_tok: int,
               n_shared_experts: int, routed_scaling_factor: float,
               experts_held: int, expert_first: int, seq_len: int,
               batch_size: int, q_block: int, expert_block: int,
               loss_chunk: int, bias_sigma: float, init_sigma: float, lr: float,
               remat: str = "block") -> str:
    """The netconfig text (with the global keys a training conf needs)
    of a decoder-only language model of DeepSeek-V3's block."""
    out: List[str] = ["netconfig=start",
                      "layer[0->1] = embed:embed",
                      "  nvocab = %d" % vocab,
                      "  nhidden = %d" % hidden]
    node = 1

    def new() -> int:
        nonlocal node
        node += 1
        return node

    for i in range(num_layers):
        x = node
        a, b, h = new(), new(), new()
        out += ["layer[%d->%d] = rmsnorm:l%d_attn_norm" % (x, a, i),
                "  eps = %g" % rms_norm_eps,
                "layer[%d->%d] = mla_attention:l%d_attn" % (a, b, i),
                "  nhead = %d" % nhead,
                "  qk_nope_head_dim = %d" % qk_nope_head_dim,
                "  qk_rope_head_dim = %d" % qk_rope_head_dim,
                "  v_head_dim = %d" % v_head_dim,
                "  kv_lora_rank = %d" % kv_lora_rank,
                "  rope_theta = %g" % rope_theta,
                "  eps = %g" % rms_norm_eps,
                "  q_block = %d" % q_block,
                "layer[%d,%d->%d] = add:l%d_attn_add" % (x, b, h, i)]
        c, d, y = new(), new(), new()
        out += ["layer[%d->%d] = rmsnorm:l%d_ffn_norm" % (h, c, i),
                "  eps = %g" % rms_norm_eps]
        if i < first_k_dense:
            out += ["layer[%d->%d] = swiglu:l%d_mlp" % (c, d, i),
                    "  nhidden = %d" % dense_width]
        else:
            out += ["layer[%d->%d] = moe:l%d_moe" % (c, d, i),
                    "  nexpert = %d" % n_routed_experts,
                    "  topk = %d" % experts_per_tok,
                    "  nhidden = %d" % expert_width,
                    "  nshared = %d" % n_shared_experts,
                    "  routed_scaling_factor = %g" % routed_scaling_factor,
                    "  norm_topk_prob = 1",
                    "  expert_first = %d" % expert_first,
                    "  expert_count = %d" % experts_held,
                    "  expert_block = %d" % expert_block,
                    "  bias_seed = %d" % i,
                    "  bias_sigma = %g" % bias_sigma]
        out += ["layer[%d,%d->%d] = add:l%d_ffn_add" % (h, d, y, i)]
    n, o = new(), new()
    out += ["layer[%d->%d] = rmsnorm:final_norm" % (node - 2, n),
            "  eps = %g" % rms_norm_eps,
            "layer[%d->%d] = fullc:head" % (n, o),
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


def kimi_vl_a3b(num_layers: int = 27, vocab: int = 163840,
                experts_held: int = 64, expert_first: int = 0,
                seq_len: int = 8192, batch_size: int = 2,
                q_block: int = 1024, expert_block: int = 512,
                loss_chunk: int = 1024, lr: float = 1e-4) -> str:
    """Kimi-VL-A3B-Instruct's language model at its published widths.
    The defaults are the uncut model; a chip's share passes fewer layers
    (the dense layer 0 and then expert layers), the experts it holds and
    its vocabulary slice. Not in the published config and set here:
    ``init_sigma`` 0.02 and the seeded ``noaux_tc`` bias at 0.01 (so that
    picking by ``s + b`` and weighting by ``s`` differ), Adam's ``lr``
    1e-4 with betas 0.9 / 0.95 (this updater's ``beta1`` / ``beta2`` keys
    are 1 - beta) and no weight decay, ``remat = block``, the block
    sizes."""
    return decoder_lm(
        vocab=vocab, hidden=2048, num_layers=num_layers, first_k_dense=1,
        nhead=16, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=512, rope_theta=800000.0, rms_norm_eps=1e-5,
        dense_width=11264, expert_width=1408, n_routed_experts=64,
        experts_per_tok=6, n_shared_experts=2, routed_scaling_factor=2.446,
        experts_held=experts_held, expert_first=expert_first,
        seq_len=seq_len, batch_size=batch_size, q_block=q_block,
        expert_block=expert_block, loss_chunk=loss_chunk, bias_sigma=0.01,
        init_sigma=0.02, lr=lr)


def kimi_vl_a3b_tiny(seq_len: int = 16, batch_size: int = 2,
                     experts_held: int = 8, expert_first: int = 0,
                     vocab: int = 64, num_layers: int = 3) -> str:
    """The same block at toy widths, for the CPU tests only."""
    return decoder_lm(
        vocab=vocab, hidden=32, num_layers=num_layers, first_k_dense=1,
        nhead=2, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        kv_lora_rank=16, rope_theta=800000.0, rms_norm_eps=1e-5,
        dense_width=48, expert_width=24, n_routed_experts=8,
        experts_per_tok=3, n_shared_experts=2, routed_scaling_factor=2.446,
        experts_held=experts_held, expert_first=expert_first,
        seq_len=seq_len, batch_size=batch_size, q_block=8, expert_block=4,
        loss_chunk=8, bias_sigma=0.5, init_sigma=0.3, lr=0.01)
