"""Trinity-Mini's decoder (arcee-ai, 2025-12, ``model_type: afmoe``) in the
netconfig DSL: a residual block with a norm before and after each half,
grouped-query attention with QK norm and a sigmoid output gate, a window
of keys and RoPE on the sliding layers and neither on every
``global_every``-th layer, leading dense SwiGLU layers, then sigmoid-routed
expert layers with one shared expert (the ``moe`` layer as it is), a
final RMSNorm and an untied head over embeddings scaled by
``sqrt(hidden)``.

``afmoe_lm`` writes the netconfig for any sizes (the tests' tiny twin);
``trinity_mini`` fills in the published widths
(https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json).

A chip's share of a deployment is asked for by arguments, never by a
width: ``num_layers`` and ``num_dense`` (depth), ``experts_held`` /
``expert_first`` (the experts that live here; the router keeps all
``num_experts``) and ``vocab`` (the rows of the vocabulary slice held
here).
"""

from typing import List


def afmoe_lm(*, vocab: int, hidden: int, num_layers: int, num_dense: int,
             nhead: int, nkvhead: int, head_dim: int, sliding_window: int,
             global_every: int, rope_theta: float, rms_norm_eps: float,
             dense_width: int, expert_width: int, num_experts: int,
             experts_per_tok: int, num_shared_experts: int,
             route_scale: float, embed_scale: float, experts_held: int,
             expert_first: int, seq_len: int, batch_size: int, q_block: int,
             expert_block: int, loss_chunk: int, bias_sigma: float,
             init_sigma: float, lr: float, remat: str = "block") -> str:
    """The netconfig text (with the global keys a training conf needs)
    of a decoder-only language model of afmoe's block. Layer ``i`` is a
    full-attention layer iff ``(i + 1) % global_every == 0``."""
    out: List[str] = ["netconfig=start",
                      "layer[0->1] = embed:embed",
                      "  nvocab = %d" % vocab,
                      "  nhidden = %d" % hidden,
                      "  scale = %.17g" % embed_scale]
    node = 1

    def new() -> int:
        nonlocal node
        node += 1
        return node

    def norm(src: int, dst: int, key: str) -> List[str]:
        return ["layer[%d->%d] = rmsnorm:%s" % (src, dst, key),
                "  eps = %g" % rms_norm_eps]

    for i in range(num_layers):
        sliding = (i + 1) % global_every != 0
        x = node
        a, b, c, h = new(), new(), new(), new()
        out += norm(x, a, "l%d_attn_norm" % i)
        out += ["layer[%d->%d] = gqa_attention:l%d_attn" % (a, b, i),
                "  nhead = %d" % nhead,
                "  nkvhead = %d" % nkvhead,
                "  head_dim = %d" % head_dim,
                "  window = %d" % (sliding_window if sliding else 0),
                "  rope = %d" % sliding,
                "  rope_theta = %g" % rope_theta,
                "  eps = %g" % rms_norm_eps,
                "  q_block = %d" % q_block]
        out += norm(b, c, "l%d_attn_post" % i)
        out += ["layer[%d,%d->%d] = add:l%d_attn_add" % (x, c, h, i)]
        d, e, f, y = new(), new(), new(), new()
        out += norm(h, d, "l%d_ffn_norm" % i)
        if i < num_dense:
            out += ["layer[%d->%d] = swiglu:l%d_mlp" % (d, e, i),
                    "  nhidden = %d" % dense_width]
        else:
            out += ["layer[%d->%d] = moe:l%d_moe" % (d, e, i),
                    "  nexpert = %d" % num_experts,
                    "  topk = %d" % experts_per_tok,
                    "  nhidden = %d" % expert_width,
                    "  nshared = %d" % num_shared_experts,
                    "  routed_scaling_factor = %g" % route_scale,
                    "  norm_topk_prob = 1",
                    "  expert_first = %d" % expert_first,
                    "  expert_count = %d" % experts_held,
                    "  expert_block = %d" % expert_block,
                    "  bias_seed = %d" % i,
                    "  bias_sigma = %g" % bias_sigma]
        out += norm(e, f, "l%d_ffn_post" % i)
        out += ["layer[%d,%d->%d] = add:l%d_ffn_add" % (h, f, y, i)]
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


def trinity_mini(num_layers: int = 32, num_dense: int = 2,
                 vocab: int = 200192, experts_held: int = 128,
                 expert_first: int = 0, seq_len: int = 8192,
                 batch_size: int = 2, q_block: int = 1024,
                 expert_block: int = 512, loss_chunk: int = 1024,
                 lr: float = 1e-4) -> str:
    """Trinity-Mini's decoder at its published widths. The defaults are
    the uncut model; a chip's share passes fewer layers (leading dense
    ones, then expert layers), the experts it holds and its vocabulary
    slice. Not in the published config and set here: ``init_sigma`` 0.02
    and the seeded expert bias at 0.01 (so that picking by ``s + b`` and
    weighting by ``s`` differ), held fixed (``load_balance_coeff`` is the
    rate of the trainer's rule that moves it, which this repository has
    not); Adam's ``lr`` 1e-4 with betas 0.9 / 0.95 (this updater's
    ``beta1`` / ``beta2`` keys are 1 - beta; Arcee trained with Muon) and
    no weight decay, ``remat = block``, the block sizes."""
    return afmoe_lm(
        vocab=vocab, hidden=2048, num_layers=num_layers, num_dense=num_dense,
        nhead=32, nkvhead=4, head_dim=128, sliding_window=2048,
        global_every=4, rope_theta=10000.0, rms_norm_eps=1e-5,
        dense_width=6144, expert_width=1024, num_experts=128,
        experts_per_tok=8, num_shared_experts=1, route_scale=2.826,
        embed_scale=2048 ** 0.5, experts_held=experts_held,
        expert_first=expert_first, seq_len=seq_len, batch_size=batch_size,
        q_block=q_block, expert_block=expert_block, loss_chunk=loss_chunk,
        bias_sigma=0.01, init_sigma=0.02, lr=lr)


def trinity_mini_tiny(seq_len: int = 16, batch_size: int = 2,
                      experts_held: int = 8, expert_first: int = 0,
                      vocab: int = 64, num_layers: int = 5,
                      num_dense: int = 1) -> str:
    """The same block at toy widths, for the CPU tests only: a window of
    6 keys on three layers of four, 4 query heads on 2 key/value heads."""
    return afmoe_lm(
        vocab=vocab, hidden=32, num_layers=num_layers, num_dense=num_dense,
        nhead=4, nkvhead=2, head_dim=8, sliding_window=6, global_every=4,
        rope_theta=10000.0, rms_norm_eps=1e-5, dense_width=48,
        expert_width=24, num_experts=8, experts_per_tok=3,
        num_shared_experts=1, route_scale=2.826, embed_scale=32 ** 0.5,
        experts_held=experts_held, expert_first=expert_first,
        seq_len=seq_len, batch_size=batch_size, q_block=8, expert_block=4,
        loss_chunk=8, bias_sigma=0.5, init_sigma=0.3, lr=0.01)
