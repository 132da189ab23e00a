"""LFM2's decoder (LiquidAI, ``model_type: lfm2_moe``) in the netconfig
DSL: a pre-norm residual block whose mixer is a double-gated short
convolution (``gated_conv``) where ``layer_types`` says ``conv`` and
grouped-query attention with QK norm, RoPE on a whole head and no output
gate (``gqa_attention`` with ``gate = 0``) where it says
``full_attention``; whose other half is a SwiGLU on the leading dense
layers and, on the others, an expert layer routed by sigmoid scores with
an expert bias and no shared expert (``moe`` with ``nshared = 0``); a
final RMSNorm and a head tied to the embedding (``share[embed]``).

``lfm2_lm`` writes the netconfig for any sizes (the tests' tiny twin);
``lfm2_24b_a2b`` fills in the published widths of LFM2-24B-A2B
(https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json).

A chip's share of a deployment is asked for by arguments, never by a
width: ``layer_types`` (the kinds of the layers held, in order),
``dense_layers`` (how many of them, from the first, are dense),
``experts_held`` / ``expert_first`` (the experts that live here; the
router keeps all ``num_experts``) and ``vocab`` (the rows of the
vocabulary slice held here, embedding and head alike: one matrix).
"""

from typing import List, Sequence

# the published pattern: attention at 2, 6, 10, ..., 38 of 40 layers
PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i % 4 == 2 else "conv" for i in range(40))


def lfm2_lm(*, vocab: int, hidden: int, layer_types: Sequence[str],
            dense_layers: int, dense_width: int, nhead: int, nkvhead: int,
            head_dim: int, rope_theta: float, conv_kernel: int,
            norm_eps: float, expert_width: int, num_experts: int,
            experts_per_tok: int, routed_scaling_factor: float,
            experts_held: int, expert_first: int, seq_len: int,
            batch_size: int, q_block: int, expert_block: int,
            loss_chunk: int, bias_sigma: float, init_sigma: float, lr: float,
            remat: str = "block") -> str:
    """The netconfig text (with the global keys a training conf needs)
    of a decoder-only language model of LFM2's block. Layer ``i`` is what
    ``layer_types[i]`` names (``conv`` or ``full_attention``); the first
    ``dense_layers`` of them have a SwiGLU, the others experts."""
    unknown = sorted(set(layer_types) - {"conv", "full_attention"})
    if unknown or not 0 <= dense_layers <= len(layer_types):
        raise ValueError("lfm2_lm: layer_types may hold conv and "
                         "full_attention (not %r), dense_layers at most "
                         "their count" % (unknown,))
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
                "  eps = %g" % norm_eps]

    for i, kind in enumerate(layer_types):
        x = node
        a, b, h = new(), new(), new()
        out += norm(x, a, "l%d_op_norm" % i)
        if kind == "conv":
            out += ["layer[%d->%d] = gated_conv:l%d_conv" % (a, b, i),
                    "  conv_kernel = %d" % conv_kernel]
        else:
            out += ["layer[%d->%d] = gqa_attention:l%d_attn" % (a, b, i),
                    "  nhead = %d" % nhead,
                    "  nkvhead = %d" % nkvhead,
                    "  head_dim = %d" % head_dim,
                    "  window = 0",
                    "  rope = 1",
                    "  gate = 0",
                    "  rope_theta = %g" % rope_theta,
                    "  eps = %g" % norm_eps,
                    "  q_block = %d" % q_block]
        out += ["layer[%d,%d->%d] = add:l%d_op_add" % (x, b, h, i)]
        c, d, y = new(), new(), new()
        out += norm(h, c, "l%d_ffn_norm" % i)
        if i < dense_layers:
            out += ["layer[%d->%d] = swiglu:l%d_mlp" % (c, d, i),
                    "  nhidden = %d" % dense_width]
        else:
            out += ["layer[%d->%d] = moe:l%d_moe" % (c, d, i),
                    "  nexpert = %d" % num_experts,
                    "  topk = %d" % experts_per_tok,
                    "  nhidden = %d" % expert_width,
                    "  nshared = 0",
                    "  routed_scaling_factor = %g" % routed_scaling_factor,
                    "  norm_topk_prob = 1",
                    "  expert_first = %d" % expert_first,
                    "  expert_count = %d" % experts_held,
                    "  expert_block = %d" % expert_block,
                    "  bias_seed = %d" % i,
                    "  bias_sigma = %g" % bias_sigma]
        out += ["layer[%d,%d->%d] = add:l%d_ffn_add" % (h, d, y, i)]
    last = node
    n, o = new(), new()
    out += norm(last, n, "final_norm")
    # the head is the embedding's own matrix: h E^T (layers/sequence.py:
    # EmbedLayer on a sequence node)
    out += ["layer[%d->%d] = share[embed]:head" % (n, o),
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


def lfm2_24b_a2b(layer_types: Sequence[str] = PUBLISHED_LAYER_TYPES,
                 dense_layers: int = 2, vocab: int = 65536,
                 experts_held: int = 64, expert_first: int = 0,
                 seq_len: int = 8192, batch_size: int = 2,
                 q_block: int = 1024, expert_block: int = 512,
                 loss_chunk: int = 1024, lr: float = 1e-4) -> str:
    """LFM2-24B-A2B's decoder at its published widths. The defaults are
    the uncut model; a chip's share passes the kinds of the layers it
    holds, how many of them are dense, the experts it holds and its
    vocabulary slice. Not in the published config and set here:
    ``init_sigma`` 0.02 and the seeded expert bias at 0.01
    (``use_expert_bias``: picking by ``s + b`` and weighting by ``s``
    differ), held fixed (the rule that moves it is not published);
    Adam's ``lr`` 1e-4 with betas 0.9 / 0.95 (this updater's ``beta1`` /
    ``beta2`` keys are 1 - beta) and no weight decay, ``remat = block``,
    the block sizes. A head's width is ``hidden_size /
    num_attention_heads`` = 64; ``conv_kernel`` is ``conv_L_cache``."""
    return lfm2_lm(
        vocab=vocab, hidden=2048, layer_types=layer_types,
        dense_layers=dense_layers, dense_width=11776, nhead=32, nkvhead=8,
        head_dim=64, rope_theta=1e6, conv_kernel=3, norm_eps=1e-5,
        expert_width=1536, num_experts=64, experts_per_tok=4,
        routed_scaling_factor=1.0, experts_held=experts_held,
        expert_first=expert_first, seq_len=seq_len, batch_size=batch_size,
        q_block=q_block, expert_block=expert_block, loss_chunk=loss_chunk,
        bias_sigma=0.01, init_sigma=0.02, lr=lr)


def lfm2_tiny(seq_len: int = 16, batch_size: int = 2, experts_held: int = 8,
              expert_first: int = 0, vocab: int = 64,
              layer_types: Sequence[str] = (
                  "conv", "full_attention", "conv", "conv", "conv"),
              dense_layers: int = 1) -> str:
    """The same block at toy widths, for the CPU tests only: the cell's
    five layers (a dense one, then a period), 4 query heads on 2
    key/value heads of 8 features, top-3 of 8 experts."""
    return lfm2_lm(
        vocab=vocab, hidden=32, layer_types=layer_types,
        dense_layers=dense_layers, dense_width=48, nhead=4, nkvhead=2,
        head_dim=8, rope_theta=1e6, conv_kernel=3, norm_eps=1e-5,
        expert_width=24, num_experts=8, experts_per_tok=3,
        routed_scaling_factor=1.0, experts_held=experts_held,
        expert_first=expert_first, seq_len=seq_len, batch_size=batch_size,
        q_block=8, expert_block=4, loss_chunk=8, bias_sigma=0.5,
        init_sigma=0.3, lr=0.01)
