"""Mellum2's decoder (JetBrains, ``model_type: mellum``) in the netconfig
DSL: a pre-norm residual block whose mixer is grouped-query attention
with QK norm and no output gate (``gqa_attention`` with ``gate = 0``): a
window of ``sliding_window`` keys and plain RoPE where ``layer_types``
says ``sliding_attention``, every earlier key and YaRN's RoPE where it
says ``full_attention``; whose other half is an expert layer routed by
softmax scores, renormalised over the picks, with no bias and no shared
expert (``moe`` with ``score_func = softmax``, ``nshared = 0``); a final
RMSNorm and an untied head (``fullc``).

``mellum2_lm`` writes the netconfig for any sizes (the tests' tiny twin);
``mellum2_12b_a2_5b`` fills in the published widths of
Mellum2-12B-A2.5B-Instruct
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json).

A deployment's share is asked for by arguments, never by a width:
``layer_types`` (the kinds of the layers held, in order), ``expert_axis``
(the mesh axis the experts are spread over; empty: each program holds
``experts_held`` from ``expert_first`` and computes no other) and
``vocab`` (the rows of the vocabulary slice held, embedding and head).
"""

from typing import List, Sequence

# the published pattern: full attention at 3, 7, ..., 27 of 28 layers
PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i % 4 == 3 else "sliding_attention"
    for i in range(28))

# rope_parameters.full_attention of the published config
PUBLISHED_YARN = dict(rope_theta=500000.0, factor=16.0,
                      original_max_position_embeddings=8192, beta_fast=32.0,
                      beta_slow=1.0, attention_factor=1.2772588722239782)


def mellum2_lm(*, vocab: int, hidden: int, layer_types: Sequence[str],
               nhead: int, nkvhead: int, head_dim: int, window: int,
               rope_theta: float, yarn: dict, norm_eps: float,
               expert_width: int, num_experts: int, experts_per_tok: int,
               experts_held: int, expert_first: int, expert_axis: str,
               seq_len: int, batch_size: int,
               q_block: int, expert_block: int, loss_chunk: int,
               init_sigma: float, lr: float, remat: str = "block") -> str:
    """The netconfig text (with the global keys a training conf needs)
    of a decoder-only language model of Mellum2's block. Layer ``i`` is
    what ``layer_types[i]`` names (``sliding_attention`` or
    ``full_attention``); every layer has experts."""
    unknown = sorted(set(layer_types)
                     - {"sliding_attention", "full_attention"})
    if unknown:
        raise ValueError("mellum2_lm: layer_types may hold "
                         "sliding_attention and full_attention, not %r"
                         % (unknown,))
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
        out += norm(x, a, "l%d_attn_norm" % i)
        out += ["layer[%d->%d] = gqa_attention:l%d_attn" % (a, b, i),
                "  nhead = %d" % nhead,
                "  nkvhead = %d" % nkvhead,
                "  head_dim = %d" % head_dim,
                "  rope = 1",
                "  gate = 0",
                "  eps = %g" % norm_eps,
                "  q_block = %d" % q_block]
        if kind == "sliding_attention":
            out += ["  window = %d" % window,
                    "  rope_theta = %g" % rope_theta]
        else:
            out += ["  window = 0",
                    "  rope_type = yarn",
                    "  rope_theta = %g" % yarn["rope_theta"],
                    "  rope_factor = %g" % yarn["factor"],
                    "  original_max_position_embeddings = %d"
                    % yarn["original_max_position_embeddings"],
                    "  beta_fast = %g" % yarn["beta_fast"],
                    "  beta_slow = %g" % yarn["beta_slow"],
                    "  attention_factor = %r" % yarn["attention_factor"]]
        out += ["layer[%d,%d->%d] = add:l%d_attn_add" % (x, b, h, i)]
        c, d, y = new(), new(), new()
        out += norm(h, c, "l%d_ffn_norm" % i)
        out += ["layer[%d->%d] = moe:l%d_moe" % (c, d, i),
                "  nexpert = %d" % num_experts,
                "  topk = %d" % experts_per_tok,
                "  nhidden = %d" % expert_width,
                "  nshared = 0",
                "  score_func = softmax",
                "  norm_topk_prob = 1",
                "  expert_first = %d" % expert_first,
                "  expert_count = %d" % experts_held,
                "  expert_block = %d" % expert_block]
        if expert_axis:
            out += ["  expert_axis = %s" % expert_axis]
        out += ["layer[%d,%d->%d] = add:l%d_ffn_add" % (h, d, y, i)]
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


def mellum2_12b_a2_5b(layer_types: Sequence[str] = PUBLISHED_LAYER_TYPES,
                      vocab: int = 98304, expert_axis: str = "data",
                      seq_len: int = 8192,
                      batch_size: int = 8, q_block: int = 1024,
                      expert_block: int = 512, loss_chunk: int = 1024,
                      lr: float = 1e-4) -> str:
    """Mellum2-12B-A2.5B's decoder at its published widths: all 64
    experts a layer, spread over ``expert_axis``. A deployment's share
    passes the kinds of the layers held and its vocabulary slice. Not in
    the published config and set here: the QK norm (the keys are
    transformers' Qwen3-MoE family's, whose attention has one), no MTP
    head, ``init_sigma`` 0.02, Adam's ``lr`` 1e-4 with betas 0.9 / 0.95
    (this updater's ``beta1`` / ``beta2`` keys are 1 - beta) and no weight
    decay, ``remat = block``, the block sizes."""
    return mellum2_lm(
        vocab=vocab, hidden=2304, layer_types=layer_types, nhead=32,
        nkvhead=4, head_dim=128, window=1024, rope_theta=500000.0,
        yarn=PUBLISHED_YARN, norm_eps=1e-6, expert_width=896,
        num_experts=64, experts_per_tok=8, experts_held=64, expert_first=0,
        expert_axis=expert_axis, seq_len=seq_len, batch_size=batch_size, q_block=q_block,
        expert_block=expert_block, loss_chunk=loss_chunk, init_sigma=0.02,
        lr=lr)


def mellum2_tiny(seq_len: int = 16, batch_size: int = 4, vocab: int = 64,
                 expert_axis: str = "data",
                 layer_types: Sequence[str] = PUBLISHED_LAYER_TYPES[:4],
                 window: int = 6) -> str:
    """The same block at toy widths, for the CPU tests only: one period
    (three window layers, one full), 4 query heads on 2 key/value heads
    of 8 features, a window of 6, YaRN at theta 100 over an original
    length of 64 (its ramp over the pairs 0 to 3 of 4), top-3 of 8
    experts."""
    yarn = dict(PUBLISHED_YARN, rope_theta=100.0,
                original_max_position_embeddings=64, factor=4.0)
    return mellum2_lm(
        vocab=vocab, hidden=32, layer_types=layer_types, nhead=4, nkvhead=2,
        head_dim=8, window=window, rope_theta=10000.0, yarn=yarn,
        norm_eps=1e-6, expert_width=24, num_experts=8, experts_per_tok=3,
        experts_held=8, expert_first=0, expert_axis=expert_axis,
        seq_len=seq_len, batch_size=batch_size, q_block=8, expert_block=4,
        loss_chunk=8,
        init_sigma=0.3, lr=0.01)
