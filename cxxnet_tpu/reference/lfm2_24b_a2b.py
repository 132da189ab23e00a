"""Plain reference of LFM2's decoder (LiquidAI, ``model_type: lfm2_moe``):
forward, loss, gradients and Adam, in straightforward ``jax.numpy``,
float32, under ``jax.default_matmul_precision("highest")``. No kernel, no
cache: the short convolution is the library's own, one group a channel;
routing is a dense one-hot over all experts; the attention's scores are
full rows with the mask written as the inequality it is. It follows the
published config's keys and, for what they do not say,
``modeling_lfm2_moe.py`` of ``transformers``. ``N`` is RMSNorm with a
learned scale from 1, ``x * rsqrt(mean(x^2) + norm_eps) * w``:

    h = embed(ids)                                       no scale
    per layer   h = h + Mixer(N(h));  h = h + FF(N(h))
    Mixer, layer_types[l] == "conv": the double-gated short convolution
                [B | C | x] = u Win          three parts of hidden_size
                s = B * x
                c_t = sum_j w_j s_(t - K + 1 + j), j < K = conv_L_cache,
                    a channel alone, zeros before the sequence, no bias
                    (Conv1d(groups = hidden, kernel K, padding K - 1) cut
                    to the sequence's length)
                y = (C * c) Wout             no activation anywhere
    Mixer, layer_types[l] == "full_attention"
                q = u Wq -> num_attention_heads heads of hidden / heads
                k = u Wk, v = u Wv -> num_key_value_heads heads
                q, k <- N over a head's features (one scale each)
                RoPE (x cos + rotate_half(x) sin, rope_theta) on all of
                    a head's features
                o = softmax(q k^T / sqrt(head_dim)) v over 0 <= i - j,
                    query head h against key/value head h // (heads / kv)
                y = o Wo                     no output gate, no biases
    FF, l < num_dense_layers:  W2(silu(u W1) * (u W3)), intermediate_size
    FF, the rest:  s = sigmoid(u Wr) over all num_experts, float32
                picks = top-k of s + b           (use_expert_bias)
                w_e = s_e / (sum_picked s + 1e-6) * routed_scaling_factor
                sum_picked w_e E_e(u), E_e a SwiGLU of
                    moe_intermediate_size; no shared expert
    logits = N(h) E^T with E the embedding (tied), mean next-token
    cross-entropy

Parameters come as the program's own pytree (``{layer key: {tag: array}}``
with the keys ``cxxnet_tpu.models.lfm2.lfm2_lm`` gives) so that both
sides can start from the same seeded weights; nothing else is shared with
the code under test. The tree has no head: ``embed``'s ``wmat`` is read
twice.

A chip's share: ``held = (first, count)`` names the experts whose weights
``params`` carries (``egate`` etc. have ``count`` leading entries); the
router still scores all ``num_experts`` and what absent experts would add
is left out. ``None`` means all experts: the uncut layer. The vocabulary
slice is whatever rows ``embed`` carries. ``cfg["layer_types"]`` lists the
layers held, in order, and ``num_dense_layers`` how many of them, from the
first, are dense.

Departures from ``modeling_lfm2_moe.py``, each marked DEPARTURE below: the
expert bias is given and fixed (the model keeps it as a buffer and moves
it by a rule the config does not publish); Adam is this repository's
updater formula; one document a sequence (no packing mask, no padding
mask). ``q_block`` and ``remat`` change no value: they bound memory so
that the benchmark can run this file at the published widths
(``benchmarks/reference/`` holds a copy): with ``q_block`` the queries go
through the attention a block after the other (``lax.map``), with
``remat`` a block's scores and a layer's inside are recomputed in the
backward pass. ``products`` rounds the operands of every matrix product
but the router's, and those of the convolution, to a lower precision, to
measure what such a change does to the result.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32

# the published sizes (config.json of LiquidAI/LFM2-24B-A2B); a test
# passes its own
PUBLISHED = dict(
    vocab_size=65536, hidden_size=2048, num_hidden_layers=40,
    layer_types=tuple("full_attention" if i % 4 == 2 else "conv"
                      for i in range(40)),
    num_dense_layers=2, num_attention_heads=32, num_key_value_heads=8,
    rope_theta=1000000.0, norm_eps=1e-5, conv_L_cache=3,
    intermediate_size=11776, moe_intermediate_size=1536, num_experts=64,
    num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=1.0,
    use_expert_bias=True)

Params = Dict[str, Dict[str, Any]]


def lower(a, products: Optional[str]):
    """``a`` rounded to the dtype ``products`` names and back."""
    return a if products is None else a.astype(products).astype(F32)


def mm(a, b, products: Optional[str]):
    """``a @ b``; with ``products`` both operands are first rounded to
    that dtype (the product itself stays float32)."""
    return jnp.matmul(lower(a, products), lower(b, products))


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta):
    """x: (time, heads, head_dim): ``x cos + rotate_half(x) sin``, the
    angles ``pos * theta^(-2i/dim)`` repeated over the two halves."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def swiglu(x, gate, up, down, products):
    return mm(jax.nn.silu(mm(x, gate, products)) * mm(x, up, products),
              down, products)


def causal_conv(x, taps, products):
    """x: (time, channels), taps: (K, channels): ``y_t = sum_j taps[j]
    x[t - K + 1 + j]``, zeros before the sequence, a channel alone: the
    library's convolution with one group a channel, padded on the left
    (the program sums K shifted products instead)."""
    kernel, channels = taps.shape
    return jax.lax.conv_general_dilated(
        lower(x, products)[None], lower(taps, products)[:, None, :], (1,),
        [(kernel - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=channels)[0]


def short_conv(p, x, products):
    """x: (time, hidden) of ONE sequence."""
    d = x.shape[1]
    bcx = mm(x, p["win"], products)
    b, c, xx = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    return mm(c * causal_conv(b * xx, p["taps"], products), p["wout"],
              products)


def attention(p, x, cfg, products, q_block, remat):
    """x: (time, hidden) of ONE sequence."""
    t = x.shape[0]
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    q = mm(x, p["wq"], products).reshape(t, h, d)
    k = mm(x, p["wk"], products).reshape(t, g, d)
    v = mm(x, p["wv"], products).reshape(t, g, d)
    q = rope(rms_norm(q, p["qnorm"], cfg["norm_eps"]), cfg["rope_theta"])
    k = rope(rms_norm(k, p["knorm"], cfg["norm_eps"]), cfg["rope_theta"])
    # query head i reads key/value head i // (h / g)
    k, v = jnp.repeat(k, h // g, axis=1), jnp.repeat(v, h // g, axis=1)
    scale = 1.0 / math.sqrt(d)

    def rows(q_rows, first):
        """The queries from position ``first`` on, against all keys."""
        s = mm(q_rows.transpose(1, 0, 2), k.transpose(1, 2, 0),
               products) * scale                       # (h, rows, t)
        i = first + jnp.arange(q_rows.shape[0])[:, None]
        j = jnp.arange(t)[None, :]
        s = jnp.where((0 <= i - j)[None], s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v.transpose(1, 0, 2),
                  products).transpose(1, 0, 2)         # (rows, h, d)

    if remat:
        rows = jax.checkpoint(rows)
    bq = q_block or t
    if bq == t:
        o = rows(q, 0)
    else:
        # one block after the other (the compiler, left to itself, runs
        # the blocks side by side)
        o = jax.lax.map(lambda block: rows(*block), (
            q.reshape(t // bq, bq, h, d), jnp.arange(0, t, bq)))
    return mm(o.reshape(t, h * d), p["wo"], products)


def moe(p, bias, x, cfg, held, products, router_dtype=None):
    """x: (tokens, hidden). ``held = (first, count)``: the experts whose
    weights ``p`` carries; None: all of them."""
    n_exp, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    first, count = held if held is not None else (0, n_exp)
    xr, wr = x, p["router"]
    if router_dtype is not None:        # what a lower-precision router does
        xr, wr = (a.astype(router_dtype).astype(F32) for a in (xr, wr))
    s = jax.nn.sigmoid(jnp.matmul(xr, wr))                       # (n, E)
    # DEPARTURE: the expert bias b is given and fixed; it only chooses,
    # it does not weigh
    _, picks = jax.lax.top_k(
        s + bias[None, :] if cfg.get("use_expert_bias", True) else s, k)
    w = s * jnp.sum(jax.nn.one_hot(picks, n_exp, dtype=F32), axis=1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * cfg["routed_scaling_factor"]

    def add(out, held_expert):
        gate, up, down, w_e = held_expert
        return out + w_e[:, None] * swiglu(x, gate, up, down, products), None

    # every held expert over every token, one after the other; no shared
    # expert
    return jax.lax.scan(add, jnp.zeros_like(x), (
        p["egate"], p["eup"], p["edown"], w[:, first:first + count].T))[0]


def sequence_loss(params: Params, biases, ids, labels, cfg, held=None,
                  products=None, router_dtype=None, q_block=None,
                  remat=False):
    """Mean next-token cross-entropy of ONE sequence: ids, labels (time,).
    DEPARTURE: the sequence is one document (no packing or padding mask)."""
    eps = cfg["norm_eps"]

    def layer(x, p_mixer, p_norms, p_ffn, bias, kind):
        z = rms_norm(x, p_norms[0], eps)
        if kind == "conv":
            a = short_conv(p_mixer, z, products)
        else:
            a = attention(p_mixer, z, cfg, products, q_block, remat)
        h = x + a
        z = rms_norm(h, p_norms[1], eps)
        if bias is None:
            f = swiglu(z, p_ffn["wgate"], p_ffn["wup"], p_ffn["wdown"],
                       products)
        else:
            f = moe(p_ffn, bias, z, cfg, held, products, router_dtype)
        return h + f

    if remat:
        layer = jax.checkpoint(layer, static_argnums=(5,))
    x = jnp.take(params["embed"]["wmat"], ids, axis=0)
    kinds = tuple(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        dense = i < cfg["num_dense_layers"]
        x = layer(x, params["l%d_%s" % (i, "conv" if kind == "conv"
                                        else "attn")],
                  tuple(params["l%d_%s" % (i, n)]["wmat"]
                        for n in ("op_norm", "ffn_norm")),
                  params["l%d_mlp" % i if dense else "l%d_moe" % i],
                  None if dense else biases["l%d_moe" % i], kind)

    def head(x, embedding):
        # the head is the embedding's own matrix: h E^T
        logits = mm(rms_norm(x, params["final_norm"]["wmat"], eps),
                    embedding.T, products)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None],
                                             axis=-1))

    return (jax.checkpoint(head) if remat else head)(
        x, params["embed"]["wmat"])


def loss(params: Params, biases, ids, labels, cfg, **kw):
    """Mean over the batch's sequences, one after the other (they share
    nothing; ``lax.map``, so that the program holds one sequence's layers
    and not the batch's): ids, labels (batch, time) integers."""
    with jax.default_matmul_precision("highest"):
        return jnp.mean(jax.lax.map(
            lambda one: sequence_loss(params, biases, one[0], one[1], cfg,
                                      **kw), (ids, labels)))


def loss_and_grad(params: Params, biases, ids, labels, cfg, **kw):
    return jax.value_and_grad(loss)(params, biases, ids, labels, cfg, **kw)


def adam_init(params: Params):
    return {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
            "v": jax.tree_util.tree_map(jnp.zeros_like, params)}


def adam_step(params: Params, grads: Params, state, t: int, lr: float,
              beta1: float = 0.9, beta2: float = 0.95):
    """DEPARTURE: Adam as this repository's updater computes it
    (updater/__init__.py: AdamUpdater, after cxxnet's adam_updater): the
    bias corrections folded into the rate, ``lr_t = lr * sqrt(1 -
    beta2^t) / (1 - beta1^t)``, and ``eps = 1e-8`` added to ``sqrt(v)``
    uncorrected. ``t`` counts from 1. No weight decay."""
    lr_t = lr * math.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
    m = jax.tree_util.tree_map(lambda m, g: m + (1 - beta1) * (g - m),
                               state["m"], grads)
    v = jax.tree_util.tree_map(lambda v, g: v + (1 - beta2) * (g * g - v),
                               state["v"], grads)
    new = jax.tree_util.tree_map(
        lambda w, m, v: w - lr_t * (m / (jnp.sqrt(v) + 1e-8)), params, m, v)
    return new, {"m": m, "v": v}


def train_steps(params: Params, biases, ids, labels, cfg, steps: int,
                lr: float, beta1: float = 0.9, beta2: float = 0.95, **kw
                ) -> Tuple[Params, list]:
    """``steps`` Adam updates on one batch; returns the parameters after
    them and each step's loss (taken before its update)."""
    state, losses = adam_init(params), []
    for t in range(1, steps + 1):
        value, grads = loss_and_grad(params, biases, ids, labels, cfg, **kw)
        losses.append(value)
        params, state = adam_step(params, grads, state, t, lr, beta1, beta2)
    return params, losses
