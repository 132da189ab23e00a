"""Plain reference of Trinity-Mini's decoder (arcee-ai, ``model_type:
afmoe``): forward, loss, gradients and Adam, in straightforward
``jax.numpy``, float32, under ``jax.default_matmul_precision("highest")``.
No kernel, no cache; routing is a dense one-hot over all experts;
the attention's scores are full rows with the mask written as the
inequality it is. It follows the published config's keys and, for what
they do not say, ``modeling_afmoe.py`` of ``transformers``:

    h = embed(ids) * sqrt(hidden_size)                     mup_enabled
    per layer   a = Attn(RMSNorm(h));  h = h + RMSNorm(a)
                m = MLP(RMSNorm(h));   h = h + RMSNorm(m)
    Attn        q = x Wq -> num_attention_heads heads of head_dim
                k = x Wk, v = x Wv -> num_key_value_heads heads
                g = x Wg -> num_attention_heads x head_dim
                q, k <- RMSNorm over a head's features (one scale each)
                sliding layer (layer_types[l] == "sliding_attention"):
                    RoPE (x cos + rotate_half(x) sin, rope_theta) on all
                    of q's and k's features; query i sees key j iff
                    0 <= i - j < sliding_window
                full layer: no positional encoding; 0 <= i - j
                o = softmax(q k^T / sqrt(head_dim)) v, query head h
                    against key/value head h // (heads / kv heads)
                y = (o * sigmoid(g)) Wo                    no biases
    MLP, l < num_dense_layers:  Wdown(silu(Wgate x) * Wup x)
    MLP, the rest:  s = sigmoid(x Wr), float32;  top-k of s + b;
                w_i = route_scale * s_i / (sum_picked s_j + 1e-20)
                sum_i w_i E_i(x) + S(x)        one shared expert S
    logits = RMSNorm(h) Whead (untied), mean next-token cross-entropy

Parameters come as the program's own pytree (``{layer key: {tag: array}}``
with the keys ``cxxnet_tpu.models.trinity.afmoe_lm`` gives) so that both
sides can start from the same seeded weights; nothing else is shared with
the code under test.

A chip's share: ``held = (first, count)`` names the experts whose weights
``params`` carries (``egate`` etc. have ``count`` leading entries); the
router still scores all ``num_experts`` and what absent experts would add
is left out. ``None`` means all experts: the uncut layer. The vocabulary
slice is whatever rows ``embed`` and ``head`` carry.

Departures from ``modeling_afmoe.py``, each marked DEPARTURE below: the
expert bias is given and fixed (``load_balance_coeff``, the rate at which
training moves it, is the trainer's rule and is not applied); Adam is this
repository's updater formula where Arcee trained with Muon; one document
a sequence (no packing mask, no padding mask). ``q_block`` and ``remat``
change no value: they bound memory so that the benchmark can run this
file at the published widths (``benchmarks/reference/`` holds a copy):
with ``q_block`` the queries go through the attention a block after the
other (``lax.map``), with ``remat`` a block's scores and a layer's inside
are recomputed in the backward pass.
``products`` rounds the operands of every matrix product but the
router's to a lower precision, to measure what such a change does to the
result.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32

# the published sizes (config.json of arcee-ai/Trinity-Mini); a test
# passes its own
PUBLISHED = dict(
    vocab_size=200192, hidden_size=2048, num_hidden_layers=32,
    num_dense_layers=2, num_attention_heads=32, num_key_value_heads=4,
    head_dim=128, rope_theta=10000.0, rms_norm_eps=1e-5,
    sliding_window=2048, global_attn_every_n_layers=4,
    intermediate_size=6144, moe_intermediate_size=1024, num_experts=128,
    num_experts_per_tok=8, num_shared_experts=1, route_norm=True,
    route_scale=2.826, mup_enabled=True)

Params = Dict[str, Dict[str, Any]]


def layer_types(cfg):
    """``layer_types`` as the config lists them, or by its rule: every
    ``global_attn_every_n_layers``-th layer is full, the rest slide."""
    if "layer_types" in cfg:
        return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    n = cfg["global_attn_every_n_layers"]
    return ["full_attention" if (i + 1) % n == 0 else "sliding_attention"
            for i in range(cfg["num_hidden_layers"])]


def mm(a, b, products: Optional[str]):
    """``a @ b``; with ``products`` both operands are first rounded to
    that dtype (the product itself stays float32)."""
    if products is not None:
        a = a.astype(products).astype(F32)
        b = b.astype(products).astype(F32)
    return jnp.matmul(a, b)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta):
    """x: (time, heads, dim): ``x cos + rotate_half(x) sin`` with the
    angles ``pos * theta^(-2i/dim)`` repeated over the two halves."""
    t, dim = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def swiglu(x, gate, up, down, products):
    return mm(jax.nn.silu(mm(x, gate, products)) * mm(x, up, products),
              down, products)


def attention(p, x, cfg, sliding, products, q_block, remat):
    """x: (time, hidden) of ONE sequence."""
    t = x.shape[0]
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    q = mm(x, p["wq"], products).reshape(t, h, d)
    k = mm(x, p["wk"], products).reshape(t, g, d)
    v = mm(x, p["wv"], products).reshape(t, g, d)
    gate = mm(x, p["wg"], products)
    q = rms_norm(q, p["qnorm"], cfg["rms_norm_eps"])
    k = rms_norm(k, p["knorm"], cfg["rms_norm_eps"])
    if sliding:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    # query head i reads key/value head i // (h / g)
    k, v = jnp.repeat(k, h // g, axis=1), jnp.repeat(v, h // g, axis=1)
    scale = 1.0 / math.sqrt(d)

    def rows(q_rows, first):
        """The queries from position ``first`` on, against all keys."""
        s = mm(q_rows.transpose(1, 0, 2), k.transpose(1, 2, 0),
               products) * scale                       # (h, rows, t)
        i = first + jnp.arange(q_rows.shape[0])[:, None]
        j = jnp.arange(t)[None, :]
        seen = 0 <= i - j
        if sliding:
            seen = seen & (i - j < cfg["sliding_window"])
        s = jnp.where(seen[None], s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v.transpose(1, 0, 2),
                  products).transpose(1, 0, 2)         # (rows, h, d)

    if remat:
        rows = jax.checkpoint(rows)
    bq = q_block or t
    if bq == t:
        o = rows(q, 0)
    else:
        # one block after the other (the compiler, left to itself, runs
        # the blocks side by side: 12 GB of scores at 32 heads x 8,192)
        o = jax.lax.map(lambda block: rows(*block), (
            q.reshape(t // bq, bq, h, d), jnp.arange(0, t, bq)))
    return mm(o.reshape(t, h * d) * jax.nn.sigmoid(gate), p["wo"], products)


def moe(p, bias, x, cfg, held, products, router_dtype=None):
    """x: (tokens, hidden). ``held = (first, count)``: the experts whose
    weights ``p`` carries; None: all of them."""
    n_exp, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    first, count = held if held is not None else (0, n_exp)
    xr, wr = x, p["router"]
    if router_dtype is not None:        # what a lower-precision router does
        xr, wr = (a.astype(router_dtype).astype(F32) for a in (xr, wr))
    s = jax.nn.sigmoid(jnp.matmul(xr, wr))                       # (n, E)
    # DEPARTURE: the expert bias b is given and fixed (load_balance_coeff
    # is the trainer's update rule, not applied); it only chooses, it
    # does not weigh
    _, picks = jax.lax.top_k(s + bias[None, :], k)
    picked = jnp.sum(jax.nn.one_hot(picks, n_exp, dtype=F32), axis=1)
    w = s * picked
    if cfg["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["route_scale"]
    out = swiglu(x, p["sgate"], p["sup"], p["sdown"], products) \
        if "sgate" in p else jnp.zeros_like(x)
    for e in range(count):
        out = out + w[:, first + e, None] * swiglu(
            x, p["egate"][e], p["eup"][e], p["edown"][e], products)
    return out


def sequence_loss(params: Params, biases, ids, labels, cfg, held=None,
                  products=None, router_dtype=None, q_block=None,
                  remat=False):
    """Mean next-token cross-entropy of ONE sequence: ids, labels (time,).
    DEPARTURE: the sequence is one document (no packing or padding mask)."""
    eps = cfg["rms_norm_eps"]

    def layer(x, p_attn, p_norms, p_ffn, bias, sliding):
        a = attention(p_attn, rms_norm(x, p_norms[0], eps), cfg, sliding,
                      products, q_block, remat)
        h = x + rms_norm(a, p_norms[1], eps)
        z = rms_norm(h, p_norms[2], eps)
        if bias is None:
            f = swiglu(z, p_ffn["wgate"], p_ffn["wup"], p_ffn["wdown"],
                       products)
        else:
            f = moe(p_ffn, bias, z, cfg, held, products, router_dtype)
        return h + rms_norm(f, p_norms[3], eps)

    if remat:
        layer = jax.checkpoint(layer, static_argnums=(5,))
    x = params["embed"]["wmat"][ids]
    if cfg.get("mup_enabled", False):
        x = x * math.sqrt(cfg["hidden_size"])
    kinds = layer_types(cfg)
    for i in range(cfg["num_hidden_layers"]):
        dense = i < cfg["num_dense_layers"]
        x = layer(x, params["l%d_attn" % i],
                  tuple(params["l%d_%s" % (i, n)]["wmat"] for n in (
                      "attn_norm", "attn_post", "ffn_norm", "ffn_post")),
                  params["l%d_mlp" % i if dense else "l%d_moe" % i],
                  None if dense else biases["l%d_moe" % i],
                  kinds[i] == "sliding_attention")

    def head(x):
        logits = mm(rms_norm(x, params["final_norm"]["wmat"], eps),
                    params["head"]["wmat"], products)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None],
                                             axis=-1))

    return (jax.checkpoint(head) if remat else head)(x)


def loss(params: Params, biases, ids, labels, cfg, **kw):
    """Mean over the batch's sequences, one after the other (they share
    nothing): ids, labels (batch, time) integers."""
    with jax.default_matmul_precision("highest"):
        per_seq = [sequence_loss(params, biases, ids[b], labels[b], cfg,
                                 **kw) for b in range(ids.shape[0])]
        return sum(per_seq) / len(per_seq)


def loss_and_grad(params: Params, biases, ids, labels, cfg, **kw):
    return jax.value_and_grad(loss)(params, biases, ids, labels, cfg, **kw)


def adam_init(params: Params):
    return {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
            "v": jax.tree_util.tree_map(jnp.zeros_like, params)}


def adam_step(params: Params, grads: Params, state, t: int, lr: float,
              beta1: float = 0.9, beta2: float = 0.95):
    """DEPARTURE: Arcee trained Trinity with Muon, which this repository
    has not; this is Adam as its updater computes it (updater/__init__.py:
    AdamUpdater, after cxxnet's adam_updater): the bias corrections folded
    into the rate, ``lr_t = lr * sqrt(1 - beta2^t) / (1 - beta1^t)``, and
    ``eps = 1e-8`` added to ``sqrt(v)`` uncorrected. ``t`` counts from 1.
    No weight decay."""
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
