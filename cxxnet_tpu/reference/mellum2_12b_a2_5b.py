"""Plain reference of Mellum2's decoder (JetBrains, ``model_type: mellum``):
forward, loss, gradients and Adam, in straightforward ``jax.numpy``,
float32, under ``jax.default_matmul_precision("highest")``. No kernel, no
cache, no ``shard_map`` and no collective written by hand: routing is a
dense weighting of every expert over every token; the attention's scores
are full rows with the mask written as the inequality it is. It follows the
published config's keys and, for what they do not say, the attention and
expert layer of ``transformers``' Qwen3-MoE family, whose keys the config
uses. ``N`` is RMSNorm with a learned scale from 1, ``x * rsqrt(mean(x^2) +
rms_norm_eps) * w``:

    h = embed(ids)                                       no scale
    per layer   h = h + Attn(N(h));  h = h + MoE(N(h))
    Attn        q = u Wq -> num_attention_heads heads of head_dim
                k = u Wk, v = u Wv -> num_key_value_heads heads
                q, k <- N over a head's features (one scale each)
                RoPE (x cos + rotate_half(x) sin) on all of a head:
                    layer_types[l] == "sliding_attention": the plain
                    angles pos * theta^(-2i/head_dim), and query i sees
                    key j iff 0 <= i - j < sliding_window;
                    "full_attention": YaRN (``yarn_inv_freq``), cos and
                    sin times attention_factor, every earlier key
                o = softmax(q k^T / sqrt(head_dim)) v, query head h
                    against key/value head h // (heads / kv heads)
                y = o Wo                     no output gate, no biases
    MoE         s = softmax(u Wr) over all num_experts, float32
                picks = top-k of s;  w_e = s_e / sum_picked s
                sum_picked w_e E_e(u), E_e a SwiGLU of
                    moe_intermediate_size; no shared expert, no bias
    logits = N(h) Whead (untied), mean next-token cross-entropy

Parameters come as the program's own pytree (``{layer key: {tag: array}}``
with the keys ``cxxnet_tpu.models.mellum2.mellum2_lm`` gives) so that both
sides can start from the same seeded weights; nothing else is shared with
the code under test. ``cfg["layer_types"]`` lists the layers held, in
order; the vocabulary slice is whatever rows ``embed`` and ``head`` carry.
Every expert is held: the experts' tensors may lie over several devices
(sharded on their leading axis), and the partitioner places the work.

Departures from the model, each marked DEPARTURE below: Adam is this
repository's updater formula; one document a sequence (no packing mask,
no padding mask). ``q_block`` and ``remat`` change no value: they bound
memory so that the benchmark can run this file at the published widths
(``benchmarks/reference/`` holds a copy): with ``q_block`` the queries go
through the attention a block after the other (``lax.map``), with
``remat`` a block's scores and a layer's inside are recomputed in the
backward pass; the expert layer takes ``q_block`` tokens at a time the
same way. ``products`` rounds the operands of every matrix product
but the router's to a lower precision, to measure what such a change does
to the result.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32

# the published sizes (config.json of JetBrains/Mellum2-12B-A2.5B-Instruct);
# a test passes its own
PUBLISHED = dict(
    vocab_size=98304, hidden_size=2304, num_hidden_layers=28,
    layer_types=tuple("full_attention" if i % 4 == 3 else "sliding_attention"
                      for i in range(28)),
    num_attention_heads=32, num_key_value_heads=4, head_dim=128,
    sliding_window=1024, rms_norm_eps=1e-6, moe_intermediate_size=896,
    num_experts=64, num_experts_per_tok=8, norm_topk_prob=True,
    rope_parameters={
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16, "original_max_position_embeddings":
                           8192, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}})

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


def yarn_inv_freq(dim: int, rope: Dict[str, Any]):
    """YaRN's inverse frequencies as ``transformers`` computes them
    (``_compute_yarn_parameters``, ``truncate`` true), written from the
    formula: the pairs whose wavelength fits ``beta_fast`` turns or more
    into the original length keep ``theta^(-2i/dim)``, those that fit
    ``beta_slow`` turns or fewer take it over ``factor``, linearly between
    the two correction dimensions."""
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def correction_dim(turns):
        # the pair index whose wavelength is original / turns
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    keep = 1.0 - ramp                   # the extrapolation's share
    return interpolation * (1.0 - keep) + extrapolation * keep


def rope(x, rope_cfg: Dict[str, Any]):
    """x: (time, heads, dim): ``x cos + rotate_half(x) sin``, the angles
    repeated over the two halves; YaRN scales cos and sin."""
    t, _, d = x.shape
    if rope_cfg["rope_type"] == "yarn":
        inv, scale = yarn_inv_freq(d, rope_cfg), \
            float(rope_cfg["attention_factor"])
    else:
        inv = 1.0 / (float(rope_cfg["rope_theta"])
                     ** (jnp.arange(0, d, 2, dtype=F32) / d))
        scale = 1.0
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    return x * (jnp.cos(ang) * scale) + rotate_half(x) * (jnp.sin(ang)
                                                          * scale)


def swiglu(x, gate, up, down, products):
    return mm(jax.nn.silu(mm(x, gate, products)) * mm(x, up, products),
              down, products)


def attention(p, x, cfg, kind, products, q_block, remat):
    """x: (time, hidden) of ONE sequence; ``kind`` the layer's type."""
    t = x.shape[0]
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    eps, rope_cfg = cfg["rms_norm_eps"], cfg["rope_parameters"][kind]
    q = mm(x, p["wq"], products).reshape(t, h, d)
    k = mm(x, p["wk"], products).reshape(t, g, d)
    v = mm(x, p["wv"], products).reshape(t, g, d)
    q = rope(rms_norm(q, p["qnorm"], eps), rope_cfg)
    k = rope(rms_norm(k, p["knorm"], eps), rope_cfg)
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
        if kind == "sliding_attention":
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
        # the blocks side by side)
        o = jax.lax.map(lambda block: rows(*block), (
            q.reshape(t // bq, bq, h, d), jnp.arange(0, t, bq)))
    return mm(o.reshape(t, h * d), p["wo"], products)


def moe(p, x, cfg, products, router_dtype=None, block=None, remat=False):
    """x: (tokens, hidden), every expert held; with ``block``, that many
    tokens at a time (``lax.map``), each block recomputed in the backward
    pass under ``remat``."""
    n = x.shape[0]
    if block and block < n:
        one = functools.partial(moe, p, cfg=cfg, products=products,
                                router_dtype=router_dtype)
        if remat:
            one = jax.checkpoint(one)
        return jax.lax.map(one, x.reshape(n // block, block, -1)).reshape(
            n, -1)
    n_exp, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    xr, wr = x, p["router"]
    if router_dtype is not None:        # what a lower-precision router does
        xr, wr = (a.astype(router_dtype).astype(F32) for a in (xr, wr))
    s = jax.nn.softmax(jnp.matmul(xr, wr), axis=-1)              # (n, E)
    _, picks = jax.lax.top_k(s, k)
    w = s * jnp.sum(jax.nn.one_hot(picks, n_exp, dtype=F32), axis=1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    # every expert over every token, weighted by w (zero where not
    # picked): the experts' axis is the one their tensors lie over
    xe = lower(x, products)
    a = jnp.einsum("nd,edf->enf", xe, lower(p["egate"], products))
    u = jnp.einsum("nd,edf->enf", xe, lower(p["eup"], products))
    y = jnp.einsum("enf,efd->end", lower(jax.nn.silu(a) * u, products),
                   lower(p["edown"], products))
    return jnp.einsum("ne,end->nd", w, y)


def sequence_loss(params: Params, ids, labels, cfg, products=None,
                  router_dtype=None, q_block=None, remat=False):
    """Mean next-token cross-entropy of ONE sequence: ids, labels (time,).
    DEPARTURE: the sequence is one document (no packing or padding mask)."""
    eps = cfg["rms_norm_eps"]

    def layer(x, p_attn, p_norms, p_moe, kind):
        h = x + attention(p_attn, rms_norm(x, p_norms[0], eps), cfg, kind,
                          products, q_block, remat)
        return h + moe(p_moe, rms_norm(h, p_norms[1], eps), cfg, products,
                       router_dtype, q_block, remat)

    if remat:
        layer = jax.checkpoint(layer, static_argnums=(4,))
    x = jnp.take(params["embed"]["wmat"], ids, axis=0)
    kinds = tuple(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        x = layer(x, params["l%d_attn" % i],
                  tuple(params["l%d_%s" % (i, n)]["wmat"]
                        for n in ("attn_norm", "ffn_norm")),
                  params["l%d_moe" % i], kind)

    def head(x):
        logits = mm(rms_norm(x, params["final_norm"]["wmat"], eps),
                    params["head"]["wmat"], products)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None],
                                             axis=-1))

    return (jax.checkpoint(head) if remat else head)(x)


def loss(params: Params, ids, labels, cfg, **kw):
    """Mean over the batch's sequences, one after the other (they share
    nothing; ``lax.map``, so that the program holds one sequence's layers
    and not the batch's): ids, labels (batch, time) integers."""
    with jax.default_matmul_precision("highest"):
        return jnp.mean(jax.lax.map(
            lambda one: sequence_loss(params, one[0], one[1], cfg, **kw),
            (ids, labels)))


def loss_and_grad(params: Params, ids, labels, cfg, **kw):
    return jax.value_and_grad(loss)(params, ids, labels, cfg, **kw)


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


def train_steps(params: Params, ids, labels, cfg, steps: int, lr: float,
                beta1: float = 0.9, beta2: float = 0.95, **kw
                ) -> Tuple[Params, list]:
    """``steps`` Adam updates on one batch; returns the parameters after
    them and each step's loss (taken before its update)."""
    state, losses = adam_init(params), []
    for t in range(1, steps + 1):
        value, grads = loss_and_grad(params, ids, labels, cfg, **kw)
        losses.append(value)
        params, state = adam_step(params, grads, state, t, lr, beta1, beta2)
    return params, losses
