"""Plain reference of Qwen3-Next's decoder (Qwen, ``model_type:
qwen3_next``): forward, loss, gradients and Adam, in straightforward
``jax.numpy``, float32, under ``jax.default_matmul_precision("highest")``.
No kernel, no cache, no chunked form: the delta rule runs a position at a
time, exactly as the recurrence is written; the convolution is the
library's own, one group a channel; routing is a dense one-hot over all
experts; the
attention's scores are full rows with the mask written as the inequality
it is. It follows the published config's keys and, for what they do not
say, ``modeling_qwen3_next.py`` of ``transformers``:

    h = embed(ids)
    per layer   h = h + Mixer(RMSNorm(h));  h = h + MoE(RMSNorm(h))
    Mixer, layer l with (l + 1) % full_attention_interval != 0: Gated
    DeltaNet
                [q | k | v] = x Wqkv: linear_num_key_heads heads of
                    linear_key_head_dim for q and for k,
                    linear_num_value_heads of linear_value_head_dim for v
                z = x Wz (as v);  b = x Wb, a = x Wa (one a value head)
                [q | k | v] <- silu(conv): y_t = sum_i w_i x_(t-K+1+i),
                    i < K = linear_conv_kernel_dim, a channel alone,
                    zeros before the sequence, no bias
                beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
                q, k <- x / sqrt(sum x^2 + 1e-6) a head;  q <- q / sqrt(dk)
                a value head (its key head: head // (value / key heads))
                carries S (dk x dv), zeros at the start, along time:
                    S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t)
                    S <- S + k_t u^T;  o_t = S^T q_t
                y = ((RMSNorm over dv of o) * w_n * silu(z)) Wo
    Mixer, the other layers: gated attention
                q = x Wq -> num_attention_heads heads of head_dim
                k = x Wk, v = x Wv -> num_key_value_heads heads
                g = x Wg -> num_attention_heads x head_dim
                q, k <- RMSNorm over a head's features (one scale each)
                RoPE (x cos + rotate_half(x) sin, rope_theta) on the first
                    partial_rotary_factor x head_dim features of q and k,
                    the others passed through
                o = softmax(q k^T / sqrt(head_dim)) v over 0 <= i - j,
                    query head h against key/value head h // (heads / kv)
                y = (o * sigmoid(g)) Wo                    no biases
    MoE         p = softmax(x Wr) over all num_experts, float32
                top-k of p;  w_i = p_i / sum_picked p_j   (norm_topk_prob)
                sum_i w_i E_i(x) + sigmoid(x w_s) S(x)   one shared expert
    logits = RMSNorm(h) Whead (untied), mean next-token cross-entropy

Parameters come as the program's own pytree (``{layer key: {tag: array}}``
with the keys ``cxxnet_tpu.models.qwen3_next.qwen3_next_lm`` gives) so
that both sides can start from the same seeded weights; nothing else is
shared with the code under test.

A chip's share: ``held = (first, count)`` names the experts whose weights
``params`` carries (``egate`` etc. have ``count`` leading entries); the
router still scores all ``num_experts`` and what absent experts would add
is left out. ``None`` means all experts: the uncut layer. The vocabulary
slice is whatever rows ``embed`` and ``head`` carry.

Departures from ``modeling_qwen3_next.py``, each marked DEPARTURE below:
an RMSNorm's scale is stored as the factor itself, from 1, where the
model stores ``w`` from 0 and multiplies by ``1 + w`` (the same function
and, without weight decay, the same Adam trajectory); ``q_proj``'s two
halves are two matrices, ``Wq`` and ``Wg``; the multi-token-prediction
module and the router's auxiliary loss are not built; one document a
sequence (no packing mask, no padding mask, the state starts at zero);
Adam is this repository's updater formula. ``q_block``, ``remat`` and
``stretch`` change no value: they bound memory so that the benchmark can
run this file at the published widths (``benchmarks/reference/`` holds a
copy): with ``q_block`` the queries go through the attention a block
after the other (``lax.map``); with ``remat`` a block's scores, a layer's
inside and each ``stretch`` positions of the recurrence are recomputed in
the backward pass, so that its gradient keeps a state a stretch and not a
state a position.
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

# the published sizes (config.json of Qwen/Qwen3-Next-80B-A3B-Instruct);
# a test passes its own
PUBLISHED = dict(
    vocab_size=151936, hidden_size=2048, num_hidden_layers=48,
    full_attention_interval=4, num_attention_heads=16,
    num_key_value_heads=2, head_dim=256, partial_rotary_factor=0.25,
    rope_theta=10000000.0, rms_norm_eps=1e-6, linear_num_key_heads=16,
    linear_num_value_heads=32, linear_key_head_dim=128,
    linear_value_head_dim=128, linear_conv_kernel_dim=4,
    moe_intermediate_size=512, shared_expert_intermediate_size=512,
    num_experts=512, num_experts_per_tok=10, norm_topk_prob=True)

Params = Dict[str, Dict[str, Any]]


def is_full_attention(cfg, layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


def lower(a, products: Optional[str]):
    """``a`` rounded to the dtype ``products`` names and back."""
    return a if products is None else a.astype(products).astype(F32)


def mm(a, b, products: Optional[str]):
    """``a @ b``; with ``products`` both operands are first rounded to
    that dtype (the product itself stays float32)."""
    return jnp.matmul(lower(a, products), lower(b, products))


def rms_norm(x, weight, eps):
    # DEPARTURE: the scale as the factor itself (from 1), not 1 + w
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta, dim):
    """x: (time, heads, head_dim): ``x cos + rotate_half(x) sin`` on the
    first ``dim`` features, the angles ``pos * theta^(-2i/dim)`` repeated
    over that part's two halves; the other features as they are."""
    t = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    turned = x[..., :dim] * jnp.cos(ang) + rotate_half(x[..., :dim]) \
        * jnp.sin(ang)
    return jnp.concatenate([turned, x[..., dim:]], axis=-1)


def swiglu(x, gate, up, down, products):
    return mm(jax.nn.silu(mm(x, gate, products)) * mm(x, up, products),
              down, products)


def causal_conv(x, taps, products):
    """x: (time, channels), taps: (K, channels): ``y_t = sum_i taps[i]
    x[t - K + 1 + i]``, zeros before the sequence, a channel alone: the
    library's convolution with one group a channel, padded on the left
    (the program sums K shifted products instead: XLA's depthwise
    convolution is slow on the chip, PERF.md, PR 34)."""
    kernel, channels = taps.shape
    return jax.lax.conv_general_dilated(
        lower(x, products)[None], lower(taps, products)[:, None, :], (1,),
        [(kernel - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=channels)[0]


def delta_rule(q, k, v, g, beta, products, stretch, remat):
    """The recurrence as written, a position at a time. q, k: (time,
    value heads, dk) (a key head already beside each of its value heads),
    v: (time, value heads, dv), g, beta: (time, value heads)."""
    t, heads, dk = q.shape
    dv = v.shape[-1]

    def position(state, now):
        q_t, k_t, v_t, g_t, beta_t = now
        state = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum("hkv,hk->hv", lower(state, products),
                          lower(k_t, products))
        u = beta_t[:, None] * (v_t - read)
        state = state + jnp.einsum("hk,hv->hkv", lower(k_t, products),
                                   lower(u, products))
        return state, jnp.einsum("hkv,hk->hv", lower(state, products),
                                 lower(q_t, products))

    def positions(state, some):
        return jax.lax.scan(position, state, some)

    xs = (q, k, v, g, beta)
    start = jnp.zeros((heads, dk, dv), F32)
    if not remat or not stretch or t % stretch:
        return positions(start, xs)[1]
    xs = tuple(a.reshape((t // stretch, stretch) + a.shape[1:]) for a in xs)
    _, o = jax.lax.scan(jax.checkpoint(positions), start, xs)
    return o.reshape(t, heads, dv)


def gated_delta_net(p, x, cfg, products, stretch, remat):
    """x: (time, hidden) of ONE sequence."""
    t = x.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    qkv = jax.nn.silu(causal_conv(mm(x, p["wqkv"], products), p["conv"],
                                  products))
    z = mm(x, p["wz"], products).reshape(t, hv, dv)
    beta = jax.nn.sigmoid(mm(x, p["wb"], products))
    g = -jnp.exp(p["alog"]) * jax.nn.softplus(
        mm(x, p["wa"], products) + p["dtbias"])
    q = l2_norm(qkv[:, :hk * dk].reshape(t, hk, dk)) / math.sqrt(dk)
    k = l2_norm(qkv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk))
    v = qkv[:, 2 * hk * dk:].reshape(t, hv, dv)
    # value head i reads key head i // (hv / hk)
    q, k = jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1)
    o = delta_rule(q, k, v, g, beta, products, stretch, remat)
    y = rms_norm(o, p["norm"], cfg["rms_norm_eps"]) * jax.nn.silu(z)
    return mm(y.reshape(t, hv * dv), p["wo"], products)


def attention(p, x, cfg, products, q_block, remat):
    """x: (time, hidden) of ONE sequence."""
    t = x.shape[0]
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    # DEPARTURE: q_proj's two halves as two matrices, Wq and Wg
    q = mm(x, p["wq"], products).reshape(t, h, d)
    k = mm(x, p["wk"], products).reshape(t, g, d)
    v = mm(x, p["wv"], products).reshape(t, g, d)
    gate = mm(x, p["wg"], products)
    q = rms_norm(q, p["qnorm"], cfg["rms_norm_eps"])
    k = rms_norm(k, p["knorm"], cfg["rms_norm_eps"])
    turned = int(cfg["partial_rotary_factor"] * d)
    q, k = (rope(a, cfg["rope_theta"], turned) for a in (q, k))
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
    return mm(o.reshape(t, h * d) * jax.nn.sigmoid(gate), p["wo"], products)


def moe(p, x, cfg, held, products, router_dtype=None):
    """x: (tokens, hidden). ``held = (first, count)``: the experts whose
    weights ``p`` carries; None: all of them."""
    n_exp, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    first, count = held if held is not None else (0, n_exp)
    xr, wr = x, p["router"]
    if router_dtype is not None:        # what a lower-precision router does
        xr, wr = (a.astype(router_dtype).astype(F32) for a in (xr, wr))
    prob = jax.nn.softmax(jnp.matmul(xr, wr), axis=-1)           # (n, E)
    _, picks = jax.lax.top_k(prob, k)
    w = prob * jnp.sum(jax.nn.one_hot(picks, n_exp, dtype=F32), axis=1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    out = jax.nn.sigmoid(mm(x, p["sharedgate"], products)) * swiglu(
        x, p["sgate"], p["sup"], p["sdown"], products)

    def add(out, held_expert):
        gate, up, down, w_e = held_expert
        return out + w_e[:, None] * swiglu(x, gate, up, down, products), None

    # every held expert over every token, one after the other
    return jax.lax.scan(add, out, (p["egate"], p["eup"], p["edown"],
                                   w[:, first:first + count].T))[0]


def sequence_loss(params: Params, ids, labels, cfg, held=None,
                  products=None, router_dtype=None, q_block=None,
                  remat=False, stretch=64):
    """Mean next-token cross-entropy of ONE sequence: ids, labels (time,).
    DEPARTURE: the sequence is one document (no packing or padding mask;
    the delta rule's state starts at zero). DEPARTURE: no multi-token
    prediction, no auxiliary router loss."""
    eps = cfg["rms_norm_eps"]

    def layer(x, p_mixer, p_norms, p_moe, full):
        z = rms_norm(x, p_norms[0], eps)
        if full:
            a = attention(p_mixer, z, cfg, products, q_block, remat)
        else:
            a = gated_delta_net(p_mixer, z, cfg, products, stretch, remat)
        h = x + a
        return h + moe(p_moe, rms_norm(h, p_norms[1], eps), cfg, held,
                       products, router_dtype)

    if remat:
        layer = jax.checkpoint(layer, static_argnums=(4,))
    x = jnp.take(params["embed"]["wmat"], ids, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        full = is_full_attention(cfg, i)
        x = layer(x, params["l%d_attn" % i if full else "l%d_delta" % i],
                  tuple(params["l%d_%s" % (i, n)]["wmat"]
                        for n in ("attn_norm", "ffn_norm")),
                  params["l%d_moe" % i], full)

    def head(x):
        logits = mm(rms_norm(x, params["final_norm"]["wmat"], eps),
                    params["head"]["wmat"], products)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None],
                                             axis=-1))

    return (jax.checkpoint(head) if remat else head)(x)


def loss(params: Params, biases, ids, labels, cfg, **kw):
    """Mean over the batch's sequences, one after the other (they share
    nothing; ``lax.map``, so that the program holds one sequence's layers
    and not the batch's): ids, labels (batch, time) integers. ``biases`` is what the
    program's expert layers carry as state for families whose router has
    a bias; this one has none, and nothing reads it."""
    del biases
    with jax.default_matmul_precision("highest"):
        return jnp.mean(jax.lax.map(
            lambda one: sequence_loss(params, one[0], one[1], cfg, **kw),
            (ids, labels)))


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
