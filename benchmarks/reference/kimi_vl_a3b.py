"""Plain reference of Kimi-VL-A3B-Instruct's language model: forward,
loss, gradients and Adam, in straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``. No scan, no kernel, no cache;
routing is a dense one-hot over all experts. It follows the published
block (DeepSeek-V3's, at this model's keys):

    per layer   h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    Attn (MLA, q_lora_rank null)
                q = x Wq -> heads of [q_nope | q_rope]
                [c | k_r] = x Wkva;  c' = RMSNorm(c)
                [k_nope | v] = c' Wkvb per head
                RoPE (interleaved pairs) on q_rope and the shared k_r
                softmax(q k^T / sqrt(d_nope + d_rope)) v, causal;  Wo
    FFN, layers < first_k_dense:  Wdown(silu(Wgate x) * Wup x)
    FFN, the rest:  s = sigmoid(x Wr);  top-k of s + b;
                w_i = scale * s_i / sum_picked s_j;
                sum_i w_i E_i(x) + S(x)
    final RMSNorm, untied head, mean next-token cross-entropy

Parameters come as the program's own pytree (``{layer key: {tag: array}}``
with the keys ``cxxnet_tpu.models.kimi_vl.decoder_lm`` gives) so that both
sides can start from the same seeded weights; nothing else is shared
with the code under test.

A chip's share: ``held = (first, count)`` names the experts whose weights
``params`` carries (``egate`` etc. have ``count`` leading entries); the
router still scores all of them and what absent experts would add is left
out. ``None`` means all experts: the uncut layer. The vocabulary slice is
whatever rows ``embed`` and ``head`` carry: a sliced vocabulary is a
smaller vocabulary.

Departures from the published model, each marked DEPARTURE below: the
``noaux_tc`` bias is given and fixed; no ``seq_aux`` balance loss; Adam is
this repository's updater formula. ``q_block`` and ``remat`` change no
value: they bound memory so that the benchmark can run this file at the
published widths (``benchmarks/reference/`` holds a copy). ``products``
rounds the operands of every matrix product but the router's to a lower
precision, to measure what such a change does to the result.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32

# the published sizes (config.json of moonshotai/Kimi-VL-A3B-Instruct,
# text_config); a test passes its own
PUBLISHED = dict(
    vocab_size=163840, hidden_size=2048, num_hidden_layers=27,
    first_k_dense_replace=1, num_attention_heads=16, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
    rope_theta=800000.0, rms_norm_eps=1e-5, intermediate_size=11264,
    moe_intermediate_size=1408, n_routed_experts=64, num_experts_per_tok=6,
    n_shared_experts=2, routed_scaling_factor=2.446, norm_topk_prob=True)

Params = Dict[str, Dict[str, Any]]


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


def rope(x, theta):
    """x: (time, ..., dim). The pair (x[2i], x[2i+1]) is rotated by
    pos * theta^(-2i/dim) and stays where it was."""
    t, dim = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (dim // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    re = even * jnp.cos(ang) - odd * jnp.sin(ang)
    im = odd * jnp.cos(ang) + even * jnp.sin(ang)
    return jnp.stack([re, im], axis=-1).reshape(x.shape)


def swiglu(x, gate, up, down, products):
    return mm(jax.nn.silu(mm(x, gate, products)) * mm(x, up, products),
              down, products)


def attention(p, x, cfg, products, q_block, remat):
    """x: (time, hidden) of ONE sequence."""
    t = x.shape[0]
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    q = mm(x, p["wq"], products).reshape(t, h, dn + dr)
    ckr = mm(x, p["wkva"], products)
    c = rms_norm(ckr[:, :rank], p["kvnorm"], cfg["rms_norm_eps"])
    kv = mm(c, p["wkvb"], products).reshape(t, h, dn + dv)
    k_r = rope(ckr[:, rank:], cfg["rope_theta"])               # (t, dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], cfg["rope_theta"])],
                        axis=-1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_r[:, None, :], (t, h, dr))],
                        axis=-1)
    v = kv[..., dn:]
    scale = 1.0 / math.sqrt(dn + dr)

    def rows(q_rows, first):
        """The queries from position ``first`` on, against all keys."""
        s = mm(q_rows.transpose(1, 0, 2), k.transpose(1, 2, 0),
               products) * scale                       # (h, rows, t)
        pos = first + jnp.arange(q_rows.shape[0])
        s = jnp.where(jnp.arange(t)[None, None, :] <= pos[None, :, None],
                      s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v.transpose(1, 0, 2),
                  products).transpose(1, 0, 2)         # (rows, h, dv)

    if remat:
        rows = jax.checkpoint(rows, static_argnums=(1,))
    bq = q_block or t
    o = jnp.concatenate([rows(q[i:i + bq], i) for i in range(0, t, bq)],
                        axis=0)
    return mm(o.reshape(t, h * dv), p["wo"], products)


def moe(p, bias, x, cfg, held, products, router_dtype=None):
    """x: (tokens, hidden). ``held = (first, count)``: the experts whose
    weights ``p`` carries; None: all of them."""
    n_exp, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    first, count = held if held is not None else (0, n_exp)
    xr, wr = x, p["router"]
    if router_dtype is not None:        # what a lower-precision router does
        xr, wr = (a.astype(router_dtype).astype(F32) for a in (xr, wr))
    s = jax.nn.sigmoid(jnp.matmul(xr, wr))                       # (n, E)
    # DEPARTURE: the bias b is given and fixed (its update rate is not in
    # the published config); it only chooses, it does not weigh
    _, picks = jax.lax.top_k(s + bias[None, :], k)
    picked = jnp.sum(jax.nn.one_hot(picks, n_exp, dtype=F32), axis=1)
    w = s * picked
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    out = swiglu(x, p["sgate"], p["sup"], p["sdown"], products) \
        if "sgate" in p else jnp.zeros_like(x)
    for e in range(count):
        out = out + w[:, first + e, None] * swiglu(
            x, p["egate"][e], p["eup"][e], p["edown"][e], products)
    # DEPARTURE: no sequence-wise balance loss (seq_aux has no coefficient
    # in the published config)
    return out


def sequence_loss(params: Params, biases, ids, labels, cfg, held=None,
                  products=None, router_dtype=None, q_block=None,
                  remat=False):
    """Mean next-token cross-entropy of ONE sequence: ids, labels (time,)."""
    def layer(x, p_attn, p_norms, p_ffn, bias):
        h = x + attention(p_attn, rms_norm(x, p_norms[0],
                                           cfg["rms_norm_eps"]),
                          cfg, products, q_block, remat)
        z = rms_norm(h, p_norms[1], cfg["rms_norm_eps"])
        if bias is None:
            f = swiglu(z, p_ffn["wgate"], p_ffn["wup"], p_ffn["wdown"],
                       products)
        else:
            f = moe(p_ffn, bias, z, cfg, held, products, router_dtype)
        return h + f

    if remat:
        layer = jax.checkpoint(layer)
    x = params["embed"]["wmat"][ids]
    for i in range(cfg["num_hidden_layers"]):
        dense = i < cfg["first_k_dense_replace"]
        x = layer(x, params["l%d_attn" % i],
                  (params["l%d_attn_norm" % i]["wmat"],
                   params["l%d_ffn_norm" % i]["wmat"]),
                  params["l%d_mlp" % i if dense else "l%d_moe" % i],
                  None if dense else biases["l%d_moe" % i])

    def head(x):
        logits = mm(rms_norm(x, params["final_norm"]["wmat"],
                             cfg["rms_norm_eps"]),
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
