"""Layers over the sequence node ``(batch, time, features)``.

``embed`` turns a matrix of integer ids into a sequence node; ``rmsnorm``,
``add``, ``swiglu``, ``mla_attention``, ``gqa_attention``, ``gated_delta``,
``gated_conv`` and ``moe`` read and write one (``fullc`` and ``softmax``
take one too, see common.py and loss.py). Together they are the decoder
blocks of four families: DeepSeek-V3's (pre-norm residual, multi-head
latent attention, a sigmoid-routed expert layer with shared experts),
Arcee's ``afmoe`` (norms before and after each half, grouped-query
attention with QK norm, an output gate and a window on some layers, the
same expert layer), Qwen3-Next's (a gated delta-rule linear-attention
layer on three layers of four, gated attention with RoPE on part of a
head on the fourth, the expert layer routed by a softmax with a gate on
its shared expert) and LiquidAI's LFM2 (a double-gated short convolution on three layers of
four, grouped-query attention without an output gate on the fourth, the
expert layer with no shared expert, the head tied to the embedding).
``doc/sequence.md`` lists the config keys.

Mixed precision follows the rest of the zoo: ``dtype = bfloat16`` casts
matmul operands to bf16 (float32 accumulation on the MXU), masters stay
float32. Normalisations, the rotary embedding, attention's softmax, the
router's scores and the delta rule's decays, triangular solve and state
are computed in float32 whatever the dtype.

An expert layer is told which experts it holds (``expert_first``,
``expert_count``): it routes over all ``nexpert``, computes the part of
the result its own experts give plus the shared experts, and leaves out
what absent experts would add. Nothing stands in for other chips.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import pallas_kernels
from .base import DELTA_KEEPS, Layer, Shape3, seq_shape

_F32 = jnp.float32


def _expect_seq(name: str, s: Shape3) -> Shape3:
    if not s.is_seq:
        raise ValueError("%s: input must be a sequence node "
                         "(batch, time, features); put an embed layer first"
                         % name)
    return s


def _dot(x, w, cd):
    """``x @ w`` with both operands in the compute dtype; the result is
    in it too (float32 accumulation inside the MXU either way)."""
    return jnp.dot(x.astype(cd), w.astype(cd))


def rms_norm(x, weight, eps: float):
    """``x / sqrt(mean(x^2) + eps) * weight`` over the last axis, in
    float32, returned in ``x``'s dtype."""
    x32 = x.astype(_F32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * weight).astype(x.dtype)


def swiglu(x, wgate, wup, wdown, cd):
    """``(silu(x Wgate) * (x Wup)) Wdown``; the gate's product in
    float32."""
    a = _dot(x, wgate, cd).astype(_F32)
    u = _dot(x, wup, cd).astype(_F32)
    return _dot((jax.nn.silu(a) * u).astype(cd), wdown, cd)


class _SeqLayer(Layer):
    """Shared: the compute dtype, and FLOPs a sequence for the MFU
    count (nnet/net.py: analytic_flops_per_example)."""

    @property
    def cd(self):
        return jnp.bfloat16 if self.param.compute_dtype == "bfloat16" \
            else _F32

    def flops_per_example(self) -> float:
        return 0.0


class EmbedLayer(_SeqLayer):
    """Integer ids ``(batch, time)`` -> rows of the held vocabulary
    slice ``(batch, time, nhidden)``. ``nvocab`` is the rows held here;
    ids are in ``[0, nvocab)``. ``scale`` multiplies the rows (afmoe's
    ``sqrt(hidden)`` under ``mup_enabled``); 1 leaves them as they are.

    Applied to a sequence node of ``nhidden`` features (a second
    connection of the same layer, ``layer[a->b] = share[<its name>]``) it
    is the head tied to the embedding: ``h E^T``, logits over the held
    rows ``(batch, time, nvocab)``, no scale. Which of the two a
    connection does is what it reads, not a key; the one matrix
    ``wmat`` is in the tree once and its gradient is the sum of both
    uses (autodiff's, as for any shared layer)."""

    def __init__(self, cfg=()):
        self.nvocab = 0
        self.scale = 1.0
        self.tied_head = False
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "nvocab":
            self.nvocab = int(val)
        if name == "scale":
            self.scale = float(val)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        if self.nvocab <= 0 or self.param.num_hidden <= 0:
            raise ValueError("embed: must set nvocab and nhidden")
        if s.is_seq:
            # the tied head; the lookup's shapes stay the layer's own
            if s.x != self.param.num_hidden:
                raise ValueError(
                    "embed as a head: the sequence has %d features, the "
                    "rows %d" % (s.x, self.param.num_hidden))
            self.tied_head = True
            return [seq_shape(s.y, self.nvocab)]
        if not s.is_mat:
            raise ValueError("embed: input must be a matrix of ids "
                             "(input_shape = 1,1,<time>), or a sequence "
                             "node for the tied head")
        self.in_shapes = [s]
        self.out_shapes = [seq_shape(s.x, self.param.num_hidden)]
        return self.out_shapes

    def init_params(self, key):
        p = self.param
        return {"wmat": p.rand_init_weight(
            key, (self.nvocab, p.num_hidden), self.nvocab, p.num_hidden)}

    def forward(self, params, state, inputs, is_train, rng):
        ids = inputs[0]
        if ids.ndim == 3:       # hidden states, not ids: the tied head
            return [jnp.einsum("btd,vd->btv", ids.astype(self.cd),
                               params["wmat"].astype(self.cd))], state
        if not jnp.issubdtype(ids.dtype, jnp.integer):
            ids = ids.astype(jnp.int32)     # a float batch of whole numbers
        rows = jnp.take(params["wmat"].astype(self.cd), ids, axis=0)
        if self.scale != 1.0:
            rows = (rows.astype(_F32) * self.scale).astype(rows.dtype)
        return [rows], state


class RMSNormLayer(_SeqLayer):
    """Root-mean-square normalisation over the features, learned scale
    (tag ``wmat``, ones at start), no bias. ``eps`` default 1e-6."""

    def __init__(self, cfg=()):
        self.eps = 1e-6
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "eps":
            self.eps = float(val)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = self._expect_one(in_shapes)
        if not (s.is_seq or s.is_mat):
            raise ValueError("rmsnorm: input must be a sequence or a matrix")
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def init_params(self, key):
        return {"wmat": jnp.ones((self.in_shapes[0].x,), _F32)}

    def forward(self, params, state, inputs, is_train, rng):
        return [rms_norm(inputs[0], params["wmat"], self.eps)], state


class AddLayer(_SeqLayer):
    """n-to-1 elementwise sum of equal shapes: the join of a residual
    fork (reading one node from two layers is the fork). ``remat =
    block`` ends a recomputed segment after each one (nnet/net.py)."""

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        if len(in_shapes) < 2:
            raise ValueError("add: needs more than one input")
        if any(tuple(s) != tuple(in_shapes[0]) or s.is_seq
               != in_shapes[0].is_seq for s in in_shapes):
            raise ValueError("add: shape mismatch %r" % (in_shapes,))
        self.in_shapes = list(in_shapes)
        self.out_shapes = [in_shapes[0]]
        return self.out_shapes

    def forward(self, params, state, inputs, is_train, rng):
        return [functools.reduce(jnp.add, inputs)], state


class SwiGLULayer(_SeqLayer):
    """``Wdown(silu(Wgate x) * Wup x)``, no biases; ``nhidden`` is the
    inner width, the output has the input's features."""

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = _expect_seq("swiglu", self._expect_one(in_shapes))
        if self.param.num_hidden <= 0:
            raise ValueError("swiglu: must set nhidden")
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def init_params(self, key):
        p, d = self.param, self.in_shapes[0].x
        kg, ku, kd = jax.random.split(key, 3)
        w = p.num_hidden
        return {"wgate": p.rand_init_weight(kg, (d, w), d, w),
                "wup": p.rand_init_weight(ku, (d, w), d, w),
                "wdown": p.rand_init_weight(kd, (w, d), w, d)}

    def forward(self, params, state, inputs, is_train, rng):
        return [swiglu(inputs[0], params["wgate"], params["wup"],
                       params["wdown"], self.cd)], state

    def flops_per_example(self) -> float:
        s = self.in_shapes[0]
        return 6.0 * s.y * s.x * self.param.num_hidden


# -- multi-head latent attention ---------------------------------------------


def yarn_frequencies(dim: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float):
    """YaRN's rotary frequencies (``rope_type: yarn``, as ``transformers``'
    ``_compute_yarn_parameters`` gives them with ``truncate``), ``(dim/2,)``
    float32: ``inv_extra = theta^(-2i/dim)`` and ``inv_inter = inv_extra /
    factor``, blended by a ramp over ``i`` from ``floor(d(beta_fast))`` to
    ``ceil(d(beta_slow))``, ``d(r) = dim ln(original / (2 pi r)) / (2 ln
    theta)``: ``inv_inter * ramp + inv_extra * (1 - ramp)``, so the fast
    pairs keep their frequency and the slow ones are divided by
    ``factor``."""
    def at(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(at(beta_fast)), 0)
    high = min(math.ceil(at(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=_F32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=_F32) - low) / (high - low),
                    0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rope_tables(time: int, dim: int, theta: float, yarn=None):
    """cos and sin of ``pos * theta^(-2i/dim)``, each ``(time, dim/2)``,
    float32. ``yarn``, where given, is ``(factor,
    original_max_position_embeddings, beta_fast, beta_slow,
    attention_factor)``: the frequencies are ``yarn_frequencies``' and
    both tables are multiplied by ``attention_factor`` (so a score of q
    and k rotated by them carries its square)."""
    if yarn is not None:
        *keys, mult = yarn
        ang = jnp.arange(time, dtype=_F32)[:, None] \
            * yarn_frequencies(dim, theta, *keys)[None, :]
        return jnp.cos(ang) * mult, jnp.sin(ang) * mult
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=_F32) / dim))
    ang = jnp.arange(time, dtype=_F32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, halves: bool = False):
    """Rotate pairs of the last axis by the position's angle. ``x`` is
    ``(batch, time, ..., dim)``. By default the interleaved pairs
    ``(x[2i], x[2i+1])`` (DeepSeek's layout): the result holds the rotated
    even members in its first half and the odd ones in its second (queries
    and keys alike, so their products are those of the interleaved form).
    ``halves``: feature ``i`` pairs with ``i + dim/2`` (``x cos +
    rotate_half(x) sin``, the form ``transformers`` gives afmoe) and every
    member stays where it was. Float32 inside."""
    x32 = x.astype(_F32)
    if halves:
        half = x.shape[-1] // 2
        even, odd = x32[..., :half], x32[..., half:]
    else:
        even, odd = x32[..., 0::2], x32[..., 1::2]
    shape = (1, cos.shape[0]) + (1,) * (x.ndim - 3) + (cos.shape[1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([even * c - odd * s, odd * c + even * s],
                           axis=-1).astype(x.dtype)


def over_batch(mesh, fn, *args):
    """``fn(*args)``, where the mesh splits the batch (its ``data`` axis
    has more than one chip) inside ``shard_map`` over that axis: each
    chip calls ``fn`` on its own rows of every argument (all batch-major)
    and its result is its rows of the whole. A Pallas kernel must be
    called so: the partitioner cannot split a Mosaic kernel by itself."""
    if mesh is None or mesh.shape.get("data", 1) == 1:
        return fn(*args)
    spec = jax.sharding.PartitionSpec("data")
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * len(args),
                         out_specs=spec, check_vma=False)(*args)


def _attend(q, k, v, q0: int, scale: float, k0: int = 0, window: int = 0):
    """One block of queries, starting at position ``q0``, over the keys
    from position ``k0`` up to its end: causal ``softmax(q k^T * scale)
    v`` with float32 scores; with a ``window``, query ``i`` sees key ``j``
    iff ``0 <= i - j < window``. All ``(batch, heads, time, dim)``: heads
    beside the batch, so that every product is a plain batched matrix
    product with ``dim`` or ``time`` on the lanes (with heads minor the
    MXU's output used 16 of its 128 lanes and the step took 3.8 s; my chip
    run, PR 28)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=_F32)
    qi = q0 + jnp.arange(q.shape[2])[:, None]
    ki = jnp.arange(k0, k0 + k.shape[2])[None, :]
    seen = ki <= qi
    if window:
        seen = seen & (qi - ki < window)
    s = jnp.where(seen, s * scale, -1e30)
    m = jax.lax.optimization_barrier(
        jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def causal_attention(q, k, v, scale: float, q_block: int, window: int = 0):
    """Causal attention over ``(batch, heads, time, dim)`` in blocks of
    ``q_block`` queries, each against the keys up to its own end only (so
    about half the square is computed; with a ``window``, from the first
    key its first query sees), each recomputed in the backward pass: the
    largest score tensor alive is ``(batch, heads, q_block, time)``."""
    t = q.shape[2]
    bq = q_block if 0 < q_block < t else t
    if t % bq:
        raise ValueError("attention: q_block %d does not divide the "
                         "sequence length %d" % (bq, t))
    outs = []
    for i in range(t // bq):
        lo, hi = i * bq, (i + 1) * bq
        k0 = max(lo - window + 1, 0) if window else 0
        block = jax.checkpoint(functools.partial(
            _attend, q0=lo, scale=scale, k0=k0, window=window))
        outs.append(block(q[:, :, lo:hi], k[:, :, k0:hi], v[:, :, k0:hi]))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)


class MLAAttentionLayer(_SeqLayer):
    """Multi-head latent attention (DeepSeek-V2/V3), the form without a
    query low-rank (``q_lora_rank`` null), causal, no biases:

        q = x Wq            -> heads of [q_nope | q_rope]
        [c | k_r] = x Wkva  -> c of kv_lora_rank, one shared k_r
        [k_nope | v] = RMSNorm(c) Wkvb, per head
        RoPE on q_rope and k_r; k = [k_nope | k_r]
        y = softmax(q k^T / sqrt(d_nope + d_rope)) v Wo
    """

    sub_scopes = ("core",)

    def __init__(self, cfg=()):
        self.nhead = 0
        self.d_nope = 0
        self.d_rope = 0
        self.d_v = 0
        self.kv_rank = 0
        self.rope_theta = 10000.0
        self.eps = 1e-6
        self.q_block = 0
        self.fused_core = False
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "nhead":
            self.nhead = int(val)
        if name == "qk_nope_head_dim":
            self.d_nope = int(val)
        if name == "qk_rope_head_dim":
            self.d_rope = int(val)
        if name == "v_head_dim":
            self.d_v = int(val)
        if name == "kv_lora_rank":
            self.kv_rank = int(val)
        if name == "rope_theta":
            self.rope_theta = float(val)
        if name == "eps":
            self.eps = float(val)
        if name == "q_block":
            self.q_block = int(val)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = _expect_seq("mla_attention", self._expect_one(in_shapes))
        if min(self.nhead, self.d_nope, self.d_rope, self.d_v,
               self.kv_rank) <= 0 or self.d_rope % 2:
            raise ValueError(
                "mla_attention: must set nhead, qk_nope_head_dim, "
                "qk_rope_head_dim (even), v_head_dim, kv_lora_rank")
        # which core runs is what the shapes allow, not a key
        self.fused_core = pallas_kernels.causal_attention_applicable(
            s.y, self.q_block, (self.d_nope, self.d_rope), self.d_v)
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def _widths(self) -> Dict[str, Tuple[int, int]]:
        d, h = self.in_shapes[0].x, self.nhead
        return {"wq": (d, h * (self.d_nope + self.d_rope)),
                "wkva": (d, self.kv_rank + self.d_rope),
                "wkvb": (self.kv_rank, h * (self.d_nope + self.d_v)),
                "wo": (h * self.d_v, d)}

    def init_params(self, key):
        p = self.param
        out = {tag: p.rand_init_weight(k, shape, *shape)
               for (tag, shape), k in zip(
                   self._widths().items(), jax.random.split(key, 4))}
        out["kvnorm"] = jnp.ones((self.kv_rank,), _F32)
        return out

    def forward(self, params, state, inputs, is_train, rng):
        x, cd, h = inputs[0], self.cd, self.nhead
        b, t, _ = x.shape
        q = _dot(x, params["wq"], cd).reshape(
            b, t, h, self.d_nope + self.d_rope)
        q_nope, q_rope = q[..., :self.d_nope], q[..., self.d_nope:]
        ckr = _dot(x, params["wkva"], cd)
        c = rms_norm(ckr[..., :self.kv_rank], params["kvnorm"], self.eps)
        kv = _dot(c, params["wkvb"], cd).reshape(
            b, t, h, self.d_nope + self.d_v)
        k_nope, v = kv[..., :self.d_nope], kv[..., self.d_nope:]
        cos, sin = rope_tables(t, self.d_rope, self.rope_theta)
        q_rope = apply_rope(q_rope, cos, sin)
        k_rope = apply_rope(ckr[..., self.kv_rank:], cos, sin)
        heads = lambda a: a.transpose(0, 2, 1, 3)   # heads beside the batch
        scale = 1.0 / math.sqrt(self.d_nope + self.d_rope)
        with jax.named_scope("core"):
            if self.fused_core:
                # the score is q_nope k_nope^T + q_rope k_r^T, the one
                # shared k_r as it is
                o = pallas_kernels.causal_attention(
                    (heads(q_nope), heads(q_rope)),
                    (heads(k_nope), k_rope[:, None]), heads(v), scale,
                    self.q_block)
            else:
                q = heads(jnp.concatenate([q_nope, q_rope], axis=-1))
                k = heads(jnp.concatenate([k_nope, jnp.broadcast_to(
                    k_rope[:, :, None, :], (b, t, h, self.d_rope))], axis=-1))
                o = causal_attention(q, k, heads(v), scale, self.q_block)
        return [_dot(heads(o).reshape(b, t, h * self.d_v), params["wo"],
                     cd)], state

    def flops_per_example(self) -> float:
        """Projections, and the causal half of the square: a query sees
        (time + 1) / 2 keys on average."""
        t = self.in_shapes[0].y
        proj = sum(2.0 * a * b for a, b in self._widths().values())
        core = 2.0 * self.nhead * (self.d_nope + self.d_rope + self.d_v) \
            * (t + 1) / 2.0
        return t * (proj + core)


# -- grouped-query attention ---------------------------------------------------


class GQAAttentionLayer(_SeqLayer):
    """Grouped-query attention with QK norm and an output gate, as afmoe
    (Arcee's Trinity) and Qwen3-Next's full-attention layers have it, or
    without the gate (``gate = 0``: LFM2's), causal, no biases:

        q = x Wq -> nhead heads;  k = x Wk, v = x Wv -> nkvhead heads;
        g = x Wg -> nhead heads
        q, k <- RMSNorm over a head's features, one learned scale each
        RoPE on the first rope_dim features of a head of q and k, the
            others passed through (rope = 1; rope_dim = 0: all of them)
        o = softmax(q k^T / sqrt(head_dim)) v, query head h against
            key/value head h // (nhead / nkvhead); query i sees key j
            iff 0 <= i - j < window (window = 0: every earlier key)
        y = (o * sigmoid(g)) Wo;  with gate = 0: y = o Wo, and no Wg

    afmoe's layers differ in ``rope`` and ``window`` alone (its sliding
    layers have both, its full layers neither); Qwen3-Next's rotate 64
    of a head's 256 features and have no window; LFM2's have 32 query
    heads on 8 key/value heads of 64 features, all rotated, and no gate.
    ``gate`` is a structural key as ``rope`` and ``window`` are: it
    decides which parameters exist. ``rope_type = yarn`` (Mellum2's full
    layers) rotates by YaRN's frequencies and scales the tables by
    ``attention_factor`` (``rope_tables``; keys ``rope_factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``); ``default`` is the plain table.

    The causal core is the fused kernel where the shapes tile
    (``pallas_kernels.causal_attention_applicable``: heads of 64 features
    or whole lanes, a sequence of whole tiles), the XLA form elsewhere."""

    sub_scopes = ("core",)

    def __init__(self, cfg=()):
        self.nhead = 0
        self.nkvhead = 0
        self.head_dim = 0
        self.window = 0
        self.rope = 1
        self.rope_dim = 0
        self.gate = 1
        self.rope_theta = 10000.0
        self.rope_type = "default"
        self.rope_factor = 1.0
        self.original_max_position_embeddings = 0
        self.beta_fast = 32.0
        self.beta_slow = 1.0
        self.attention_factor = 1.0
        self.eps = 1e-6
        self.q_block = 0
        self.fused_core = False
        self.mesh = None
        super().__init__(cfg)

    def bind_mesh(self, mesh) -> None:
        """The mesh the layer's program runs on (the trainer's): where it
        splits the batch, the fused core runs a chip's rows at a time
        (``over_batch``)."""
        self.mesh = mesh

    def set_param(self, name, val):
        super().set_param(name, val)
        if name in ("nhead", "nkvhead", "head_dim", "window", "rope",
                    "rope_dim", "q_block", "gate",
                    "original_max_position_embeddings"):
            setattr(self, name, int(val))
        if name in ("rope_theta", "eps", "rope_factor", "beta_fast",
                    "beta_slow", "attention_factor"):
            setattr(self, name, float(val))
        if name == "rope_type":
            if val not in ("default", "yarn"):
                raise ValueError("gqa_attention: rope_type must be default "
                                 "or yarn, not %r" % val)
            self.rope_type = val

    def yarn(self):
        """``rope_tables``' ``yarn`` argument, None for the plain table."""
        if self.rope_type != "yarn":
            return None
        return (self.rope_factor, self.original_max_position_embeddings,
                self.beta_fast, self.beta_slow, self.attention_factor)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = _expect_seq("gqa_attention", self._expect_one(in_shapes))
        if min(self.nhead, self.nkvhead, self.head_dim) <= 0 \
                or self.nhead % self.nkvhead or self.head_dim % 2 \
                or self.window < 0 or self.rope_dim % 2 \
                or not 0 <= self.rope_dim <= self.head_dim \
                or (self.rope_type == "yarn" and min(
                    self.rope_factor, self.original_max_position_embeddings,
                    self.beta_fast, self.beta_slow) <= 0):
            raise ValueError(
                "gqa_attention: must set nhead, nkvhead (a divisor of "
                "nhead), head_dim (even), window >= 0, an even "
                "rope_dim within head_dim, and with rope_type = yarn a "
                "positive rope_factor, original_max_position_embeddings, "
                "beta_fast and beta_slow")
        # which core runs is what the shapes allow, not a key
        self.fused_core = pallas_kernels.causal_attention_applicable(
            s.y, self.q_block, (self.head_dim,), self.head_dim,
            self.nhead, self.nkvhead, self.window)
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def _widths(self) -> Dict[str, Tuple[int, int]]:
        d, hd = self.in_shapes[0].x, self.head_dim
        out = {"wq": (d, self.nhead * hd), "wk": (d, self.nkvhead * hd),
               "wv": (d, self.nkvhead * hd), "wg": (d, self.nhead * hd),
               "wo": (self.nhead * hd, d)}
        if not self.gate:
            del out["wg"]
        return out

    def init_params(self, key):
        p, widths = self.param, self._widths()
        out = {tag: p.rand_init_weight(k, shape, *shape)
               for (tag, shape), k in zip(
                   widths.items(), jax.random.split(key, len(widths)))}
        out["qnorm"] = jnp.ones((self.head_dim,), _F32)
        out["knorm"] = jnp.ones((self.head_dim,), _F32)
        return out

    def forward(self, params, state, inputs, is_train, rng):
        x, cd, h, g, hd = inputs[0], self.cd, self.nhead, self.nkvhead, \
            self.head_dim
        b, t, _ = x.shape
        q = _dot(x, params["wq"], cd).reshape(b, t, h, hd)
        k = _dot(x, params["wk"], cd).reshape(b, t, g, hd)
        v = _dot(x, params["wv"], cd).reshape(b, t, g, hd)
        q = rms_norm(q, params["qnorm"], self.eps)
        k = rms_norm(k, params["knorm"], self.eps)
        if self.rope:
            rd = self.rope_dim or hd
            cos, sin = rope_tables(t, rd, self.rope_theta, self.yarn())
            if rd == hd:
                turn = lambda a: apply_rope(a, cos, sin, True)
            else:       # the features past rope_dim carry no position
                turn = lambda a: jnp.concatenate(
                    [apply_rope(a[..., :rd], cos, sin, True), a[..., rd:]],
                    axis=-1)
            q, k = turn(q), turn(k)
        heads = lambda a: a.transpose(0, 2, 1, 3)   # heads beside the batch
        scale = 1.0 / math.sqrt(hd)
        with jax.named_scope("core"):
            if self.fused_core:
                o = over_batch(self.mesh, lambda q, k, v:
                               pallas_kernels.causal_attention(
                                   (q,), (k,), v, scale, self.q_block,
                                   self.window), heads(q), heads(k), heads(v))
            else:
                # a key/value head beside each query head of its group
                each = lambda a: jnp.repeat(heads(a), h // g, axis=1)
                o = causal_attention(heads(q), each(k), each(v), scale,
                                     self.q_block, self.window)
        o = heads(o).reshape(b, t, h * hd)
        if self.gate:
            gate = jax.nn.sigmoid(_dot(x, params["wg"], cd).astype(_F32))
            o = (o.astype(_F32) * gate).astype(o.dtype)
        return [_dot(o, params["wo"], cd)], state

    def pairs_per_sequence(self) -> float:
        """Query-key pairs a head computes: ``sum_i min(i + 1, window)``,
        the causal triangle where no window cuts it."""
        t = self.in_shapes[0].y
        w = min(self.window, t) if self.window else t
        return w * (w + 1) / 2.0 + (t - w) * w

    def flops_per_example(self) -> float:
        """Projections, and the core at the pairs inside its band."""
        t = self.in_shapes[0].y
        proj = sum(2.0 * a * b for a, b in self._widths().values())
        return t * proj + 4.0 * self.nhead * self.head_dim \
            * self.pairs_per_sequence()


# -- the causal short convolution, and LFM2's mixer ------------------------------


def causal_depthwise_conv(x, taps, cd):
    """``y_t = sum_i taps[i] x_(t - K + 1 + i)``, ``K = len(taps)``: a
    channel alone, the taps' last on the position itself and zeros before
    the sequence. ``x`` ``(batch, time, channels)``, ``taps`` ``(K,
    channels)``; ``K`` shifted products with the operands in ``cd``,
    summed in float32 and returned so (as XLA's depthwise convolution it
    took 56 ms a layer a step on the chip, nine times the projections
    beside it; PERF.md, PR 34). The one causal convolution of the
    sequence layers: ``gated_delta`` and ``gated_conv`` both call it, each
    with its own passes around it."""
    kernel, t = taps.shape[0], x.shape[1]
    taps = taps.astype(cd).astype(_F32)
    past = jnp.pad(x.astype(cd), [(0, 0), (kernel - 1, 0), (0, 0)])
    return sum(taps[i] * past[:, i:i + t].astype(_F32)
               for i in range(kernel))


class GatedConvLayer(_SeqLayer):
    """LFM2's double-gated short convolution (LiquidAI), causal, no
    biases, no activation:

        [B | C | x] = u Win          three parts of the input's width
        y = C * conv(B * x)          elementwise gates around a causal
                                     depthwise convolution of
                                     ``conv_kernel`` taps along time
        out = y Wout

    The width is the sequence node's own. ``taps`` start as ``U(-1 /
    sqrt(K), 1 / sqrt(K))`` (torch's default for a depthwise kernel of
    ``K`` taps). The convolution is ``causal_depthwise_conv``, the one
    ``gated_delta`` uses; the two gates and it are made again in the
    backward pass from ``[B | C | x]`` in the compute dtype."""

    # "short_conv", not "conv": see gated_delta's sub_scopes
    sub_scopes = ("in_proj", "short_conv", "out_proj")

    def __init__(self, cfg=()):
        self.conv_kernel = 3
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "conv_kernel":
            self.conv_kernel = int(val)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = _expect_seq("gated_conv", self._expect_one(in_shapes))
        if self.conv_kernel <= 0:
            raise ValueError("gated_conv: conv_kernel must be positive")
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def init_params(self, key):
        p, d = self.param, self.in_shapes[0].x
        kin, ktaps, kout = jax.random.split(key, 3)
        bound = 1.0 / math.sqrt(self.conv_kernel)
        return {"win": p.rand_init_weight(kin, (d, 3 * d), d, 3 * d),
                "taps": jax.random.uniform(ktaps, (self.conv_kernel, d), _F32,
                                           -bound, bound),
                "wout": p.rand_init_weight(kout, (d, d), d, d)}

    def forward(self, params, state, inputs, is_train, rng):
        x, cd = inputs[0], self.cd
        d = x.shape[-1]

        def gated_conv(bcx, taps):
            b, c, xx = (bcx[..., i * d:(i + 1) * d].astype(_F32)
                        for i in range(3))
            return (c * causal_depthwise_conv((b * xx).astype(cd), taps,
                                              cd)).astype(cd)

        with jax.named_scope("in_proj"):
            bcx = _dot(x, params["win"], cd)
        # the passes between the products are made again in the backward
        # pass from what goes into them, in cd (as gated_delta's)
        with jax.named_scope("short_conv"):
            y = jax.checkpoint(gated_conv)(bcx, params["taps"])
        with jax.named_scope("out_proj"):
            return [_dot(y, params["wout"], cd)], state

    def flops_per_example(self) -> float:
        """The two projections and the convolution's taps."""
        s = self.in_shapes[0]
        return s.y * (2.0 * s.x * 4 * s.x + 2.0 * self.conv_kernel * s.x)


# -- gated delta-rule linear attention ------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST


def _inv_unit_lower_impl(a):
    c = a.shape[-1]
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    m = c
    while m > 16 and m % 2 == 0:
        m //= 2
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    # every block stays where it lies in the whole matrix (products of
    # block-diagonal matrices): a block of 16 columns alone would fill an
    # eighth of a tile's lanes
    n = jnp.where(row // m == col // m, -a, 0.0)
    out, power, terms = jnp.eye(c, dtype=a.dtype) + n, n, 2
    while terms < m:
        power = mm(power, power)
        out, terms = out + mm(out, power), 2 * terms
    while m < c:
        below = jnp.where((row // (2 * m) == col // (2 * m))
                          & (row // m != col // m), a, 0.0)
        out, m = out - mm(mm(out, below), out), 2 * m
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _solve_unit_lower(a, cd):
    """``(I + a)^-1`` in ``cd`` for ``a`` strictly lower triangular over
    its last two axes, made in float32 by matrix products alone: the
    diagonal blocks of at most 16 rows as the finite series ``(I - a)(I +
    a^2)(I + a^4)...`` (``a`` is nilpotent, so it ends; its terms stay
    small over so few rows), then blocks of twice the rows from pairs of
    them, ``[[P, 0], [-Q a21 P, Q]] = D - D a21 D`` with ``D = diag(P,
    Q)``: the forward substitution of the triangular solve a block at a
    time. Its gradient is the inverse's own, ``-T^T dT T^T``, from the
    ``T`` it handed on, so the backward pass keeps that and none of the
    steps that made it; the forward rule names it (``DELTA_KEEPS``), so
    that a ``jax.checkpoint`` that keeps the name does not make it
    again."""
    return _inv_unit_lower_impl(a).astype(cd)


def _solve_unit_lower_fwd(a, cd):
    t = checkpoint_name(_inv_unit_lower_impl(a).astype(cd), *DELTA_KEEPS)
    return t, t


def _solve_unit_lower_bwd(cd, t, g):
    tt = t.astype(_F32).swapaxes(-1, -2)
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    c = t.shape[-1]
    # a's upper half is no argument: only the strictly lower part moves
    return (jnp.where(jnp.arange(c)[:, None] > jnp.arange(c)[None, :],
                      -mm(mm(tt, g.astype(_F32)), tt), 0.0),)


_solve_unit_lower.defvjp(_solve_unit_lower_fwd, _solve_unit_lower_bwd)


@jax.custom_vjp
def _decay_state(keep, s, s_cd):
    """``keep * s``: the state a chunk's decay leaves. The gradient in
    ``keep`` reads the chunk's copy of the state in the compute dtype,
    which the products keep anyway, so that the scan's backward pass
    holds no float32 state a chunk."""
    return keep[..., None, None] * s


def _decay_state_fwd(keep, s, s_cd):
    return keep[..., None, None] * s, (keep, s_cd)


def _decay_state_bwd(res, g):
    keep, s_cd = res
    return (jnp.sum(g * s_cd.astype(_F32), axis=(-1, -2)),
            keep[..., None, None] * g, jnp.zeros_like(s_cd))


_decay_state.defvjp(_decay_state_fwd, _decay_state_bwd)


def _fused_delta_rule(q, k, v, g, beta, c: int, cd):
    """``gated_delta_rule`` through the fused kernels
    (pallas_kernels.gated_delta_scan) for a ``time`` of whole chunks of
    ``c``: the running sums and a chunk's triangular inverse are made
    here, over all chunks at once and again in the backward pass but for
    ``T`` (kept by its name, as in the other form); everything else of a
    chunk stays in the kernels' VMEM."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, n = hv // hk, t // c
    # a key head's value heads beside it, time last: a lane a position
    g, beta = (a.astype(_F32).reshape(b, n, c, hk, r).transpose(0, 3, 4, 1, 2)
               for a in (g, beta))                           # b h r n c
    run = jnp.cumsum(g, axis=-1)
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]

    def solve(k, run, beta):
        k = k.reshape(b, n, c, hk, dk)
        kk = jnp.einsum("bnihk,bnjhk->bhnij", k, k,
                        preferred_element_type=_F32)
        decay = jnp.exp(jnp.where(
            i >= j, run[..., :, None] - run[..., None, :], -jnp.inf))
        return _solve_unit_lower(jnp.where(
            i > j, beta[..., :, None] * decay * kk[:, :, None], 0.0), cd)

    k = k.astype(cd)
    # (a scope for who reads the compiled text: the float32 matrices a
    # chunk that are left outside the kernels are all under it)
    with jax.named_scope("solve"):
        t_inv = jax.checkpoint(
            solve, policy=jax.checkpoint_policies.save_only_these_names(
                *DELTA_KEEPS))(k, run, beta)
    o = pallas_kernels.gated_delta_scan(
        q.astype(cd).reshape(b, t, hk * dk), k.reshape(b, t, hk * dk),
        v.astype(cd).reshape(b, t, hv * dv), run.reshape(b, hk, r, t),
        beta.reshape(b, hk, r, t), t_inv.reshape(b, hk, r, t, c))
    return o.reshape(b, t, hv, dv)


def gated_delta_rule(q, k, v, g, beta, chunk: int, cd, fused=None):
    """The gated delta rule over a sequence, in chunks of ``chunk``
    positions. A value head carries a state ``S`` (key width x value
    width, zeros at the start) along time:

        S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t)
        S <- S + k_t u_t^T;  o_t = S^T q_t

    ``q``, ``k`` ``(batch, time, key heads, dk)`` (normalised and scaled
    by the caller), ``v`` ``(batch, time, value heads, dv)``, ``g <= 0``
    and ``beta`` ``(batch, time, value heads)`` float32; key head ``h``
    serves the value heads ``h * r .. (h + 1) * r``. Returns ``o``
    ``(batch, time, value heads, dv)`` in ``cd``.

    Inside a chunk, with ``G`` the running sum of ``g`` from its start
    and ``S0`` the state there: ``(I + A) u = beta (v - exp(G) k S0)``
    with ``A_ij = beta_i exp(G_i - G_j) k_i.k_j`` for ``j < i``, so
    ``u = T (beta v) - T (beta exp(G) k) S0`` with ``T = (I + A)^-1``
    made once a chunk whatever the state; then ``o = exp(G) q S0 + ((q
    k^T) * exp(G_i - G_j), j <= i) u`` and the next chunk starts from
    ``exp(G_end) S0 + (exp(G_end - G) k)^T u``. Only that last line and
    ``u`` run one chunk after the other (a ``lax.scan`` whose step is two
    products a head); what comes before (``before``) and after
    (``after``) is products over all chunks at once. Every decay is a
    difference of running sums, at most 1. Products take operands in
    ``cd`` with float32 results, and what one product hands the next
    (``T``, ``u``, a chunk's copy of the state) is held in ``cd``; the
    decays, the solve and the state from chunk to chunk are float32.
    Differentiable by ``jax``'s rules; ``before`` and ``after`` are made
    again in the backward pass (``jax.checkpoint``: small products) but
    for ``T``, which is kept by its name, so what a layer holds for it is
    q, k, v, ``T``, the scan's inputs and a state a chunk, all in
    ``cd``.

    That is the XLA form. Where the shapes tile
    (``pallas_kernels.gated_delta_applicable``: heads of whole lanes, a
    chunk of 64 or 128, a padded ``time`` of whole time tiles; ``fused``
    None asks it, False keeps the XLA form) the same rule runs as one
    fused kernel a direction (``_fused_delta_rule``): the same products
    on the same roundings, but of a chunk only its inputs, ``T``, ``o``
    and what the backward kernel is handed (the chunk's starting state
    and ``u``, in ``cd``) cross HBM, and the state stays in VMEM from
    chunk to chunk."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, c = hv // hk, min(chunk, t)
    pad = -t % c
    if pad:     # positions that neither decay nor write: g 0, k 0, beta 0
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
    if fused is None:
        fused = pallas_kernels.gated_delta_applicable(t, chunk, dk, dv, r, cd)
    if fused:
        return _fused_delta_rule(q, k, v, g, beta, c, cd)[:, :t]
    n = (t + pad) // c
    # chunks first (the scan's axis), heads beside the batch, a key
    # head's value heads beside it
    q, k = (a.reshape(b, n, c, hk, dk).transpose(1, 0, 3, 2, 4).astype(cd)
            for a in (q, k))                                 # n b h c k
    v = v.reshape(b, n, c, hk, r, dv).transpose(1, 0, 3, 4, 2, 5).astype(cd)
    g, beta = (a.astype(_F32).reshape(b, n, c, hk, r).transpose(
        1, 0, 3, 4, 2) for a in (g, beta))                   # n b h r c
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]

    def decays(run):                                          # n b h r c c
        return jnp.exp(jnp.where(
            i >= j, run[..., :, None] - run[..., None, :], -jnp.inf))

    def before(k, v, g, beta):
        run = jnp.cumsum(g, axis=-1)
        kk = jnp.einsum("nbhik,nbhjk->nbhij", k, k,
                        preferred_element_type=_F32)
        solve = _solve_unit_lower(jnp.where(
            i > j, beta[..., :, None] * decays(run) * kk[:, :, :, None],
            0.0), cd)
        u0 = jnp.einsum("nbhrij,nbhrjv->nbhriv", solve,
                        (beta[..., None] * v.astype(_F32)).astype(cd),
                        preferred_element_type=_F32).astype(cd)
        kf = k.astype(_F32)[:, :, :, None]                   # n b h 1 c k
        w = jnp.einsum("nbhrij,nbhrjk->nbhrik", solve,
                       ((beta * jnp.exp(run))[..., None] * kf).astype(cd),
                       preferred_element_type=_F32).astype(cd)
        end = run[..., -1:]
        return w, u0, (jnp.exp(end - run)[..., None] * kf).astype(cd), \
            jnp.exp(end[..., 0])

    def step(s, xs):
        # the state is float32 from chunk to chunk; the products read it,
        # and u, in cd, and so does everything after the scan
        w_n, u0_n, k_n, keep = xs
        s_cd = s.astype(cd)
        u = (u0_n.astype(_F32) - jnp.einsum(
            "bhrik,bhrkv->bhriv", w_n, s_cd,
            preferred_element_type=_F32)).astype(cd)
        nxt = _decay_state(keep, s, s_cd) + jnp.einsum(
            "bhrik,bhriv->bhrkv", k_n, u, preferred_element_type=_F32)
        return nxt, (s_cd, u)

    def after(q, k, g, s0, u):
        run = jnp.cumsum(g, axis=-1)
        qk = jnp.einsum("nbhik,nbhjk->nbhij", q, k,
                        preferred_element_type=_F32)
        return (jnp.exp(run)[..., None] * jnp.einsum(
            "nbhik,nbhrkv->nbhriv", q, s0, preferred_element_type=_F32)
            + jnp.einsum("nbhrij,nbhrjv->nbhriv",
                         (qk[:, :, :, None] * decays(run)).astype(cd), u,
                         preferred_element_type=_F32)).astype(cd)

    _, (s0, u) = jax.lax.scan(
        step, jnp.zeros((b, hk, r, dk, dv), _F32),
        jax.checkpoint(before, policy=jax.checkpoint_policies
                       .save_only_these_names(*DELTA_KEEPS))(k, v, g, beta))
    o = jax.checkpoint(after)(q, k, g, s0, u)
    return o.transpose(1, 0, 4, 2, 3, 5).reshape(b, n * c, hv, dv)[:, :t]


def short_conv(qkv, taps, hk: int, dk: int, dv: int, cd):
    """``gated_delta``'s short convolution in XLA: ``silu(causal
    depthwise convolution)`` of ``[q | k | v]`` (``causal_depthwise_conv``),
    q and k of unit length a head of ``dk`` and q over ``sqrt(dk)``;
    returns q, k ``(batch, time, hk, dk)`` and v ``(batch, time, value
    heads, dv)`` in ``cd``. The form for shapes the fused kernel
    (``pallas_kernels.gated_delta_conv``) does not take."""
    b, t, _ = qkv.shape
    kw = hk * dk
    qkv = jax.nn.silu(causal_depthwise_conv(qkv, taps, cd))
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q = unit(qkv[..., :kw].reshape(b, t, hk, dk)) / math.sqrt(dk)
    k = unit(qkv[..., kw:2 * kw].reshape(b, t, hk, dk))
    return q.astype(cd), k.astype(cd), \
        qkv[..., 2 * kw:].reshape(b, t, -1, dv).astype(cd)


class GatedDeltaLayer(_SeqLayer):
    """Gated DeltaNet's mixer as Qwen3-Next has it, causal, no biases:

        [q | k | v] = x Wqkv -> nkhead, nkhead heads of key_dim and
            nvhead heads of value_dim, side by side; z = x Wz -> nvhead
            heads of value_dim; b = x Wb, a = x Wa -> one a value head
        [q | k | v] <- silu(causal depthwise convolution of conv_kernel
            taps along time, no bias)
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
        q, k <- x / sqrt(sum x^2 + 1e-6) over a head;  q <- q / sqrt(key_dim)
        o = the gated delta rule (``gated_delta_rule``), key head h
            serving the value heads h r .. (h + 1) r, in chunks of
            ``chunk`` positions
        y = ((RMSNorm over a head's value_dim of o) * silu(z)) Wo

    ``A_log`` starts as ``log(U(0, 16))``, ``dt_bias`` and the norm's
    scale at 1, the convolution's taps as ``U(-1/2, 1/2)`` (torch's
    default for a depthwise kernel of four).

    Which form of the rule runs is what the shapes allow, not a key
    (``fused_scan``, decided in ``infer_shape``): the fused kernels where
    ``key_dim`` and ``value_dim`` are multiples of 128, the chunk is 64
    or 128 and the padded sequence is whole time tiles of 128 positions
    or more (Qwen3-Next's published widths), the XLA form everywhere
    else. The ``layout`` record's ``linear_attention_fused_layers`` says
    how many layers took the kernels. So with the short convolution
    (``fused_conv``): one fused kernel a direction
    (``pallas_kernels.gated_delta_conv``) where the heads are whole
    lanes, the taps at most nine and the sequence whole time tiles,
    writing q, k and v in the layout the scan's kernels read; the XLA
    form (``short_conv``) elsewhere. ``linear_attention_fused_conv_layers``
    counts those."""

    # "short_conv", not "conv": a reduction that reads an op's innermost
    # scope as a layer type would count it as a convolution layer
    sub_scopes = ("proj", "short_conv", "scan", "gate_norm", "out")

    def __init__(self, cfg=()):
        self.nkhead = 0
        self.nvhead = 0
        self.key_dim = 0
        self.value_dim = 0
        self.conv_kernel = 4
        self.chunk = 64
        self.eps = 1e-6
        self.fused_scan = False
        self.fused_conv = False
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name in ("nkhead", "nvhead", "key_dim", "value_dim",
                    "conv_kernel", "chunk"):
            setattr(self, name, int(val))
        if name == "eps":
            self.eps = float(val)

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = _expect_seq("gated_delta", self._expect_one(in_shapes))
        if min(self.nkhead, self.nvhead, self.key_dim, self.value_dim,
               self.conv_kernel, self.chunk) <= 0 \
                or self.nvhead % self.nkhead:
            raise ValueError(
                "gated_delta: must set nkhead, nvhead (a multiple of "
                "nkhead), key_dim, value_dim, and conv_kernel, chunk > 0")
        # which form of the rule runs is what the shapes allow, not a key
        self.fused_scan = pallas_kernels.gated_delta_applicable(
            s.y, self.chunk, self.key_dim, self.value_dim,
            self.nvhead // self.nkhead, self.cd)
        self.fused_conv = pallas_kernels.gated_delta_conv_applicable(
            s.y, self.conv_kernel, self.nkhead, self.nvhead, self.key_dim,
            self.value_dim, self.cd)
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def _widths(self) -> Dict[str, Tuple[int, int]]:
        d = self.in_shapes[0].x
        kw, vw = self.nkhead * self.key_dim, self.nvhead * self.value_dim
        return {"wqkv": (d, 2 * kw + vw), "wz": (d, vw),
                "wb": (d, self.nvhead), "wa": (d, self.nvhead),
                "wo": (vw, d)}

    def init_params(self, key):
        p, widths = self.param, self._widths()
        ks = jax.random.split(key, len(widths) + 2)
        out = {tag: p.rand_init_weight(k, shape, *shape)
               for (tag, shape), k in zip(widths.items(), ks)}
        out["conv"] = jax.random.uniform(
            ks[-2], (self.conv_kernel, widths["wqkv"][1]), _F32, -0.5, 0.5)
        out["alog"] = jnp.log(jax.random.uniform(
            ks[-1], (self.nvhead,), _F32, jnp.finfo(_F32).tiny, 16.0))
        out["dtbias"] = jnp.ones((self.nvhead,), _F32)
        out["norm"] = jnp.ones((self.value_dim,), _F32)
        return out

    def forward(self, params, state, inputs, is_train, rng):
        x, cd = inputs[0], self.cd
        b, t, _ = x.shape
        hk, hv, dk, dv = self.nkhead, self.nvhead, self.key_dim, \
            self.value_dim
        with jax.named_scope("proj"):
            qkv = _dot(x, params["wqkv"], cd)
            z = _dot(x, params["wz"], cd)
            beta = jax.nn.sigmoid(_dot(x, params["wb"], cd).astype(_F32))
            g = -jnp.exp(params["alog"]) * jax.nn.softplus(
                _dot(x, params["wa"], cd).astype(_F32) + params["dtbias"])

        def gate_norm(o, z, scale):
            return (rms_norm(o.astype(_F32), scale, self.eps) * jax.nn.silu(
                z.astype(_F32).reshape(b, t, hv, dv))).astype(cd)

        # the passes between the products are made again in the backward
        # pass from what goes into them, in cd (their float32 insides,
        # held for it, were most of what a layer's backward pass held)
        with jax.named_scope("short_conv"):
            if self.fused_conv:
                q, k, v = (a.reshape(b, t, -1, d) for a, d in zip(
                    pallas_kernels.gated_delta_conv(
                        qkv, params["conv"], hk * dk, dk), (dk, dk, dv)))
            else:
                q, k, v = jax.checkpoint(functools.partial(
                    short_conv, hk=hk, dk=dk, dv=dv, cd=cd))(
                        qkv, params["conv"])
        with jax.named_scope("scan"):
            o = gated_delta_rule(q, k, v, g, beta, self.chunk, cd,
                                 self.fused_scan)
        with jax.named_scope("gate_norm"):
            y = jax.checkpoint(gate_norm)(o, z, params["norm"])
        with jax.named_scope("out"):
            return [_dot(y.reshape(b, t, hv * dv), params["wo"], cd)], state

    def flops_per_example(self) -> float:
        """Projections, the convolution, and the rule at the
        recurrence's work: three products of key_dim x value_dim a value
        head a position (the decayed state read by the key, the key's
        write, the query's read), not what the chunked form adds."""
        t = self.in_shapes[0].y
        widths = self._widths()
        proj = sum(2.0 * a * b for a, b in widths.values())
        conv = 2.0 * self.conv_kernel * widths["wqkv"][1]
        rule = 6.0 * self.nvhead * self.key_dim * self.value_dim
        return t * (proj + conv + rule)


# -- the expert layer ---------------------------------------------------------


@jax.custom_vjp
def _rows_of_picks(w, src, dest):
    """``w[src]``: a pick's value in each row that holds it, 0 where
    ``src`` is past the picks (padding). ``dest`` is the inverse index,
    the row of each pick (past the rows for a pick that no row holds):
    each pick lands in one row at most, so the cotangent gathered
    through it is the gradient a scatter of ``w`` into the rows would
    have, without the scatter (or the ids a scatter's gradient scatters
    and gathers back to find which of its writes held)."""
    return jnp.take(w, src, mode="fill", fill_value=0)


def _rows_of_picks_fwd(w, src, dest):
    return _rows_of_picks(w, src, dest), dest


def _rows_of_picks_bwd(dest, g):
    return jnp.take(g, dest, mode="fill", fill_value=0), None, None


_rows_of_picks.defvjp(_rows_of_picks_fwd, _rows_of_picks_bwd)


def dispatch_plan(picks, weights, first: int, count: int, block: int):
    """Where each pick that lands on a held expert goes.

    ``picks`` ``(tokens, topk)`` are expert ids over ALL experts,
    ``weights`` their combine weights. The held experts are ``first ..
    first + count``. Rows are laid out expert by expert, each expert's
    rows padded to whole blocks of ``block``, so that a block belongs to
    one expert; nothing is bounded by a capacity, so no pick is dropped
    however uneven the routing. Returns

    tok    (rows,) int32  the token of each row; ``tokens`` marks padding
    cw     (rows,) f32    the row's combine weight, 0 in padding
    expert (blocks,) int32 the held expert (0-based) of each block
    nb     () int32       blocks in use: the loops run this far
    load   (count,) int32 picks each held expert got

    The plan is integers: ``dest``, the row of each pick (``rows`` for a
    pick off the held experts), and ``src``, the pick in each row (``tokens
    x topk`` in padding), one scatter of indices that has no derivative;
    the weights move into the rows by a gather through ``src`` whose
    gradient is a gather through ``dest`` (``_rows_of_picks``). The plan
    carries the names of ``MOE_KEEPS`` (layers/base.py), so that a
    ``remat = block`` segment keeps it and its recomputed forward makes
    none of it again.
    """
    n, k = picks.shape
    rows = (-(-n * k // block) + count) * block
    flat = picks.reshape(-1) - first
    held = (flat >= 0) & (flat < count)
    onehot = (flat[:, None] == jnp.arange(count)[None, :]).astype(jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    load = checkpoint_name(jnp.sum(onehot, axis=0), "moe_load")
    nblk = (load + block - 1) // block
    ends = jnp.cumsum(nblk)
    start = (ends - nblk) * block
    dest = checkpoint_name(
        jnp.where(held, start[jnp.clip(flat, 0, count - 1)] + rank, rows),
        "moe_dest")
    src = checkpoint_name(jnp.full((rows,), n * k, jnp.int32).at[dest].set(
        jnp.arange(n * k, dtype=jnp.int32), mode="drop"), "moe_src")
    # padding's ``n * k`` gives ``n``
    tok = checkpoint_name(src // k, "moe_tok")
    cw = _rows_of_picks(weights.reshape(-1).astype(_F32), src, dest)
    expert = checkpoint_name(jnp.clip(jnp.searchsorted(
        ends, jnp.arange(rows // block), side="right"), 0, count - 1)
        .astype(jnp.int32), "moe_expert")
    return tok, cw, expert, ends[-1].astype(jnp.int32), load


# Picks that can land on a chip's experts in one exchange on an expert
# axis: every pick of a part of each chip's tokens. The grouped kernels
# keep a token id a row of their plan in SMEM (1 MiB): twice these rows
# would pass it.
EXCHANGE_ROWS = 131072


def _rows_used(picks, first, count):
    """Tokens with at least one of their ``picks`` on experts ``first ..
    first + count``."""
    held = (picks >= first) & (picks < first + count)
    return jnp.sum(jnp.any(held, axis=1), dtype=jnp.int32)


def _take_rows(x, idx):
    # padding rows point past the last token: they read zeros
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


def _block_rows(b, block, x, tok, cw):
    idx = jax.lax.dynamic_slice(tok, (b * block,), (block,))
    c = jax.lax.dynamic_slice(cw, (b * block,), (block,))
    return idx, c, _take_rows(x, idx)


def _gate_up(xs, wg, wu):
    a = jnp.dot(xs, wg, preferred_element_type=_F32)
    u = jnp.dot(xs, wu, preferred_element_type=_F32)
    return a, u


def _loop_forward(x, wgate, wup, wdown, cw, tok, expert, nb, block):
    """The experts a block at a time: a loop over the ``nb`` blocks in
    use. It needs no buffer of rows, so it holds whatever the routing
    does."""
    def body(b, out):
        idx, c, xs = _block_rows(b, block, x, tok, cw)
        e = expert[b]
        a, u = _gate_up(xs, wgate[e], wup[e])
        h = (jax.nn.silu(a) * u).astype(x.dtype)
        y = jnp.dot(h, wdown[e], preferred_element_type=_F32)
        return out.at[idx].add(c[:, None] * y, mode="drop")
    return jax.lax.fori_loop(0, nb, body, jnp.zeros(x.shape, _F32))


def _loop_backward(x, wgate, wup, wdown, cw, tok, expert, nb, block, g_out):
    """Each block again: recompute its hidden rows, then the products'
    transposes. Weight gradients accumulate in float32."""
    def body(b, carry):
        dx, dwg, dwu, dwd, dcw = carry
        idx, c, xs = _block_rows(b, block, x, tok, cw)
        g = _take_rows(g_out, idx)
        e = expert[b]
        a, u = _gate_up(xs, wgate[e], wup[e])
        sig = jax.nn.sigmoid(a)
        act = a * sig
        h = (act * u).astype(x.dtype)
        # sum(h * (g Wdown^T)) is sum((h Wdown) * g) without the product
        dh0 = jnp.dot(g.astype(x.dtype), wdown[e].T,
                      preferred_element_type=_F32)
        dcw = jax.lax.dynamic_update_slice(
            dcw, jnp.sum(h.astype(_F32) * dh0, axis=-1), (b * block,))
        dh = c[:, None] * dh0
        dy = (c[:, None] * g).astype(x.dtype)
        dwd = dwd.at[e].add(jnp.dot(h.T, dy, preferred_element_type=_F32))
        du = (dh * act).astype(x.dtype)
        da = (dh * u * (sig * (1.0 + a * (1.0 - sig)))).astype(x.dtype)
        dwg = dwg.at[e].add(jnp.dot(xs.T, da, preferred_element_type=_F32))
        dwu = dwu.at[e].add(jnp.dot(xs.T, du, preferred_element_type=_F32))
        dxs = jnp.dot(da, wgate[e].T, preferred_element_type=_F32) \
            + jnp.dot(du, wup[e].T, preferred_element_type=_F32)
        return dx.at[idx].add(dxs, mode="drop"), dwg, dwu, dwd, dcw

    zeros = [jnp.zeros(a.shape, _F32) for a in (x, wgate, wup, wdown, cw)]
    dx, dwg, dwu, dwd, dcw = jax.lax.fori_loop(0, nb, body, tuple(zeros))
    return (dx.astype(x.dtype), dwg.astype(wgate.dtype),
            dwu.astype(wup.dtype), dwd.astype(wdown.dtype), dcw)


def _gather_blocks(x, tok, nb, block, budget):
    """``x[tok]`` for the rows of the blocks in use among the plan's
    first ``budget``, zeros after them: eight blocks a trip of a loop
    that follows ``nb``, because XLA's gather costs the same 45 ns a row
    whatever the row holds (PERF.md, PR 31) and a step uses a third of
    the buffer when its routing is even."""
    group = math.gcd(budget, 8)
    rows = group * block
    if not budget:
        return jnp.zeros((0, x.shape[1]), x.dtype)

    def body(i, xs):
        idx = jax.lax.dynamic_slice(tok, (i * rows,), (rows,))
        return jax.lax.dynamic_update_slice(
            xs, _take_rows(x, idx), (i * rows, 0))

    return jax.lax.fori_loop(
        0, -(-jnp.minimum(nb, budget) // group), body,
        jnp.zeros((budget * block, x.shape[1]), x.dtype))


def _kernel_backward(xs, wgate, wup, wdown, cw, tok, expert, nb, block,
                     g_out):
    """The backward pass of the grouped kernels' schedule
    (layers/pallas_kernels.py: experts_backward) over the gathered rows
    ``xs``, which hold the ``nb`` blocks in use."""
    rows = xs.shape[0]
    dx, dwg, dwu, dwd, dcw = pallas_kernels.experts_backward(
        xs, _gather_blocks(g_out.astype(xs.dtype), tok, nb, block,
                           rows // block), cw[:rows, None],
        tok[:rows], wgate, wup, wdown, expert, nb, block, g_out.shape[0])
    # the kernel wrote the rows of the blocks in use and no others
    dcw = jnp.where(jnp.arange(rows) < nb * block, dcw[:, 0], 0.0)
    return dx, dwg, dwu, dwd, jnp.zeros(cw.shape, _F32).at[:rows].set(dcw)


def _by_budget(nb, budget, blocks, kernel, loop, nothing):
    """``kernel()`` where the blocks in use fit the kernels' row buffers,
    ``loop()`` where they do not (or no kernel applies: ``budget`` 0).
    Not ``lax.cond``: each is the body of a loop of one trip or none, the
    first starting from ``nothing`` (zeros shaped as the result) and the
    second from the first's results. The device runs the same thing,
    and the profiler reports a ``while`` as what encloses its body's
    ops, which trace reductions know to leave out, where a conditional
    and its branch's call each come back as one more op as long as all
    they hold."""
    if not budget:
        return loop()
    if budget >= blocks:
        return kernel()
    fits = (nb <= budget).astype(jnp.int32)
    done = jax.lax.fori_loop(0, fits, lambda _, res: kernel(), nothing)
    return jax.lax.fori_loop(0, 1 - fits, lambda _, res: loop(), done)


def _grouped_fwd(x, wgate, wup, wdown, cw, tok, expert, nb, block, budget):
    """``grouped_swiglu`` and what its backward pass needs: the operands,
    and the rows of the plan's first ``budget`` blocks, which the
    kernels' schedule reads in both directions (gathered whichever
    schedule the step takes: the loop is the rare one)."""
    xs = _gather_blocks(x, tok, nb, block, budget)
    out = _by_budget(
        nb, budget, expert.shape[0],
        lambda: pallas_kernels.experts_forward(
            xs, cw[:len(xs), None], tok[:len(xs)], wgate, wup, wdown, expert,
            nb, block, x.shape[0]),
        lambda: _loop_forward(x, wgate, wup, wdown, cw, tok, expert, nb,
                              block),
        jnp.zeros(x.shape, _F32))
    return out, (x, wgate, wup, wdown, cw, tok, expert, nb, xs)


def _grouped_impl(x, wgate, wup, wdown, cw, tok, expert, nb, block, budget):
    """``out[t] = sum over the rows r of token t of cw[r] * E_expert(r)(x[t])``
    with ``E`` a SwiGLU, over the plan of ``dispatch_plan``. ``x`` is
    ``(tokens, d)``, the weights ``(held, d, w)`` / ``(held, w, d)``; the
    result is float32. Two schedules of one computation, both
    proportional to the ``nb`` blocks in use (a count the routing
    decides, which is why the backward pass is written by hand): grouped
    kernels over a buffer of ``budget`` blocks of rows, and the loop a
    block at a time where the routing needs more than that; ``budget``
    0 is the loop alone."""
    return _grouped_fwd(x, wgate, wup, wdown, cw, tok, expert, nb, block,
                        budget)[0]


grouped_swiglu = jax.custom_vjp(_grouped_impl, nondiff_argnums=(8, 9))


def _grouped_bwd(block, budget, res, g_out):
    x, wgate, wup, wdown, cw, tok, expert, nb, xs = res
    grads = _by_budget(
        nb, budget, expert.shape[0],
        lambda: _kernel_backward(xs, wgate, wup, wdown, cw, tok, expert, nb,
                                 block, g_out),
        lambda: _loop_backward(x, wgate, wup, wdown, cw, tok, expert, nb,
                               block, g_out.astype(_F32)),
        tuple(jnp.zeros_like(a) for a in (x, wgate, wup, wdown, cw)))
    return grads + (None, None, None)


grouped_swiglu.defvjp(_grouped_fwd, _grouped_bwd)


class MoELayer(_SeqLayer):
    """Routed expert layer, with shared experts or (``nshared = 0``:
    LFM2's) without, a chip's share of it: DeepSeek-V3's (sigmoid scores,
    ``noaux_tc`` without group limits) and, by two keys, Qwen3-Next's
    (softmax scores, a gate on the shared expert):

        s = sigmoid(x Wr)                      all nexpert, float32
            softmax(x Wr) over them            (score_func = softmax)
        picks = top-k of s + bias              bias is layer STATE
        w_i = scale * s_i / sum_picked s_j     from s, not s + bias
        y = sum_{held picks} w_i E_i(x) + S(x)
            ... + sigmoid(x w_s) S(x)          (shared_gate = 1)

    ``E_i`` is a SwiGLU of width ``nhidden``, ``S`` one SwiGLU of width
    ``nshared * nhidden`` (absent, parameters and pass, at ``nshared =
    0``). ``expert_first`` / ``expert_count`` say which
    experts live here (default: all). The bias is seeded from
    ``bias_seed`` at ``bias_sigma`` (0: no bias) and held fixed (its
    update rate is not part of the published config). State also carries
    the last forward's counters for the ``moe`` telemetry record:
    ``load`` (picks each held expert got), ``picks_held``, ``dropped``;
    and ``grouped``, the forward passes so far whose experts ran as the
    grouped kernels.

    Which schedule the experts run (``grouped_swiglu``) is what the
    shapes allow, not a key: the grouped kernels where the widths tile
    (``pallas_kernels.grouped_experts_applicable``), with buffers for
    three times the rows the held experts get when the routing is even
    plus a block an expert, and the loop a block at a time in a step whose
    routing needs more rows than that, and everywhere else.

    ``expert_axis`` names a mesh axis (the trainer's ``data`` axis:
    expert parallelism over the data-parallel chips) that the held
    experts are spread over, ``expert_count / chips`` a chip, their
    tensors sharded on their leading axis (``leading_axes``). Where the
    bound mesh (``bind_mesh``) gives that axis more than one chip, each
    chip routes its own tokens over all ``nexpert`` and sends each token's
    row once to every chip of the axis, with its picks and their weights
    (``jax.lax.all_gather`` inside ``shard_map``, scope ``exchange``). A
    chip runs ``dispatch_plan`` and the grouped kernels over the picks
    that land on its experts, each weighted and summed into its token in
    float32, and one ``jax.lax.all_to_all`` sends every token's partial
    sum back to its own chip, where the chips' sums are added in float32.
    A chip exchanges its tokens a part at a time (``part``:
    ``EXCHANGE_ROWS / (chips * topk)`` tokens, a ``lax.map``, each part
    made again in the backward pass). A chip receives ``chips * part``
    rows an exchange (``capacity``) whatever the routing, so no pick can
    be dropped (``dropped`` stays counted). The state's ``exchange``
    carries ``[token rows sent off-chip, fewest picks a chip's experts
    received, most, rows received that carry a pick for the chip's
    experts]``.
    """

    sub_scopes = ("route", "dispatch", "exchange", "experts", "combine",
                  "shared")

    def __init__(self, cfg=()):
        self.nexpert = 0
        self.topk = 0
        self.nshared = 0
        self.scale = 1.0
        self.norm_topk = 1
        self.first = 0
        self.count = 0
        self.block = 512
        self.bias_seed = 0
        self.bias_sigma = 0.0
        self.score_func = "sigmoid"
        self.shared_gate = 0
        self.expert_axis = ""
        self.mesh = None
        self.grouped = False
        super().__init__(cfg)

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "nexpert":
            self.nexpert = int(val)
        if name == "topk":
            self.topk = int(val)
        if name == "nshared":
            self.nshared = int(val)
        if name == "routed_scaling_factor":
            self.scale = float(val)
        if name == "norm_topk_prob":
            self.norm_topk = int(val)
        if name == "expert_first":
            self.first = int(val)
        if name == "expert_count":
            self.count = int(val)
        if name == "expert_block":
            self.block = int(val)
        if name == "bias_seed":
            self.bias_seed = int(val)
        if name == "bias_sigma":
            self.bias_sigma = float(val)
        if name == "score_func":
            if val not in ("sigmoid", "softmax"):
                raise ValueError("moe: score_func must be sigmoid or "
                                 "softmax, not %r" % val)
            self.score_func = val
        if name == "shared_gate":
            self.shared_gate = int(val)
        if name == "expert_axis":
            self.expert_axis = val

    def infer_shape(self, in_shapes: List[Shape3]) -> List[Shape3]:
        s = _expect_seq("moe", self._expect_one(in_shapes))
        if self.count == 0:
            self.count = self.nexpert - self.first
        if min(self.nexpert, self.topk, self.param.num_hidden) <= 0 \
                or self.topk > self.nexpert or self.first < 0 \
                or self.count <= 0 or self.first + self.count > self.nexpert \
                or (self.shared_gate and not self.nshared) \
                or (self.expert_axis and self.nshared):
            raise ValueError(
                "moe: must set nexpert, topk <= nexpert, nhidden, "
                "expert_first / expert_count inside nexpert, nshared "
                "where shared_gate is on, and no shared expert on an "
                "expert axis (not built)")
        self.grouped = pallas_kernels.grouped_experts_applicable(
            s.x, self.param.num_hidden, self.block, self.cd)
        self.in_shapes = [s]
        self.out_shapes = [s]
        return self.out_shapes

    def init_params(self, key):
        p, d, w, e = self.param, self.in_shapes[0].x, self.param.num_hidden, \
            self.count
        ks = jax.random.split(key, 7)
        out = {"router": p.rand_init_weight(ks[0], (d, self.nexpert), d,
                                            self.nexpert),
               "egate": p.rand_init_weight(ks[1], (e, d, w), d, w),
               "eup": p.rand_init_weight(ks[2], (e, d, w), d, w),
               "edown": p.rand_init_weight(ks[3], (e, w, d), w, d)}
        if self.nshared:
            sw = self.nshared * w
            out.update(sgate=p.rand_init_weight(ks[4], (d, sw), d, sw),
                       sup=p.rand_init_weight(ks[5], (d, sw), d, sw),
                       sdown=p.rand_init_weight(ks[6], (sw, d), sw, d))
        if self.shared_gate:
            # its own key, so that the other tensors start where they
            # do without the gate
            out["sharedgate"] = p.rand_init_weight(
                jax.random.fold_in(key, 7), (d, 1), d, 1)
        return out

    def init_state(self):
        bias = self.bias_sigma * jax.random.normal(
            jax.random.PRNGKey(self.bias_seed), (self.nexpert,), _F32)
        out = {"bias": bias,
               "load": jnp.zeros((self.count,), jnp.int32),
               "picks_held": jnp.int32(0), "dropped": jnp.int32(0),
               "grouped": jnp.int32(0)}
        if self.expert_axis:
            out["exchange"] = jnp.zeros((4,), jnp.int32)
        return out

    def bind_mesh(self, mesh) -> None:
        """The mesh the layer's program runs on (the trainer's)."""
        self.mesh = mesh

    def chips(self) -> int:
        """Chips the held experts are spread over: the size of
        ``expert_axis`` in the bound mesh, 1 without either."""
        if not self.expert_axis or self.mesh is None:
            return 1
        chips = int(self.mesh.shape[self.expert_axis])
        if self.count % chips:
            raise ValueError("moe: %d held experts do not divide over the "
                             "%d chips of axis %r"
                             % (self.count, chips, self.expert_axis))
        return chips

    def leading_axes(self) -> Dict[str, str]:
        """The tensors sharded on their leading axis, and the mesh axis:
        the experts', on an expert axis."""
        if not self.expert_axis:
            return {}
        return {tag: self.expert_axis for tag in ("egate", "eup", "edown")}

    def part(self, tokens: int) -> int:
        """Tokens a chip exchanges at a time, of its ``tokens``: the most
        that divide them and whose picks from every chip fill at most
        ``EXCHANGE_ROWS``."""
        most = max(EXCHANGE_ROWS // (self.chips() * self.topk), 1)
        return next(c for c in range(min(tokens, most), 0, -1)
                    if tokens % c == 0)

    def capacity(self, tokens: int) -> int:
        """Token rows a chip receives in one exchange, for a chip's
        ``tokens``: a ``part`` from every chip."""
        return self.chips() * self.part(tokens)

    def budget(self, tokens: int) -> int:
        """Blocks of rows the grouped kernels' buffers hold for a step
        of ``tokens``: three times the picks that land on held experts
        in expectation (a net that trains only the experts it holds
        draws picks to them: 2.4 times the even share within forty steps
        on one batch, PERF.md, PR 31), plus a block an expert for the
        padding, and never
        more than the plan's own bound; 0 where the kernels do not
        apply (the layer's widths, or a step whose tokens are not whole
        sublanes of 8)."""
        if not self.grouped or tokens % 8:
            return 0
        picks = tokens * self.topk
        even = 3 * picks * self.count // self.nexpert
        return min(-(-even // self.block), -(-picks // self.block)) \
            + self.count

    def route(self, xt, router, bias):
        """(picks, weights) of each token: float32 throughout, the
        product at full precision (a bf16 pass would move near-tied
        picks). The logits and the picks carry names of ``MOE_KEEPS``
        (layers/base.py): a ``remat = block`` segment keeps them, so that
        its recomputed forward makes only the scores again, elementwise."""
        s = checkpoint_name(jnp.dot(xt.astype(_F32), router.astype(_F32),
                                    precision=jax.lax.Precision.HIGHEST),
                            "moe_logits")
        s = jax.nn.sigmoid(s) if self.score_func == "sigmoid" \
            else jax.nn.softmax(s, axis=-1)
        picks = checkpoint_name(
            jax.lax.top_k(s + bias[None, :], self.topk)[1], "moe_picks")
        w = jnp.take_along_axis(s, picks, axis=1)
        if self.norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return picks, w * self.scale

    def forward(self, params, state, inputs, is_train, rng):
        x, cd = inputs[0], self.cd
        b, t, d = x.shape
        xt = x.reshape(b * t, d)
        with jax.named_scope("route"):
            picks, w = self.route(xt, params["router"], state["bias"])
        if self.chips() > 1:
            return self._forward_exchange(params, state, x, picks, w)
        with jax.named_scope("dispatch"):
            tok, cw, expert, nb, load = dispatch_plan(
                picks, w, self.first, self.count, self.block)
        budget = self.budget(b * t)
        with jax.named_scope("experts"):
            y = grouped_swiglu(
                xt.astype(cd), params["egate"].astype(cd),
                params["eup"].astype(cd), params["edown"].astype(cd),
                cw, tok, expert, nb, self.block, budget)
        if self.nshared:
            with jax.named_scope("shared"):
                shared = swiglu(xt, params["sgate"], params["sup"],
                                params["sdown"], cd)
                if self.shared_gate:
                    shared = jax.nn.sigmoid(_dot(
                        xt, params["sharedgate"], cd).astype(_F32)) \
                        * shared.astype(_F32)
        with jax.named_scope("combine"):
            if self.nshared:
                y = y + shared.astype(_F32)
            out = y.astype(x.dtype).reshape(b, t, d)
        held = jnp.sum(load)
        took = (nb <= budget).astype(jnp.int32) if budget else 0
        new_state = dict(state, load=load, picks_held=held,
                         dropped=held - jnp.sum(tok < b * t),
                         grouped=state["grouped"] + took)
        if "exchange" in state:     # an axis of one chip: nothing travels
            new_state["exchange"] = jnp.stack(
                [jnp.int32(0), held, held,
                 _rows_used(picks, self.first, self.count)])
        return [out], new_state

    def _forward_exchange(self, params, state, x, picks, w):
        """The layer over an expert axis of ``chips()`` chips (the class
        doc). Inside ``shard_map`` a chip has its own tokens, their picks
        and weights, and its ``expert_count / chips`` experts, and
        exchanges them a part at a time; the routing stays outside, where
        the partitioner keeps it with the tokens."""
        cd, axis, chips = self.cd, self.expert_axis, self.chips()
        b, t, d = x.shape
        k, per, block = self.topk, self.count // chips, self.block
        part = self.part(b * t // chips)
        rows = self.capacity(b * t // chips)
        # a buffer for every pick that can land on a chip: the kernels
        # always apply where the widths tile
        budget = -(-(rows * k) // block) + per if self.grouped else 0

        def local(xt, picks, w, wgate, wup, wdown):
            first = self.first + jax.lax.axis_index(axis) * per

            # a part made again in the backward pass: what the kernels
            # and the exchange leave for it is a part's rows, and a map
            # would keep them for every part
            @jax.checkpoint
            def one(args):
                with jax.named_scope("exchange"):
                    # every chip's rows of the part, each once, with their
                    # picks and weights
                    got, picks, w = (jax.lax.all_gather(a, axis, tiled=True)
                                     for a in args)
                with jax.named_scope("dispatch"):
                    tok, cw, expert, nb, load = dispatch_plan(
                        picks, w, first, per, block)
                with jax.named_scope("experts"):
                    y = grouped_swiglu(got, wgate, wup, wdown, cw, tok,
                                       expert, nb, block, budget)
                with jax.named_scope("exchange"):
                    # each token's partial sum back to its own chip, where
                    # the chips' sums are added
                    y = jnp.sum(jax.lax.all_to_all(
                        y.astype(cd).reshape(chips, part, d), axis, 0,
                        0).astype(_F32), axis=0)
                stats = jnp.stack([jnp.sum(load) - jnp.sum(tok < rows),
                                   _rows_used(picks, first, per)])
                return y, load, stats

            y, load, stats = jax.lax.map(one, (
                xt.reshape(-1, part, d), picks.reshape(-1, part, k),
                w.reshape(-1, part, k)))
            return (y.reshape(-1, d), jnp.sum(load, axis=0)[None],
                    jnp.sum(stats, axis=0)[None])

        spec = jax.sharding.PartitionSpec(axis)
        y, load, stats = jax.shard_map(
            local, mesh=self.mesh, in_specs=(spec,) * 6,
            out_specs=(spec, spec, spec), check_vma=False)(
                x.reshape(b * t, d).astype(cd), picks, w,
                params["egate"].astype(cd), params["eup"].astype(cd),
                params["edown"].astype(cd))
        with jax.named_scope("combine"):
            out = y.astype(x.dtype).reshape(b, t, d)
        received = jnp.sum(load, axis=1)
        new_state = dict(
            state, load=load.reshape(self.count),
            picks_held=jnp.sum(received), dropped=jnp.sum(stats[:, 0]),
            grouped=state["grouped"] + (1 if budget else 0),
            exchange=jnp.stack([jnp.int32(b * t * (chips - 1)),
                                jnp.min(received), jnp.max(received),
                                jnp.sum(stats[:, 1])]))
        return [out], new_state

    def flops_per_example(self) -> float:
        """Router, shared experts (and their gate), and the routed
        experts at the picks that land on held experts in expectation:
        ``topk * count / nexpert`` a token. On an expert axis the held
        experts are those of all its chips, and the tokens all of theirs:
        with every expert held, all ``topk`` picks a token count."""
        s, w = self.in_shapes[0], self.param.num_hidden
        per_token = 2.0 * s.x * self.nexpert \
            + 6.0 * s.x * w * self.nshared + 2.0 * s.x * self.shared_gate \
            + 6.0 * s.x * w * self.topk * self.count / self.nexpert
        return s.y * per_token
