"""Pallas TPU kernels of the language models' layers.

Four families, each with a custom VJP and a shape gate (its
``*_applicable``) that the calling layer asks; where the gate says no,
the layer runs its XLA form, which the kernels' tests pair them with
(in interpret mode on the CPU). No key turns a kernel on or off: the
shapes decide.

- :func:`causal_attention`: the causal softmax core of ``mla_attention``
  and ``gqa_attention`` (grouped key/value heads, a window of keys).
- :func:`gated_delta_scan`: ``gated_delta``'s chunked delta-rule scan.
- :func:`gated_delta_conv`: ``gated_delta``'s short convolution with its
  SiLU and the per-head unit norm of ``q`` and ``k``.
- :func:`experts_forward` / :func:`experts_backward`: a ``moe`` layer's
  routed experts as grouped products over blocks of rows.

:func:`interpret` says whether they are built for the Pallas
interpreter or compiled by Mosaic.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from .base import ATTENTION_KEEPS, DELTA_SCAN_KEEPS


# The explicit interpret-mode choice (None = none made). The CPU tests
# choose True in conftest.py; chip_smoke.py chooses False.
_INTERPRET: Optional[bool] = None


def set_interpret(value: Optional[bool]) -> None:
    global _INTERPRET
    _INTERPRET = value


def interpret() -> bool:
    """Whether kernels built in this process go through the Pallas
    interpreter: the explicit choice when one was made, else decided
    by the backend — compiled by Mosaic on ``tpu``, interpreted on
    anything else (no kernel compiles for a CPU). The trainer's
    ``layout`` record reports it as ``pallas_interpret``, so a record
    stream says which of the two a run's kernels were."""
    if _INTERPRET is not None:
        return _INTERPRET
    return jax.default_backend() != "tpu"


def _build_interpret() -> bool:
    """:func:`interpret` for a kernel that is being built now. A
    kernel going interpreted by the backend's say-so alone is said
    once, out loud: interpret mode checks a kernel's arithmetic, never
    that it compiles, fits VMEM or runs on a chip."""
    mode = interpret()
    if mode and _INTERPRET is None:
        from ..monitor import warn_once
        warn_once("pallas_interpret",
                  "Pallas kernels run in INTERPRET mode on the %s "
                  "backend: results are checked, but nothing here "
                  "shows the kernels compile or run on a TPU"
                  % jax.default_backend())
    return mode


def _pad_to(v: int, m: int) -> int:
    return (v + m - 1) // m * m


# ------------------------------------------- fused causal attention

# a masked score, as the XLA core writes it (layers/sequence.py)
_MASKED = -1e30
_NT = (((1,), (1,)), ((), ()))       # a @ b.T
_TN = (((0,), (0,)), ((), ()))       # a.T @ b
_ATTN_VMEM = 64 * 1024 * 1024


def _diag_key_tile(i, bq, bk):
    """The last key tile that query tile ``i`` sees."""
    return ((i + 1) * bq - 1) // bk


def _first_query_tile(j, bq, bk):
    """The first query tile that sees key tile ``j``."""
    return j * bk // bq


def _first_key_tile(i, bq, bk, window):
    """The first key tile that query tile ``i`` sees through a window:
    the one that holds key ``i * bq - window + 1``."""
    lo = i * bq - window + 1
    return (max(lo, 0) if isinstance(i, int) else jnp.maximum(lo, 0)) // bk


def _last_query_tile(j, bq, bk, window, nq):
    """The last query tile that sees key tile ``j`` through a window:
    the one that holds query ``(j + 1) * bk - 1 + window - 1``."""
    hi = ((j + 1) * bk + window - 2) // bq
    return min(hi, nq - 1) if isinstance(j, int) else jnp.minimum(hi, nq - 1)


def _band_tiles(time, bq, bk, window):
    """(key tiles a query tile visits, query tiles a key tile visits):
    the most over the sequence, so the grids' inner axes are as long as
    the band is wide and no longer."""
    nq, nk = time // bq, time // bk
    if not window:
        return nk, nq
    return (max(_diag_key_tile(i, bq, bk)
                - _first_key_tile(i, bq, bk, window) + 1 for i in range(nq)),
            max(_last_query_tile(j, bq, bk, window, nq)
                - _first_query_tile(j, bq, bk) + 1 for j in range(nk)))


def _attn_fwd_kernel(scale, bq, bk, nparts, window, *refs):
    """One (batch x head, query tile i, key tile j) step of the online
    softmax: ``s = q k^T * scale`` on the MXU, the running row maximum
    ``m``, row sum ``l`` and the un-normalised ``acc = sum p v`` in
    float32 VMEM scratch across the key tiles, ``p`` in the values'
    dtype for ``p v``. Key tiles wholly above the diagonal do nothing
    (and fetch nothing: the index maps stop at the diagonal); only a
    tile the diagonal crosses pays for the mask. At the diagonal's tile
    the output is normalised once and the row log-sum-exp goes out as a
    lane-dense row. With a ``window`` a query sees the keys ``0 <= i - j <
    window`` only: the key axis of the grid counts from the first tile of
    the query tile's band, so the tiles wholly before the band are never
    visited, and the tile the band's far edge crosses is masked too."""
    from jax.experimental import pallas as pl
    q_refs, k_refs = refs[:nparts], refs[nparts:2 * nparts]
    v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[2 * nparts:]
    i, j = pl.program_id(1), pl.program_id(2)
    last = _diag_key_tile(i, bq, bk)
    first = _first_key_tile(i, bq, bk, window) if window else 0
    if window:
        j = first + j

    @pl.when(j == first)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(masked):
        s = sum(jax.lax.dot_general(q[...], k[...], _NT,
                                    preferred_element_type=jnp.float32)
                for q, k in zip(q_refs, k_refs)) * scale
        if masked:
            qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            ki = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = ki <= qi
            if window:
                seen = jnp.logical_and(seen, qi - ki < window)
            s = jnp.where(seen, s, _MASKED)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)

    below = (j + 1) * bk - 1 <= i * bq     # every key before every query
    if window:                             # ... and none too far back
        below = jnp.logical_and(below, (i + 1) * bq - 1 - j * bk < window)
    pl.when(below)(lambda: tile(False))
    pl.when(jnp.logical_and(j <= last, jnp.logical_not(below)))(
        lambda: tile(True))

    @pl.when(j == last)
    def _():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse = m_ref[...] + jnp.log(l)
        lse_ref[...] = jnp.broadcast_to(lse, (bq, 128)).T[:1]


def _attn_bwd_kernel(scale, bq, bk, nparts, window, *refs):
    """One (batch x head, key tile j, query tile i) step of the backward
    pass, keys on the sublanes and queries on the lanes so that the
    saved log-sum-exp and ``D = rowsum(dO * O)`` broadcast as rows:
    ``p^T = exp(k q^T * scale - lse)`` again from the residuals, ``dV +=
    p^T dO``, ``dS^T = p^T * (v dO^T - D)``, ``dK += dS^T q`` in float32
    scratch across the query tiles, ``dQ += dS k`` into the float32
    ``dQ`` of the whole sequence, which stays in VMEM for all of one
    batch x head. ``scale`` multiplies ``dK`` once at the end and ``dQ``
    outside. Query tiles wholly before the key tile do nothing; with a
    ``window`` the query axis of the grid counts from the key tile's first
    query tile and is as long as the band is wide, so the query tiles
    wholly past the band are never visited."""
    from jax.experimental import pallas as pl
    q_refs, k_refs = refs[:nparts], refs[nparts:2 * nparts]
    v_ref, do_ref, lse_ref, dd_ref = refs[2 * nparts:2 * nparts + 4]
    outs = refs[2 * nparts + 4:]
    dq_refs, dk_refs, dv_ref = outs[:nparts], outs[nparts:2 * nparts], \
        outs[2 * nparts]
    dk_accs, dv_acc = outs[2 * nparts + 1:3 * nparts + 1], outs[-1]
    j, i = pl.program_id(1), pl.program_id(2)
    step = i                               # along the grid's query axis
    if window:
        i = _first_query_tile(j, bq, bk) + step

    @pl.when(jnp.logical_and(j == 0, step == 0))
    def _():
        for dq in dq_refs:
            dq[...] = jnp.zeros(dq.shape, jnp.float32)

    @pl.when(step == 0)
    def _():
        for acc in dk_accs + (dv_acc,):
            acc[...] = jnp.zeros(acc.shape, jnp.float32)

    def tile(masked):
        st = sum(jax.lax.dot_general(k[...], q[...], _NT,
                                     preferred_element_type=jnp.float32)
                 for q, k in zip(q_refs, k_refs)) * scale
        if masked:
            ki = j * bk + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
            qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
            seen = ki <= qi
            if window:
                seen = jnp.logical_and(seen, qi - ki < window)
            st = jnp.where(seen, st, _MASKED)
        pt = jnp.exp(st - lse_ref[...])
        do = do_ref[...]
        dv_acc[...] += jnp.dot(pt.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[...], do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - dd_ref[...])).astype(do.dtype)
        rows = pl.ds(pl.multiple_of(i * bq, bq), bq)
        for q, k, dq, dk_acc in zip(q_refs, k_refs, dq_refs, dk_accs):
            dk_acc[...] += jnp.dot(dst, q[...],
                                   preferred_element_type=jnp.float32)
            dq[rows, :] += jax.lax.dot_general(
                dst, k[...], _TN, preferred_element_type=jnp.float32)

    below = (j + 1) * bk - 1 <= i * bq     # every key before every query
    if window:
        # ... none too far back, and the tile one of the sequence's (the
        # grid's axis may run past the key tile's last query tile)
        seen = i <= _last_query_tile(j, bq, bk, window,
                                     dq_refs[0].shape[0] // bq)
        below = jnp.logical_and(jnp.logical_and(below, seen),
                                (i + 1) * bq - 1 - j * bk < window)
    pl.when(below)(lambda: tile(False))
    if not window:
        seen = i >= _first_query_tile(j, bq, bk)
    pl.when(jnp.logical_and(seen, jnp.logical_not(below)))(
        lambda: tile(True))

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        for dk, acc in zip(dk_refs, dk_accs):
            dk[...] = (acc[...] * scale).astype(dk.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _attn_fwd_call(qs, ks, v, scale, bq, bk, window):
    """``(o, lse)`` of ``qs[n]`` ``(bh, t, d_n)`` against ``ks[n]``
    ``(bh or fewer, t, d_n)`` and ``v`` ``(bh or fewer, t, dv)`` (one
    with fewer leading entries is shared by that many consecutive query
    heads: a group's key/value head, or MLA's one ``k_rope`` a batch
    item); the score is the sum of the parts' products."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bh, t, dv = qs[0].shape[0], v.shape[1], v.shape[2]
    n = len(qs)

    def at_q(b, i, j):
        return b, i, 0

    def at_k(share):
        # past the diagonal the same tile again: nothing is fetched
        if window:
            return lambda b, i, j: (b // share, jnp.minimum(
                _first_key_tile(i, bq, bk, window) + j,
                _diag_key_tile(i, bq, bk)), 0)
        return lambda b, i, j: (
            b // share, jnp.minimum(j, _diag_key_tile(i, bq, bk)), 0)

    return pl.pallas_call(
        partial(_attn_fwd_kernel, scale, bq, bk, n, window),
        grid=(bh, t // bq, _band_tiles(t, bq, bk, window)[0]),
        in_specs=[pl.BlockSpec((None, bq, q.shape[2]), at_q) for q in qs]
        + [pl.BlockSpec((None, bk, k.shape[2]), at_k(bh // k.shape[0]))
           for k in ks]
        + [pl.BlockSpec((None, bk, dv), at_k(bh // v.shape[0]))],
        out_specs=[pl.BlockSpec((None, bq, dv), at_q),
                   pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((bh, t, dv), v.dtype),
                   jax.ShapeDtypeStruct((bh, 1, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_ATTN_VMEM),
        interpret=_build_interpret(),
    )(*qs, *ks, v)


def _attn_bwd_call(qs, ks, v, do, lse, dd, scale, bq, bk, window):
    """``(dqs, dks, dv)``: ``dqs`` float32 and without ``scale`` (the
    caller's cast applies it), ``dks`` and ``dv`` a query head each
    whatever ``ks`` and ``v`` share, in their dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bh, t, dv = qs[0].shape[0], v.shape[1], v.shape[2]
    n = len(qs)

    def at_k(share):
        return lambda b, j, i: (b // share, j, 0)

    def first(j, i):
        # before the key tile's first query tile the same tile again
        # (with a window: past its last)
        if window:
            return jnp.minimum(
                _first_query_tile(j, bq, bk) + i,
                _last_query_tile(j, bq, bk, window, t // bq))
        return jnp.maximum(i, _first_query_tile(j, bq, bk))

    def at_q(b, j, i):
        return b, first(j, i), 0

    def at_row(b, j, i):
        return b, 0, first(j, i)

    def whole(b, j, i):
        return b, 0, 0

    return pl.pallas_call(
        partial(_attn_bwd_kernel, scale, bq, bk, n, window),
        grid=(bh, t // bk, _band_tiles(t, bq, bk, window)[1]),
        in_specs=[pl.BlockSpec((None, bq, q.shape[2]), at_q) for q in qs]
        + [pl.BlockSpec((None, bk, k.shape[2]), at_k(bh // k.shape[0]))
           for k in ks]
        + [pl.BlockSpec((None, bk, dv), at_k(bh // v.shape[0])),
           pl.BlockSpec((None, bq, dv), at_q),
           pl.BlockSpec((None, 1, bq), at_row),
           pl.BlockSpec((None, 1, bq), at_row)],
        out_specs=[pl.BlockSpec((None, t, q.shape[2]), whole) for q in qs]
        + [pl.BlockSpec((None, bk, k.shape[2]), at_k(1)) for k in ks]
        + [pl.BlockSpec((None, bk, dv), at_k(1))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, jnp.float32) for q in qs]
        + [jax.ShapeDtypeStruct((bh,) + k.shape[1:], k.dtype) for k in ks]
        + [jax.ShapeDtypeStruct((bh,) + v.shape[1:], v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, k.shape[2]), jnp.float32)
                        for k in ks]
        + [pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_ATTN_VMEM),
        interpret=_build_interpret(),
    )(*qs, *ks, v, do, lse, dd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention(qs, ks, v, scale, bq, bk, window):
    return _attn_fwd_call(qs, ks, v, scale, bq, bk, window)[0]


def _attention_fwd(qs, ks, v, scale, bq, bk, window):
    # the kernel's two outputs are the residuals that are no inputs: a
    # remat = block segment keeps them by these names (base.py), so its
    # backward pass does not run this kernel again to have them
    o, lse = map(checkpoint_name,
                 _attn_fwd_call(qs, ks, v, scale, bq, bk, window),
                 ATTENTION_KEEPS)
    return o, (qs, ks, v, o, lse)


def _attention_bwd(scale, bq, bk, window, res, do):
    qs, ks, v, o, lse = res
    dd = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1)[:, None, :]
    out = _attn_bwd_call(qs, ks, v, do, lse, dd, scale, bq, bk, window)
    n = len(qs)
    dqs = tuple((dq * scale).astype(q.dtype) for dq, q in zip(out[:n], qs))
    # what several query heads share gets the sum of their gradients
    dkv = tuple(d if d.shape == a.shape else
                d.reshape((a.shape[0], -1) + a.shape[1:])
                .astype(jnp.float32).sum(axis=1).astype(a.dtype)
                for d, a in zip(out[n:], ks + (v,)))
    return dqs, dkv[:n], dkv[n]


_attention.defvjp(_attention_fwd, _attention_bwd)


_ATTN_TILES = (1024, 512, 256, 128)


def _attn_tiles(time: int, q_block: int):
    """(query tile, key tile): the largest of ``_ATTN_TILES`` that
    divides ``time``, the query tile no larger than ``q_block`` (0: no
    cap). No search at run time: on a v5e at 2 x 16 x 8,192 a layer's
    two forwards and one backward took 31.9 ms at 1,024 x 1,024, 34.0 at
    512 x 1,024, 33.8 at 2,048 x 1,024, 42.0 at 1,024 x 512, 43.7 at
    512 x 512, 73.6 at 256 x 256 (my chip run, PR 29)."""
    fits = [b for b in _ATTN_TILES if time % b == 0]
    capped = [b for b in fits if not 0 < q_block < b]
    return (capped[0] if capped else 0), (fits[0] if fits else 0)


def causal_attention_applicable(time: int, q_block: int, qk_widths,
                                v_width: int, nhead: int = 1,
                                nkvhead: int = 1, window: int = 0) -> bool:
    """Shape gate of :func:`causal_attention`: the sequence tiles (a
    multiple of 128 positions, a query tile within ``q_block``), every
    part of the query/key features is whole half-lanes (64) and the
    values whole lanes (128) or one half-lane (64: LFM2's heads, which
    the kernels take as they are, a block of 64 lanes), the float32
    ``dQ`` of one sequence, which
    the backward kernel keeps in VMEM twice over, stays within half of
    the kernels' VMEM, each key/value head serves a whole group of query
    heads, and a window is no negative number."""
    lanes = sum(_pad_to(d, 128) for d in qk_widths)
    return (min(_attn_tiles(time, q_block)) > 0
            and all(d > 0 and d % 64 == 0 for d in qk_widths)
            and v_width > 0 and (v_width % 128 == 0 or v_width == 64)
            and 2 * 4 * time * lanes <= _ATTN_VMEM // 2
            and nkvhead > 0 and nhead % nkvhead == 0 and window >= 0)


def causal_attention(qs, ks, v, scale: float, q_block: int = 0,
                     window: int = 0):
    """Causal ``softmax(sum_n qs[n] ks[n]^T * scale) v`` as one fused
    kernel a direction: no score tile leaves VMEM. ``qs[n]`` ``(batch,
    heads, time, d_n)``; ``ks[n]`` and ``v`` ``(batch, kv heads, time,
    d)`` with the query heads a multiple of the kv heads: key/value head
    ``g`` serves the query heads ``g * heads / kv heads`` on (grouped
    queries; one kv head is a part all heads share, MLA's ``k_rope``),
    and its gradient is the sum over them. ``window`` > 0: query ``i``
    sees key ``j`` iff ``0 <= i - j < window``, and the key tiles wholly
    outside that band are neither fetched nor computed; 0: every earlier
    key. Products take the operands' dtype with float32 accumulation,
    the softmax is float32. Differentiable in ``qs``, ``ks`` and ``v``;
    the backward pass recomputes the probabilities from ``q``, ``k`` and
    the saved row log-sum-exp."""
    b, h, t = qs[0].shape[:3]
    bq, bk = _attn_tiles(t, q_block)
    flat = lambda a: a.reshape((-1,) + a.shape[2:])
    o = _attention(tuple(map(flat, qs)), tuple(map(flat, ks)), flat(v),
                   scale, bq, bk, 0 if window >= t else window)
    return o.reshape(b, h, t, v.shape[3])


# ------------------------------------------- gated delta rule's scan

_DELTA_VMEM = 64 * 1024 * 1024
_DELTA_TILES = (512, 256, 128)
_NN = (((1,), (0,)), ((), ()))       # a @ b


def _delta_tile(time: int) -> int:
    """Positions a grid step of the delta rule's kernels takes: the
    largest of ``_DELTA_TILES`` that divides ``time`` (0: none does)."""
    return next((b for b in _DELTA_TILES if time % b == 0), 0)


def _mm(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _stage_gates(rows_ref, cols_ref):
    """``rows_ref`` ``(r, n)`` float32, a row a value head along time,
    into ``cols_ref`` ``(r, n, 128)`` with a position a sublane and every
    lane the same: what scales a chunk's rows."""
    rows = rows_ref[...]
    for p in range(rows.shape[0]):
        cols_ref[p] = jnp.broadcast_to(rows[p:p + 1],
                                       (_LANES, rows.shape[1])).T


def _delta_gates(a, width):
    """A chunk's decays from the running sum of one value head's ``g``,
    ``a`` ``(c, 128)`` with every lane the same: ``exp(a)`` ``(c, 1)``,
    ``exp(a_i - a_j)`` for ``j <= i`` (zeros above), ``exp(a_end - a)``
    ``(c, 1)`` and ``exp(a_end)`` as a row of ``width`` lanes.
    Differences of running sums, so none passes 1."""
    c = a.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    end, a = a[c - 1:c], a[:, :1]
    a_row = jnp.sum(jnp.where(i == j, a, 0.0), axis=0, keepdims=True)
    return (jnp.exp(a), jnp.exp(jnp.where(i >= j, a - a_row, -jnp.inf)),
            jnp.exp(end[:, :1] - a),
            jnp.exp(jnp.concatenate([end] * (width // _LANES), axis=1)))


def _delta_fwd_kernel(c, r, q_ref, k_ref, v_ref, run_ref, beta_ref, t_ref,
                      o_ref, s0_ref, u_ref, s_ref, a_ref, b_ref):
    """One (batch, key head, time tile) step of the gated delta rule
    (layers/sequence.py: gated_delta_rule), the tile's chunks one after
    the other and the key head's ``r`` value heads side by side. The
    float32 state ``S`` ``(r, dk, dv)`` lives in VMEM scratch across the
    time tiles. A chunk, with ``T`` its triangular inverse: ``u = T (beta
    v) - (T (beta exp(G) k)) S``, ``o = exp(G) q S + ((q k^T) * decays)
    u``, ``S <- exp(G_end) S + (exp(G_end - G) k)^T u``. Products take
    operands in the inputs' dtype with float32 results, the decays and
    the state are float32. Besides ``o`` the chunk's starting state and
    ``u`` go out in the operands' dtype: the backward kernel reads
    them."""
    from jax.experimental import pallas as pl
    cd = q_ref.dtype
    dv = v_ref.shape[1] // r

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, jnp.float32)

    _stage_gates(run_ref, a_ref)
    _stage_gates(beta_ref, b_ref)

    def chunk(n, carry):
        rows = pl.ds(pl.multiple_of(n * c, c), c)
        q, k = q_ref[rows, :], k_ref[rows, :]
        kf = k.astype(jnp.float32)
        qk = _mm(q, k, _NT)
        for p in range(r):
            at = slice(p * dv, (p + 1) * dv)
            beta = b_ref[p, rows, :][:, :1]
            grow, decay, tail, keep = _delta_gates(a_ref[p, rows, :], dv)
            solve = t_ref[p, rows, :]
            s = s_ref[p]
            s_cd = s.astype(cd)
            s0_ref[p, n] = s_cd
            u0 = _mm(solve, (beta * v_ref[rows, at].astype(jnp.float32))
                     .astype(cd)).astype(cd)
            w = _mm(solve, ((beta * grow) * kf).astype(cd)).astype(cd)
            u = (u0.astype(jnp.float32) - _mm(w, s_cd)).astype(cd)
            u_ref[rows, at] = u
            o_ref[rows, at] = (grow * _mm(q, s_cd) + _mm(
                (qk * decay).astype(cd), u)).astype(cd)
            s_ref[p] = keep * s + _mm((tail * kf).astype(cd), u, _TN)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0] // c, chunk, 0)


def _delta_bwd_kernel(c, r, q_ref, k_ref, v_ref, run_ref, beta_ref, t_ref,
                      s0_ref, u_ref, do_ref, dq_ref, dk_ref, dv_ref,
                      drun_ref, dbeta_ref, dt_ref, ds_ref, a_ref, b_ref,
                      da_ref, db_ref):
    """The same step backward, the time tiles and a tile's chunks last
    to first, the gradient of the state ``dS`` ``(r, dk, dv)`` float32
    in VMEM scratch as the state was. A chunk's products are made again
    from its inputs, its starting state and ``u``; cotangents are cast
    to the operands' dtype where a product reads them. ``dq`` and ``dk``
    are summed over the key head's value heads here."""
    from jax.experimental import pallas as pl
    cd = q_ref.dtype
    f32 = jnp.float32
    dv = v_ref.shape[1] // r
    nc = q_ref.shape[0] // c

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros(ds_ref.shape, f32)

    _stage_gates(run_ref, a_ref)
    _stage_gates(beta_ref, b_ref)

    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1

    def chunk(m, carry):
        n = nc - 1 - m
        rows = pl.ds(pl.multiple_of(n * c, c), c)
        q, k = q_ref[rows, :], k_ref[rows, :]
        kf = k.astype(f32)
        qk = _mm(q, k, _NT)
        dq = jnp.zeros(kf.shape, f32)
        dk = jnp.zeros(kf.shape, f32)
        for p in range(r):
            at = slice(p * dv, (p + 1) * dv)
            beta = b_ref[p, rows, :][:, :1]
            grow, decay, tail, keep = _delta_gates(a_ref[p, rows, :], dv)
            solve = t_ref[p, rows, :]
            s_cd, u = s0_ref[p, n], u_ref[rows, at]
            vf = v_ref[rows, at].astype(f32)
            do = do_ref[rows, at]
            ds = ds_ref[p]
            ds_cd = ds.astype(cd)
            dof, bg = do.astype(f32), beta * grow
            vb, kb = (beta * vf).astype(cd), (bg * kf).astype(cd)
            kd = tail * kf
            kd_cd = kd.astype(cd)
            w = _mm(solve, kb).astype(cd)
            # o = exp(G) q S + P u
            edo = (grow * dof).astype(cd)
            dq += _mm(edo, s_cd, _NT)
            dsc = _mm(q, edo, _TN)
            dgrow = jnp.sum(dof * _mm(q, s_cd), axis=1, keepdims=True)
            dp = _mm(do, u, _NT)
            pf = qk * decay
            du = _mm(pf.astype(cd), do, _TN)
            dqk = (dp * decay).astype(cd)
            dq += _mm(dqk, k)
            dk += _mm(dqk, q, _TN)
            mix = dp * pf
            da = jnp.sum(mix, axis=1, keepdims=True) - jnp.sum(
                jnp.where(i == j, jnp.sum(mix, axis=0, keepdims=True), 0.0),
                axis=1, keepdims=True)
            # S' = exp(G_end) S + (exp(G_end - G) k)^T u
            dkd = _mm(u, ds_cd, _NT)
            du += _mm(kd_cd, ds_cd)
            dtail = jnp.sum(dkd * kd, axis=1, keepdims=True)
            da -= dtail
            dend = keep[:, :1] * jnp.sum(ds * s_cd.astype(f32),
                                         keepdims=True) \
                + jnp.sum(dtail, axis=0, keepdims=True)
            dk += tail * dkd
            # u = T (beta v) - (T (beta exp(G) k)) S
            du = du.astype(cd)
            dw = (-_mm(du, s_cd, _NT)).astype(cd)
            dsc -= _mm(w, du, _TN)
            dt_ref[p, rows, :] = (_mm(du, vb, _NT) + _mm(dw, kb, _NT)) \
                .astype(dt_ref.dtype)
            dvb, dkb = _mm(solve, du, _TN), _mm(solve, dw, _TN)
            dv_ref[rows, at] = (beta * dvb).astype(dv_ref.dtype)
            dk += bg * dkb
            dkbk = jnp.sum(dkb * kf, axis=1, keepdims=True)
            dgrow += beta * dkbk
            da += grow * dgrow + jnp.where(last, dend, 0.0)
            da_ref[p, rows, :] = jnp.broadcast_to(da, (c, _LANES))
            db_ref[p, rows, :] = jnp.broadcast_to(
                jnp.sum(dvb * vf, axis=1, keepdims=True) + grow * dkbk,
                (c, _LANES))
            ds_ref[p] = keep * ds + dsc
        dq_ref[rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[rows, :] = dk.astype(dk_ref.dtype)
        return carry

    jax.lax.fori_loop(0, nc, chunk, 0)
    for p in range(r):
        drun_ref[p:p + 1, :] = da_ref[p].T[:1]
        dbeta_ref[p:p + 1, :] = db_ref[p].T[:1]


def _delta_specs(nt, bt, dk, r, dv, c, backward):
    """Block specs of the delta rule's kernels over ``(batch, key head,
    time tile)``, the time tiles last to first where ``backward``: rows
    of a key head's features (q, k), of its value heads' (v, o, u),
    gates a value head along time, a matrix of ``c`` columns a chunk a
    value head (``T``), a state a chunk a value head."""
    from jax.experimental import pallas as pl
    at = (lambda i: nt - 1 - i) if backward else (lambda i: i)
    return (pl.BlockSpec((None, bt, dk), lambda b, h, i: (b, at(i), h)),
            pl.BlockSpec((None, bt, r * dv), lambda b, h, i: (b, at(i), h)),
            pl.BlockSpec((None, None, r, bt),
                         lambda b, h, i: (b, h, 0, at(i))),
            pl.BlockSpec((None, None, r, bt, c),
                         lambda b, h, i: (b, h, 0, at(i), 0)),
            pl.BlockSpec((None, None, r, bt // c, dk, dv),
                         lambda b, h, i: (b, h, 0, at(i), 0, 0)))


def _delta_call(kernel, ins, specs, outs, out_specs, scratch, grid,
                semantics=("parallel", "parallel", "arbitrary")):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, grid=grid, in_specs=specs, out_specs=out_specs,
        out_shape=outs, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=_DELTA_VMEM),
        interpret=_build_interpret(),
    )(*ins)


def _delta_plan(q, v, solve, backward):
    """(chunk, value heads a key head, time tile, grid, block specs)."""
    b, t, kw = q.shape
    hk, r, c = solve.shape[1], solve.shape[2], solve.shape[4]
    bt = _delta_tile(t)
    return c, r, bt, (b, hk, t // bt), _delta_specs(
        t // bt, bt, kw // hk, r, v.shape[2] // (hk * r), c, backward)


def _delta_fwd_call(q, k, v, run, beta, solve):
    from jax.experimental.pallas import tpu as pltpu
    c, r, bt, grid, (qs, vs, gs, ts, ss) = _delta_plan(q, v, solve, False)
    (b, t, kw), hk = q.shape, grid[1]
    dk, dv = kw // hk, v.shape[2] // (hk * r)
    return _delta_call(
        partial(_delta_fwd_kernel, c, r), (q, k, v, run, beta, solve),
        [qs, qs, vs, gs, gs, ts],
        [jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct((b, hk, r, t // c, dk, dv), v.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)], [vs, ss, vs],
        [pltpu.VMEM((r, dk, dv), jnp.float32)]
        + [pltpu.VMEM((r, bt, _LANES), jnp.float32)] * 2, grid)


def _delta_bwd_call(q, k, v, run, beta, solve, s0, u, do):
    from jax.experimental.pallas import tpu as pltpu
    c, r, bt, grid, (qs, vs, gs, ts, ss) = _delta_plan(q, v, solve, True)
    return _delta_call(
        partial(_delta_bwd_kernel, c, r),
        (q, k, v, run, beta, solve, s0, u, do),
        [qs, qs, vs, gs, gs, ts, ss, vs, vs],
        [jax.ShapeDtypeStruct(a.shape, a.dtype)
         for a in (q, k, v, run, beta, solve)], [qs, qs, vs, gs, gs, ts],
        [pltpu.VMEM(s0.shape[2:3] + s0.shape[4:], jnp.float32)]
        + [pltpu.VMEM((r, bt, _LANES), jnp.float32)] * 4, grid)


@jax.custom_vjp
def gated_delta_scan(q, k, v, run, beta, solve):
    """The gated delta rule along time as one fused kernel a direction:
    a chunk's intermediates and the running state stay in VMEM. ``q``,
    ``k`` ``(batch, time, key heads * dk)`` and ``v`` ``(batch, time,
    value heads * dv)`` in the compute dtype, a head's features side by
    side; ``run`` (the running sum of ``g`` from its chunk's start) and
    ``beta`` ``(batch, key heads, r, time)`` float32; ``solve`` ``(batch,
    key heads, r, time, chunk)``, a chunk's triangular inverse ``T`` in
    its ``chunk`` rows. ``time`` is whole tiles (``_delta_tile``).
    Returns ``o`` shaped as ``v``. Differentiable in all six: the
    forward kernel hands the backward one every chunk's starting state
    and ``u`` in the compute dtype."""
    return _delta_fwd_call(q, k, v, run, beta, solve)[0]


def _gated_delta_scan_fwd(q, k, v, run, beta, solve):
    o, s0, u = map(checkpoint_name,
                   _delta_fwd_call(q, k, v, run, beta, solve),
                   DELTA_SCAN_KEEPS)
    return o, (q, k, v, run, beta, solve, s0, u)


def _gated_delta_scan_bwd(res, do):
    return tuple(_delta_bwd_call(*res, do))


gated_delta_scan.defvjp(_gated_delta_scan_fwd, _gated_delta_scan_bwd)


def gated_delta_applicable(time: int, chunk: int, dk: int, dv: int, r: int,
                           dtype) -> bool:
    """Shape gate of :func:`gated_delta_scan`: key and value heads of
    whole lanes (128), a chunk of 64 or 128 positions (``time`` itself
    where the sequence is shorter), a padded ``time`` that a time tile
    divides, bfloat16 or float32 operands, and the backward kernel's
    blocks (twice over) and scratch within half of the kernels' VMEM."""
    if min(time, chunk, dk, dv, r) <= 0 or dk % _LANES or dv % _LANES \
            or jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                        jnp.dtype(jnp.float32)):
        return False
    c = min(chunk, time)
    bt = _delta_tile(_pad_to(time, c))
    if c not in (64, 128) or not bt:
        return False
    size = jnp.dtype(dtype).itemsize
    blocks = bt * size * (4 * dk + 4 * r * dv + 2 * r * _LANES) \
        + r * (bt // c) * dk * dv * size + 4 * 8 * bt * 4
    scratch = 4 * r * (dk * dv + 4 * bt * _LANES)
    return 2 * blocks + scratch <= _DELTA_VMEM // 2


# ------------------------------------------- gated delta rule's short convolution

_CONV_TILES = (1024, 512, 256, 128)
_CONV_ROWS = 64     # positions a step of the loop inside a tile
_CONV_BACK = 8      # rows a step reads before (forward) or after itself
_HALO = 16          # the rows of a bfloat16 block before a tile


def _conv_lanes(kw: int, vw: int, dk: int) -> int:
    """Lanes a grid step of the convolution's kernels takes: the widest
    of 512, 256, 128 that is whole key heads and divides the key and the
    value widths (0: none is)."""
    return next((w for w in (512, 256, 128)
                 if w % dk == 0 and kw % w == 0 and vw % w == 0), 0)


def _conv_tile(time: int, w: int, dtype) -> int:
    """Positions a grid step takes: the largest of ``_CONV_TILES`` that
    divides ``time`` and whose backward blocks (twice over: x, dq, dk,
    dv and dx, the halo, the taps and their gradient) and scratch fit
    half of the kernels' VMEM (0: none does)."""
    size = jnp.dtype(dtype).itemsize
    for bt in _CONV_TILES:
        blocks = (5 * bt + _HALO) * w * size + 2 * 8 * w * 4
        if time % bt == 0 and 2 * blocks + 2 * (bt + _HALO) * w * 4 \
                <= _DELTA_VMEM // 2:
            return bt
    return 0


def _conv_index(lo, n, nt, backward):
    """Index map of one of q, k, v, ``n`` column blocks from the grid's
    column ``lo``, over (batch, column block, time tile), the time tiles
    last to first where ``backward``. Outside its columns it points at
    the block it visits first (before) or last (after), so that Pallas,
    which moves a block when its index changes, neither writes back an
    output block nor fetches an input one in between."""
    at = (lambda i: nt - 1 - i) if backward else (lambda i: i)

    def index(b, j, i):
        c = j - lo
        return b, jnp.where(c < 0, at(0), jnp.where(c >= n, at(nt - 1),
                                                    at(i))), \
            jnp.clip(c, 0, n - 1)
    return index


def _conv_taps(ext_ref, r0, cols, taps):
    """The taps' products for the ``_CONV_ROWS`` positions from ``r0``
    of a tile held in ``ext_ref`` from row ``_HALO`` (the positions
    before it above), float32 and in tap order as
    layers/sequence.py: causal_depthwise_conv sums them; and the
    shifted inputs, ``x_(t - K + 1 + m)`` for tap ``m``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    k = len(taps)
    both = ext_ref[pl.ds(r0 + _HALO - _CONV_BACK, _CONV_ROWS + _CONV_BACK),
                   cols]
    xs = [(pltpu.roll(both, k - 1 - m, 0) if m < k - 1 else both)
          [_CONV_BACK:] for m in range(k)]
    return sum(taps[m][:, cols] * xs[m] for m in range(k)), xs


def _conv_fwd_kernel(nq, dk, x_ref, taps_ref, q_ref, k_ref, v_ref, ext_ref):
    """One (batch, column block, time tile) step of gated_delta's short
    convolution (layers/sequence.py: GatedDeltaLayer), the time tiles in
    order: the taps, SiLU and, in q's and k's columns, the unit length a
    head of ``dk`` lanes (q's over ``sqrt(dk)``), written in the inputs'
    dtype to whichever of q, k, v the column block belongs. The
    ``_HALO`` positions before a tile come from the tile before it in
    VMEM scratch, zeros before the first. The work goes in chunks of
    ``_CONV_ROWS`` positions and a head, so that what a chunk computes
    stays in registers."""
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    bt, w = x_ref.shape
    taps = [taps_ref[m:m + 1, :].astype(x_ref.dtype).astype(f32)
            for m in range(taps_ref.shape[0])]

    @pl.when(pl.program_id(2) == 0)
    def _():
        ext_ref[bt:, :] = jnp.zeros((_HALO, w), f32)

    ext_ref[:_HALO, :] = ext_ref[bt:, :]
    ext_ref[_HALO:, :] = x_ref[...].astype(f32)

    def part(out_ref, unit, scale):
        def chunk(n, carry):
            r0 = pl.multiple_of(n * _CONV_ROWS, _CONV_ROWS)
            for h in range(w // dk):
                cols = slice(h * dk, (h + 1) * dk)
                y, _ = _conv_taps(ext_ref, r0, cols, taps)
                a = y * jax.nn.sigmoid(y)
                if unit:
                    a = a * jax.lax.rsqrt(jnp.sum(
                        a * a, axis=1, keepdims=True) + 1e-6) * scale
                out_ref[pl.ds(r0, _CONV_ROWS), cols] = a.astype(out_ref.dtype)
            return carry
        jax.lax.fori_loop(0, bt // _CONV_ROWS, chunk, 0)

    j = pl.program_id(1)
    pl.when(j < nq)(lambda: part(q_ref, True, 1.0 / float(np.sqrt(dk))))
    pl.when((j >= nq) & (j < 2 * nq))(lambda: part(k_ref, True, 1.0))
    pl.when(j >= 2 * nq)(lambda: part(v_ref, False, 1.0))


def _conv_bwd_kernel(nq, dk, x_ref, before_ref, taps_ref, dq_ref, dk_ref,
                     dv_ref, dx_ref, dt_ref, ext_ref, dy_ref):
    """The same step backward, the time tiles and a tile's chunks last to
    first. The taps, SiLU and the norm are made again from the inputs (the
    ``_HALO`` positions before a tile are a block of their own); the
    gradient before the taps, ``dy``, goes to VMEM scratch, whose rows
    after the tile hold the first ``_HALO`` of the tile after it (zeros
    after the last), which the input's gradient reads: it is
    anti-causal. The taps' gradient is summed over the tiles in float32,
    in a block a (batch, column block)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    f32 = jnp.float32
    bt, w = x_ref.shape
    nk = taps_ref.shape[0]
    span = _CONV_ROWS + _CONV_BACK
    taps = [taps_ref[m:m + 1, :].astype(x_ref.dtype).astype(f32)
            for m in range(nk)]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dy_ref[bt:, :] = jnp.zeros((_HALO, w), f32)
        dt_ref[...] = jnp.zeros(dt_ref.shape, f32)

    ext_ref[:_HALO, :] = before_ref[...].astype(f32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        ext_ref[:_HALO, :] = jnp.zeros((_HALO, w), f32)

    ext_ref[_HALO:, :] = x_ref[...].astype(f32)

    def part(g_ref, unit, scale):
        def chunk(m, carry):
            r0 = pl.multiple_of((bt // _CONV_ROWS - 1 - m) * _CONV_ROWS,
                                _CONV_ROWS)
            rows = pl.ds(r0, _CONV_ROWS)
            for h in range(w // dk):
                cols = slice(h * dk, (h + 1) * dk)
                y, xs = _conv_taps(ext_ref, r0, cols, taps)
                sg = jax.nn.sigmoid(y)
                g = g_ref[rows, cols].astype(f32) * scale
                if unit:
                    a = y * sg
                    s = jax.lax.rsqrt(jnp.sum(a * a, axis=1, keepdims=True)
                                      + 1e-6)
                    g = s * g - a * (s * s * s) * jnp.sum(
                        g * a, axis=1, keepdims=True)
                dy = g * (sg * (1.0 + y * (1.0 - sg)))
                dy_ref[rows, cols] = dy
                for t in range(nk):
                    dt_ref[t:t + 1, cols] += jnp.sum(dy * xs[t], axis=0,
                                                     keepdims=True)
                after = dy_ref[pl.ds(r0, span), cols]
                dx_ref[rows, cols] = sum(
                    taps[t][:, cols] * (pltpu.roll(after, span - nk + 1 + t, 0)
                                        if t < nk - 1 else after)
                    [:_CONV_ROWS] for t in range(nk)).astype(dx_ref.dtype)
            return carry
        jax.lax.fori_loop(0, bt // _CONV_ROWS, chunk, 0)

    j = pl.program_id(1)
    pl.when(j < nq)(lambda: part(dq_ref, True, 1.0 / float(np.sqrt(dk))))
    pl.when((j >= nq) & (j < 2 * nq))(lambda: part(dk_ref, True, 1.0))
    pl.when(j >= 2 * nq)(lambda: part(dv_ref, False, 1.0))
    dy_ref[bt:, :] = dy_ref[:_HALO, :]


def _conv_plan(qkv, kw, dk, backward):
    """(grid, the three parts' block specs, a tile's block spec, tile,
    lanes) of the convolution's kernels."""
    from jax.experimental import pallas as pl
    b, t, c = qkv.shape
    w = _conv_lanes(kw, c - 2 * kw, dk)
    bt = _conv_tile(t, w, qkv.dtype)
    nq, nt = kw // w, t // bt
    at = (lambda i: nt - 1 - i) if backward else (lambda i: i)
    parts = [pl.BlockSpec((None, bt, w), _conv_index(lo, n, nt, backward))
             for lo, n in ((0, nq), (nq, nq), (2 * nq, c // w - 2 * nq))]
    return (b, c // w, nt), parts, \
        pl.BlockSpec((None, bt, w), lambda b, j, i: (b, at(i), j)), bt, w


_CONV_SEMANTICS = ("parallel", "arbitrary", "arbitrary")


def _conv_fwd_call(qkv, taps, kw, dk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    grid, parts, tile, bt, w = _conv_plan(qkv, kw, dk, False)
    b, t, c = qkv.shape
    return _delta_call(
        partial(_conv_fwd_kernel, kw // w, dk), (qkv, taps),
        [tile, pl.BlockSpec((taps.shape[0], w), lambda b, j, i: (0, j))],
        [jax.ShapeDtypeStruct((b, t, n), qkv.dtype)
         for n in (kw, kw, c - 2 * kw)], parts,
        [pltpu.VMEM((bt + _HALO, w), jnp.float32)], grid, _CONV_SEMANTICS)


def _conv_bwd_call(qkv, taps, dq, dk_, dv, kw, dk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    grid, parts, tile, bt, w = _conv_plan(qkv, kw, dk, True)
    nk, nt, per = taps.shape[0], grid[2], bt // _HALO
    return _delta_call(
        partial(_conv_bwd_kernel, kw // w, dk), (qkv, qkv, taps, dq, dk_, dv),
        [tile, pl.BlockSpec((None, _HALO, w), lambda b, j, i: (
            b, jnp.maximum((nt - 1 - i) * per - 1, 0), j)),
         pl.BlockSpec((nk, w), lambda b, j, i: (0, j))] + parts,
        [jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
         jax.ShapeDtypeStruct((qkv.shape[0], nk, qkv.shape[2]), jnp.float32)],
        [tile, pl.BlockSpec((None, nk, w), lambda b, j, i: (b, 0, j))],
        [pltpu.VMEM((bt + _HALO, w), jnp.float32)] * 2, grid, _CONV_SEMANTICS)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def gated_delta_conv(qkv, taps, kw: int, dk: int):
    """``gated_delta``'s short convolution as one fused kernel a
    direction: ``qkv`` ``(batch, time, 2 kw + vw)`` in the compute dtype
    (the projection's output), ``taps`` ``(K, 2 kw + vw)``; returns q,
    k ``(batch, time, kw)`` and v ``(batch, time, vw)`` in ``qkv``'s
    dtype, a head's features side by side (the layout
    :func:`gated_delta_scan` reads): ``silu(causal depthwise
    convolution)``, q and k of unit length a head of ``dk``, q over
    ``sqrt(dk)``. The arithmetic of layers/sequence.py's XLA form
    (``short_conv``) to float32 rounding, the taps summed in its order;
    the backward kernel makes it again from ``qkv``
    and ``taps``, so nothing else is kept for it. The taps' gradient
    is rounded through the compute dtype, as the XLA form's cast of the
    taps has it."""
    return tuple(_conv_fwd_call(qkv, taps, kw, dk))


def _gated_delta_conv_fwd(qkv, taps, kw, dk):
    return tuple(_conv_fwd_call(qkv, taps, kw, dk)), (qkv, taps)


def _gated_delta_conv_bwd(kw, dk, res, g):
    qkv, taps = res
    dqkv, dt = _conv_bwd_call(qkv, taps, *g, kw, dk)
    return dqkv, jnp.sum(dt, axis=0).astype(qkv.dtype).astype(taps.dtype)


gated_delta_conv.defvjp(_gated_delta_conv_fwd, _gated_delta_conv_bwd)


def gated_delta_conv_applicable(time: int, kernel: int, hk: int, hv: int,
                                dk: int, dv: int, dtype) -> bool:
    """Shape gate of :func:`gated_delta_conv`: key and value heads of
    whole lanes (128) whose widths a block of whole key heads divides, at
    most ``_CONV_BACK + 1`` taps, a ``time`` that a time tile divides
    with the kernels' blocks and scratch within half of their VMEM, and
    bfloat16 or float32 operands."""
    if min(time, kernel, hk, hv, dk, dv) <= 0 or dk % _LANES \
            or dv % _LANES or kernel - 1 > _CONV_BACK \
            or jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                        jnp.dtype(jnp.float32)):
        return False
    w = _conv_lanes(hk * dk, hv * dv, dk)
    return bool(w and _conv_tile(time, w, dtype))


# ------------------------------------------- grouped expert products

_EXPERT_VMEM = 96 * 1024 * 1024
_LANES = 128


def _expert_call(kernel, expert, tok, nb, block, ins, outs, acc=None,
                 scratch=()):
    """One grouped kernel over the row blocks in use. ``expert``
    ``(blocks,)`` and ``tok`` ``(rows,)`` are scalar-prefetched: whose
    block each is, and the token of each row. The grid is the traced
    ``nb``, so work follows the routing. A two-dimensional operand is
    rows: block ``i`` of it; rows past ``nb * block`` of such an output
    are never written. A three-dimensional one is a matrix a held
    expert: ``expert[i]``'s, whole, so consecutive blocks of one expert
    fetch it once; such an output starts as zeros (an input left in HBM,
    aliased to it), which an expert without a block keeps. ``acc``,
    where given, stays in HBM too and is aliased to one more, last
    output: the kernel reads and writes it by its own copies. The kernel
    sees the inputs, one unused reference a matrix output, ``acc``, the
    outputs, the scratch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(a):
        if len(a.shape) == 2:
            return pl.BlockSpec((block, a.shape[1]), lambda i, e, t: (i, 0))
        return pl.BlockSpec((None,) + tuple(a.shape[1:]),
                            lambda i, e, t: (e[i], 0, 0))

    in_specs, out_specs = [spec(a) for a in ins], [spec(o) for o in outs]
    ins, outs, aliases = list(ins), list(outs), {}
    for at, o in enumerate(list(outs)):
        if len(o.shape) == 3:
            aliases[2 + len(ins)] = at
            ins.append(jnp.zeros(o.shape, o.dtype))
    if acc is not None:
        aliases[2 + len(ins)] = len(outs)
        ins.append(acc)
        outs.append(jax.ShapeDtypeStruct(acc.shape, acc.dtype))
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * (len(ins)
                                                       - len(in_specs))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nb,),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=list(scratch)),
        out_shape=outs,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_EXPERT_VMEM),
        interpret=_build_interpret(),
    )(expert, tok, *ins)


# The token-major sums (the layer's result, and the gradient of its
# input) are kept as SLABS while the kernels add rows to them: a row of
# ``d = c * 128`` float32 as ``c`` rows of 128, so that a token's row is
# one piece of HBM (8 KB at 2,048) that a copy can address. Mosaic takes
# no one-row slice of a ``(tokens, d)`` array, whose rows lie eight to a
# tile. ``_from_slabs`` turns the sums back into rows.


def _token_copies(i, tok_ref, acc_hbm, buf, sem, block, dump, to_hbm):
    """Start one copy a row of block ``i`` between ``acc_hbm``'s slab of
    the row's token and the row's slab of ``buf``, ``to_hbm`` or from
    it. A padding row's token is past the last one: its slab is
    ``dump``, which nobody reads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    c = buf.shape[0] // block

    def row(r, carry):
        t = jnp.minimum(tok_ref[i * block + r], dump)
        mine = buf.at[pl.ds(pl.multiple_of(r * c, c), c)]
        theirs = acc_hbm.at[pl.ds(pl.multiple_of(t * c, c), c)]
        (pltpu.make_async_copy(mine, theirs, sem) if to_hbm
         else pltpu.make_async_copy(theirs, mine, sem)).start()
        return carry

    # (unrolled by hand by 2, 4 or 8 it is no faster: PERF.md, PR 31)
    jax.lax.fori_loop(0, block, row, 0)


def _token_copies_wait(acc_hbm, buf, sem):
    """Wait for a block's copies, all at once: a copy's semaphore counts
    bytes, and a block's copies move as many as one copy of all of
    ``buf`` would."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    pltpu.make_async_copy(acc_hbm.at[pl.ds(0, buf.shape[0])], buf, sem).wait()


def _add_to_tokens_begin(tok_ref, acc_hbm, buf, sems, block, dump):
    """First half of ``acc[tok[r]] += rows[r]`` for this block: once the
    block before has written its rows back (a token may be in both),
    start reading this block's tokens' sums; the products run
    meanwhile."""
    from jax.experimental import pallas as pl
    # (interpreted, a grid position can be asked for at a kernel's top
    # level only: not under ``pl.when``)
    i = pl.program_id(0)

    @pl.when(i > 0)
    def _():
        _token_copies_wait(acc_hbm, buf, sems.at[1])

    _token_copies(i, tok_ref, acc_hbm, buf, sems.at[0], block, dump, False)


def _add_to_tokens_end(tok_ref, acc_hbm, buf, sems, block, dump, rows):
    """Second half: the sums have arrived, ``rows`` ``(block, d)`` are
    added and the copies back start; the last block waits for its own.
    A token is in a block at most once (a block is one expert's, and a
    token picks an expert once), so no two rows of a block share a
    slab but the padding's."""
    from jax.experimental import pallas as pl
    c = buf.shape[0] // block
    i = pl.program_id(0)
    _token_copies_wait(acc_hbm, buf, sems.at[0])
    for k in range(c):
        buf[pl.ds(k, block, stride=c), :] += \
            rows[:, k * _LANES:(k + 1) * _LANES]
    _token_copies(i, tok_ref, acc_hbm, buf, sems.at[1], block, dump, True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        _token_copies_wait(acc_hbm, buf, sems.at[1])


def _from_slabs_kernel(c, s_ref, o_ref):
    from jax.experimental import pallas as pl
    for k in range(c):
        o_ref[:, k * _LANES:(k + 1) * _LANES] = s_ref[
            pl.ds(k, o_ref.shape[0], stride=c), :].astype(o_ref.dtype)


def _from_slabs(slabs, tokens, dtype):
    """``(tokens, c * 128)`` rows in ``dtype`` of the first ``tokens`` of
    ``slabs`` ``(more * c, 128)``."""
    from jax.experimental import pallas as pl
    c = slabs.shape[0] // (tokens + 1)
    # the largest block of rows whose two buffers in and out stay within
    # the default scoped VMEM (16 MiB: 512 rows at d = 2,048 float32 just
    # fit, at 2,304 they do not)
    row = c * _LANES * (4 + jnp.dtype(dtype).itemsize)
    bt = next(b for b in (512, 256, 128, 64, 32, 16, 8)
              if tokens % b == 0 and 2 * b * row <= 16 * 1024 * 1024)
    return pl.pallas_call(
        partial(_from_slabs_kernel, c),
        grid=(tokens // bt,),
        in_specs=[pl.BlockSpec((bt * c, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bt, c * _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tokens, c * _LANES), dtype),
        interpret=_build_interpret(),
    )(slabs)


def _token_sums(tokens, d, block):
    """Zeroed slabs for ``tokens`` rows of ``d`` and the padding's dump,
    and the scratch a kernel needs to add a block of rows to them."""
    from jax.experimental.pallas import tpu as pltpu
    c = d // _LANES
    return jnp.zeros(((tokens + 1) * c, _LANES), jnp.float32), \
        [pltpu.VMEM((block * c, _LANES), jnp.float32),
         pltpu.SemaphoreType.DMA((2,))]


def _gate_up(x, wg_ref, wu_ref):
    """``a = x Wgate``, ``u = x Wup`` in float32, ``sigmoid(a)`` and
    ``silu(a)``: none of the four leaves VMEM."""
    a = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    sig = jax.nn.sigmoid(a)
    return a, u, sig, a * sig


def _experts_fwd_kernel(tokens, e_ref, tok_ref, x_ref, cw_ref, wg_ref, wu_ref,
                        wd_ref, _, out_hbm, buf, sems):
    """A block of rows through its expert's SwiGLU, added to its tokens:
    ``out[tok] += cw * ((silu(x Wgate) * (x Wup)) Wdown)``, the hidden
    rows in the operands' dtype for the third product, the combine
    weight applied and the sum kept in float32."""
    block = x_ref.shape[0]
    _add_to_tokens_begin(tok_ref, out_hbm, buf, sems, block, tokens)
    x = x_ref[...]
    _, u, _, act = _gate_up(x, wg_ref, wu_ref)
    y = jnp.dot((act * u).astype(x.dtype), wd_ref[...],
                preferred_element_type=jnp.float32)
    _add_to_tokens_end(tok_ref, out_hbm, buf, sems, block, tokens,
                       cw_ref[...] * y)


def _experts_bwd_kernel(tokens, e_ref, tok_ref, x_ref, g_ref, cw_ref, wg_ref,
                        wu_ref, wd_ref, _, da_ref, du_ref, hc_ref, dcw_ref,
                        dx_hbm, buf, sems):
    """The same block backward, rows in and rows out: the hidden rows
    again from ``x``, ``dh0 = g Wdown^T``, the combine weight's gradient
    ``sum(h * dh0)`` (which is ``sum((h Wdown) * g)`` without forming
    ``h Wdown``), ``da`` and ``du`` through the SwiGLU with ``dh = cw *
    dh0``, and ``dx[tok] += da Wgate^T + du Wup^T`` in float32. ``da``,
    ``du`` and ``cw * h`` go out in the operands' dtype for the weight
    gradients' kernel."""
    block = x_ref.shape[0]
    _add_to_tokens_begin(tok_ref, dx_hbm, buf, sems, block, tokens)
    x, c = x_ref[...], cw_ref[...]
    a, u, sig, act = _gate_up(x, wg_ref, wu_ref)
    h = (act * u).astype(x.dtype).astype(jnp.float32)
    dh0 = jax.lax.dot_general(g_ref[...], wd_ref[...], _NT,
                              preferred_element_type=jnp.float32)
    dcw_ref[...] = jnp.sum(h * dh0, axis=1, keepdims=True)
    hc_ref[...] = (c * h).astype(hc_ref.dtype)
    dh = c * dh0
    du = (dh * act).astype(x.dtype)
    da = (dh * u * (sig * (1.0 + a * (1.0 - sig)))).astype(x.dtype)
    da_ref[...] = da
    du_ref[...] = du
    dx = jax.lax.dot_general(
        da, wg_ref[...], _NT, preferred_element_type=jnp.float32) \
        + jax.lax.dot_general(
            du, wu_ref[...], _NT, preferred_element_type=jnp.float32)
    _add_to_tokens_end(tok_ref, dx_hbm, buf, sems, block, tokens, dx)


def _experts_wgrad_kernel(nout, e_ref, tok_ref, lhs_ref, *refs):
    """``out[n][e] = sum over e's blocks of lhs^T rhs[n]``: rows are the
    reduction axis, so the float32 sums stay in VMEM scratch over an
    expert's consecutive blocks and are cast and written once, at its
    last block."""
    from jax.experimental import pallas as pl
    rhs_refs, out_refs, acc_refs = (refs[:nout], refs[2 * nout:3 * nout],
                                    refs[3 * nout:])
    i, e = pl.program_id(0), e_ref[pl.program_id(0)]
    before = e_ref[jnp.maximum(i - 1, 0)]
    after = e_ref[jnp.minimum(i + 1, e_ref.shape[0] - 1)]

    @pl.when(jnp.logical_or(i == 0, before != e))
    def _():
        for acc in acc_refs:
            acc[...] = jnp.zeros(acc.shape, jnp.float32)

    lhs = lhs_ref[...]
    for rhs, acc in zip(rhs_refs, acc_refs):
        acc[...] += jax.lax.dot_general(lhs, rhs[...], _TN,
                                        preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_or(i == pl.num_programs(0) - 1, after != e))
    def _():
        for out, acc in zip(out_refs, acc_refs):
            out[...] = acc[...].astype(out.dtype)


def experts_forward(xs, cw, tok, wgate, wup, wdown, expert, nb, block,
                    tokens):
    """``out[t] = sum over the rows r of token t of cw[r] *
    E_expert(r)(xs[r])``, ``(tokens, d)`` float32, over the rows of the
    ``nb`` blocks in use. ``xs`` ``(rows, d)`` are the gathered tokens
    laid out expert by expert in whole blocks (``dispatch_plan`` in
    layers/sequence.py), ``cw`` ``(rows, 1)`` float32 their combine
    weights, ``tok`` ``(rows,)`` int32 their tokens (``tokens`` or more
    marks padding; a block holds a token once), the weights ``(held, d,
    w)`` / ``(held, w, d)``, ``expert`` ``(blocks,)`` int32. The rows
    are added to their tokens inside the kernel: XLA's scatter-add of as
    many rows takes longer than the products (PERF.md, PR 31)."""
    acc, scratch = _token_sums(tokens, xs.shape[1], block)
    out, = _expert_call(
        partial(_experts_fwd_kernel, tokens), expert, tok, nb, block,
        (xs, cw, wgate, wup, wdown), (), acc, scratch)
    return _from_slabs(out, tokens, jnp.float32)


def experts_backward(xs, g, cw, tok, wgate, wup, wdown, expert, nb, block,
                     tokens):
    """Gradients of :func:`experts_forward` for ``g`` ``(rows, d)``, the
    result's cotangent gathered like ``xs``: ``(dx, dwgate, dwup,
    dwdown, dcw)``, ``dx`` ``(tokens, d)`` (what ``xs`` was gathered
    from) in the operands' dtype, summed in float32; the weights' in
    the weights' dtype with zeros for an expert no block belongs to;
    ``dcw`` ``(rows, 1)``, not written past ``nb * block``."""
    from jax.experimental.pallas import tpu as pltpu
    rows, d = xs.shape
    w = wgate.shape[2]
    acc, scratch = _token_sums(tokens, d, block)
    da, du, hc, dcw, dx = _expert_call(
        partial(_experts_bwd_kernel, tokens), expert, tok, nb, block,
        (xs, g, cw, wgate, wup, wdown),
        (jax.ShapeDtypeStruct((rows, w), xs.dtype),
         jax.ShapeDtypeStruct((rows, w), xs.dtype),
         jax.ShapeDtypeStruct((rows, w), xs.dtype),
         jax.ShapeDtypeStruct((rows, 1), jnp.float32)),
        acc, scratch)
    dwg, dwu = _expert_call(
        partial(_experts_wgrad_kernel, 2), expert, tok, nb, block,
        (xs, da, du),
        (jax.ShapeDtypeStruct(wgate.shape, wgate.dtype),
         jax.ShapeDtypeStruct(wup.shape, wup.dtype)),
        scratch=[pltpu.VMEM((d, w), jnp.float32)] * 2)
    dwd, = _expert_call(
        partial(_experts_wgrad_kernel, 1), expert, tok, nb, block, (hc, g),
        (jax.ShapeDtypeStruct(wdown.shape, wdown.dtype),),
        scratch=[pltpu.VMEM((w, d), jnp.float32)])
    return _from_slabs(dx, tokens, xs.dtype), dwg, dwu, dwd, dcw


def grouped_experts_applicable(d: int, w: int, block: int, dtype) -> bool:
    """Shape gate of the grouped expert kernels: the model width and the
    experts' width are whole lanes (128), the row block whole lanes too
    (it is the contraction of the weight gradients), and the kernel
    with most in VMEM, the backward one (three weight matrices twice
    over, a block of ``x`` and ``g`` twice over and of ``dx`` once in
    float32, the float32 rows of width ``w`` it works on) stays within
    the kernels' VMEM."""
    size = jnp.dtype(dtype).itemsize
    vmem = 6 * d * w * size + block * (4 * d * size + 8 * d + 40 * w)
    return (min(d, w, block) > 0 and d % _LANES == 0 and w % _LANES == 0
            and block % _LANES == 0 and vmem <= _EXPERT_VMEM)
