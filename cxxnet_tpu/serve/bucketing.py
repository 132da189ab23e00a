"""Batch-size buckets: the static-shape vocabulary of the serve path.

XLA compiles one executable per input shape, so a server that dispatched
every request at its natural batch size would recompile constantly. The
serve subsystem instead rounds every micro-batch up to a small ladder of
batch-size *buckets* (e.g. 1/8/32/max_batch), pads the tail rows, and
masks them with the same ``num_batch_padd`` machinery the training tail
batches use — steady-state serving then touches only the executables the
warmup compiled.

The helpers here are shared by the serve engine and ``wrapper.Net``'s
pred-executable cache; keeping them in one place is what lets the
schema guarantee "zero compile events after warmup" mean the same thing
everywhere.
"""

from __future__ import annotations

from math import gcd
from typing import Optional, Sequence, Tuple

import numpy as np

# the default ladder below max_batch; max_batch itself is always a
# bucket. Small buckets keep single-request latency off the full-batch
# pad cost; the jumps are coarse enough that a handful of executables
# covers every fill level.
DEFAULT_LADDER = (1, 2, 4, 8, 16, 32, 64, 128)


def bucket_ladder(max_batch: int, align: int = 1,
                  base: Sequence[int] = DEFAULT_LADDER) -> Tuple[int, ...]:
    """Ascending bucket sizes ending at ``max_batch``.

    ``align`` is the mesh data-axis size: every bucket must split
    evenly across the data axis (jax shardings do not support uneven
    splits), so candidates that are not multiples of it are dropped.
    ``max_batch`` itself must satisfy the alignment.
    """
    max_batch = int(max_batch)
    if max_batch < 1:
        raise ValueError("max_batch must be >= 1, got %d" % max_batch)
    if align < 1 or max_batch % align:
        raise ValueError(
            "max_batch %d must be a multiple of the mesh data axis %d"
            % (max_batch, align))
    out = sorted({b for b in base
                  if 0 < b < max_batch and b % align == 0}
                 | {max_batch})
    return tuple(out)


def parse_buckets(spec: str, max_batch: int,
                  align: int = 1) -> Tuple[int, ...]:
    """Parse the ``serve_buckets`` config value: ``auto`` (the default
    ladder) or an explicit comma list like ``1,8,32``. Explicit buckets
    are validated (ascending after sort, aligned, capped by and always
    including ``max_batch``)."""
    if not spec or spec == "auto":
        return bucket_ladder(max_batch, align)
    sizes = sorted({int(t) for t in spec.split(",") if t.strip()})
    for b in sizes:
        if b < 1 or b > max_batch:
            raise ValueError(
                "serve bucket %d outside [1, max_batch=%d]"
                % (b, max_batch))
        if b % align:
            raise ValueError(
                "serve bucket %d must be a multiple of the mesh data "
                "axis %d" % (b, align))
    if max_batch % align:
        raise ValueError(
            "max_batch %d must be a multiple of the mesh data axis %d"
            % (max_batch, align))
    if not sizes or sizes[-1] != max_batch:
        sizes.append(max_batch)
    return tuple(sizes)


def pick_bucket(n: int, buckets: Sequence[int],
                extend: bool = False) -> Optional[int]:
    """Smallest bucket >= ``n``; None when ``n`` exceeds the ladder and
    ``extend`` is off. With ``extend``, oversized requests round up to
    ``max_bucket * 2**k`` — the library predictor path, where splitting
    is not an option and the compiled-shape count must stay bounded."""
    if n < 1:
        raise ValueError("batch of %d rows" % n)
    for b in buckets:
        if b >= n:
            return b
    if not extend:
        return None
    m = buckets[-1]
    while m < n:
        m *= 2
    return m


def reachable_variants(
        buckets: Sequence[int]) -> Tuple[Tuple[int, int], ...]:
    """The ``(bucket, rows)`` dispatch variants steady-state traffic
    can reach: every bucket exactly full (``rows == bucket``, the
    mask-free program), plus — when some row count actually rounds up
    to this bucket — the smallest such count (``prev_bucket + 1``, the
    padded-mask program). The one definition shared by
    ``NetTrainer.precompile_pred`` and ``InferenceEngine.warmup`` so
    the compiled set and the warm-run set cannot desynchronize."""
    out = []
    prev = 0
    for b in sorted({int(x) for x in buckets}):
        out.append((b, b))
        if prev + 1 < b:
            out.append((b, prev + 1))
        prev = b
    return tuple(out)


def mesh_align(buckets: Sequence[int], max_devices: int) -> int:
    """Largest data-axis size <= ``max_devices`` that divides every
    bucket — the mesh a serve engine built for these buckets can use.
    A ladder containing 1 (the usual case) forces a single-device data
    axis; coarse ladders (8/32/...) can shard across chips."""
    g = 0
    for b in buckets:
        g = gcd(g, int(b))
    d = max(1, min(g, max_devices))
    while g % d:
        d -= 1
    return d


def pad_to_bucket(rows: np.ndarray,
                  bucket: int) -> Tuple[np.ndarray, int]:
    """Pad ``rows`` (leading axis = batch) with zero rows up to
    ``bucket``. Returns (padded, num_batch_padd); a perfectly filled
    bucket passes through without a copy."""
    n = rows.shape[0]
    if n > bucket:
        raise ValueError("cannot pad %d rows into a bucket of %d"
                         % (n, bucket))
    if n == bucket:
        return rows, 0
    pad = np.zeros((bucket - n,) + rows.shape[1:], rows.dtype)
    return np.concatenate([rows, pad], axis=0), bucket - n
