"""Data pipeline types: DataInst / DataBatch / IIterator.

Mirrors ``/root/reference/src/io/data.h:20-183``: a two-level iterator
pattern — instance iterators (one example at a time) composed into batch
iterators by adapters — configured by ordered ``iter = type ... iter =
end`` blocks with chaining.

TPU-first difference: batches are host NumPy arrays with **static
shapes**. The reference's dynamic tail batches (AdjustBatchSize,
neural_net-inl.hpp:287-298) become pad-and-mask: every batch is full
size and ``num_batch_padd`` marks trailing padding rows that loss,
metrics, and predictions must ignore (same field as data.h:115).

Batch layout: ``data`` is NHWC (batch, y, x, ch) for spatial inputs or
(batch, features) for flat inputs — the device layout — while configs
keep describing shapes as (ch, y, x).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..monitor.spans import no_span


@dataclass
class DataInst:
    """Single training instance (data.h:42-56)."""
    index: int
    data: np.ndarray                  # (y, x, ch) or (features,)
    label: np.ndarray                 # (label_width,)
    extra_data: List[np.ndarray] = field(default_factory=list)


@dataclass
class DataBatch:
    """A batch of instances (data.h:80-150).

    ``release`` is the host-buffer ownership hand-off: when the batch's
    arrays live in a preallocated ring buffer (BatchAdapter's zero-copy
    assembly), calling it returns the buffer for reuse. Only call it
    once nothing will read the arrays again — the prefetch chain calls
    it after the device copy completes. None means the arrays are
    ordinary garbage-collected allocations.
    """
    data: np.ndarray                  # (batch, y, x, ch) | (batch, features)
    label: np.ndarray                 # (batch, label_width)
    inst_index: Optional[np.ndarray] = None
    num_batch_padd: int = 0
    extra_data: List[np.ndarray] = field(default_factory=list)
    release: Optional[Callable[[], None]] = None

    @property
    def batch_size(self) -> int:
        return self.data.shape[0]


class IIterator:
    """Iterator interface (data.h:20-39): init / before_first / next /
    value, plus set_param for config plumbing.

    ``span`` times one chunk's or batch's work: ``Monitor.span`` when
    an enabled monitor was attached to the chain
    (``iter_batch.attach_chain_spans``), else the shared no-op, so an
    unmonitored chain reads no clock."""

    span = staticmethod(no_span)

    # an image source that can hand out contiguous uint8 RGB pixels
    # says so here; ``emit_uint8`` is switched on by the augmenter above
    # it once a consumer took the normalisation over (defer_normalize)
    can_emit_uint8 = False
    emit_uint8 = False

    def set_param(self, name: str, val: str) -> None:
        pass

    def defer_normalize(self, accept=None):
        """The consumer of this chain offers to run the input
        normalisation itself (a ``NetTrainer`` does it as the first ops
        of the compiled step). Returns the spec handed over, ``(mean,
        scale)`` with ``mean`` a float32 ``(C,)`` / ``(H, W, C)`` array
        or None, or None when the chain keeps the work (it then delivers
        normalised float32, as without the question). ``accept(spec)``
        lets the consumer turn a spec down before anything changes.
        After a yes, from the chain's next ``before_first`` on, image
        batches are raw uint8 pixels, cropped and mirrored only: a
        batch's dtype says who normalises it. Adapters forward the
        question to their base; ``AugmentAdapter`` answers it."""
        base = getattr(self, "base", None)
        return None if base is None else base.defer_normalize(accept)

    def init(self) -> None:
        pass

    def before_first(self) -> None:
        raise NotImplementedError

    def next(self) -> bool:
        raise NotImplementedError

    def value(self):
        raise NotImplementedError

    def close(self) -> None:
        """Release background resources (threads, pools). Adapters
        forward to their base; safe to call more than once."""
        base = getattr(self, "base", None)
        if base is not None:
            base.close()

    # python-iterator convenience
    def __iter__(self):
        self.before_first()
        while self.next():
            yield self.value()


def rgb_pixels(bgr: np.ndarray, uint8: bool) -> np.ndarray:
    """OpenCV's decoded BGR image as RGB: float32 (the host path, which
    normalises next) or contiguous uint8 (``emit_uint8``: one cvtColor,
    GIL released, instead of a float cast of a negative-stride view)."""
    if uint8:
        import cv2
        return cv2.cvtColor(bgr, cv2.COLOR_BGR2RGB)
    return bgr[:, :, ::-1].astype(np.float32)


def shape_from_conf(val: str) -> Tuple[int, int, int]:
    """Parse 'z,y,x' input_shape (ch, y, x)."""
    z, y, x = (int(t) for t in val.split(","))
    return (z, y, x)


def inst_array_shape(shape3: Tuple[int, int, int]) -> Tuple[int, ...]:
    ch, y, x = shape3
    if ch == 1 and y == 1:
        return (x,)
    return (y, x, ch)


def resolve_data_shard(part_index: int, num_parts: int):
    """Resolve a (part_index, num_parts) data shard for this process.

    Explicit config wins; otherwise the distributed process rank is
    auto-detected so every base iterator reads a disjoint shard under
    multi-process dp — the PS_RANK sniffing of the reference
    (iter_image_recordio-inl.hpp:169-173) applied uniformly.
    """
    if num_parts > 1:
        return part_index, num_parts
    try:
        import jax
        if jax.process_count() > 1:
            return jax.process_index(), jax.process_count()
    except Exception as e:
        # a failed autodetect in a real multi-process run would make
        # every rank read the SAME shard (silently duplicated data) —
        # say so instead of passing
        from ..monitor import warn_once
        warn_once("shard_autodetect_failed",
                  "distributed shard autodetect failed (%s); "
                  "assuming single process — set part_index/num_parts "
                  "explicitly if this is a multi-process run" % e)
    return 0, 1
