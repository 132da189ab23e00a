"""RecordIO image iterator: the production ImageNet input path.

Parity with ``/root/reference/src/io/iter_image_recordio-inl.hpp:92-342``:
reads image records from a .rec archive, decodes JPEG in a thread pool
(the reference's OpenMP parallel decode, :214-250), supports

- ``path_imgrec`` archive (or comma list of part files)
- distributed sharding: ``part_index``/``num_parts`` byte-range splits
  (InputSplit rank/size, :183-185), with env autodetect of the process
  rank like the PS_RANK sniffing (:169-173)
- ``path_imglist``: optional list file remapping image_id -> label(s)
  (label_width > 1 support, :120-147) without repacking
- ``shuffle_chunk``: shuffles decode chunks within a window

The decode pool (doc/io.md): a chunk of ``_chunk`` records goes to the
pool as contiguous slices, one task a thread, and ``_AHEAD`` chunks are
in flight, so the pool decodes the next chunk while the thread that
called ``next`` crops, assembles and copies the one handed out. The
sequence of DataInst is that of a loop decoding one record after the
other.

Emits DataInst (float32 NHWC in [0,255], or contiguous uint8 RGB once
the augmenter above switched ``emit_uint8`` on: ``defer_normalize``,
io/data.py); stack augment/batch adapters on top (the factory wires
this like the reference's chained iterators).
"""

from __future__ import annotations

import collections
import math
import os
import threading
import time
from concurrent import futures
from concurrent.futures import ThreadPoolExecutor
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from .data import DataInst, IIterator, rgb_pixels
from .recordio import (RAW_TENSOR_FLAG, RecordIOReader,
                       parse_image_record, record_flag,
                       unpack_raw_tensor_record)
from ..utils.stream import open_stream


# chunks read and handed to the pool but not yet handed out: while one
# chunk is consumed the next is being decoded. With the chunk in
# ``_buf`` that is 2 x 256 decoded records, 100 MB of 256 x 256 uint8
_AHEAD = 2


def usable_cpus() -> int:
    """The CPUs this process may run on: its affinity mask where the
    platform has one (a container's or ``taskset``'s cut shows there,
    not in ``os.cpu_count()``), else the machine's count."""
    if hasattr(os, "sched_getaffinity"):
        return len(os.sched_getaffinity(0)) or 1
    return os.cpu_count() or 1


class ImageRecordIterator(IIterator):
    can_emit_uint8 = True                # JPEG and raw-tensor records

    def __init__(self):
        self.path_imgrec = ""
        self.path_imglist = ""
        self.label_width = 1
        self.silent = 0
        self.dist_num_parts = 1
        self.dist_part_index = 0
        # shard_kind = stride keeps the byte-range split (InputSplit
        # parity); batch applies the deterministic batch-block record
        # map (io/shard.py): the reader scans every record header but
        # DECODES only its own slice, so the expensive per-host work
        # stays 1/H as hosts grow while the fleet's rank-order
        # assembly reconstructs the exact single-host batch
        self.shard_kind = "stride"
        self.shard_global_batch = 0
        self.shard_start_record = 0
        self._shard_plan = None
        self._rec_seq = 0
        self._pass_ended = False
        # the pool's size, and the slices a chunk is cut into: half the
        # CPUs the process may use, no fewer than 4; ``nthread =`` wins.
        # Half, because the other threads of a job want cores too (the
        # one that calls next, the trainer's, the runtime's) and every
        # worker more asks for the interpreter lock twice an image: on
        # 13 CPUs 6 workers decode 7 % more a second than 13, 4 or 8
        # (PERF.md, PR 35)
        self._cpus = usable_cpus()
        self.nthread = max(4, self._cpus // 2)
        self.shuffle = 0
        self.seed = 0
        self._label_map: Optional[Dict[int, np.ndarray]] = None
        self._readers: List = []
        self._pool: Optional[ThreadPoolExecutor] = None
        self._buf: List[DataInst] = []
        self._bufpos = 0
        self._chunk = 256
        # chunks in flight, oldest first: (records, a future a slice)
        self._ahead: Deque[Tuple[int, List[futures.Future]]] = \
            collections.deque()
        # per-round decode counters (``decode_snapshot``); the workers
        # add their time under the lock
        self._stat_lock = threading.Lock()
        self._chunks = 0
        self._chunks_ready = 0
        self._busy_ns = 0

    def set_param(self, name: str, val: str) -> None:
        if name in ("path_imgrec", "image_rec"):   # reference alias
            self.path_imgrec = val
        if name == "path_imglist":
            self.path_imglist = val
        if name == "label_width":
            self.label_width = int(val)
        if name == "silent":
            self.silent = int(val)
        if name == "num_parts":
            self.dist_num_parts = int(val)
        if name == "part_index":
            self.dist_part_index = int(val)
        if name == "shard_kind":
            if val not in ("stride", "batch"):
                raise ValueError(
                    "shard_kind must be stride or batch, got %r" % val)
            self.shard_kind = val
        if name == "shard_global_batch":
            self.shard_global_batch = int(val)
        if name == "shard_start_record":
            self.shard_start_record = int(val)
        if name == "nthread":
            self.nthread = int(val)
        if name == "shuffle":
            self.shuffle = int(val)
        if name == "seed_data":
            self.seed = int(val)

    # -- init ------------------------------------------------------------

    def _autodetect_rank(self) -> None:
        """Pick up distributed identity when not configured explicitly
        (the PS_RANK autodetect, iter_image_recordio-inl.hpp:169-173)."""
        if self.dist_num_parts > 1:
            return
        try:
            import jax
            if jax.process_count() > 1:
                self.dist_num_parts = jax.process_count()
                self.dist_part_index = jax.process_index()
        except Exception as e:
            # same hazard as resolve_data_shard: every rank reading the
            # whole archive is silent data duplication
            from ..monitor import warn_once
            warn_once("shard_autodetect_failed",
                      "distributed shard autodetect failed (%s); "
                      "imgrec reads unsharded — set part_index/"
                      "num_parts explicitly for multi-process runs"
                      % e)

    def init(self) -> None:
        assert self.path_imgrec, "imgrec: must set path_imgrec"
        self._autodetect_rank()
        paths = [p for p in self.path_imgrec.split(",") if p]
        self._readers = []
        if self.shard_kind == "batch":
            # batch-block sharding (io/shard.py): every reader scans
            # the FULL archive stream in record order and _fill skips
            # decode for records other hosts own — exact record-index
            # ownership, which byte-range splits cannot express
            from .shard import plan_from_params
            assert self.shard_global_batch > 0, \
                "shard_kind=batch requires shard_global_batch"
            self._shard_plan = plan_from_params(
                self.dist_part_index, self.dist_num_parts,
                self.shard_global_batch, self.shard_start_record)
            for p in paths:
                self._readers.append(RecordIOReader(p, 0, 1))
        elif len(paths) == 1:
            self._readers.append(RecordIOReader(
                paths[0], self.dist_part_index, self.dist_num_parts))
        else:
            # multiple part files: shard whole files round-robin
            for i, p in enumerate(paths):
                if i % self.dist_num_parts == self.dist_part_index:
                    self._readers.append(RecordIOReader(p, 0, 1))
        if self.path_imglist:
            self._label_map = {}
            with open_stream(self.path_imglist, "r") as f:
                for line in f:
                    # bound the split so an image path containing
                    # spaces stays ONE trailing token (reference reads
                    # the path with getline after the labels,
                    # iter_image_recordio-inl.hpp:120-147)
                    toks = line.split(None, 1 + self.label_width)
                    if not toks:
                        continue
                    idx = int(float(toks[0]))
                    # labels are the numeric prefix (rows end with the
                    # image path); zero-pad short rows to label_width
                    # (same fill as archive-packed label vectors in
                    # _with_label) so mixed-width lists can't break
                    # batch stacking or crash on the path token
                    vals = []
                    for t in toks[1:1 + self.label_width]:
                        try:
                            vals.append(float(t))
                        except ValueError:
                            # the trailing path token legitimately ends
                            # the numeric prefix (short rows zero-pad);
                            # a non-numeric token BEFORE it is a
                            # malformed row — warn rather than silently
                            # zero-fill a typo'd label
                            if t is not toks[-1] and self.silent == 0:
                                print("imglist: non-numeric label %r "
                                      "in row %r" % (t, line.strip()))
                            break
                    lab = np.zeros((self.label_width,), np.float32)
                    lab[:len(vals)] = vals
                    self._label_map[idx] = lab
        self._pool = ThreadPoolExecutor(max_workers=self.nthread)
        self._rng = np.random.RandomState(self.seed)
        if self.silent == 0:
            print("ImageRecordIterator: %s part %d/%d"
                  % (self.path_imgrec, self.dist_part_index,
                     self.dist_num_parts))
        self.before_first()

    def before_first(self) -> None:
        # a reset after any consumption ends the resumed pass: the
        # shard_start_record handoff offset applies to the FIRST pass
        # only — later epochs read the full shard (ShardPlan.steady);
        # resets before consumption (init / epoch start) keep it
        if self._shard_plan is not None \
                and (self._pass_ended or self._rec_seq > 0):
            self._shard_plan = self._shard_plan.steady()
        self._pass_ended = False
        self._drop_ahead()
        for r in self._readers:
            r.reset()
        self._cur_reader = 0
        self._rec_seq = 0
        self._buf, self._bufpos = [], 0

    # -- decode ----------------------------------------------------------

    def _decode_slice(self, recs: List[bytes]
                      ) -> List[Optional[DataInst]]:
        """One contiguous slice of a chunk, in record order; None for a
        record no decoder takes. Three passes, not a record at a time:
        the Python around the decoder (headers before, labels after) is
        then not run between two OpenCV calls, so a worker coming back
        from a call needs the interpreter lock for a loop step and not
        for the parsing of the next record, and a pool of workers does
        not queue for the lock (doc/io.md)."""
        t0 = time.perf_counter_ns()
        uint8 = self.emit_uint8
        heads = []
        for rec in recs:
            if record_flag(rec) == RAW_TENSOR_FLAG:
                # pre-decoded uint8 tensor record: no jpeg in the loop
                index, label, data = unpack_raw_tensor_record(rec)
                heads.append((index, label, None, data, True))
            else:
                index, label, labels, payload = parse_image_record(rec)
                heads.append((index, label, labels,
                              np.frombuffer(payload, np.uint8), False))
        if not all(raw for *_, raw in heads):
            import cv2
        pixels: List[Optional[np.ndarray]] = []
        for *_, data, raw in heads:
            if raw:
                pixels.append(data if uint8 else data.astype(np.float32))
                continue
            img = cv2.imdecode(data, cv2.IMREAD_COLOR)
            pixels.append(None if img is None else rgb_pixels(img, uint8))
        out = [None if px is None
               else self._with_label(index, label, px, labels)
               for (index, label, labels, _, _), px in zip(heads, pixels)]
        dur = time.perf_counter_ns() - t0
        with self._stat_lock:
            self._busy_ns += dur
        return out

    def _with_label(self, index: int, label: float,
                    data: np.ndarray,
                    labels: Optional[np.ndarray] = None) -> DataInst:
        # precedence mirrors the reference: an imglist remap overrides
        # whatever the archive carries (image_recordio.h:21-24 "just
        # supply a list file"), then archive-packed label vectors, then
        # the header's single label broadcast to label_width
        lab = None
        if self._label_map is not None:
            lab = self._label_map.get(index)
        if lab is None and labels is not None:
            lab = np.zeros((self.label_width,), np.float32)
            n = min(self.label_width, labels.size)
            lab[:n] = labels[:n]
        if lab is None:
            lab = np.full((self.label_width,), label, np.float32)
        return DataInst(index=index, data=data, label=lab)

    def _read_chunk(self) -> List[bytes]:
        """The next ``_chunk`` records this host owns, fewer at the
        pass's end: a reader is asked for what the chunk still lacks in
        one call (``next_records``), so the last record read is the
        chunk's last, as in a loop a record at a time."""
        recs: List[bytes] = []
        with self.span("io.read"):
            while len(recs) < self._chunk and \
                    self._cur_reader < len(self._readers):
                got = self._readers[self._cur_reader].next_records(
                    self._chunk - len(recs))
                if not got:
                    self._cur_reader += 1
                    continue
                if self._shard_plan is None:
                    recs += got
                    continue
                for r in got:
                    # another host's record: no decode
                    if self._shard_plan.owns(self._rec_seq):
                        recs.append(r)
                    self._rec_seq += 1
        return recs

    def _submit_ahead(self) -> None:
        """Read chunks and hand them to the pool until ``_AHEAD`` are
        in flight or the pass has no record left (the readers are not
        rewound: the next pass starts at ``before_first``). A chunk is
        cut into one contiguous slice a thread: a record a task would
        cost the pool more in futures and lock hand-overs than the
        decode itself takes."""
        pool = self._pool
        if pool is None:                 # closed
            return
        while len(self._ahead) < _AHEAD \
                and self._cur_reader < len(self._readers):
            recs = self._read_chunk()
            if not recs:
                return
            step = math.ceil(len(recs) / self.nthread)
            self._ahead.append((len(recs), [
                pool.submit(self._decode_slice, recs[i:i + step])
                for i in range(0, len(recs), step)]))

    def _drop_ahead(self) -> None:
        """Forget the chunks in flight: cancel the slices still queued
        and wait for the ones a thread is in, so that nothing of an old
        pass is running, or can be handed out, when the next begins."""
        running = [f for _, futs in self._ahead for f in futs
                   if not f.cancel()]
        self._ahead.clear()
        futures.wait(running)

    def _fill(self) -> bool:
        self._submit_ahead()
        if not self._ahead:
            return False
        n, futs = self._ahead.popleft()
        ready = all(f.done() for f in futs)
        # the time this thread is blocked on the pool: the exposed part
        # of the decode, what is left after the look-ahead
        with self.span("io.decode", n=n):
            insts = [i for f in futs for i in f.result()]
        with self._stat_lock:
            self._chunks += 1
            self._chunks_ready += int(ready)
        insts = [i for i in insts if i is not None]
        if self.shuffle:
            self._rng.shuffle(insts)
        self._buf, self._bufpos = insts, 0
        # progress was made even if every record in this chunk failed to
        # decode; next() loops to the following chunk
        return True

    def decode_snapshot(self) -> dict:
        """Per-round decode counters (reset on read) for the
        ``pipeline`` record: chunks handed out, how many of them the
        pool had finished when they were asked for, the workers' summed
        time inside their slices; and what sized the pool."""
        with self._stat_lock:
            out = {"decode_chunks": self._chunks,
                   "decode_ahead_ready": self._chunks_ready,
                   "decode_busy_ms": round(self._busy_ns / 1e6, 3),
                   "decode_pool": self.nthread,
                   "decode_cpus": self._cpus,
                   "cpu_count": os.cpu_count() or 0}
            self._chunks = self._chunks_ready = self._busy_ns = 0
        return out

    def next(self) -> bool:
        while self._bufpos >= len(self._buf):
            if not self._fill():
                self._pass_ended = True
                return False
        self._out = self._buf[self._bufpos]
        self._bufpos += 1
        return True

    def value(self) -> DataInst:
        return self._out

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None
        self._ahead.clear()
        for r in self._readers:
            if hasattr(r, "close"):
                r.close()
        self._readers = []
