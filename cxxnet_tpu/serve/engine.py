"""The frozen inference engine: a snapshot turned into a predictor.

Wraps an eval-mode :class:`~cxxnet_tpu.nnet.trainer.NetTrainer` whose
weights never change again: the forward runs with ``is_train=False``, so
``bn_fold_eval`` folds running-stats scale/shift into the conv weights
and dropout/augment-time randomness is off. ``warmup()`` AOT-compiles
the pred executables at every batch-size bucket (both mask variants)
via ``NetTrainer.precompile_pred`` — after that, a dispatch at any
bucket goes straight to a compiled executable and the engine's
``compile_events`` counter stays at zero.

The engine exposes a two-phase dispatch for the batcher's pipelined
hand-off (stage the H2D transfer for batch N+1 while batch N computes —
the PR 2 prefetch-chain pattern applied to serving):

- :meth:`stage` — pad rows to their bucket and issue the device_put
- :meth:`dispatch` — run the executable and fetch the depadded rows

plus one-shot helpers (:meth:`run`, :meth:`predict`) for library
callers that do not need the concurrent path.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .bucketing import bucket_ladder, pick_bucket, reachable_variants

# staging ring depth per bucket: must cover every concurrently
# in-flight staged batch of the batcher pipeline (stage_depth staged +
# one dispatching + one being staged); reuse additionally gates on the
# slot's previous H2D having completed, so the depth is a throughput
# knob, not a correctness bound
STAGE_RING_DEPTH = 4


class _StageSlot:
    """One preallocated host staging buffer: the rows written since
    the last zeroing (``high``) and the device array its last H2D
    produced (reuse must wait for that transfer, PR 2's release
    discipline applied to serving)."""

    __slots__ = ("buf", "high", "last_dev", "busy")

    def __init__(self, buf: np.ndarray):
        self.buf = buf
        self.high = 0
        self.last_dev = None
        self.busy = True                 # created for its first caller


def _aliases_host(buf: np.ndarray, dev) -> bool:
    """Does the staged device array still reference the host staging
    buffer? CPU-backend device_put is immutable-zero-copy for aligned
    arrays — reusing the buffer would overwrite an in-flight batch.
    Conservative: any doubt counts as aliasing (the iter_batch
    ``_batch_aliases`` probe, specialized to one array)."""
    try:
        import jax
        if isinstance(dev, jax.Array):
            return any(np.shares_memory(np.asarray(s.data), buf)  # cxxlint: disable=CXL003 -- one-time aliasing probe on the FIRST stage only (self._ring_ok latches); CPU shard views are zero-copy
                       for s in dev.addressable_shards)
        if isinstance(dev, np.ndarray):
            return bool(np.shares_memory(dev, buf))
    except Exception:
        return True
    return True


def input_dtype_for(serve_dtype: str):
    """The staging dtype a ladder warms for a ``serve_dtype``: bf16
    ladders warm and stage bf16 (half the H2D bytes); int8/fp8 graphs
    quantize on device, so their input stays f32."""
    import jax.numpy as jnp
    return jnp.bfloat16 if serve_dtype == "bfloat16" else np.float32


class StagedBatch:
    """A micro-batch whose H2D transfer has been issued: device-resident
    data + mask, the valid-row count, and the node set to fetch."""

    __slots__ = ("data", "mask", "nvalid", "bucket", "nodes")

    def __init__(self, data, mask, nvalid: int, bucket: int,
                 nodes: Tuple[int, ...]):
        self.data = data
        self.mask = mask
        self.nvalid = nvalid
        self.bucket = bucket
        self.nodes = nodes


class InferenceEngine:
    """Bucketed AOT predictor over a loaded trainer.

    ``trainer`` must be initialized (init_model/load_model). Buckets
    must split evenly across the trainer's mesh data axis; engines
    built through :func:`build_engine` / ``ServeSession`` choose the
    mesh from the bucket ladder automatically (a ladder containing 1
    forces a single-device data axis).

    Thread safety: :meth:`dispatch` (and the one-shot helpers) hold an
    internal lock — one dispatch at a time, callers from any thread.
    """

    def __init__(self, trainer, buckets: Optional[Sequence[int]] = None,
                 node: str = "", monitor=None,
                 input_dtype=np.float32):
        assert trainer._initialized, \
            "InferenceEngine needs an initialized trainer"
        self.trainer = trainer
        # the dtype the bucket ladder warms (and therefore the ONLY
        # dtype stage() may ship): a bf16-warmed ladder staging f32
        # would recompile-hazard every dispatch
        self.input_dtype = np.dtype(input_dtype)
        mesh_axes = dict(trainer.mesh.shape)
        align = int(mesh_axes.get("data", 1))
        if buckets is None:
            buckets = bucket_ladder(trainer.batch_size, align=align)
        self.buckets = tuple(sorted(int(b) for b in buckets))
        for b in self.buckets:
            if b % align:
                raise ValueError(
                    "bucket %d does not split across the mesh data "
                    "axis %d" % (b, align))
        self.max_batch = self.buckets[-1]
        top = trainer.graph.num_nodes - 1
        self.nodes = (trainer.net.node_index_by_name(node) if node
                      else top,)
        self._mon = monitor
        self._lock = threading.Lock()
        self._sigs = set()               # jit signatures seen (compile
        #                                  detection on the fallback path)
        # preallocated per-bucket staging rings (zero-copy request
        # assembly straight into the H2D source buffer); reuse is
        # probed on the first stage the way BatchAdapter's prefetch
        # chain does — a backend whose device_put aliases host memory
        # (CPU zero-copy) never reuses a slot
        self._stage_lock = threading.Lock()
        self._ring: Dict[int, List[_StageSlot]] = {}
        self._ring_next: Dict[int, int] = {}
        self._ring_ok: Optional[bool] = None
        self.counters: Dict[str, int] = {
            "dispatches": 0, "rows": 0, "pad_rows": 0, "aot_hits": 0,
            "compile_events": 0, "staging_reuse": 0, "staging_alloc": 0,
            "d2h_bytes": 0}

    # -- warmup ----------------------------------------------------------

    def warmup(self, warm_run: bool = True) -> int:
        """Compile every (bucket, mask-variant) pred executable; with
        ``warm_run`` also push one zero batch through each bucket so
        first-request latency pays no lazy-init cost. Resets the
        compile counter: events counted afterwards are real steady-
        state compiles — the number a healthy server keeps at zero."""
        # donate=True: the serve-ladder executables take the staged
        # data/mask buffers as donated arguments (consumed exactly once
        # per dispatch; serve_donate=0 opts out). This is also where
        # the serve weight tree freezes — a serve_device_mem_budget
        # breach surfaces here as the typed ResidencyBudgetError
        compiled = self.trainer.precompile_pred(self.buckets, self.nodes,
                                                dtype=self.input_dtype,
                                                donate=True)
        if warm_run:
            inst = self._inst_shape()
            for _, rows in reachable_variants(self.buckets):
                self.dispatch(self.stage(
                    np.zeros((rows,) + inst, self.input_dtype)))
        with self._lock, self._stage_lock:
            # both counter writers held: dispatch counters live under
            # _lock, staging-ring counters under _stage_lock
            for k in self.counters:
                self.counters[k] = 0
        return compiled

    def _inst_shape(self) -> Tuple[int, ...]:
        from ..io.data import inst_array_shape
        return inst_array_shape(tuple(self.trainer.graph.input_shape))

    # -- two-phase dispatch (the batcher path) ---------------------------

    def stage(self, rows: Union[np.ndarray, Sequence[np.ndarray]]
              ) -> StagedBatch:
        """Assemble ``rows`` (one array, or the batcher's list of
        per-request row arrays) into a preallocated staging buffer and
        issue the H2D transfer. Cheap host work + an async device_put —
        safe to run for batch N+1 while batch N computes.

        Request rows copy ONCE, straight from the caller arrays into a
        bucket-sized slot of the staging ring (cast to the warmed
        ``input_dtype`` during the copy, pad tail zeroed to its
        high-water mark) — no intermediate concatenate/astype/pad
        copies, and steady state allocates nothing. Slot reuse waits
        for the slot's previous transfer and is disabled entirely on
        backends whose device_put aliases host memory (probed on the
        first stage, the BatchAdapter discipline)."""
        if isinstance(rows, (list, tuple)):
            parts = [np.asarray(r) for r in rows]  # cxxlint: disable=CXL003 -- host staging: request rows arrive as host numpy/json, never device values
        else:
            parts = [np.asarray(rows)]  # cxxlint: disable=CXL003 -- host staging (single-request path), same contract as above
        inst = self._inst_shape()
        for p in parts:
            # the copy below would silently BROADCAST a mis-shaped
            # row (e.g. a singleton channel) into the buffer; the
            # replaced device_put path surfaced those as aval errors
            if tuple(p.shape[1:]) != inst:
                raise ValueError(
                    "request row shape %r does not match the served "
                    "instance shape %r" % (p.shape[1:], inst))
        n = sum(p.shape[0] for p in parts)
        bucket = pick_bucket(n, self.buckets)
        if bucket is None:
            raise ValueError(
                "batch of %d rows exceeds the largest bucket %d"
                % (n, self.max_batch))
        slot = self._acquire_slot(bucket, n)
        try:
            buf = slot.buf if slot is not None else np.zeros(
                (bucket,) + inst, self.input_dtype)
            off = 0
            for p in parts:
                buf[off:off + p.shape[0]] = p  # casts during the copy
                off += p.shape[0]
            t = self.trainer
            mask = None
            if n < bucket:
                m = np.ones((bucket,), np.float32)
                m[n:] = 0.0
                mask = t._put_batch_array(m)
            # only self.nodes is servable: warmup compiled exactly
            # that node set, so any other request would jit-compile in
            # the hot path and break the zero-compile-after-warmup
            # contract
            data = t._put_batch_array(buf)
        except BaseException:
            # a failed stage must hand its slot back, or a few
            # transient errors would silently retire the whole ring
            if slot is not None:
                slot.busy = False
            raise
        self._note_staged(slot, buf, data)
        return StagedBatch(data, mask, n, bucket, self.nodes)

    def _acquire_slot(self, bucket: int,
                      n: int) -> Optional[_StageSlot]:
        """A staging-ring slot for ``bucket`` whose buffer is safe to
        overwrite, or None when ring reuse is disabled (aliasing
        backend: every stage gets a fresh buffer, the pre-ring
        behavior)."""
        with self._stage_lock:
            if self._ring_ok is False:
                self.counters["staging_alloc"] += 1
                return None
            ring = self._ring.setdefault(bucket, [])
            slot = None
            start = self._ring_next.get(bucket, 0)
            for k in range(len(ring)):           # oldest-first scan
                cand = ring[(start + k) % len(ring)]
                if not cand.busy:
                    slot = cand
                    self._ring_next[bucket] = (start + k + 1) \
                        % len(ring)
                    self.counters["staging_reuse"] += 1
                    break
            if slot is None:
                if len(ring) >= STAGE_RING_DEPTH:
                    # every slot is being written by a concurrent
                    # caller (library run() fan-in beyond the ring):
                    # fall back to a transient buffer, never block
                    self.counters["staging_alloc"] += 1
                    return None
                slot = _StageSlot(np.zeros(
                    (bucket,) + self._inst_shape(), self.input_dtype))
                ring.append(slot)
                self.counters["staging_alloc"] += 1
            slot.busy = True
        if slot.last_dev is not None:
            # the slot's previous H2D must complete before its host
            # buffer is overwritten (an almost-always-satisfied wait:
            # the slot is STAGE_RING_DEPTH batches old). A DELETED
            # array means the donated serve executable already
            # consumed it — the transfer is long done, overwriting is
            # safe (donation deletes inputs at dispatch; waiting on a
            # deleted jax.Array raises instead of returning)
            import jax
            dev, slot.last_dev = slot.last_dev, None
            try:
                if not dev.is_deleted():
                    jax.block_until_ready(dev)  # cxxlint: disable=CXL003 -- bounded reuse guard: waits only for a DEPTH-batches-old H2D copy, the PR 2 release discipline
            except RuntimeError:
                pass  # cxxlint: disable=CXL006 -- deleted-between-check-and-wait race: deletion IS the proof the transfer completed
        if slot.high > n:
            slot.buf[n:slot.high] = 0        # zero the pad tail once
        slot.high = n
        return slot

    def _note_staged(self, slot: Optional[_StageSlot],
                     buf: np.ndarray, data) -> None:
        """First-stage aliasing probe + per-slot transfer bookkeeping.
        When device_put zero-copy-aliased the host buffer, ring reuse
        would overwrite an in-flight batch — disable it for good and
        orphan the handed-out slots."""
        if self._ring_ok is None:
            with self._stage_lock:
                if self._ring_ok is None:
                    self._ring_ok = not _aliases_host(buf, data)
                    if not self._ring_ok:
                        self._ring.clear()
                        self._ring_next.clear()
        if slot is not None:
            if self._ring_ok:
                slot.last_dev = data
            slot.busy = False

    def dispatch(self, staged: StagedBatch) -> np.ndarray:
        """Run the staged batch and return the valid rows of the first
        requested node as float32 numpy (natural node shape, depadded
        both in channels and batch rows)."""
        t = self.trainer
        with self._lock:
            sig = ("pred",) + t.pred_sig(
                staged.data.shape, t._dtype_tag(staged.data.dtype),
                staged.mask is None, 0, staged.nodes)
            if sig in t._aot:
                self.counters["aot_hits"] += 1
            elif sig not in self._sigs:
                self._sigs.add(sig)
                self.counters["compile_events"] += 1
            vals = t._call_pred(staged.data, staged.mask, (),
                                staged.nodes)
        # the result materialization is the expensive part of dispatch
        # (wait for device compute + D2H copy) and needs no shared
        # state: it must happen OUTSIDE the lock, or every concurrent
        # dispatcher/library caller convoys behind one device round
        # trip. _call_pred above only *issues* the async dispatch.
        out_dev = vals[0]
        if staged.nvalid < staged.bucket:
            # slice the valid rows ON DEVICE before materializing:
            # only nvalid rows cross the D2H (PCIe/host) boundary, the
            # pad tail never does (the slice is a tiny device op,
            # shape-cached by jax after its first use per fill level)
            out_dev = out_dev[:staged.nvalid]
        out = np.asarray(out_dev)  # cxxlint: disable=CXL003 -- boundary D2H: the client consumes host rows; runs lock-free
        # success counters AFTER materialization: a device error
        # surfaces at the D2H copy, and a failed dispatch must not
        # count served rows (the batcher accounts the error separately)
        with self._lock:
            self.counters["dispatches"] += 1
            self.counters["rows"] += staged.nvalid
            self.counters["pad_rows"] += staged.bucket - staged.nvalid
            self.counters["d2h_bytes"] += int(out.nbytes)
        return out

    # -- one-shot helpers (library path) ---------------------------------

    def run(self, rows: np.ndarray) -> np.ndarray:
        """Score ``rows`` of any count: chunks of ``max_batch`` rows
        dispatch bucket-padded, results concatenate back."""
        rows = np.asarray(rows)
        if rows.shape[0] < 1:
            raise ValueError("run() needs at least one row")
        outs = []
        for i in range(0, rows.shape[0], self.max_batch):
            chunk = rows[i:i + self.max_batch]
            outs.append(self.dispatch(self.stage(chunk)))
        return np.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]

    def predict(self, rows: np.ndarray) -> np.ndarray:
        """Per-row predicted class index (or raw scalar) of the top
        node — ``NetTrainer.predict`` semantics on the bucketed path."""
        return self.trainer.rows_to_prediction(self.run(rows))

    def counters_snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.counters)


def build_engine(cfg, model_path: str,
                 buckets: Optional[Sequence[int]] = None,
                 max_batch: int = 0, node: str = "",
                 monitor=None) -> InferenceEngine:
    """Load a snapshot — or a sealed artifact bundle — into a frozen
    engine with a bucket-aligned mesh.

    ``cfg`` is the ordered config-pair stream (netconfig + globals, the
    same stream ``NetTrainer`` takes). The mesh data axis is the
    largest device count that divides every bucket, so any ladder is
    servable on any host (a ladder with bucket 1 runs single-device).

    When ``model_path`` is a bundle (doc/artifacts.md), the serve
    contract the executables were sealed for fills any knob the config
    left at its default: the manifest's bucket ladder replaces
    ``auto``, its serve dtype applies when the config names none, and
    its node likewise — so booting with the export-time config (or no
    serve config at all) requests exactly the sealed keys and warmup
    compiles nothing. Explicit config values still win; mismatched
    keys just re-lower per key.
    """
    import jax

    from ..nnet.quantize import normalize_serve_dtype
    from ..nnet.trainer import NetTrainer
    from ..parallel import make_mesh
    from .bucketing import mesh_align, parse_buckets
    cfg = list(cfg)
    serve_dtype = ""
    if not max_batch:
        for k, v in cfg:
            if k == "batch_size":
                max_batch = int(v)
    for k, v in cfg:
        if k == "serve_dtype":
            serve_dtype = normalize_serve_dtype(v)
    from ..artifact import bundle as _ab
    manifest = None
    if _ab.is_bundle(model_path):
        manifest = _ab.bundle_manifest(model_path)
        if buckets is None or buckets in ("", "auto"):
            buckets = tuple(int(b) for b in manifest["buckets"])
        if not max_batch:
            max_batch = max(manifest["buckets"])
        if not serve_dtype and manifest.get("serve_dtype"):
            serve_dtype = normalize_serve_dtype(
                manifest["serve_dtype"])
            # the trainer must build the SAME graph the executables
            # were sealed from (quantized dtypes change the traced
            # forward); appended last so it wins inside the trainer
            cfg = cfg + [("serve_dtype", serve_dtype)]
        if not node and manifest.get("node"):
            node = manifest["node"]
        # the sealed weight calling convention (frozen serve tree vs
        # raw masters as pred arguments) must survive the boot, or the
        # installed executables would re-lower; explicit config wins.
        # A manifest WITHOUT the field predates weight residency — its
        # executables were sealed against the raw masters, so default
        # the boot to the legacy convention instead of discarding
        # every sealed program against the new default
        if not any(k == "serve_weight_residency" for k, _ in cfg):
            cfg = cfg + [("serve_weight_residency",
                          str(int(manifest.get("weight_residency",
                                               0))))]
    serve_dtype = serve_dtype or "float32"
    if not max_batch:
        raise ValueError("serve needs batch_size (or serve_max_batch)")
    spec = buckets if isinstance(buckets, str) else ""
    if isinstance(buckets, str) or buckets is None:
        buckets = parse_buckets(spec, max_batch)
    align = mesh_align(buckets, len(jax.devices()))
    trainer = NetTrainer(cfg, mesh=make_mesh(align, 1))
    if monitor is not None:
        # monitor BEFORE load: a bundle load emits its artifact_load
        # hit/rebuild accounting during load_model
        trainer.set_monitor(monitor)
    trainer.load_model(model_path)
    return InferenceEngine(trainer, buckets=buckets, node=node,
                           monitor=monitor,
                           input_dtype=input_dtype_for(serve_dtype))
