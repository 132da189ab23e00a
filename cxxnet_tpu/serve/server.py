"""Config-driven serve session: snapshot -> engine -> batcher.

``ServeSession`` is the surface both the ``task = serve`` CLI entry and
library embedders use: it loads a model into a frozen
:class:`~cxxnet_tpu.serve.engine.InferenceEngine` (bucket-aligned mesh,
AOT warmup), fronts it with a
:class:`~cxxnet_tpu.serve.batcher.DynamicBatcher`, and exposes
``submit`` / ``predict`` / ``close``. All knobs come from the same
``key = value`` config grammar as the rest of the system:

- ``serve_buckets`` — ``auto`` (1/2/4/.../max_batch ladder) or an
  explicit comma list like ``1,8,32``
- ``serve_max_batch`` — micro-batch row cap (default: ``batch_size``)
- ``serve_max_delay_ms`` — batch-close deadline (default 2 ms)
- ``serve_queue_rows`` — backpressure bound (default 8x max_batch)
- ``serve_timeout_ms`` — default per-request deadline (0 = none)
- ``serve_node`` — node to serve (default: the top node)
- ``serve_warm_run`` — dispatch each bucket once at warmup (default 1)
- ``serve_clients`` / ``serve_requests`` / ``serve_request_rows`` —
  the CLI soak drive (``task = serve``): N closed-loop clients each
  issuing M requests of K rows

See doc/serving.md for the full reference and the telemetry records.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .batcher import DynamicBatcher
from .engine import InferenceEngine, build_engine


class ServeConfig:
    """Parsed ``serve_*`` keys (plus the globals serving depends on)."""

    def __init__(self, cfg: Sequence) -> None:
        self.buckets = "auto"
        self.max_batch = 0
        self.max_delay_ms = 2.0
        self.queue_rows = 0
        self.timeout_ms = 0.0
        self.node = ""
        self.warm_run = 1
        self.clients = 8
        self.requests = 32
        self.request_rows = 1
        # retrieval overrides (doc/retrieval.md): 0/"" defer to the
        # bundle's sealed search contract, so a plain boot requests
        # exactly the sealed search keys (zero compiles)
        self.search_k = 0
        self.search_buckets = ""
        batch_size = 0
        for name, val in cfg:
            if name == "batch_size":
                batch_size = int(val)
            if name == "serve_buckets":
                self.buckets = val
            if name == "serve_max_batch":
                self.max_batch = int(val)
            if name == "serve_max_delay_ms":
                self.max_delay_ms = float(val)
            if name == "serve_queue_rows":
                self.queue_rows = int(val)
            if name == "serve_timeout_ms":
                self.timeout_ms = float(val)
            if name == "serve_node":
                self.node = val
            if name == "serve_warm_run":
                self.warm_run = int(val)
            if name == "serve_clients":
                self.clients = int(val)
            if name == "serve_requests":
                self.requests = int(val)
            if name == "serve_request_rows":
                self.request_rows = int(val)
            if name == "search_k":
                self.search_k = int(val)
            if name == "search_buckets":
                self.search_buckets = val
        if not self.max_batch:
            self.max_batch = batch_size
        if not self.max_batch:
            raise ValueError(
                "serving needs serve_max_batch (or batch_size)")


class ServeSession:
    """A long-lived concurrent predictor over one snapshot.

    Build either from config + model path (the CLI path; the engine
    gets its own bucket-aligned mesh) or around an existing engine
    (library/test path). ``close`` drains in-flight work and emits the
    ``serve_summary`` record.
    """

    def __init__(self, cfg: Sequence = (),
                 model_path: Optional[str] = None,
                 engine: Optional[InferenceEngine] = None,
                 monitor=None):
        self.cfg = ServeConfig(cfg)
        c = self.cfg
        if engine is None:
            assert model_path, "ServeSession needs model_path or engine"
            engine = build_engine(cfg, model_path, buckets=c.buckets,
                                  max_batch=c.max_batch, node=c.node,
                                  monitor=monitor)
        self.engine = engine
        self.warmup_programs = engine.warmup(warm_run=bool(c.warm_run))
        # a bundle that seals an embedding index gets a retrieval
        # engine beside the predictor: same program registry (search
        # executables install from the bundle → zero-compile search
        # warmup), same residency budget books (weights + index), one
        # atomic swap unit
        self.retrieval = None
        self.index_bytes = 0
        if model_path:
            self._attach_index(model_path, monitor)
        self.batcher = DynamicBatcher(
            engine.stage, engine.dispatch,
            max_batch=engine.max_batch, max_delay_ms=c.max_delay_ms,
            max_queue_rows=c.queue_rows, timeout_ms=c.timeout_ms,
            monitor=monitor, row_shape=engine._inst_shape(),
            extra_summary=self._engine_summary)
        self._closed = False

    def _attach_index(self, model_path: str, monitor) -> None:
        """Load the bundle's sealed index (digest-verified) into a
        warmed :class:`~cxxnet_tpu.retrieval.engine.RetrievalEngine`.
        No-op for snapshot models and index-less bundles. Explicit
        ``search_k`` / ``search_buckets`` config wins over the sealed
        contract (those keys then re-lower instead of installing)."""
        from ..artifact import bundle as _ab
        if not _ab.is_bundle(model_path):
            return
        man = _ab.bundle_manifest(model_path)
        entry = man.get("index")
        if entry is None:
            return
        from ..retrieval import EmbeddingIndex, RetrievalEngine
        index = EmbeddingIndex.deserialize(
            _ab.read_index_member(model_path, man))
        c = self.cfg
        spec = c.search_buckets
        if spec and spec != "auto":
            buckets = tuple(sorted({int(t) for t in spec.split(",")
                                    if t.strip()}))
        elif spec != "auto" and entry.get("buckets"):
            buckets = tuple(int(b) for b in entry["buckets"])
        else:
            buckets = None               # the engine's default ladder
        self.retrieval = RetrievalEngine(
            index, self.engine.trainer.programs,
            k=c.search_k or int(entry.get("k", 0)) or 10,
            buckets=buckets, monitor=monitor)
        # the same budget the weight tree froze under: index bytes
        # stack on top of the registry's weight residency
        budget = int(self.engine.trainer.serve_device_mem_budget * 1e6)
        self.retrieval.warmup(warm_run=bool(c.warm_run),
                              budget_bytes=budget)
        self.index_bytes = index.nbytes

    def _engine_summary(self) -> Dict[str, int]:
        # one snapshot: compile_events and aot_hits must come from the
        # same instant in the emitted serve_summary record
        snap = self.engine.counters_snapshot()
        res = self.engine.trainer.programs.residency
        return {"compile_events": snap["compile_events"],
                "aot_hits": snap["aot_hits"],
                # zero-copy dispatch accounting: bytes that actually
                # crossed D2H (valid rows only) and the staging-ring
                # reuse split (doc/serving.md)
                "d2h_bytes": snap["d2h_bytes"],
                "staging_reuse": snap["staging_reuse"],
                "staging_alloc": snap["staging_alloc"],
                "resident_bytes": res.total_bytes if res else 0}

    def submit(self, rows: np.ndarray,
               timeout_ms: Optional[float] = None):
        """Queue rows (internal layout); returns their result Future."""
        return self.batcher.submit(rows, timeout_ms)

    def predict(self, rows: np.ndarray,
                timeout_ms: Optional[float] = None) -> np.ndarray:
        """Blocking score: the served node's rows for ``rows``."""
        return self.batcher(rows, timeout_ms)

    def close(self, drain: bool = True) -> Dict[str, Any]:
        if self._closed:
            return self.batcher.summary()
        self._closed = True
        return self.batcher.close(drain=drain)


def run_closed_loop(session: ServeSession, pool: np.ndarray,
                    clients: int, requests: int,
                    request_rows: int = 1) -> Dict[str, Any]:
    """Drive ``clients`` threaded closed-loop clients through the
    session: each sends ``requests`` requests of ``request_rows``
    consecutive pool rows (wrapping), waiting for each result before
    sending the next — the classic serving load model, and the drive
    behind ``task = serve``.

    Returns aggregate stats (client errors surface in ``errors``; a
    failed request does not kill its client loop)."""
    results: List[Dict[str, int]] = [
        {"ok": 0, "busy": 0, "timeout": 0, "error": 0}
        for _ in range(clients)]
    npool = pool.shape[0]

    def client(ci: int) -> None:
        from .batcher import ServeBusyError, ServeTimeoutError
        for r in range(requests):
            start = ((ci * requests + r) * request_rows) % npool
            rows = np.take(pool,
                           range(start, start + request_rows),
                           axis=0, mode="wrap")
            try:
                session.predict(rows)
                results[ci]["ok"] += 1
            except ServeBusyError:
                results[ci]["busy"] += 1
            except ServeTimeoutError:
                results[ci]["timeout"] += 1
            except Exception:
                results[ci]["error"] += 1

    t0 = time.monotonic()
    threads = [threading.Thread(target=client, args=(i,),
                                name="serve-client-%d" % i)
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    agg = {k: sum(r[k] for r in results)
           for k in ("ok", "busy", "timeout", "error")}
    agg["wall_s"] = wall
    agg["clients"] = clients
    agg["rows"] = agg["ok"] * request_rows
    agg["rows_per_sec"] = agg["rows"] / wall if wall > 0 else 0.0
    return agg
