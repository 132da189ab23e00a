"""Observability: structured per-step tracing and a metrics sink.

The reference surfaced exactly two signals — the round eval line
(metric.h printing format) and the ``round %8d:[%8d] %ld sec elapsed``
progress print. This subsystem keeps those lines byte-identical (they
are the *parity surface*) and adds a structured event stream beside
them, configured through the same ``key = value`` config grammar:

- ``monitor = none|stdout|jsonl`` — sink selection. ``none`` (default)
  is a true no-op: no per-step host sync, no extra device transfers,
  and stdout stays byte-identical to the unmonitored build.
- ``monitor_path`` — JSONL output file for ``monitor = jsonl``
  (default ``monitor.jsonl``; truncated per run, one JSON object per
  line — one file is one run's stream).
- ``monitor_flush_period`` — seconds between sink flushes (0 = flush
  every record).
- ``monitor_rotate_mb`` — size bound on the live JSONL file (0 =
  unbounded); crossing it atomically rotates to ``<path>.<n>`` so a
  long-lived ``task = continual`` process cannot grow one unbounded
  stream.
- ``monitor_trace_dir`` — when set, a ``jax.profiler`` trace is
  captured into this directory over ONE round window (Python tracer
  off), so a perf trace is one config line away.
- ``monitor_trace_begin`` / ``monitor_trace_end`` — first/last round
  (0-based) of the trace window; both default to round 1 (skipping the
  compile-heavy round 0).

``Monitor.span(name, **attrs)`` times one interval of host work on the
profiler's clock (``monitor/spans.py``); over a null sink it is one
shared no-op object.

Multi-process runs gate emission on process 0 (the rabit
``IsRoot``-style gating main.py already applies to prints,
cxxnet_main.cpp:424-435): non-root ranks get a null sink so one run
produces one stream. Record vocabulary and validation live in
``cxxnet_tpu.monitor.schema``.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .spans import NULL_SPAN, SpanRecorder, no_span

__all__ = [
    "Monitor", "NullSink", "StdoutSink", "JsonlSink", "MemorySink",
    "LatencyHistogram", "create_monitor", "config_hash",
    "device_memory_snapshot", "get_global", "set_global", "warn_once",
    "NULL_SPAN", "no_span",
]

# records just ahead of which the closed spans are written out: the
# ones a reader waits for, so a sink that is read without a close (a
# MemorySink after a library caller's loop) holds their spans too, and
# ``run_end`` stays a stream's last record
_FLUSH_SPANS_AT = frozenset(("step", "precompile", "round_end", "run_end"))


# -- sinks ---------------------------------------------------------------


class NullSink:
    """Drop everything. ``Monitor.enabled`` is False over this sink, so
    callers skip record assembly entirely — the monitor = none fast
    path costs one attribute check."""

    enabled = False

    def write(self, record: Dict[str, Any]) -> None:
        pass

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


class StdoutSink:
    """Structured records as JSON lines on stdout, interleaved with the
    parity text lines (which print unchanged — filtering lines that
    start with ``{`` recovers the exact unmonitored output). ``log``
    records are dropped: their text was already printed verbatim by
    ``Monitor.line`` and echoing it as JSON would duplicate content."""

    enabled = True

    def write(self, record: Dict[str, Any]) -> None:
        if record.get("event") == "log":
            return
        sys.stdout.write(json.dumps(record, sort_keys=True) + "\n")

    def flush(self) -> None:
        sys.stdout.flush()

    def close(self) -> None:
        self.flush()


class JsonlSink:
    """Write records to a JSONL file, flushing every
    ``flush_period`` seconds (0 = every record). Buffering bounds the
    per-step file-system cost; ``close()`` always drains. The file is
    truncated per run — one file is one run's stream (re-running with
    the same monitor_path must not interleave runs, and the schema's
    monotonic-step check reads one run at a time); point monitor_path
    at distinct files to keep history.

    ``rotate_mb`` > 0 bounds the live file: once a record write takes
    it past the limit, the file atomically rotates to
    ``<path>.<n>`` (``os.replace`` — a reader tailing the live path
    sees the old stream or the new one, never a torn file) and a
    fresh ``<path>`` continues the run. A long-lived ``task =
    continual`` process would otherwise grow one unbounded file
    (``monitor_rotate_mb``, doc/observability.md). Rotation failure
    (read-only dir, cross-device quirk) warns once on stderr and
    keeps appending to the current file — losing the bound, never the
    records."""

    enabled = True

    def __init__(self, path: str, flush_period: float = 1.0,
                 rotate_mb: float = 0.0):
        self.path = path
        self.flush_period = max(0.0, float(flush_period))
        self.rotate_bytes = int(max(0.0, float(rotate_mb)) * 1e6)
        self.rotations = 0
        self._written = 0
        self._rotate_broken = False
        # one file set = one run: a re-run reusing this monitor_path
        # truncates the live file, so any rotated segments of a
        # previous run must go too — a stale <path>.<n> would
        # interleave two runs' streams for any consumer walking the
        # segment chain. Unconditional: a rerun with rotation OFF
        # must not inherit the rotated history either.
        n = 1
        while True:
            try:
                os.remove("%s.%d" % (path, n))
            except OSError:
                break                    # first gap ends the chain
            n += 1
        self._f = open(path, "w")
        self._last_flush = time.monotonic()
        # serve workers emit from several threads into one stream;
        # unsynchronized writes would interleave bytes mid-line
        self._wlock = threading.Lock()

    def write(self, record: Dict[str, Any]) -> None:
        line = json.dumps(record, sort_keys=True) + "\n"
        with self._wlock:
            self._f.write(line)
            self._written += len(line)
            if self.rotate_bytes and self._written >= self.rotate_bytes:
                self._rotate_locked()
            now = time.monotonic()
            if now - self._last_flush >= self.flush_period:
                self._f.flush()
                self._last_flush = now

    def _rotate_locked(self) -> None:
        """Rotate under ``_wlock``: flush, atomically rename the live
        file aside, reopen a fresh one. Record boundaries only — a
        record never splits across files. NEVER raises: a sink
        failure must not take down the run it observes (the warn_once
        discipline, but latched locally — routing through the monitor
        would re-enter this sink)."""
        if self._rotate_broken:
            return
        try:
            self._f.flush()
            target = "%s.%d" % (self.path, self.rotations + 1)
            os.replace(self.path, target)
        except OSError as e:
            self._rotate_broken = True   # warn once, keep appending
            sys.stderr.write(
                "[cxxnet_tpu monitor] warning monitor_rotate_failed: "
                "could not rotate %r (%s); the stream keeps appending "
                "to the current file without a size bound\n"
                % (self.path, e))
            return
        old = self._f
        try:
            self._f = open(self.path, "w")
        except OSError as e:
            # the rename committed but a fresh file will not open:
            # fall back to the (renamed) old handle — still a valid
            # stream, just no longer at the live path
            self._f = old
            self._rotate_broken = True
            sys.stderr.write(
                "[cxxnet_tpu monitor] warning monitor_rotate_failed: "
                "rotated %r but could not reopen it (%s); records "
                "continue into the rotated file\n" % (self.path, e))
            return
        old.close()
        self.rotations += 1
        self._written = 0

    def flush(self) -> None:
        with self._wlock:
            self._f.flush()
            self._last_flush = time.monotonic()

    def close(self) -> None:
        with self._wlock:
            if not self._f.closed:
                self._f.flush()
                self._f.close()


class MemorySink:
    """In-process record list — the sink the tests and
    ``chip_smoke.py`` read records back from."""

    enabled = True

    def __init__(self):
        self.records: List[Dict[str, Any]] = []

    def write(self, record: Dict[str, Any]) -> None:
        self.records.append(record)

    def clear(self) -> None:
        self.records = []

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


# -- latency histogram ---------------------------------------------------


class LatencyHistogram:
    """Power-of-two millisecond buckets for host-side wait latencies
    (batch fetch in the prefetch chain). observe() is two float ops and
    an int increment — cheap enough for the per-batch path, and only
    attached at all when monitoring is on."""

    # bucket upper bounds in ms; last bucket is open-ended
    BOUNDS = (0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0,
              256.0, 512.0, 1024.0)

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.counts = [0] * (len(self.BOUNDS) + 1)
        self.n = 0
        self.total_ms = 0.0
        self.max_ms = 0.0

    def observe(self, seconds: float) -> None:
        ms = seconds * 1e3
        self.n += 1
        self.total_ms += ms
        if ms > self.max_ms:
            self.max_ms = ms
        for i, b in enumerate(self.BOUNDS):
            if ms <= b:
                self.counts[i] += 1
                return
        self.counts[-1] += 1

    def percentile(self, q: float) -> float:
        """Estimate the q-th percentile (q in [0, 1]) from the bucket
        counts: linear interpolation inside the bucket the rank lands
        in, capped by the observed max. Bucketed estimation keeps
        observe() O(1); the power-of-two bounds give <=2x resolution,
        plenty for 'did the tail collapse' comparisons."""
        if self.n == 0:
            return 0.0
        rank = q * self.n
        seen = 0
        lo = 0.0
        for i, hi in enumerate(self.BOUNDS):
            c = self.counts[i]
            if seen + c >= rank and c > 0:
                frac = (rank - seen) / c
                return min(lo + (hi - lo) * frac, self.max_ms)
            seen += c
            lo = hi
        return self.max_ms               # rank in the open-ended bucket

    def snapshot(self) -> Dict[str, Any]:
        buckets = {}
        for i, b in enumerate(self.BOUNDS):
            if self.counts[i]:
                buckets["<=%gms" % b] = self.counts[i]
        if self.counts[-1]:
            buckets[">%gms" % self.BOUNDS[-1]] = self.counts[-1]
        mean = self.total_ms / self.n if self.n else 0.0
        return {"count": self.n, "total_ms": round(self.total_ms, 3),
                "mean_ms": round(mean, 3),
                "max_ms": round(self.max_ms, 3),
                "p50_ms": round(self.percentile(0.50), 3),
                "p99_ms": round(self.percentile(0.99), 3),
                "buckets": buckets}


# -- monitor -------------------------------------------------------------


class Monitor:
    """Event logger over one sink.

    ``line(text)`` is the parity channel: the text prints to stdout
    exactly as the unmonitored code did (callers keep their own
    silent/is_root gating), and enabled sinks additionally record it as
    a ``log`` event. ``emit(event, **fields)`` is the structured
    channel; it is a no-op over a null sink. ``span(name, **attrs)``
    is the timing channel: a context manager around one interval of
    host work, the shared ``NULL_SPAN`` over a null sink.
    """

    def __init__(self, sink=None, trace_dir: str = "",
                 trace_begin: int = 1, trace_end: Optional[int] = None):
        self.sink = sink if sink is not None else NullSink()
        self.trace_dir = trace_dir
        self.trace_begin = trace_begin
        self.trace_end = trace_begin if trace_end is None else trace_end
        self._tracing = False
        self._trace_started = False
        self._trace_round = trace_begin
        # the warn-once latch is touched from worker threads (serve,
        # checkpoint writer, prefetch) as well as the main thread
        self._warn_lock = threading.Lock()
        self._warned = set()
        self.spans = SpanRecorder() if self.sink.enabled else None

    @property
    def enabled(self) -> bool:
        return self.sink.enabled

    def emit(self, event: str, **fields: Any) -> None:
        if not self.sink.enabled:
            return
        if event in _FLUSH_SPANS_AT:
            self.flush_spans()
        record = {"event": event, "t": time.time()}
        record.update(fields)
        self.sink.write(record)

    def span(self, name: str, **attrs: int):
        """Time one interval of host work: ``with mon.span("io.decode",
        n=256) as sp: ...``; ``sp.dur_ns`` afterwards. ``attrs`` are
        small integers (round, step, batch, n)."""
        if self.spans is None:
            return NULL_SPAN
        return self.spans.span(name, **attrs)

    def flush_spans(self) -> None:
        """Write the closed spans out as ``span`` records."""
        if self.spans is None:
            return
        for sp in self.spans.drain():
            self.emit("span", **sp.record())
        if self.spans.dropped:
            self.warn_once("spans_dropped",
                           "the span ring wrapped before a flush: %d "
                           "span(s) lost" % self.spans.dropped)

    def line(self, text: str) -> None:
        """Print a parity stdout line; record it when enabled."""
        print(text)
        if self.sink.enabled:
            self.emit("log", text=text)

    def warn_once(self, code: str, message: str) -> None:
        """Once-per-run structured warning; also surfaces on stderr so
        a silent fallback (e.g. distributed metric reduction failing)
        is visible even with monitor = none.

        NEVER raises: warn_once is called from fallback/cleanup paths
        that were infallible before they warned (shard autodetect, dir
        fsync on the checkpoint writer thread), and a dead sink must
        not turn a warning into a crash — or flip a successful async
        commit into a recorded failure."""
        with self._warn_lock:
            if code in self._warned:
                return
            self._warned.add(code)
        sys.stderr.write("[cxxnet_tpu monitor] warning %s: %s\n"
                         % (code, message))
        try:
            self.emit("warning", code=code, message=message)
        except Exception:
            pass  # cxxlint: disable=CXL006 -- the stderr line above already delivered the warning; a dead sink must not make warn_once raise

    # -- profiler trace window ------------------------------------------

    def maybe_start_trace(self, round_idx: int) -> None:
        """Start at the first observed round >= trace_begin (not only
        on exact equality: a resumed run may begin past the window,
        and a silent no-trace would be worse than a late one)."""
        if (not self.trace_dir or self._trace_started
                or round_idx < self.trace_begin):
            return                       # one window a run
        try:
            import jax
            # the Python tracer's events of a training loop ran a
            # 40 GiB host out of memory (PERF.md, PR 24)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=opts)
        except Exception as e:  # profiler backend is best-effort
            self.warn_once("trace_start_failed",
                           "jax.profiler.start_trace failed: %s" % e)
            return
        self._tracing = True
        self._trace_started = True
        self._trace_round = round_idx
        self.emit("trace_start", dir=self.trace_dir, round=round_idx)

    def maybe_stop_trace(self, round_idx: int,
                         force: bool = False) -> None:
        if not self._tracing:
            return
        if not force and round_idx < self.trace_end:
            self._trace_round = round_idx    # last round seen tracing
            return
        if force:
            # close-time stop (run ended inside the window): attribute
            # the stop to the last traced round, not the caller's 0
            round_idx = max(round_idx, self._trace_round)
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:
            # no trace was written: warn (and stop retrying), but do
            # NOT emit trace_stop — the stream must not claim a trace
            # that does not exist
            self._tracing = False
            self.warn_once("trace_stop_failed",
                           "jax.profiler.stop_trace failed: %s" % e)
            return
        self._tracing = False
        self.emit("trace_stop", dir=self.trace_dir, round=round_idx)

    def close(self) -> None:
        self.maybe_stop_trace(0, force=True)
        self.flush_spans()
        if self.trace_dir and not self._trace_started:
            # trace requested but the run never reached trace_begin —
            # say so instead of leaving an empty dir with no diagnostic
            self.warn_once(
                "trace_never_started",
                "monitor_trace_dir was set but no round >= "
                "monitor_trace_begin (%d) ran; no trace captured"
                % self.trace_begin)
        self.sink.close()


# -- construction --------------------------------------------------------


def config_hash(cfg) -> str:
    """Stable digest of the full ordered (name, value) config stream —
    ties every record stream back to the exact run configuration."""
    text = "\n".join("%s=%s" % (k, v) for k, v in cfg)
    return hashlib.sha1(text.encode()).hexdigest()[:12]


def create_monitor(cfg, root: Optional[bool] = None) -> Monitor:
    """Build a Monitor from ``key = value`` config pairs.

    Non-root processes always get a null sink (process-0 gating, the
    same rule main.py applies to prints) — pass ``root`` explicitly to
    override, e.g. in single-process library use before jax init.
    """
    mode = "none"
    path = "monitor.jsonl"
    flush_period = 1.0
    rotate_mb = 0.0
    trace_dir = ""
    trace_begin, trace_end = 1, None
    for name, val in cfg:
        if name == "monitor":
            if val not in ("none", "stdout", "jsonl"):
                raise ValueError(
                    "monitor must be none|stdout|jsonl, got %r" % val)
            mode = val
        if name == "monitor_path":
            path = val
        if name == "monitor_flush_period":
            flush_period = float(val)
        if name == "monitor_rotate_mb":
            rotate_mb = float(val)
        if name == "monitor_trace_dir":
            trace_dir = val
        if name == "monitor_trace_begin":
            trace_begin = int(val)
        if name == "monitor_trace_end":
            trace_end = int(val)
    if root is None:
        from ..parallel import is_root
        root = is_root()
    if not root:
        # process-0 gating: one run, one record stream, one trace —
        # non-root ranks must not race on the trace dir or duplicate
        # the close-time trace warnings
        mode = "none"
        trace_dir = ""
    if mode == "stdout":
        sink = StdoutSink()
    elif mode == "jsonl":
        sink = JsonlSink(path, flush_period, rotate_mb=rotate_mb)
    else:
        sink = NullSink()
    return Monitor(sink, trace_dir=trace_dir, trace_begin=trace_begin,
                   trace_end=trace_end)


def run_metadata(task: str, cfg, mesh=None,
                 device: bool = True) -> Dict[str, Any]:
    """Run-level metadata for the ``run_start`` record: mesh shape,
    process topology, backend and versions, config digest.

    ``device=False`` is for tasks that must never initialize a jax
    backend (the ``fleet`` / ``fleet_balancer`` parents, whose replica
    children own the chips): the record then says ``platform: none``
    with zero devices instead of asking jax what it could attach to."""
    import platform as _platform

    import jax

    from ..parallel import rank, world_size
    meta: Dict[str, Any] = {
        "task": task,
        "config_hash": config_hash(cfg),
        "jax_version": jax.__version__,
        "python_version": _platform.python_version(),
        "platform": jax.default_backend() if device else "none",
        "process_count": world_size(),
        "process_index": rank(),
        "device_count": len(jax.devices()) if device else 0,
        "device_kind": jax.devices()[0].device_kind if device else "",
        "mesh": dict(mesh.shape) if mesh is not None else None,
    }
    return meta


def device_memory_snapshot() -> Dict[str, Any]:
    """Per-device memory stats where the backend provides them
    (``Device.memory_stats()`` — TPU/GPU runtimes; CPU returns None).
    Host-side query only: no device computation, safe at round
    boundaries."""
    import jax
    devices = []
    available = False
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            available = True
            devices.append({
                "id": d.id,
                "kind": d.device_kind,
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(
                    stats.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(stats.get("bytes_limit", 0)),
            })
        else:
            devices.append({"id": d.id, "kind": d.device_kind})
    return {"available": available, "devices": devices}


# -- global registry (the warn-once channel for deep call sites) ---------

class SafeEmitter:
    """Emit wrapper for worker-thread telemetry: a sink failure (full
    disk, closed file) must neither kill the emitting thread nor spam
    — the first failure prints ONE stderr line (latched under a lock:
    emitters run on several threads at once) and serving/training
    continues without records. The single implementation of the latch
    the serve batcher and fleet frontend both need."""

    def __init__(self, monitor, label: str):
        self._mon = monitor
        self._label = label
        self._lock = threading.Lock()
        self._broken = False

    def __call__(self, kind: str, **fields: Any) -> None:
        if self._mon is None or not self._mon.enabled:
            return
        try:
            self._mon.emit(kind, **fields)
        except Exception as e:
            with self._lock:
                already, self._broken = self._broken, True
            if not already:
                print("%s: telemetry emit failed (continuing without "
                      "records): %s" % (self._label, e),
                      file=sys.stderr)


_global_monitor: Optional[Monitor] = None
_fallback_warned: set = set()
_fallback_lock = threading.Lock()


def set_global(mon: Optional[Monitor]) -> None:
    """Install the run's monitor so deep call sites (utils/metric.py)
    can reach it without threading it through every signature."""
    global _global_monitor
    _global_monitor = mon


def get_global() -> Optional[Monitor]:
    return _global_monitor


def warn_once(code: str, message: str) -> None:
    """Module-level warn-once: routes through the installed monitor, or
    falls back to a bare once-per-process stderr line when no monitor
    is active (library callers outside the CLI)."""
    if _global_monitor is not None:
        _global_monitor.warn_once(code, message)
        return
    with _fallback_lock:
        if code in _fallback_warned:
            return
        _fallback_warned.add(code)
    sys.stderr.write("[cxxnet_tpu monitor] warning %s: %s\n"
                     % (code, message))
