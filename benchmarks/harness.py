"""What every driver and metric reader shares: the run's context object,
compile accounting, and the few statistics the metrics are made of."""

import json
import math
import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple


class BenchFailure(RuntimeError):
    """The run cannot give a result: exit non-zero, print no line."""


class MissingPeak(BenchFailure):
    """The run's device kind is not in peaks.json."""


def load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchFailure("no such file: %s" % path)


def by_name(entries: Sequence[Dict[str, Any]], name: str,
            what: str) -> Dict[str, Any]:
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise BenchFailure("BENCHMARK.json has %d %ss named %r (has: %s)"
                           % (len(found), what, name,
                              ", ".join(e["name"] for e in entries)))
    return found[0]


def device_line(devices) -> Dict[str, Any]:
    """The device as jax reports it; the peak is the fullest chip's. The
    TPU allocator counts live arrays under ``peak_bytes_in_use`` and the
    temporaries of running programs apart, under ``peak_bytes_reserved``
    (a program with 1.07 GB of temporaries moved only the second; my
    chip run, PR 24): a chip's peak is their sum."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def percentile(values: Sequence[float], q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between order
    statistics; raises on an empty sample."""
    if not values:
        raise BenchFailure("percentile of an empty sample")
    s = sorted(values)
    pos = q * (len(s) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 0.5)


def trace_options(traffic: Dict[str, Any]):
    """The profiler's options for a traced window: no Python tracer, and
    the mix's ``trace_host_level`` where it has one. At host levels 2 (the
    default) and 1 the pipeline's H2D linearisation writes so many host
    events that stopping a 3 s trace took 90 s and 104 MB and a 10 s trace
    never ended inside its run; at level 0 a 10 s trace stops in 1.4 s
    with the device's planes whole (my chip runs, PR 24)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    if "trace_host_level" in traffic:
        opts.host_tracer_level = int(traffic["trace_host_level"])
    return opts


class CompileMeter:
    """Seconds jax spent getting executables (compiling, or reading the
    persistent cache) and the cache's hit and miss counts, from jax's own
    monitoring events (after chip_smoke.py's CompileMeter), each stamped
    with the time it ended so that compiles inside the window show."""

    def __init__(self) -> None:
        import jax.monitoring
        self.durations: List[Tuple[float, float]] = []   # (ended_at, secs)
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.durations.append((time.time(), secs))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Tuple[float, int, int]:
        return sum(s for _, s in self.durations), self.hits, self.misses

    def seconds_since(self, t0: float) -> float:
        """Compile seconds of programs that began after ``t0``."""
        return sum(s for end, s in self.durations if end - s >= t0)


@dataclass
class Run:
    """One run of one cell. ``run.py`` fills the first block, the driver
    the second; the metric readers only read."""
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    out_dir: str
    root: str
    rehearse: bool
    devices: Sequence[Any]

    window: Tuple[float, float] = (0.0, 0.0)   # time.time() at start, end
    records: List[Dict[str, Any]] = field(default_factory=list)
    end_to_end: Dict[str, float] = field(default_factory=dict)
    samples: Dict[str, List[float]] = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    failures: List[str] = field(default_factory=list)
    notes: Dict[str, Any] = field(default_factory=dict)
    trace_dir: Optional[str] = None
    trace_span_s: float = 0.0          # host clock, start_trace..stop_trace
    trace_summary: Any = None          # trace_reduce.TraceSummary

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    def fail(self, why: str) -> None:
        """A check on what the run produced did not hold: the line says
        ``correct: false`` and an earlier line says why."""
        self.failures.append(why)

    def check(self, cond: bool, why: str) -> None:
        if not cond:
            self.fail(why)

    def in_window(self, event: str) -> List[Dict[str, Any]]:
        """The program's records of one event type stamped inside the
        window (``t`` is time.time(), as the window's ends are)."""
        t0, t1 = self.window
        return [r for r in self.records
                if r["event"] == event and t0 < r["t"] <= t1]

    def one_record(self, event: str) -> Optional[Dict[str, Any]]:
        found = [r for r in self.records if r["event"] == event]
        return found[-1] if found else None

    def peak(self, key: str) -> float:
        """A published peak of this run's chip from peaks.json. A device
        that is not in the table is an error, not a default."""
        table = load_json(os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "peaks.json"))
        kind = self.devices[0].device_kind
        if kind not in table:
            raise MissingPeak("device kind %r is not in peaks.json" % kind)
        return float(table[kind][key])

    def seed32(self) -> int:
        """--seed folded into what numpy's and the program's seeds take."""
        return int(self.seed) % (2 ** 31 - 1)
