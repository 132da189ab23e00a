"""The program's own spans and scopes, laid beside the device trace.

The program stamps each ``span`` record with ``time.time_ns()``
(``cxxnet_tpu/monitor/spans.py``); a profiler trace carries
``profile_start_time`` (ns since the epoch) in its ``Task Environment``
plane, and every event's ``start_ns`` counts from it. So a span lies on
the trace's clock at ``t0_ns - profile_start_time``, also where the mix
traces no host events (``trace_host_level = 0``). The program's
``program_scopes`` records map the HLO instructions of each train
program to the layer scope that made them, so device time can be summed
by layer type, which a fusion's kind cannot say.

Readers under ``layer_metrics/`` import this by name. A program that
writes no ``span`` or ``program_scopes`` record (any commit before
they existed) gives ``None`` everywhere here, and the metric is left out.
Device intervals, their union and the gaps come from ``trace_reduce``.
"""

import bisect
from collections import defaultdict
from dataclasses import dataclass
import functools
import json
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import trace_reduce
from harness import median

Interval = Tuple[float, float]
Segment = Tuple[float, float, str]       # start, end (trace clock, ns), name

UNNAMED = "unnamed"
# while the main thread is in one of these it waits for another thread,
# and an idle gap belongs to what that thread was doing
WAITS_FOR_OTHERS = ("train.data_wait",)
_INSTR_RE = re.compile(r"^%?([\w.\-]+)\s*=")
_CORE_RE = re.compile(r"^\w+\((.*)\)$")


# -- spans -------------------------------------------------------------------


def spans(run, window: bool = True) -> List[Dict[str, Any]]:
    """The run's ``span`` records; with ``window`` those that ENDED
    inside the measured window (a span's ``t`` is its end)."""
    if window:
        return run.in_window("span")
    return [r for r in run.records if r["event"] == "span"]


def total_ms(run, names: Sequence[str], window: bool = True
             ) -> Optional[float]:
    """Summed duration of the spans with one of ``names``; None where
    the program recorded none of them."""
    found = [s["dur_ns"] for s in spans(run, window) if s["name"] in names]
    return sum(found) / 1e6 if found else None


def per_batch_ms(run, names: Sequence[str]) -> Optional[float]:
    """``total_ms`` over the batches the window's dispatches trained."""
    ms = total_ms(run, names)
    batches = sum(s["n_batches"] for s in run.in_window("step"))
    return None if ms is None or not batches else ms / batches


def host_dispatch_ms(run) -> Optional[float]:
    """Median over the window's dispatches of ``trainer.stage`` +
    ``trainer.enqueue``: the host work before the device can start."""
    by_step: Dict[int, float] = defaultdict(float)
    for s in spans(run):
        if s["name"] in ("trainer.stage", "trainer.enqueue"):
            by_step[s["attrs"]["step"]] += s["dur_ns"] / 1e6
    return median(list(by_step.values())) if by_step else None


# -- the trace ---------------------------------------------------------------


@dataclass
class Trace:
    device_ops: List[List[Segment]]      # per chip, trace_reduce's triples
    modules: List[List[Segment]]         # per chip, the XLA Modules line
    host: List[Segment]
    start_ns: Optional[int]              # profile_start_time


@functools.lru_cache(maxsize=2)
def read_trace(path: str) -> Trace:
    from jax.profiler import ProfileData
    device_ops, host = trace_reduce.read_xplane(path)
    modules, start = [], None
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules.append([
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         ev.name.split("(")[0]) for ev in line.events])
        elif plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
    return Trace(device_ops, modules, host, start)


def trace_of(run) -> Optional[Trace]:
    path = trace_reduce.find_xplane(run.trace_dir) if run.trace_dir else None
    if path is None:
        return None
    t = read_trace(path)
    return t if t.device_ops and any(t.device_ops) else None


# -- idle gaps by span -------------------------------------------------------


def deepest(spans_of_thread: Sequence[Segment]) -> List[Segment]:
    """One thread's spans (properly nested) flattened to non-overlapping
    segments, each named by the deepest span open in it."""
    cuts = sorted({t for s, e, _ in spans_of_thread for t in (s, e)})
    out: List[Segment] = []
    for a, b in zip(cuts, cuts[1:]):
        # the innermost of the spans covering (a, b) started last
        cover = [(s, n) for s, e, n in spans_of_thread if s <= a and e >= b]
        if cover:
            name = max(cover)[1]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def overlaps(segments: Sequence[Segment], starts: Sequence[float],
             gap: Interval) -> List[Segment]:
    """The parts of sorted, non-overlapping ``segments`` inside ``gap``."""
    out = []
    i = max(0, bisect.bisect_right(starts, gap[0]) - 1)
    while i < len(segments) and segments[i][0] < gap[1]:
        s, e, n = segments[i]
        lo, hi = max(s, gap[0]), min(e, gap[1])
        if hi > lo:
            out.append((lo, hi, n))
        i += 1
    return out


def idle_by_span(gaps: Sequence[Interval], main: Sequence[Segment],
                 others: Sequence[Sequence[Segment]]) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` by the span that was open in them: the
    main thread's deepest span, and while that one only waits for
    another thread (or no span is open) what the other threads were in.
    What no span covers is ``unnamed``."""
    main_starts = [s for s, _, _ in main]
    other_starts = [[s for s, _, _ in o] for o in others]
    out: Dict[str, float] = defaultdict(float)

    def from_others(piece: Interval, fallback: str) -> None:
        left = piece[1] - piece[0]
        for o, starts in zip(others, other_starts):
            for s, e, n in overlaps(o, starts, piece):
                out[n] += e - s
                left -= e - s
        if left > 0:
            out[fallback] += left

    for gap in gaps:
        at = gap[0]
        for s, e, n in overlaps(main, main_starts, gap):
            if s > at:
                from_others((at, s), UNNAMED)
            if n in WAITS_FOR_OTHERS:
                from_others((s, e), n)
            else:
                out[n] += e - s
            at = e
        if gap[1] > at:
            from_others((at, gap[1]), UNNAMED)
    return dict(out)


def traced_span(trace: Trace, span_s: float) -> Interval:
    """The traced interval on the trace's clock, for the first chip: the
    host's own measure of it (``span_s``, start_trace's return to
    stop_trace's call) laid from the trace's first event, host or device;
    where the trace holds no host event (host tracer level 0), from
    ``profile_start_time`` itself, which start_trace stamps some 45 ms
    before it returns. Never narrower than the chip's ops. The trace's
    stop stamp is no help: it comes after the profiler's own stop work
    (0.24 s late in the trace under tests/, 1.3 s in the pipeline cell)."""
    ops = trace.device_ops[0]
    lo, hi = ops[0][0], max(e for _, e, _ in ops)
    if span_s <= 0:
        return lo, hi
    lo = min([lo] + [s for s, _, _ in trace.host]) if trace.host \
        else min(lo, 0.0)
    return lo, max(hi, lo + span_s * 1e9)


def span_segments(run, trace: Trace
                  ) -> Tuple[List[Segment], List[List[Segment]]]:
    """(main thread's, other threads') deepest-span segments on the
    trace's clock. The main thread is the one that dispatches."""
    by_tid: Dict[int, List[Segment]] = defaultdict(list)
    main_tid = None
    for s in spans(run, window=False):
        t0 = float(s["t0_ns"] - trace.start_ns)
        by_tid[s["tid"]].append((t0, t0 + s["dur_ns"], s["name"]))
        if s["name"].startswith("trainer."):
            main_tid = s["tid"]
    main = deepest(by_tid.pop(main_tid, []))
    return main, [deepest(v) for v in by_tid.values()]


def idle_report(run) -> Optional[Dict[str, Any]]:
    """Idle seconds of the first chip in the traced interval by the
    program's span, and the share no span covers. None without a trace,
    its start stamp, or any span record."""
    trace = trace_of(run)
    if trace is None or trace.start_ns is None \
            or not spans(run, window=False):
        return None
    busy = trace_reduce.union([(s, e) for s, e, _ in trace.device_ops[0]])
    gaps = trace_reduce.gaps(busy, traced_span(trace, run.trace_span_s))
    main, others = span_segments(run, trace)
    by_name = idle_by_span(gaps, main, others)
    idle = trace_reduce.total(gaps)
    if idle <= 0:
        return None
    return {"idle_s": idle / 1e9,
            "unnamed_share": by_name.get(UNNAMED, 0.0) / idle,
            "by_span_s": {k: v / 1e9 for k, v in
                          sorted(by_name.items(), key=lambda kv: -kv[1])},
            "clock": clock_check(run, trace)}


def clock_check(run, trace: Trace) -> Optional[Dict[str, float]]:
    """Where the trace holds host events (host level >= 1), each span's
    mirrored ``TraceAnnotation`` against the span's own stamp: the
    distance between the two starts, in microseconds. The proof that
    records and trace share a clock."""
    mine: Dict[str, List[float]] = defaultdict(list)
    for s in spans(run, window=False):
        if s["name"].startswith("trainer."):
            mine[s["name"]].append(float(s["t0_ns"] - trace.start_ns))
    off = []
    for s, _, name in trace.host:
        if mine.get(name):
            off.append(min(abs(s - t) for t in mine[name]) / 1e3)
    if not off:
        return None
    return {"annotations": len(off), "median_us": median(off),
            "max_us": max(off)}


# -- device time by scope ----------------------------------------------------


@functools.lru_cache(maxsize=None)
def is_container(text: str) -> bool:
    """A ``while`` / ``conditional`` / ``call`` op: its time is its
    body's. Cached: a trace repeats a step's few thousand instructions."""
    return trace_reduce.categorize(text) in trace_reduce.CONTAINERS


# a scope is <layer type>.<layer key>, or a step-level name
GROUPS = {"conv": ("conv",), "fullc": ("fullc", "pallas_fullc", "fixconn"),
          "batch_norm": ("batch_norm",), "update": ("update",)}
POOLING = ("max_pooling", "avg_pooling", "sum_pooling")


def group_of(path: str) -> str:
    """The metric group of one scope path: the innermost scope's layer
    type; pooling counts only backward (``transpose(jvp(...))``)."""
    part = path.split("/")[-1]
    core = part
    while True:
        m = _CORE_RE.match(core)
        if m is None:
            break
        core = m.group(1)
    kind = core.split(".")[0]
    if kind in POOLING:
        return "pool_bwd" if part.startswith("transpose(") else "pool_fwd"
    for group, kinds in GROUPS.items():
        if kind in kinds:
            return group
    return "other_scoped"


def scope_maps(run) -> Dict[str, Dict[str, str]]:
    """{HLO module name: {instruction: scope path}} of the run's
    ``program_scopes`` records (they lie in set-up, before the window)."""
    return {r["module"]: r["scopes"] for r in run.records
            if r["event"] == "program_scopes"}


def device_report(run, trace: Optional[Trace] = None
                  ) -> Optional[Dict[str, Any]]:
    """Device time of the traced train dispatches by scope group, in ms a
    trained batch, mean over the chips, and the share of device op time
    that maps to a scope: of all of it (``coverage``), and of the scoped
    programs' own (``program_coverage``). Ops are joined to their program
    by the ``XLA Modules`` event they start in; containers (``while``)
    are not counted, their bodies are. A dispatch the trace's end cut
    short (its module event is under 0.9 of that program's longest) is
    in the coverage but not in the ms a batch."""
    trace = trace or trace_of(run)
    maps = scope_maps(run)
    steps = run.in_window("step")
    if trace is None or not maps or not steps:
        return None
    n_batches = median([s["n_batches"] for s in steps])
    chips = len(trace.device_ops)
    groups: Dict[str, float] = defaultdict(float)
    mapped = in_programs = everything = 0.0
    for ops, modules in zip(trace.device_ops, trace.modules):
        modules = sorted(modules)
        starts = [m[0] for m in modules]
        longest: Dict[str, float] = defaultdict(float)
        for s, e, name in modules:
            longest[name] = max(longest[name], e - s)
        whole = sum(1 for s, e, name in modules
                    if name in maps and e - s >= 0.9 * longest[name])
        for s, e, text in ops:
            if is_container(text):
                continue
            everything += e - s
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s > modules[i][1] or modules[i][2] not in maps:
                continue                 # another program's
            ms, me, module = modules[i]
            in_programs += e - s
            name = _INSTR_RE.match(text)
            path = maps[module].get(name.group(1)) if name else None
            mapped += (e - s) if path else 0.0
            if whole and me - ms >= 0.9 * longest[module]:
                groups[group_of(path) if path else "unscoped"] += \
                    (e - s) / 1e6 / (whole * n_batches) / chips
    if in_programs <= 0:
        return None
    return {"coverage": mapped / everything,
            "program_coverage": mapped / in_programs,
            "ms_a_batch": dict(groups), "batches_a_dispatch": n_batches,
            "whole_dispatches": whole,
            "busy_ms": run.trace_summary.busy_s * 1e3
            if run.trace_summary else None,
            # what reading each map cost the program, in its set-up
            "scopes_read_ms": {r["module"]: r["wall_ms"] for r in run.records
                               if r["event"] == "program_scopes"}}


def device_ms(run, group: str) -> Optional[float]:
    """One group of ``device_report``. None where under 90 % of the
    scoped programs' op time maps to a scope: a program read from a
    compile cache that an earlier build wrote (the cache's key ignores
    scopes) maps nothing, and must not print a wrong split."""
    rep = device_report(run)
    if rep is None or rep["program_coverage"] < 0.9:
        return None
    return rep["ms_a_batch"].get(group, 0.0)


def phase(name: str, **fields: Any) -> None:
    """One more line before the result line, like run.py's own."""
    print(json.dumps({"phase": name, **fields}), flush=True)
