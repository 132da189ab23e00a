"""From a ``jax.profiler`` trace (``*.xplane.pb``) to device numbers.

Busy time is the union of the intervals in which an XLA operation ran
on a chip's TensorCore (the ``XLA Ops`` line of its plane), averaged
over the chips used; the idle share is one minus busy over the traced
span. Device time by category follows doc/profile_model.py's
``categorize`` (convolution, fusion kinds, pooling, collectives,
copies). Idle gaps are named by what the host was doing in them: the
benchmark's own ``bench.*`` annotation where one overlaps, else the
host span that covers most of the gap. Checked against a small trace
recorded on the chip (benchmarks/tests/data/).
"""

from collections import defaultdict
from dataclasses import dataclass
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]           # start, end, in nanoseconds

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
_OPCODE_RE = re.compile(r"=\s+\S+\s+([\w-]+)\(")
_KIND_RE = re.compile(r"kind=k(\w+)")
_FORMAT_OPS = ("copy", "transpose", "bitcast", "reshape", "slice",
               "dynamic-slice", "dynamic-update-slice", "concatenate", "pad")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")
# ops that only enclose others, whose time is their bodies' time
CONTAINERS = ("while", "conditional", "call")


def categorize(name: str) -> str:
    """Category of one device op from its name or HLO text (after
    doc/profile_model.py): works on ``%fusion.3 = ... fusion(...),
    kind=kLoop`` and on a bare ``fusion.3`` alike. On the TPU a fusion
    rooted in a convolution or a dot is ``kind=kOutput`` and its name
    says no more, so those are one category, ``conv/dot fusion``."""
    n = name.lower()
    m = _OPCODE_RE.search(name)
    op = m.group(1) if m else \
        re.sub(r"[.\d]+$", "", name.split(" ")[0].lstrip("%"))
    if "convolution" in n or "conv" in op:
        return "convolution"
    if any(c in op for c in _COLLECTIVES):
        return "collective"
    if op == "fusion" or op.endswith("fusion"):
        k = _KIND_RE.search(name)
        if k and k.group(1) == "Output":
            return "conv/dot fusion"
        return "fusion:%s" % k.group(1).lower() if k else \
            ("fusion" if op == "fusion" else op)
    if "select-and-scatter" in op:
        return "select-and-scatter"
    if "reduce-window" in op:
        return "reduce-window"
    if op in _FORMAT_OPS or any(op.startswith(f + "-") or op.startswith(f + "_")
                                for f in _FORMAT_OPS):
        return "copy/format"
    return op or "other"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], span: Interval) -> List[Interval]:
    """The idle intervals of ``span`` between merged ``busy`` ones."""
    out, at = [], span[0]
    for s, e in busy:
        if s > at:
            out.append((at, min(s, span[1])))
        at = max(at, e)
    if span[1] > at:
        out.append((at, span[1]))
    return [g for g in out if g[1] > g[0]]


@dataclass
class TraceSummary:
    busy_s: float                        # mean over the chips
    window_s: float
    chips: int
    categories: Dict[str, float]         # seconds, mean over the chips
    idle_by_host: Dict[str, float]       # seconds of the first chip's gaps
    ops: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> Dict[str, List[List]]:
        def top(d: Dict[str, float]) -> List[List]:
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.categories),
                "idle_gaps": top(self.idle_by_host)}


def name_gap(gap: Interval, host: Sequence[Tuple[float, float, str]]) -> str:
    """What the host was doing in ``gap``: a ``bench.*`` annotation that
    overlaps it, else the host span covering most of it."""
    best, best_cover, best_is_ours = "unattributed", 0.0, False
    for s, e, name in host:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        ours = name.startswith("bench.")
        if (ours, cover) > (best_is_ours, best_cover):
            best, best_cover, best_is_ours = name, cover, ours
    return best


def reduce_planes(device_ops: Sequence[Sequence[Tuple[float, float, str]]],
                  host: Sequence[Tuple[float, float, str]],
                  span_s: float) -> Optional[TraceSummary]:
    """``device_ops``: per chip, its ops as (start_ns, end_ns, name).
    ``span_s``: the traced span by the host's clock; where it is 0 the
    span is the first op's start to the last op's end over all chips."""
    device_ops = [ops for ops in device_ops if ops]
    if not device_ops:
        return None
    lo = min(s for ops in device_ops for s, _, _ in ops)
    hi = max(e for ops in device_ops for _, e, _ in ops)
    window_s = span_s if span_s > 0 else (hi - lo) / 1e9
    # the op span can pass the host's by the clocks' slack: never read
    # a chip busier than its window
    window_s = max(window_s, (hi - lo) / 1e9)
    n = len(device_ops)
    cats: Dict[str, float] = defaultdict(float)
    busy = 0.0
    for ops in device_ops:
        busy += total(union([(s, e) for s, e, _ in ops])) / 1e9 / n
        for s, e, name in ops:
            cat = categorize(name)
            if cat not in CONTAINERS:
                cats[cat] += (e - s) / 1e9 / n
    # centre the host's span on the ops' span to place the edge gaps
    pad = max(0.0, window_s * 1e9 - (hi - lo)) / 2
    idle: Dict[str, float] = defaultdict(float)
    first = union([(s, e) for s, e, _ in device_ops[0]])
    for g in gaps(first, (lo - pad, hi + pad)):
        idle[name_gap(g, host)] += (g[1] - g[0]) / 1e9
    return TraceSummary(busy_s=busy, window_s=window_s, chips=n,
                        categories=dict(cats), idle_by_host=dict(idle),
                        ops=sum(len(o) for o in device_ops))


def find_xplane(trace_dir: str) -> Optional[str]:
    found = []
    for root, _, files in os.walk(trace_dir):
        found += [os.path.join(root, f) for f in files
                  if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def read_xplane(path: str):
    """``(device_ops, host_spans)`` of one xplane file."""
    from jax.profiler import ProfileData
    device_ops, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.append([
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in line.events]
    return device_ops, host


def reduce_dir(trace_dir: Optional[str], chips: int,
               span_s: float) -> Optional[TraceSummary]:
    """The newest trace under ``trace_dir``, reduced; None where there
    is no trace or no device op in it (a CPU rehearsal)."""
    path = find_xplane(trace_dir) if trace_dir else None
    if path is None:
        return None
    device_ops, host = read_xplane(path)
    return reduce_planes(device_ops[:chips], host, span_s)


def describe(path: str, events: int = 3) -> None:
    """Print what a trace holds: look at one by hand before trusting a
    reduction of it."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print("plane %r: %d lines" % (plane.name, len(list(plane.lines))))
        for line in plane.lines:
            evs = list(line.events)
            print("  line %r: %d events" % (line.name, len(evs)))
            for ev in evs[:events]:
                print("    %r start_ns=%r dur_ns=%r stats=%r"
                      % (ev.name[:160], ev.start_ns, ev.duration_ns,
                         {k: (v if not isinstance(v, str) else v[:80])
                          for k, v in list(ev.stats)[:8]}))


if __name__ == "__main__":
    import json
    import sys
    xplane = find_xplane(sys.argv[1])
    describe(xplane)
    summary = reduce_planes(*read_xplane(xplane), span_s=0.0)
    print(json.dumps(None if summary is None else {
        "busy_s": summary.busy_s, "window_s": summary.window_s,
        "chips": summary.chips, "ops": summary.ops,
        **summary.breakdown()}))
