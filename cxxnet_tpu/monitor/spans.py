"""Host spans and device-scope maps: the monitor's timing vocabulary.

A span is one interval of host work at a layer boundary (wait for a
batch, stage, enqueue, decode a chunk, ...): a name, a start and an end
on ``time.time_ns()`` (CLOCK_REALTIME), the thread, and the enclosing
span of that thread. That clock is the one a ``jax.profiler`` trace is
anchored to: the xplane's ``Task Environment`` plane carries
``profile_start_time`` in ns since the epoch and every event's
``start_ns`` is relative to it, so a span lies on the device trace at
``t0_ns - profile_start_time`` — also at host tracer level 0, where a
``TraceAnnotation`` records nothing. Each span also enters a
``TraceAnnotation`` of its name, so a profile at host level >= 1 shows
the same spans with no second call site.

Nothing is formatted or written where the span closes: spans go into a
bounded ring and the monitor writes them out as ``span`` records just
ahead of its next ``step`` / ``precompile`` / ``round_end`` / ``run_end``
record and at close.

The device side has no clock of its own to add: ``jax.named_scope``
puts the layer's name into each HLO instruction's ``op_name`` metadata,
and :func:`scope_map` reads the map {instruction -> scope path} back
from the text of the executable that was actually loaded.
"""

from __future__ import annotations

import collections
import itertools
import re
import threading
import time
from typing import Any, Dict, Iterable, List, Tuple

RING_SPANS = 4096


class _NullSpan:
    """The span of a disabled monitor: one shared object, no clock
    read, no record. ``dur_ns`` reads 0 so callers need no branch."""

    __slots__ = ()
    t0_ns = t1_ns = dur_ns = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


def no_span(name: str, **attrs: Any) -> _NullSpan:
    """``Monitor.span`` of nobody: what an iterator chain or a trainer
    without an enabled monitor calls."""
    return NULL_SPAN


class Span:
    __slots__ = ("_rec", "_ann", "name", "attrs", "id", "parent", "tid",
                 "t0_ns", "t1_ns")

    def __init__(self, rec: "SpanRecorder", name: str,
                 attrs: Dict[str, int]):
        self._rec = rec
        self.name = name
        self.attrs = attrs

    @property
    def dur_ns(self) -> int:
        return self.t1_ns - self.t0_ns

    def __enter__(self) -> "Span":
        rec = self._rec
        stack = rec.stack()
        self.parent = stack[-1].id if stack else 0
        self.id = next(rec.ids)
        self.tid = threading.get_ident()
        stack.append(self)
        self._ann = rec.annotation(self.name)
        self._ann.__enter__()
        self.t0_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1_ns = time.time_ns()
        self._ann.__exit__(*exc)
        self._rec.stack().pop()
        self._rec.add(self)
        return False

    def record(self) -> Dict[str, Any]:
        # t is the span's END: a reader that selects records by time
        # selects by when the work happened, not by when it was written
        return {"t": self.t1_ns / 1e9, "name": self.name,
                "t0_ns": self.t0_ns, "dur_ns": self.dur_ns,
                "tid": self.tid, "id": self.id, "parent": self.parent,
                "attrs": self.attrs}


class SpanRecorder:
    """The ring closed spans wait in, appended from the main and the
    prefetch producer thread. ``dropped`` counts spans the ring lost by
    wrapping before a flush took them."""

    def __init__(self, maxlen: int = RING_SPANS):
        from jax.profiler import TraceAnnotation
        self.annotation = TraceAnnotation
        self.ids = itertools.count(1)        # next() is atomic
        self.dropped = 0
        self._ring: collections.deque = collections.deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._local = threading.local()

    def stack(self) -> List[Span]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def span(self, name: str, **attrs: int) -> Span:
        return Span(self, name, attrs)

    def add(self, span: Span) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(span)

    def drain(self) -> List[Span]:
        with self._lock:
            out = list(self._ring)
            self._ring.clear()
        return out


# -- device scopes ---------------------------------------------------------

# step-level scopes the trainer opens beside the net's per-layer ones
STEP_SCOPES = ("window", "input_norm", "loss", "grad_cast", "update",
               "grad_sync")

_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(")
_CALLS_RE = re.compile(r"\bfusion\(.*calls=%?([\w.\-]+)")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# transpose(jvp(x)), vmap(x), ...: a transform around a scope keeps its
# name; jit(f) is a function's name, never a scope
_TRANSFORM_RE = re.compile(r"^(?!p?jit\()\w+\((.*)\)$")
# instructions that take no time of their own on the device
_NO_TIME = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                      "bitcast", "after-all", "partition-id", "replica-id"))


def scope_path(op_name: str, known: Iterable[str]) -> str:
    """The components of one ``op_name`` that are scopes the program
    opened, transform wrappers kept: ``jit(step)/while/body/
    transpose(jvp(conv.c1))/conv_general_dilated`` -> ``transpose(jvp(
    conv.c1))``. Empty where the op lies in no known scope."""
    kept = []
    for part in op_name.split(";")[0].split("/"):
        core = part
        while True:
            m = _TRANSFORM_RE.match(core)
            if m is None:
                break
            core = m.group(1)
        if core in known:
            kept.append(part)
    return "/".join(kept)


def scope_map(hlo_text: str, known: Iterable[str]
              ) -> Tuple[str, Dict[str, str], int, int]:
    """``(module, {instruction: scope path}, fusions, fusions_mapped)``
    of one compiled module's text (``executable.as_text()``). Every
    instruction outside fused computations whose ``op_name`` lies in a
    known scope is mapped (a fusion carries its root's). ``fusions``
    counts the fusion and convolution instructions there, which carry
    nearly all of a step's device time, and ``fusions_mapped`` those of
    them that got a scope; what the compiler adds itself (async copies
    and slices, layout copies) has no ``op_name`` and stays unmapped."""
    known = frozenset(known)
    lines = hlo_text.splitlines()
    m = _MODULE_RE.match(lines[0]) if lines else None
    module = m.group(1) if m else ""
    fused = {c.group(1) for c in map(_CALLS_RE.search, lines) if c}
    scopes: Dict[str, str] = {}
    fusions = fusions_mapped = 0
    skip = False
    for line in lines:
        if not line.startswith(" "):
            head = _COMPUTATION_RE.match(line)
            if head is not None:
                skip = head.group(1) in fused
            continue
        if skip:
            continue
        ins = _INSTR_RE.match(line)
        if ins is None or ins.group(2) in _NO_TIME:
            continue
        op = _OP_NAME_RE.search(line)
        path = scope_path(op.group(1), known) if op else ""
        if path:
            scopes[ins.group(1)] = path
        if ins.group(2) in ("fusion", "convolution"):
            fusions += 1
            fusions_mapped += bool(path)
    return module, scopes, fusions, fusions_mapped
