"""Device time of the traced train dispatches by the OUTERMOST layer
scope of each op, for layer types whose scopes nest (an expert layer's
``moe.<key>/experts``) or that ``span_reduce.GROUPS`` does not name.
After ``span_reduce.device_report`` (same joins, same guard, same unit:
ms a trained batch over the whole dispatches the trace holds, mean over
the chips); that file is the accepted yardstick and is not edited, so
the walk is repeated here for the new groups. None wherever ``span_reduce`` gives None: a program
that writes no ``program_scopes`` record, or maps under 90 % of its own
op time.
"""

import bisect
from collections import defaultdict
from typing import Dict, Optional, Sequence

import span_reduce


def _core(part: str) -> str:
    while True:
        m = span_reduce._CORE_RE.match(part)
        if m is None:
            return part
        part = m.group(1)


def outer_kind(path: str) -> str:
    """``window/transpose(jvp(moe.l1_moe))/transpose(jvp(experts))`` ->
    ``moe``: the type of the outermost LAYER scope (``<type>.<key>``);
    where the path holds none, its innermost step-level scope
    (``window/loss`` -> ``loss``)."""
    cores = [_core(p) for p in path.split("/")]
    layers = [c for c in cores if "." in c]
    return layers[0].split(".")[0] if layers else cores[-1]


def inner_part(path: str) -> str:
    """What lies under the outermost layer scope: ``experts`` above,
    empty for a layer without named parts."""
    cores = [_core(p) for p in path.split("/")]
    at = next((i for i, c in enumerate(cores) if "." in c), len(cores))
    return "/".join(cores[at + 1:])


def walk(run):
    """(ms a trained batch, scope path, instruction) of every mapped op
    of the whole traced dispatches, mean over the chips; None under
    span_reduce's guard."""
    trace = span_reduce.trace_of(run)
    rep = span_reduce.device_report(run, trace)
    if rep is None or rep["program_coverage"] < 0.9:
        return None
    maps = span_reduce.scope_maps(run)
    n_batches, chips = rep["batches_a_dispatch"], len(trace.device_ops)
    out = []
    for ops, modules in zip(trace.device_ops, trace.modules):
        modules = sorted(modules)
        starts = [m[0] for m in modules]
        longest: Dict[str, float] = defaultdict(float)
        for s, e, name in modules:
            longest[name] = max(longest[name], e - s)
        whole = sum(1 for s, e, name in modules
                    if name in maps and e - s >= 0.9 * longest[name])
        for s, e, text in ops:
            if span_reduce.is_container(text):
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s > modules[i][1] or modules[i][2] not in maps:
                continue
            ms, me, module = modules[i]
            name = span_reduce._INSTR_RE.match(text)
            path = maps[module].get(name.group(1)) if name else None
            if path and whole and me - ms >= 0.9 * longest[module]:
                out.append(((e - s) / 1e6 / (whole * n_batches) / chips,
                            path, name.group(1)))
    return out


def ms_by_kind(run) -> Optional[Dict[str, float]]:
    ops = walk(run)
    if ops is None:
        return None
    out: Dict[str, float] = defaultdict(float)
    for ms, path, _ in ops:
        out[outer_kind(path)] += ms
    return dict(out)


def report(run, top: int = 12) -> Optional[Dict[str, object]]:
    """For one more line before the result line: ms a trained batch by
    layer type, by the named parts inside the types that have some, and
    the ``top`` instructions."""
    ops = walk(run)
    if ops is None:
        return None
    kinds: Dict[str, float] = defaultdict(float)
    parts: Dict[str, float] = defaultdict(float)
    instr: Dict[tuple, float] = defaultdict(float)
    for ms, path, name in ops:
        kinds[outer_kind(path)] += ms
        if inner_part(path):
            parts["%s/%s" % (outer_kind(path), inner_part(path))] += ms
        instr[(name, path)] += ms
    order = sorted(instr.items(), key=lambda kv: -kv[1])[:top]
    return {"ms_a_batch": dict(kinds), "parts_ms_a_batch": dict(parts),
            "top_instructions": [[n, p, ms] for (n, p), ms in order]}


def device_ms(run, kinds: Sequence[str]) -> Optional[float]:
    by_kind = ms_by_kind(run)
    if by_kind is None:
        return None
    return sum(by_kind.get(k, 0.0) for k in kinds)
