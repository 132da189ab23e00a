"""Device time under one named part of one layer type, a layer each: what
the roofline readers of PR 38 divide by. ``scope_groups.walk``'s ops whose
scope path has ``part`` right under an outermost ``<kind>.<key>``, in ms a
trained batch over the whole dispatches the trace holds, mean over the
chips; None under ``scope_groups``' guard (that file is the accepted
yardstick's and is not edited, so this sits beside it)."""

from collections import defaultdict
from typing import Dict, Optional

import scope_groups


def part_ms_by_layer(run, kind: str, part: str) -> Optional[Dict[str, float]]:
    """``{"<kind>.<key>": ms, ...}``, empty where the program opens no
    such scope."""
    ops = scope_groups.walk(run)
    if ops is None:
        return None
    out: Dict[str, float] = defaultdict(float)
    for ms, path, _ in ops:
        if scope_groups.outer_kind(path) == kind \
                and scope_groups.inner_part(path).split("/")[0] == part:
            out[next(c for c in map(scope_groups._core, path.split("/"))
                     if "." in c)] += ms
    return dict(out)
