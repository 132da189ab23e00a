"""Device: device op time in the trace whose HLO instruction the
program's ``program_scopes`` records map to a layer scope, over all
device op time, in percent. One ``device_by_scope`` line before the
result line gives the ms a trained batch of every scope group, of the
unscoped rest, the trace's busy time, and the same share over the
scoped programs' own ops alone. Near zero means the chip runs
programs read from a compile cache that an earlier build wrote. Moves
train_img_per_s.
"""

import span_reduce


def read(run):
    rep = span_reduce.device_report(run)
    if rep is None:
        return None
    span_reduce.phase("device_by_scope", **rep)
    return 100.0 * rep["coverage"]
