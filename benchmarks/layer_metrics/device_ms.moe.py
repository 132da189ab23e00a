"""Layers / XLA fusions: device time of the ops the program's
``program_scopes`` records place in the expert layers (scope type moe, with route, dispatch, experts, shared and combine under it, forward and backward, recomputation included),
by the op's OUTERMOST layer scope, in ms a trained batch over the whole
dispatches the trace holds, mean over the chips. Left out where under
90 % of the scoped programs' op time maps to a scope, or where the
program writes no such record (scope_groups.py). One ``device_by_layer``
line before the result line gives every layer type's ms, the named parts
inside the expert layers (route, dispatch, experts, shared, combine) and
the longest instructions. Moves train_img_per_s.
"""

import span_reduce

import scope_groups


def read(run):
    rep = scope_groups.report(run)
    if rep is not None:
        span_reduce.phase("device_by_layer", **rep)
    return scope_groups.device_ms(run, ("moe",))
