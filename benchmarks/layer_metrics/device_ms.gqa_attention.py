"""Layers / XLA fusions: device time of the ops the program's
``program_scopes`` records place in the grouped-query attention layers (scope type gqa_attention: projections, QK norm, RoPE, the causal core with or without a window, the output gate, forward and backward, recomputation included),
by the op's OUTERMOST layer scope, in ms a trained batch over the whole
dispatches the trace holds, mean over the chips. Left out where under
90 % of the scoped programs' op time maps to a scope, or where the
program writes no such record (scope_groups.py). One ``device_by_layer``
line before the result line gives every layer type's ms, the named parts
inside the layers that have some (``gqa_attention/core``, the expert
layers' route, dispatch, experts, shared, combine) and the longest
instructions, each with its layer's key, and what the program's
``layout`` record says of its attention layers (how many, how many on the
fused kernel, how many with a window). Moves train_img_per_s.
"""

import span_reduce

import scope_groups


def read(run):
    rep = scope_groups.report(run, top=24)
    if rep is not None:
        layout = next((r for r in run.records if r["event"] == "layout"), {})
        span_reduce.phase("device_by_layer", layout={
            k: v for k, v in layout.items() if k.startswith("attention_")},
            **rep)
    return scope_groups.device_ms(run, ("gqa_attention",))
