"""Layers / XLA fusions: device time of the ops the program's
``program_scopes`` records place in the gated delta-rule linear-attention layers (scope type gated_delta: projections, the causal convolution with SiLU and the unit-length q and k, the chunked scan along time, the gated norm, the output projection, forward and backward, recomputation included),
by the op's OUTERMOST layer scope, in ms a trained batch over the whole
dispatches the trace holds, mean over the chips. Left out where under
90 % of the scoped programs' op time maps to a scope, or where the
program writes no such record (scope_groups.py). One ``device_by_layer``
line before the result line gives every layer type's ms, the named parts
inside the layers that have some (``gated_delta/proj``, ``/short_conv``,
``/scan``, ``/gate_norm``, ``/out``; ``gqa_attention/core``; the expert
layers' route, dispatch, experts, shared, combine) and the longest
instructions, each with its layer's key, and what the program's
``layout`` record counts: attention layers (how many, on the fused
kernel, saved, with a window), expert layers (how many, on the grouped
kernels), linear-attention layers and their chunk. Moves train_img_per_s.
"""

import span_reduce

import scope_groups

_COUNTS = ("attention_", "moe_", "linear_attention_")


def read(run):
    rep = scope_groups.report(run, top=24)
    if rep is not None:
        layout = next((r for r in run.records if r["event"] == "layout"), {})
        span_reduce.phase("device_by_layer", layout={
            k: v for k, v in layout.items() if k.startswith(_COUNTS)}, **rep)
    return scope_groups.device_ms(run, ("gated_delta",))
