"""Layers / XLA fusions: device time of the ops the program's
``program_scopes`` records place in the gated short-convolution mixers (scope type gated_conv: the in-projection, the two elementwise gates around the causal depthwise convolution, the out-projection, forward and backward, recomputation included),
by the op's OUTERMOST layer scope, in ms a trained batch over the whole
dispatches the trace holds, mean over the chips. Left out where under
90 % of the scoped programs' op time maps to a scope, or where the
program writes no such record (scope_groups.py), or opens no
``gated_conv`` scope at all (any program before PR 38). One
``device_by_layer`` line before the result line gives every layer type's
ms (``gated_conv``, ``gqa_attention``, ``moe``, ``swiglu`` the dense
layer, ``embed`` the lookup and the tied head, ``loss``, ``rmsnorm``,
``add``, ``update``), the named parts inside the layers that have some
(``gated_conv/in_proj``, ``/short_conv``, ``/out_proj``;
``gqa_attention/core``; the expert layers' route, dispatch, experts,
combine) and the longest instructions, each with its layer's key, and
what the program's ``layout`` record counts: attention layers (how many,
on the fused kernel, saved), expert layers (how many, on the grouped
kernels), short-convolution layers and whether the head is tied. Moves
train_img_per_s.
"""

import span_reduce

import scope_groups

_COUNTS = ("attention_", "moe_", "short_conv_", "head_tied")


def read(run):
    rep = scope_groups.report(run, top=24)
    if rep is None or "gated_conv" not in rep["ms_a_batch"]:
        return None
    layout = next((r for r in run.records if r["event"] == "layout"), {})
    span_reduce.phase("device_by_layer", layout={
        k: v for k, v in layout.items() if k.startswith(_COUNTS)}, **rep)
    return rep["ms_a_batch"]["gated_conv"]
