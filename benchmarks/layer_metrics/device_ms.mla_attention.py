"""Layers / XLA fusions: device time of the ops the program's
``program_scopes`` records place in the multi-head latent attention layers (scope type mla_attention: projections, RoPE, the blocked causal core, forward and backward, recomputation included),
by the op's OUTERMOST layer scope, in ms a trained batch over the whole
dispatches the trace holds, mean over the chips. Left out where under
90 % of the scoped programs' op time maps to a scope, or where the
program writes no such record (scope_groups.py). Moves train_img_per_s.
"""

import scope_groups


def read(run):
    return scope_groups.device_ms(run, ("mla_attention",))
