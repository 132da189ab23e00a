"""Layers / XLA fusions: device time of the ops the program's
``program_scopes`` records place in convolution layers (scope type
conv), forward and backward, in ms a trained batch over the whole
dispatches the trace holds, mean over the chips. Left out where under 90
% of the scoped programs' op time maps to a scope
(span_reduce.device_ms). Moves train_img_per_s.
"""

import span_reduce


def read(run):
    return span_reduce.device_ms(run, "conv")
