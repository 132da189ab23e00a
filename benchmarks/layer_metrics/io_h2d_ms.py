"""Input pipeline: producer-thread time handing a batch to the device
(the program's ``io.h2d_issue`` spans, where the host linearises the
float32 batch, and ``io.h2d_wait``, blocked until the copy is done)
inside the window, over the batches trained, in ms a batch. Moves
train_img_per_s.
"""

import span_reduce


def read(run):
    return span_reduce.per_batch_ms(run, ("io.h2d_issue", "io.h2d_wait"))
