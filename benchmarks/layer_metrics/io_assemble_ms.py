"""Input pipeline: producer-thread time augmenting and assembling a
batch (the program's ``io.augment`` spans, and ``io.assemble``: crop and
mirror into the ring buffer, float32 mean and scale) inside the window,
over the batches trained, in ms a batch. Moves train_img_per_s.
"""

import span_reduce


def read(run):
    return span_reduce.per_batch_ms(run, ("io.augment", "io.assemble"))
