"""Input pipeline: producer-thread time reading records and decoding
them (the program's ``io.read`` + ``io.decode`` spans, one a chunk)
inside the window, over the batches the window's dispatches trained, in
ms a batch. With ``io_assemble_ms``, ``io_h2d_ms`` and the queue-full
time it accounts for the producer's cycle. Moves train_img_per_s.
"""

import span_reduce


def read(run):
    return span_reduce.per_batch_ms(run, ("io.read", "io.decode"))
