"""Input pipeline: the share of the window its producer thread spent
blocked on the consumer, in percent: on a full prefetch queue (the
program's ``io.queue_full`` spans) or, an epoch done, until the next one
is asked for (``io.epoch_wait``). Higher means the pipeline runs ahead
of the chip; near zero means the chip waits for it. With
``io_decode_ms``, ``io_assemble_ms`` and ``io_h2d_ms`` it accounts for
the producer's cycle. Moves train_img_per_s.
"""

import span_reduce


def read(run):
    ms = span_reduce.total_ms(run, ("io.queue_full", "io.epoch_wait"))
    if ms is None or run.window_s <= 0:
        return None
    return 100.0 * ms / 1e3 / run.window_s
