"""Input pipeline: of the chunks of records imgrec handed out in the
window, the share its decode pool had finished before the producer
thread asked for them, in percent: ``decode_ahead_ready`` over
``decode_chunks``, summed over the window's ``pipeline`` records (one a
round). 100 means the decode is hidden beneath the rest of the
producer's cycle; near zero means the producer still waits for it
(``io_decode_ms`` is that wait). None where the program's records lack
the counters. Moves train_img_per_s.
"""


def read(run):
    recs = [r for r in run.in_window("pipeline") if "decode_chunks" in r]
    chunks = sum(r["decode_chunks"] for r in recs)
    if not chunks:
        return None
    return 100.0 * sum(r["decode_ahead_ready"] for r in recs) / chunks
