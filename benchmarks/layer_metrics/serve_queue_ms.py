"""Serving, batcher: the median ``serve_batch.queue_ms`` in the window,
the wait of a micro-batch's oldest request until the batch formed, in
ms. Source: the program's ``serve_batch`` records. Moves serve_p95_ms.
"""

from harness import median


def read(run):
    batches = run.in_window("serve_batch")
    if not batches:
        return None
    return median([b["queue_ms"] for b in batches])
