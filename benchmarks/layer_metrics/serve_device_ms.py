"""Serving, engine: the median ``serve_batch.device_ms`` in the window,
staging's wait, the executable and the copy back of one micro-batch, in
ms. Source: the program's ``serve_batch`` records. Moves serve_p95_ms.
"""

from harness import median


def read(run):
    batches = run.in_window("serve_batch")
    if not batches:
        return None
    return median([b["device_ms"] for b in batches])
