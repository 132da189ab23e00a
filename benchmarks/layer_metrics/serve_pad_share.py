"""Serving, engine (bucketing): padded rows over all rows the device
scored in the window, ``pad_rows / (rows + pad_rows)`` summed over the
``serve_batch`` records, in percent. Moves serve_p95_ms.
"""


def read(run):
    batches = run.in_window("serve_batch")
    scored = sum(b["rows"] + b["pad_rows"] for b in batches)
    if not scored:
        return None
    return 100.0 * sum(b["pad_rows"] for b in batches) / scored
