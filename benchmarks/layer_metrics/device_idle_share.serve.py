"""Device: one minus (union of the device-op intervals over the traced
span), mean over the chips, in percent, from a profiler trace of a few
seconds inside the window (trace_reduce.py). Moves serve_p95_ms.
"""


def read(run):
    if run.trace_summary is None:
        return None
    return 100.0 * run.trace_summary.idle_share
