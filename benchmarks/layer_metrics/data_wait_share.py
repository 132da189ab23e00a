"""Input pipeline: the share of the window's dispatch cycles that the
task runner's loop spent waiting for the iterator. Sum of
``step.data_wait_ms`` over the sum of ``data_wait_ms + wall_ms``, in
percent. Source: the program's ``step`` records. Moves train_img_per_s.
"""


def read(run):
    steps = run.in_window("step")
    cycle = sum(s["data_wait_ms"] + s["wall_ms"] for s in steps)
    if not steps or cycle <= 0:
        return None
    return 100.0 * sum(s["data_wait_ms"] for s in steps) / cycle
