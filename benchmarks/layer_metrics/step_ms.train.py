"""Trainer / dispatch: the median over the window's dispatches of
``step.wall_ms / step.n_batches``, the loss-blocked time of one batch's
update, in ms. Source: the program's ``step`` records. Moves
train_img_per_s.
"""

from harness import median


def read(run):
    steps = run.in_window("step")
    if not steps:
        return None
    return median([s["wall_ms"] / s["n_batches"] for s in steps])
