"""Layers / XLA fusions: the busiest held expert's picks over the mean
held expert's, the worst expert layer of a dispatch, median over the
window's dispatches: from the program's ``moe`` records (one a dispatch,
of its last step). 1.0 is even routing; the expert loop's trip count and
its padding grow with it. None where the program writes no such record.
Moves train_img_per_s.
"""

from harness import median


def read(run):
    recs = run.in_window("moe")
    if not recs:
        return None
    return median([r["load_max_over_mean"] for r in recs])
