"""Layers / XLA fusions, the expert axis: the busiest chip's received
rows over the mean chip's, the worst expert layer of a dispatch, median
over the window's dispatches: from the program's ``moe`` records (one a
dispatch, of its last step; ``exchange_max_over_mean``). 1.0 is even
routing over the chips; the busiest chip's experts set the pace of the
exchange's every pass. None where the program writes no such counter (no
expert axis). Moves train_img_per_s.
"""

from harness import median


def read(run):
    spread = [r["exchange_max_over_mean"] for r in run.in_window("moe")
              if "exchange_max_over_mean" in r]
    return median(spread) if spread else None
