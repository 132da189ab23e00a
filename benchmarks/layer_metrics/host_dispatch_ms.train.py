"""Trainer / dispatch: the median over the window's dispatches of the
program's ``trainer.stage`` + ``trainer.enqueue`` spans, the host work
between the call and the device having a program to run, in ms a
dispatch. Moves train_img_per_s.
"""

import span_reduce


def read(run):
    return span_reduce.host_dispatch_ms(run)
