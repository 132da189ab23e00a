"""Trainer / dispatch: seconds in ``NetTrainer.precompile`` (the
program's ``setup.precompile`` spans) among the run's records. They lie
in set-up, before the window. Moves setup_s.
"""

import span_reduce


def read(run):
    ms = span_reduce.total_ms(run, ("setup.precompile",), window=False)
    return None if ms is None else ms / 1e3
