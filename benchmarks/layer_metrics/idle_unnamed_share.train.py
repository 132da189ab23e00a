"""Device: the share of the first chip's idle time in the traced
interval (span_reduce.traced_span) that no span of the program covers,
in percent. Idle gaps are laid on
the program's spans through the trace's ``profile_start_time`` and
named by the deepest span open in them; while the main thread waits for
a batch, by what the producer thread was in. One ``idle_by_span`` line
before the result line gives the seconds by span, and where the trace
holds host events how far each span's mirrored annotation lies from the
span's own stamp. Moves train_img_per_s.
"""

import span_reduce


def read(run):
    rep = span_reduce.idle_report(run)
    if rep is None:
        return None
    span_reduce.phase("idle_by_span", **rep)
    return 100.0 * rep["unnamed_share"]
