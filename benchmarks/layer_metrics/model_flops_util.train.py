"""Layers / XLA fusions: images per second of the window's dispatches
times ``model_info.train_flops_per_example`` over chips times the chip's
published bf16 peak (peaks.json), in percent. The rate is the ``step``
records' own: examples over (``wall_ms`` + ``data_wait_ms``), which is
``train_img_per_s`` without the seconds the profiler's start and stop
take from a traced window. The FLOP count is the program's analytic one:
2 FLOP a multiply-accumulate, training = 3 x forward, recomputation not
counted. An end-to-end utilization, named as one: not a kernel's
roofline share, and it says nothing about idle time. Moves
train_img_per_s.
"""


def read(run):
    info = run.one_record("model_info")
    steps = run.in_window("step")
    seconds = sum(s["wall_ms"] + s["data_wait_ms"] for s in steps) / 1e3
    if info is None or seconds <= 0:
        return None
    rate = sum(s["examples"] for s in steps) / seconds
    peak = run.chips * run.peak("bf16_flops_per_s")
    return 100.0 * rate * info["train_flops_per_example"] / peak
