"""The ``io_decode_ahead_share`` reader on ``pipeline`` records as a
traced run of ``alexnet.train_pipeline`` wrote them on the chip
(data/pipeline_records.jsonl: the measured call's, one a round, with its
``round_start`` and ``step`` records' times, which place the window).
Run by hand, like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import Run  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "pipeline_records.jsonl")
NEW = ("decode_chunks", "decode_ahead_ready", "decode_busy_ms",
       "decode_pool", "decode_cpus", "cpu_count")


def _reader():
    spec = importlib.util.spec_from_file_location(
        "reader_io_decode_ahead_share",
        os.path.join(BENCH, "layer_metrics", "io_decode_ahead_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(records, window):
    run = Run(cell={"name": "t", "chips": 1}, config={}, traffic={}, seed=1,
              seconds=1.0, trace=True, out_dir="", root="", rehearse=True,
              devices=[])
    run.records, run.window = records, window
    return run


def _recorded():
    with open(DATA) as f:
        recs = [json.loads(ln) for ln in f]
    # the driver's window: the first round_start after round 0 to the
    # last step (benchmarks/drivers/train.py: run_pipeline)
    t0 = min(r["t"] for r in recs
             if r["event"] == "round_start" and r["round"] > 0)
    t1 = max(r["t"] for r in recs if r["event"] == "step")
    return recs, (t0, t1)


def test_the_recorded_lines_hold_to_the_schema():
    from cxxnet_tpu.monitor.schema import OPTIONAL, validate_record
    recs, _ = _recorded()
    pipes = [r for r in recs if r["event"] == "pipeline"]
    assert len(pipes) >= 3
    assert not [e for r in pipes for e in validate_record(r)]
    assert set(NEW) == set(OPTIONAL["pipeline"])
    for r in pipes:
        assert all(k in r for k in NEW)
        assert 0 <= r["decode_ahead_ready"] <= r["decode_chunks"] == 16
        assert r["decode_pool"] >= 4 and r["decode_cpus"] <= r["cpu_count"]


def test_the_share_is_ready_over_chunks_of_the_windows_rounds():
    recs, window = _recorded()
    pipes = [r for r in recs if r["event"] == "pipeline"]
    inside = [r for r in pipes if window[0] < r["t"] <= window[1]]
    # round 0 and the last round's record lie outside the window
    assert 0 < len(inside) == len(pipes) - 2
    got = _reader().read(_run(recs, window))
    assert got == pytest.approx(
        100.0 * sum(r["decode_ahead_ready"] for r in inside)
        / sum(r["decode_chunks"] for r in inside))
    assert 0.0 < got <= 100.0


def test_nothing_to_read_leaves_the_metric_out():
    recs, window = _recorded()
    reader = _reader()
    # the parent commit's records: no counters
    old = [{k: v for k, v in r.items() if k not in NEW} for r in recs]
    assert reader.read(_run(old, window)) is None
    # no pipeline record at all, and rounds that handed out no chunk
    assert reader.read(_run([], window)) is None
    idle = [dict(r, decode_chunks=0, decode_ahead_ready=0) for r in recs]
    assert reader.read(_run(idle, window)) is None
