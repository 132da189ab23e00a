"""trace_reduce.py: busy-interval union, category sums, idle share and
gap naming on hand-made intervals, then on a small trace recorded on the
chip (data/, by record_trace.py)."""

import glob
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlapping_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == \
        [(0, 4), (5, 7), (10, 11)]
    assert tr.total(tr.union([(0, 10), (2, 3)])) == 10


def test_gaps_are_what_the_span_holds_between_busy_intervals():
    assert tr.gaps([(2, 4), (6, 7)], (0, 10)) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps([(0, 10)], (0, 10)) == []


@pytest.mark.parametrize("name,want", [
    ("%convolution.5 = bf16[128,56,56,64] convolution(%a, %b)", "convolution"),
    ("%fusion.12 = f32[8] fusion(%p), kind=kLoop, calls=%f", "fusion:loop"),
    ("%fusion.3 = bf16[8] fusion(%p), kind=kOutput, calls=%fused_computation",
     "conv/dot fusion"),
    ("%while.2 = (s32[], f32[8]) while(%tuple), condition=%c, body=%b",
     "while"),
    ("fusion.7", "fusion"),
    ("all-reduce.1", "collective"),
    ("all-reduce-start.2", "collective"),
    ("select-and-scatter.4", "select-and-scatter"),
    ("reduce-window.9", "reduce-window"),
    ("copy.11", "copy/format"),
    ("dynamic-update-slice.2", "copy/format"),
    ("custom-call.3", "custom-call"),
])
def test_categorize(name, want):
    assert tr.categorize(name) == want


def test_reduce_planes_on_two_chips_by_hand():
    ms = 1_000_000
    chip0 = [(0, 2 * ms, "convolution.1"), (1 * ms, 3 * ms, "fusion.2"),
             (6 * ms, 8 * ms, "all-reduce.3")]
    chip1 = [(0, 4 * ms, "convolution.1"), (6 * ms, 8 * ms, "all-reduce.3"),
             (0, 8 * ms, "while.9")]      # encloses the rest: not counted
    host = [(3 * ms, 6 * ms, "bench.wait_batch"), (0, 8 * ms, "main loop")]
    s = tr.reduce_planes([chip0, chip1], host, span_s=0.010)
    # chip0 busy 3 + 2 ms, chip1 all 8 ms under its while: mean 6.5 of 10
    assert s.chips == 2 and s.ops == 6 and "while" not in s.categories
    assert s.busy_s == pytest.approx(0.0065)
    assert s.window_s == pytest.approx(0.010)
    assert s.idle_share == pytest.approx(0.35)
    assert s.categories["convolution"] == pytest.approx(0.003)
    assert s.categories["collective"] == pytest.approx(0.002)
    # chip0's inner gap (3..6 ms) is the benchmark's own span; the span's
    # edges (1 ms each side of the ops) lie outside any host span
    assert s.idle_by_host["bench.wait_batch"] == pytest.approx(0.003)
    assert s.idle_by_host["unattributed"] == pytest.approx(0.002)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "convolution"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_device_op_reduces_to_nothing():
    assert tr.reduce_planes([], [], 1.0) is None
    assert tr.reduce_dir(None, 1, 1.0) is None


def test_the_recorded_chip_trace():
    """Four dispatches of one conv + matmul program with 20 ms sleeps
    between them, recorded on a TPU v5 lite by record_trace.py."""
    if not glob.glob(os.path.join(DATA, "**", "*.xplane.pb"), recursive=True):
        pytest.skip("no recorded trace under benchmarks/tests/data")
    device_ops, host = tr.read_xplane(tr.find_xplane(DATA))
    assert len(device_ops) == 1 and device_ops[0]
    names = {n for _, _, n in host}
    assert {"bench.dispatch", "bench.sleep"} <= names
    s = tr.reduce_planes(device_ops, host, span_s=0.0)
    assert 0 < s.busy_s < s.window_s
    # the device sleeps at least the three 20 ms pauses between dispatches
    assert s.window_s - s.busy_s >= 0.055
    assert s.idle_by_host.get("bench.sleep", 0.0) >= 0.055
    assert s.categories.get("conv/dot fusion", 0.0) > 0
    assert sum(s.categories.values()) == pytest.approx(s.busy_s, rel=0.05)
