"""Self-validating bench capture (bench.capture / bench.compare_models).

A multi-second host stall inside bench.py's single timed window once
corrupted a headline (VERDICT r4): 712.7 img/s went on record where
the other captures of that round read ~20k. These tests prove the r5
capture logic turns that failure mode into a retried measurement or
an explicit ``suspect`` flag — never a silent bad number — and that
the --compare mode flags only deltas outside recorded spread.
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
import bench


def _fake_windows(dts):
    """Test double: a window_fn replaying a fixed dt sequence."""
    it = iter(dts)
    return lambda: next(it)


def test_stable_windows_no_retry():
    best, dts, suspect = bench.capture(_fake_windows([1.0, 1.05, 99.0]))
    assert best == 1.0
    assert dts == [1.0, 1.05]          # third window never consumed
    assert not suspect


def test_single_stall_retried_and_recovered():
    # a 10x stall in the FIRST window (the r4 failure): retry breaks
    # the tie, the steady-state number wins, nothing is flagged
    best, dts, suspect = bench.capture(_fake_windows([10.0, 1.0, 1.02]))
    assert best == 1.0
    assert len(dts) == 3
    assert not suspect
    # and the recorded error bar comes from the agreeing pair — the
    # discarded stall window must not inflate the --compare tolerance
    # (which would mask real regressions next round)
    assert bench.agreeing_spread(dts) == 1.02


def test_compare_rejects_corrupt_record(tmp_path):
    # a failed round writes "parsed": null; --compare must fail fast
    # BEFORE the minutes-long sweep, not traceback after it
    f = tmp_path / "BENCH_bad.json"
    f.write_text(json.dumps({"rc": 1, "parsed": None}))
    p = subprocess.run(
        [sys.executable, "bench.py", "--compare", str(f)],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2                    # argparse error exit
    assert "no usable bench record" in p.stderr


def test_persistent_disagreement_flagged_suspect():
    # two of three windows stalled: no trustworthy pair exists, so the
    # capture must self-declare suspect rather than publish quietly
    best, dts, suspect = bench.capture(_fake_windows([10.0, 1.0, 9.5]))
    assert best == 1.0
    assert suspect


def test_injected_sleep_stall_is_retried():
    # the VERDICT-prescribed form: a real sleep injected into one
    # window of a real timed closure produces a retried capture
    calls = {"n": 0}

    def window():
        calls["n"] += 1
        start = time.perf_counter()
        if calls["n"] == 1:
            time.sleep(0.30)           # 10x stall
        time.sleep(0.03)
        return time.perf_counter() - start

    best, dts, suspect = bench.capture(window)
    assert calls["n"] == 3             # disagreement -> retry
    assert best < 0.1                  # steady-state, not the stall
    assert not suspect


def test_compare_flags_only_beyond_spread():
    old = {"alexnet": {"value": 20000.0, "spread": 1.1},
           "inception_bn": 5280.0,     # r4-era bare-float form
           "kaiming": 9500.0}
    new = {"alexnet": {"value": 9000.0, "spread": 1.05},   # real 2.2x drop
           "inception_bn": {"value": 5100.0, "spread": 1.08},  # within noise
           "kaiming": {"value": 12000.0, "spread": 1.02}}  # real gain
    out = bench.compare_models(old, new)
    assert out["alexnet"]["verdict"] == "regression"
    assert out["inception_bn"]["verdict"] == "ok"
    assert out["kaiming"]["verdict"] == "improvement"


def test_compare_suspect_side_never_verdicts():
    out = bench.compare_models(
        {"alexnet": {"value": 20000.0, "suspect": True}},
        {"alexnet": {"value": 700.0, "spread": 1.0}})
    assert out["alexnet"]["verdict"] == "suspect"


def test_compare_respects_recorded_spread_over_floor():
    # a 30% delta with a recorded 1.4x spread is noise, not regression
    out = bench.compare_models(
        {"m": {"value": 1000.0, "spread": 1.4}},
        {"m": {"value": 750.0, "spread": 1.05}})
    assert out["m"]["verdict"] == "ok"


def test_bench_cli_emits_capture_fields():
    """One tiny real bench run end-to-end: the JSON line must carry
    dt list, spread, and suspect so BENCH_r* records error bars."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench.py", "--model", "alexnet",
         "--steps", "1", "--batch", "4"],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env)
    assert p.returncode == 0, p.stderr
    line = [l for l in p.stdout.splitlines() if l.startswith("{")][-1]
    rec = json.loads(line)
    assert len(rec["dt"]) in (2, 3)
    assert rec["spread"] >= 1.0
    assert isinstance(rec["suspect"], bool)


def test_compare_rows_carry_dtype_annotation():
    out = bench.compare_models(
        {"m": {"value": 1000.0, "dtype": "bfloat16"}},
        {"m": {"value": 990.0, "dtype": "int8"}})
    assert out["m"]["old_dtype"] == "bfloat16"
    assert out["m"]["new_dtype"] == "int8"
    # untagged (pre-dtype) records annotate as unknown, not a crash
    out = bench.compare_models({"m": 1000.0},
                               {"m": {"value": 990.0}})
    assert out["m"]["old_dtype"] == "unknown"
    assert out["m"]["new_dtype"] == "unknown"


def test_dtype_mismatches_helper():
    old = {"a": {"value": 1.0, "dtype": "float32"},
           "b": {"value": 1.0, "dtype": "bfloat16"},
           "c": {"value": 1.0}}                  # untagged: comparable
    assert bench.dtype_mismatches(old, "bfloat16") == [("a", "float32")]
    assert bench.dtype_mismatches(old, "float32") == [("b", "bfloat16")]


def test_compare_refuses_cross_dtype_without_flag(tmp_path):
    """--compare against a record measured in another compute dtype
    exits 2 BEFORE the sweep unless --allow-dtype-mismatch is passed
    (img/s across dtypes is not a regression signal)."""
    f = tmp_path / "BENCH_f32.json"
    f.write_text(json.dumps({
        "models": {"alexnet": {"value": 9000.0, "dtype": "float32"}}}))
    p = subprocess.run(
        [sys.executable, "bench.py", "--compare", str(f)],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2, (p.returncode, p.stderr[-500:])
    assert "cannot compare across dtypes" in p.stderr
    assert "--allow-dtype-mismatch" in p.stderr
