"""span_reduce.py and the readers built on it: first on the small trace
recorded on the chip (data/tiny_trace) with synthetic ``span`` records
made from its ``bench.sleep`` intervals through ``profile_start_time``,
then a rehearsal of both train mixes' tiny twins, where the program
writes the records itself. Run by hand, like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import span_reduce as sr  # noqa: E402
import trace_reduce as tr  # noqa: E402
from harness import Run  # noqa: E402
from test_rehearsal import copy, last_line, run_cell  # noqa: E402,F401

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_trace")
MS = 1_000_000


def make_run(records, window=(0.0, 4e9)):
    run = Run(cell={"name": "t", "chips": 1}, config={}, traffic={}, seed=1,
              seconds=1.0, trace=True, out_dir="", root="", rehearse=True,
              devices=[])
    run.trace_dir = DATA
    run.window = window
    run.records = records
    run.trace_summary = tr.reduce_dir(DATA, 1, 0.0)
    return run


def span(name, t0_ns, dur_ns, tid=1, **attrs):
    return {"event": "span", "t": (t0_ns + dur_ns) / 1e9, "name": name,
            "t0_ns": int(t0_ns), "dur_ns": int(dur_ns), "tid": tid, "id": 1,
            "parent": 0, "attrs": attrs}


# -- by hand -----------------------------------------------------------------


def test_deepest_names_each_stretch_by_the_innermost_span():
    flat = sr.deepest([(0, 10, "round"), (2, 5, "wait"), (3, 4, "inner"),
                       (6, 8, "dispatch")])
    assert flat == [(0, 2, "round"), (2, 3, "wait"), (3, 4, "inner"),
                    (4, 5, "wait"), (5, 6, "round"), (6, 8, "dispatch"),
                    (8, 10, "round")]


def test_idle_goes_to_the_producer_while_the_main_thread_waits():
    main = sr.deepest([(0, 100, "train.round"), (10, 60, "train.data_wait"),
                       (60, 90, "train.dispatch")])
    producer = sr.deepest([(5, 30, "io.decode"), (30, 40, "io.assemble"),
                           (50, 55, "io.h2d_issue")])
    got = sr.idle_by_span([(0, 70), (95, 120)], main, [producer])
    assert got == {
        "train.round": 10 + 5,           # 0..10 and 95..100
        "io.decode": 20, "io.assemble": 10, "io.h2d_issue": 5,
        "train.data_wait": 15,           # 40..50, 55..60: nobody else's
        "train.dispatch": 10,            # 60..70
        sr.UNNAMED: 20}                  # 100..120: no span at all


@pytest.mark.parametrize("path,group", [
    ("jvp(conv.conv1)", "conv"), ("transpose(jvp(conv.conv1))", "conv"),
    ("conv.conv1", "conv"), ("transpose(jvp(fullc.fc6))", "fullc"),
    ("jvp(batch_norm.bn_2a)", "batch_norm"), ("update", "update"),
    ("transpose(jvp(max_pooling.pool1))", "pool_bwd"),
    ("jvp(avg_pooling.layer7)", "pool_fwd"),
    ("jvp(loss)", "other_scoped"), ("transpose(jvp(relu.r1))", "other_scoped"),
    ("jvp(ch_concat.c3)/jvp(conv.x)", "conv")])
def test_group_of(path, group):
    assert sr.group_of(path) == group


# -- on the recorded trace ---------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    if tr.find_xplane(DATA) is None:
        pytest.skip("no recorded trace under benchmarks/tests/data")
    return sr.read_trace(tr.find_xplane(DATA))


def sleeps_as_spans(trace, name="train.data_wait"):
    return [span(name, trace.start_ns + s, e - s) for s, e, n in trace.host
            if n == "bench.sleep"]


def test_the_trace_carries_its_start_on_the_epoch_clock(tiny):
    # 2026-09-28 19:12:49 UTC, the trace directory's own name
    assert tiny.start_ns == 1790622769139141968
    assert [m[2] for m in tiny.modules[0]] == ["jit_work"] * 4


def test_idle_unnamed_share_reads_the_known_value(tiny):
    """Four dispatches with 20 ms sleeps between: the sleeps, turned into
    the program's spans, name all idle time but the dispatch overhead."""
    run = make_run(sleeps_as_spans(tiny))
    rep = sr.idle_report(run)
    assert rep["idle_s"] == pytest.approx(0.063550, abs=1e-5)
    # 60.893 ms of the 63.551 ms idle lie under a sleep (summed by hand
    # from the trace's host events against its device gaps)
    assert rep["by_span_s"]["train.data_wait"] == pytest.approx(0.060893,
                                                                abs=1e-6)
    assert rep["unnamed_share"] == pytest.approx(0.041818, abs=1e-5)
    # the same gaps with the spans elsewhere: nothing is named
    far = make_run([span("train.data_wait", tiny.start_ns - 10 ** 12, MS)])
    assert sr.idle_report(far)["unnamed_share"] == 1.0
    # and a program that records no span at all gives no metric
    assert sr.idle_report(make_run([])) is None


def test_the_traced_interval_is_the_hosts_span_from_the_first_event():
    ops = [[(50 * MS, 60 * MS, "%fusion.1 = f32[4] fusion(%p)"),
            (90 * MS, 95 * MS, "%fusion.1 = f32[4] fusion(%p)")]]
    level0 = sr.Trace(ops, [[]], [], 10 ** 18)
    level2 = sr.Trace(ops, [[]], [(45 * MS, 46 * MS, "bench.dispatch")],
                      10 ** 18)
    # no span measured: the ops' own extent
    assert sr.traced_span(level0, 0.0) == (50 * MS, 95 * MS)
    # host level 0: from profile_start_time; level 2: from the first event
    assert sr.traced_span(level0, 0.2) == (0.0, 200 * MS)
    assert sr.traced_span(level2, 0.2) == (45 * MS, 245 * MS)
    # never narrower than the ops
    assert sr.traced_span(level2, 0.01) == (45 * MS, 95 * MS)


def test_the_mirrored_annotation_lies_on_the_span_clock(tiny):
    """A span record stamped with the annotation's own start, as the
    program's recorder stamps it, is found at offset zero."""
    recs = [span("trainer.stage", tiny.start_ns + s, e - s)
            for s, e, n in tiny.host if n == "bench.dispatch"]
    trace_like = sr.Trace(tiny.device_ops, tiny.modules,
                          [(s, e, "trainer.stage") for s, e, n in tiny.host
                           if n == "bench.dispatch"],
                          tiny.start_ns)
    got = sr.clock_check(make_run(recs), trace_like)
    assert got["annotations"] == 4 and got["max_us"] < 1.0


def test_device_time_by_scope_on_the_recorded_trace(tiny):
    """The trace's program is one conv fusion, a copy, a reshape and a
    matmul fusion: map the two fusions and the rest stays unscoped."""
    scopes = {"event": "program_scopes", "t": 1.0, "program": "update",
              "module": "jit_work", "fusions": 2, "fusions_mapped": 2,
              "wall_ms": 1.0,
              "scopes": {"fusion": "transpose(jvp(conv.c1))",
                         "fusion.7": "jvp(fullc.fc)"}}
    step = {"event": "step", "t": 1.0, "n_batches": 2}
    run = make_run([scopes, step])
    rep = sr.device_report(run)
    busy_ms = run.trace_summary.busy_s * 1e3
    ms = rep["ms_a_batch"]
    # four dispatches of two batches each; groups and the rest tile busy
    assert sum(ms.values()) * 8 == pytest.approx(busy_ms, rel=0.01)
    assert ms["conv"] * 8 == pytest.approx(0.4717, abs=2e-3)
    assert 0 < ms["fullc"] < ms["conv"]
    assert rep["coverage"] == pytest.approx(
        (ms["conv"] + ms["fullc"]) / sum(ms.values()))
    # one program in the trace: its own share is the trace's share
    assert rep["program_coverage"] == rep["coverage"]
    # 59 % mapped: under the guard, so no split is printed
    assert rep["coverage"] < 0.9 and sr.device_ms(run, "conv") is None
    scopes["scopes"].update({"copy.2": "jvp(conv.c1)",
                             "reshape.1": "jvp(flatten.f)"})
    assert sr.device_report(run)["coverage"] > 0.99
    assert sr.device_ms(run, "conv") > ms["conv"]
    # a stale cache: the record is there, its map is empty
    scopes["scopes"] = {}
    assert sr.device_report(run)["coverage"] == 0.0
    assert sr.device_ms(run, "conv") is None
    # no record at all (the parent commit): nothing to read
    assert sr.device_report(make_run([step])) is None


def test_a_dispatch_cut_by_the_trace_end_is_not_averaged_in(tiny):
    """The trace's end cuts the last dispatch short: its ops still count
    for the coverage, not for the ms a batch; and another program's ops
    (no scope map) lower the trace's coverage, not the program's."""
    ms = 1_000_000.0
    ops = [[(0, 4 * ms, "%fusion.1 = f32[4] fusion(%p), kind=kLoop"),
            (4 * ms, 5 * ms, "%copy.2 = f32[4] copy(%fusion.1)"),
            (10 * ms, 12 * ms, "%concatenate.9 = f32[8] concatenate(%a)"),
            (20 * ms, 22 * ms, "%fusion.1 = f32[4] fusion(%p), kind=kLoop")]]
    modules = [[(0, 5 * ms, "jit_step"), (10 * ms, 12 * ms, "jit_stack"),
                (20 * ms, 22 * ms, "jit_step")]]
    trace = sr.Trace(ops, modules, [], tiny.start_ns)
    run = make_run([{"event": "program_scopes", "t": 1.0, "module": "jit_step",
                     "wall_ms": 3.0, "scopes": {"fusion.1": "jvp(conv.c1)"}},
                    {"event": "step", "t": 1.0, "n_batches": 2}])
    rep = sr.device_report(run, trace)
    assert rep["ms_a_batch"] == {"conv": 2.0, "unscoped": 0.5}
    assert rep["whole_dispatches"] == 1
    assert rep["scopes_read_ms"] == {"jit_step": 3.0}
    assert rep["coverage"] == pytest.approx(6 / 9)
    assert rep["program_coverage"] == pytest.approx(6 / 7)


def test_span_sums_by_batch_and_dispatch():
    recs = [span("io.decode", 1e9, 30 * MS), span("io.read", 1e9, 10 * MS),
            span("io.decode", 9e9, 50 * MS),          # outside the window
            span("trainer.stage", 1e9, 2 * MS, step=1),
            span("trainer.enqueue", 1e9, 1 * MS, step=1),
            span("trainer.stage", 2e9, 6 * MS, step=2),
            span("setup.precompile", 1, 200 * MS),
            {"event": "step", "t": 1.5, "n_batches": 4},
            {"event": "step", "t": 2.5, "n_batches": 4}]
    run = make_run(recs, window=(0.5, 3.0))
    assert sr.per_batch_ms(run, ("io.read", "io.decode")) == 5.0
    assert sr.per_batch_ms(run, ("io.assemble",)) is None
    assert sr.host_dispatch_ms(run) == 4.5          # median of 3 and 6
    assert sr.total_ms(run, ("setup.precompile",), window=False) == 200.0
    assert sr.total_ms(run, ("setup.precompile",)) is None


# -- the program's own records, rehearsed ------------------------------------

SPAN_METRICS = {"host_dispatch_ms.train", "setup_precompile_s"}
PIPELINE_ONLY = {"io_decode_ms", "io_assemble_ms", "io_h2d_ms",
                 "io_queue_full_share"}


@pytest.mark.parametrize("mix", ["train_pipeline", "train_resident"])
def test_the_new_metrics_rehearse_on_both_mixes(copy, mix):
    """A CPU trace has no device plane, so the metrics that need device
    ops are left out of a rehearsal (and covered above on the recorded
    trace); every metric read from spans alone must be there."""
    proc = run_cell(copy, "tiny." + mix, "--trace", "1", "--rehearse")
    line = last_line(proc)
    assert line["correct"], line["why_incorrect"]
    names = {k[len("rehearsal."):] for k in line["metrics"]}
    want = SPAN_METRICS | (PIPELINE_ONLY if mix == "train_pipeline" else set())
    assert want <= names, sorted(want - names)
    assert not (PIPELINE_ONLY & names) or mix == "train_pipeline"
    for name in want:
        print(json.dumps({"rehearsal." + name:
                          line["metrics"]["rehearsal." + name]}))
        assert line["metrics"]["rehearsal." + name]["value"] >= 0.0
