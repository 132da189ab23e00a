"""Telemetry subsystem: sinks, schema validation, per-step tracing,
stdout parity, and the CI smoke run (one tiny train with
``monitor = jsonl`` whose every record is schema-validated)."""

import json
import os
import re

import numpy as np
import pytest

from cxxnet_tpu.main import main
from cxxnet_tpu.monitor import (JsonlSink, LatencyHistogram, MemorySink,
                                Monitor, NullSink, config_hash,
                                create_monitor, set_global, warn_once)
from cxxnet_tpu.monitor.schema import (read_jsonl, validate_record,
                                       validate_records)
from tests.test_main import write_conf
from tests.test_trainer import synth_idx


@pytest.fixture
def conf(tmp_path):
    pimg, plab = synth_idx(str(tmp_path), n=300, name="tr")
    pimg2, plab2 = synth_idx(str(tmp_path), n=100, seed=5, name="te")
    return write_conf(tmp_path, pimg, plab, pimg2, plab2)


# -- unit: sinks and monitor core ---------------------------------------


def test_null_sink_is_disabled():
    mon = Monitor()
    assert not mon.enabled
    mon.emit("step", anything="goes")       # no-op, no error
    mon.close()


def test_memory_sink_records_and_clears():
    sink = MemorySink()
    mon = Monitor(sink)
    assert mon.enabled
    mon.emit("round_start", round=0)
    assert sink.records[0]["event"] == "round_start"
    assert sink.records[0]["round"] == 0
    assert sink.records[0]["t"] > 0
    sink.clear()
    assert sink.records == []


def test_line_prints_and_records(capsys):
    sink = MemorySink()
    Monitor(sink).line("hello parity")
    assert capsys.readouterr().out == "hello parity\n"
    assert len(sink.records) == 1
    assert sink.records[0]["event"] == "log"
    assert sink.records[0]["text"] == "hello parity"
    # over a null sink the line still prints (the parity channel) but
    # nothing is recorded
    Monitor().line("still prints")
    assert capsys.readouterr().out == "still prints\n"


def test_jsonl_sink_flush_and_close(tmp_path):
    p = str(tmp_path / "m.jsonl")
    sink = JsonlSink(p, flush_period=3600.0)   # never flush on time
    mon = Monitor(sink)
    mon.emit("round_start", round=1)
    mon.close()                                # close drains the buffer
    recs = read_jsonl(p)
    assert len(recs) == 1 and recs[0]["round"] == 1
    # flush_period=0 flushes every record; re-opening the same path
    # truncates (one file = one run: re-runs must not interleave, and
    # the monotonic-step schema check reads one run per file)
    sink = JsonlSink(p, flush_period=0.0)
    Monitor(sink).emit("round_start", round=2)
    recs = read_jsonl(p)                       # visible pre-close
    assert len(recs) == 1 and recs[0]["round"] == 2
    sink.close()


def test_jsonl_sink_rotation(tmp_path):
    """monitor_rotate_mb bounds the live file: crossing the limit
    atomically rotates to <path>.<n> at a record boundary and a fresh
    file continues the run — no record lost, none split across
    files."""
    p = str(tmp_path / "r.jsonl")
    # stale segments from a "previous run" must be cleared at init
    # (one file set = one run), not left to interleave two streams
    for n in (1, 2, 3):
        with open("%s.%d" % (p, n), "w") as f:
            f.write('{"event": "stale", "run": "previous"}\n')
    # ~0.0005 MB = 500 bytes: a few records per segment
    sink = JsonlSink(p, flush_period=0.0, rotate_mb=0.0005)
    mon = Monitor(sink)
    for i in range(40):
        mon.emit("round_start", round=i, pad="x" * 64)
    mon.close()
    assert sink.rotations >= 2
    segs = [str(tmp_path / ("r.jsonl.%d" % (n + 1)))
            for n in range(sink.rotations)]
    rounds = []
    for f in segs + [p]:
        recs = read_jsonl(f)             # every segment parses whole
        # rotated segments are never empty; the live file may be (the
        # last record can itself trigger the rotation)
        assert recs or f == p, "empty segment %s" % f
        rounds += [r["round"] for r in recs]
    assert rounds == list(range(40))     # nothing lost, order kept
    # no segment beyond this run's rotations survives (stale cleanup)
    assert not os.path.exists("%s.%d" % (p, sink.rotations + 1))
    # every rotated segment respects the bound (+ one record of slack:
    # rotation triggers on the write that crosses it)
    for f in segs:
        assert os.path.getsize(f) <= 500 + 200, f


def test_jsonl_sink_rotation_failure_warns_once_and_keeps_writing(
        tmp_path, capsys, monkeypatch):
    """A failed rotation (read-only dir, EXDEV quirk) must not take
    down the run it observes: one stderr warning, then the stream
    keeps appending unbounded to the current file."""
    p = str(tmp_path / "f.jsonl")
    sink = JsonlSink(p, flush_period=0.0, rotate_mb=0.0001)

    def boom(src, dst):
        raise OSError("no rotation today")

    monkeypatch.setattr(os, "replace", boom)
    mon = Monitor(sink)
    for i in range(30):
        mon.emit("round_start", round=i)
    mon.close()
    err = capsys.readouterr().err
    assert err.count("monitor_rotate_failed") == 1   # warned ONCE
    assert sink.rotations == 0
    recs = read_jsonl(p)                 # all records in the one file
    assert [r["round"] for r in recs] == list(range(30))


def test_create_monitor_rotate_key(tmp_path):
    m = create_monitor(
        [("monitor", "jsonl"),
         ("monitor_path", str(tmp_path / "x.jsonl")),
         ("monitor_rotate_mb", "2.5")], root=True)
    assert isinstance(m.sink, JsonlSink)
    assert m.sink.rotate_bytes == int(2.5e6)
    m.close()


def test_create_monitor_modes(tmp_path):
    assert not create_monitor([], root=True).enabled
    assert isinstance(
        create_monitor([("monitor", "none")], root=True).sink, NullSink)
    m = create_monitor(
        [("monitor", "jsonl"),
         ("monitor_path", str(tmp_path / "x.jsonl")),
         ("monitor_flush_period", "0")], root=True)
    assert m.enabled and isinstance(m.sink, JsonlSink)
    m.close()
    with pytest.raises(ValueError):
        create_monitor([("monitor", "bogus")], root=True)
    # non-root ranks are forced to a null sink (process-0 gating)
    assert not create_monitor([("monitor", "jsonl")], root=False).enabled


def test_warn_once_is_once(capsys):
    sink = MemorySink()
    mon = Monitor(sink)
    mon.warn_once("code_a", "first")
    mon.warn_once("code_a", "second")
    mon.warn_once("code_b", "other")
    warns = [r for r in sink.records if r["event"] == "warning"]
    assert [w["code"] for w in warns] == ["code_a", "code_b"]
    err = capsys.readouterr().err
    assert err.count("code_a") == 1 and err.count("code_b") == 1


def test_module_warn_once_routes_to_global_monitor(capsys):
    sink = MemorySink()
    mon = Monitor(sink)
    set_global(mon)
    try:
        warn_once("glob_code", "via global")
    finally:
        set_global(None)
    assert any(r["event"] == "warning" and r["code"] == "glob_code"
               for r in sink.records)


def test_latency_histogram():
    h = LatencyHistogram()
    for s in (0.0001, 0.0006, 0.010, 0.010, 5.0):
        h.observe(s)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["max_ms"] == pytest.approx(5000.0)
    assert snap["buckets"]["<=0.25ms"] == 1
    assert snap["buckets"]["<=16ms"] == 2
    assert snap["buckets"][">1024ms"] == 1
    assert sum(snap["buckets"].values()) == 5
    h.reset()
    assert h.snapshot()["count"] == 0


def test_config_hash_stable_and_order_sensitive():
    a = [("x", "1"), ("y", "2")]
    assert config_hash(a) == config_hash(list(a))
    assert config_hash(a) != config_hash([("y", "2"), ("x", "1")])


# -- unit: schema validation --------------------------------------------


def test_validate_record_catches_problems():
    assert validate_record({"t": 1.0}) != []
    assert validate_record({"event": "no_such", "t": 1.0}) != []
    errs = validate_record({"event": "round_start", "t": 1.0})
    assert any("round" in e for e in errs)
    errs = validate_record(
        {"event": "compile", "t": 1.0, "kind": "first",
         "signature": "s", "wall_ms": -3.0})
    assert any("non-negative" in e for e in errs)


def test_validate_records_monotonic_step():
    def step(i, rnd=0):
        return {"event": "step", "t": 1.0, "step": i, "round": rnd,
                "dispatch": "update", "n_batches": 1, "examples": 8,
                "wall_ms": 1.0, "data_wait_ms": 0.0,
                "examples_per_sec": 8.0, "update_counter": i,
                "lr": 0.1, "loss": 2.3, "compile": False}
    assert validate_records([step(1), step(2), step(3)]) == []
    with pytest.raises(ValueError, match="not monotonic"):
        validate_records([step(2), step(2)])
    with pytest.raises(ValueError, match="backwards"):
        validate_records([step(1, rnd=1), step(2, rnd=0)])
    errs = validate_records([step(2), step(1)], strict=False)
    assert len(errs) == 1


# -- the metric-fallback satellite --------------------------------------


def test_metric_allreduce_fallback_warns_once(monkeypatch, capsys):
    """A failing distributed metric reduction falls back to local
    values but emits ONE structured warning — the silent
    ``except Exception: pass`` is gone."""
    import jax

    import cxxnet_tpu.parallel as par
    from cxxnet_tpu.utils.metric import MetricError

    def boom(x):
        raise RuntimeError("DCN collective timed out")

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(par, "allreduce_host_sum", boom)
    sink = MemorySink()
    mon = Monitor(sink)
    set_global(mon)
    try:
        m = MetricError()
        m.add_eval(np.array([[0.9, 0.1]], np.float32),
                   np.array([[0.0]], np.float32))
        assert m.get() == 0.0                  # local value, not nan
        assert m.get() == 0.0                  # second reduction: no spam
    finally:
        set_global(None)
    warns = [r for r in sink.records if r["event"] == "warning"]
    assert len(warns) == 1
    assert warns[0]["code"] == "metric_allreduce_failed"
    assert "RuntimeError" in warns[0]["message"]
    assert capsys.readouterr().err.count("metric_allreduce_failed") == 1


def test_metric_allreduce_programming_error_propagates(monkeypatch):
    """Only environment/backend failures fall back; a TypeError (a
    bug) must raise, not hide behind local values."""
    import jax

    import cxxnet_tpu.parallel as par
    from cxxnet_tpu.utils.metric import MetricError

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(par, "allreduce_host_sum",
                        lambda x: (_ for _ in ()).throw(TypeError("bug")))
    m = MetricError()
    m.add_eval(np.array([[0.9, 0.1]], np.float32),
               np.array([[0.0]], np.float32))
    with pytest.raises(TypeError):
        m.get()


# -- trainer counters (the wrapper poll surface) ------------------------


def test_trainer_counters_and_round_rate():
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config
    t = NetTrainer(parse_config("""
netconfig = start
layer[0->1] = fullc:fc1
  nhidden = 8
layer[1->1] = softmax
netconfig = end
input_shape = 1,1,6
batch_size = 8
eta = 0.1
"""))
    t.init_model()
    assert t.counters_snapshot() == {
        "steps": 0, "examples": 0, "last_round_examples_per_sec": 0.0}
    rng = np.random.RandomState(0)
    b = DataBatch(data=rng.rand(8, 6).astype(np.float32),
                  label=rng.randint(0, 8, (8, 1)).astype(np.float32))
    t.start_round(0)
    t.update(b)
    t.update(b)
    pad = DataBatch(data=b.data, label=b.label, num_batch_padd=3)
    t.update(pad)                              # padding rows don't count
    c = t.counters_snapshot()
    assert c["steps"] == 3
    assert c["examples"] == 8 + 8 + 5
    assert c["last_round_examples_per_sec"] == 0.0   # round still open
    t.end_round()
    c = t.counters_snapshot()
    assert c["last_round_examples_per_sec"] > 0
    assert t.last_round_examples == 21
    # update_many is ONE dispatch (one step) covering K batches, but
    # counts every real row in the window
    t.start_round(1)
    t.update_many([b, b, b])
    assert t.counters_snapshot()["steps"] == 4
    assert t.counters_snapshot()["examples"] == 21 + 24


def test_trainer_step_records_and_compile_detection():
    """Monitored dispatches emit schema-valid step records with the
    wait/step split, and a shape change is caught as a recompile."""
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config
    t = NetTrainer(parse_config("""
netconfig = start
layer[0->1] = fullc:fc1
  nhidden = 8
layer[1->1] = softmax
netconfig = end
input_shape = 1,1,6
batch_size = 8
eta = 0.05
"""))
    t.init_model()
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    rng = np.random.RandomState(0)
    b = DataBatch(data=rng.rand(8, 6).astype(np.float32),
                  label=rng.randint(0, 8, (8, 1)).astype(np.float32))
    t.start_round(0)
    t.note_data_wait(0.25)
    t.update(b)
    t.update(b)
    pad = DataBatch(data=b.data, label=b.label, num_batch_padd=2)
    t.update(pad)                    # masked variant: a recompile
    validate_records(sink.records)
    steps = [r for r in sink.records if r["event"] == "step"]
    compiles = [r for r in sink.records if r["event"] == "compile"]
    assert [s["step"] for s in steps] == [1, 2, 3]
    assert [c["kind"] for c in compiles] == ["first", "recompile"]
    assert steps[0]["compile"] and not steps[1]["compile"]
    assert steps[2]["compile"]
    # the loop-reported iterator wait rides on the NEXT record only
    assert steps[0]["data_wait_ms"] == pytest.approx(250.0)
    assert steps[1]["data_wait_ms"] == 0.0
    assert steps[0]["examples"] == 8 and steps[2]["examples"] == 6
    assert steps[0]["lr"] == pytest.approx(0.05)
    assert all(s["wall_ms"] > 0 for s in steps)


# -- the CI smoke test: tiny train round, every record validated --------


def test_smoke_jsonl_schema(conf, tmp_path, capsys):
    mpath = str(tmp_path / "mon.jsonl")
    assert main([conf, "num_round=2", "monitor=jsonl",
                 "monitor_path=" + mpath,
                 "monitor_flush_period=0"]) == 0
    recs = read_jsonl(mpath)
    validate_records(recs)                     # raises on any violation
    events = set(r["event"] for r in recs)
    assert {"run_start", "round_start", "step", "compile", "eval",
            "round_end", "memory", "run_end", "log"} <= events
    rs = [r for r in recs if r["event"] == "run_start"][0]
    assert rs["task"] == "train" and rs["mesh"] is not None
    assert rs["process_count"] == 1 and rs["device_count"] == 8
    steps = [r for r in recs if r["event"] == "step"]
    # 300 instances / batch 50 = 6 batches x 2 rounds
    assert sum(s["n_batches"] for s in steps) == 12
    assert sum(s["examples"] for s in steps) == 600
    # timing split fields present and sane on every step record
    for s in steps:
        assert s["wall_ms"] >= 0 and s["data_wait_ms"] >= 0
        assert s["examples_per_sec"] >= 0
    evs = [r for r in recs if r["event"] == "eval"]
    assert {e["name"] for e in evs} == {"train", "test"}
    assert all("error" in e["metrics"] for e in evs)
    ends = [r for r in recs if r["event"] == "round_end"]
    assert [e["round"] for e in ends] == [0, 1]
    assert all(e["examples"] == 300 for e in ends)
    mem = [r for r in recs if r["event"] == "memory"][0]
    assert isinstance(mem["available"], bool)
    assert len(mem["devices"]) == 8
    run_end = recs[-1]
    assert run_end["event"] == "run_end"
    assert run_end["steps"] == 12 and run_end["examples"] == 600
    # the eval record values match the parity stdout line
    out = capsys.readouterr().out
    m = re.search(r"\[1\]\ttrain-error:([0-9.]+)", out)
    assert m is not None
    tr = [e for e in evs if e["name"] == "train"][0]
    assert tr["metrics"]["error"] == pytest.approx(float(m.group(1)),
                                                   abs=1e-6)


def test_stdout_parity_across_monitor_modes(conf, tmp_path, capsys):
    """The parity criterion: monitor=none output is byte-identical to
    monitor=jsonl stdout, and monitor=stdout differs only by added
    JSON record lines. Volatile elapsed-seconds digits are normalized
    before comparing (wall time is not part of the format)."""
    def run(tag, *over):
        assert main([conf, "num_round=1",
                     "model_dir=" + str(tmp_path / tag)] +
                    list(over)) == 0
        return capsys.readouterr().out

    def norm(out):
        return re.sub(r"\d+ sec", "N sec", out)

    base = run("m0")
    jsonl = run("m1", "monitor=jsonl",
                "monitor_path=" + str(tmp_path / "p.jsonl"))
    assert norm(jsonl) == norm(base)
    sout = run("m2", "monitor=stdout")
    text_lines = [l for l in sout.splitlines()
                  if not l.startswith("{")]
    assert norm("\n".join(text_lines) + "\n") == norm(base)
    # and the JSON lines really are the structured stream
    json_recs = [json.loads(l) for l in sout.splitlines()
                 if l.startswith("{")]
    assert any(r["event"] == "step" for r in json_recs)
    validate_records(json_recs)


def test_test_io_task_emits_record(conf, tmp_path, capsys):
    mpath = str(tmp_path / "io.jsonl")
    assert main([conf, "test_io=1", "num_round=1", "monitor=jsonl",
                 "monitor_path=" + mpath]) == 0
    out = capsys.readouterr().out
    assert "test_io:" in out                   # parity line unchanged
    recs = read_jsonl(mpath)
    validate_records(recs)
    tio = [r for r in recs if r["event"] == "test_io"]
    assert len(tio) == 1 and tio[0]["instances"] == 300


def test_pred_task_emits_records(conf, tmp_path, capsys):
    assert main([conf, "num_round=1"]) == 0
    capsys.readouterr()
    model = str(tmp_path / "models" / "0001.model.npz")
    mpath = str(tmp_path / "pred.jsonl")
    assert main([conf, "task=pred", "model_in=" + model,
                 "pred=" + str(tmp_path / "pred.txt"),
                 "monitor=jsonl", "monitor_path=" + mpath]) == 0
    assert "finished prediction" in capsys.readouterr().out
    recs = read_jsonl(mpath)
    validate_records(recs)
    assert [r["task"] for r in recs if r["event"] == "run_start"] \
        == ["pred"]
    te = [r for r in recs if r["event"] == "task_end"]
    assert te[0]["task"] == "pred" and te[0]["rows"] == 300


def test_io_wait_histogram_with_threadbuffer(conf, tmp_path):
    """A threadbuffer train run records the batch-fetch latency
    histogram at round boundaries."""
    mpath = str(tmp_path / "tb.jsonl")
    # splice a threadbuffer stage into the train iterator chain
    with open(conf) as f:
        text = f.read()
    text = text.replace("iter = end",
                        "iter = threadbuffer\niter = end", 1)
    conf2 = str(tmp_path / "tb.conf")
    with open(conf2, "w") as f:
        f.write(text)
    assert main([conf2, "num_round=2", "monitor=jsonl",
                 "monitor_path=" + mpath,
                 "model_dir=" + str(tmp_path / "mtb")]) == 0
    recs = read_jsonl(mpath)
    validate_records(recs)
    waits = [r for r in recs if r["event"] == "io_wait"]
    assert [w["round"] for w in waits] == [0, 1]
    # exactly the delivered batches: the end-of-epoch sentinel wait is
    # NOT a batch fetch and must not be observed
    assert all(w["count"] == 6 for w in waits)
    assert all(sum(w["buckets"].values()) == w["count"]
               for w in waits)


def test_monitor_trace_window(tmp_path):
    """monitor_trace_dir captures a jax.profiler trace over the
    configured round window (or degrades to a warning record if the
    profiler backend refuses)."""
    sink = MemorySink()
    mon = Monitor(sink, trace_dir=str(tmp_path / "trace"),
                  trace_begin=1, trace_end=1)
    mon.maybe_start_trace(0)                   # outside window: no-op
    assert not mon._tracing
    mon.maybe_start_trace(1)
    mon.maybe_stop_trace(1)
    mon.close()
    events = [r["event"] for r in sink.records]
    assert ("trace_start" in events and "trace_stop" in events) \
        or any(r["event"] == "warning" for r in sink.records)
