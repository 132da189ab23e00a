"""Spans, layer scopes and the trace hook (monitor/spans.py): the
recorder by itself, the span names a monitored ``task = train`` writes
and the records they replace, the scopes in the compiled step, and the
profiler window starting once."""

import contextlib
import re
import threading

import numpy as np
import pytest

from cxxnet_tpu.monitor import (NULL_SPAN, MemorySink, Monitor, NullSink,
                                no_span)
from cxxnet_tpu.monitor.schema import read_jsonl, validate_records
from cxxnet_tpu.monitor.spans import (STEP_SCOPES, SpanRecorder, scope_map,
                                      scope_path)

SPAN_NAMES = {
    "setup.iterator", "setup.init_model", "setup.precompile",
    "setup.program_scopes",
    "train.round", "train.data_wait", "train.dispatch", "train.round_end",
    "trainer.stage", "trainer.enqueue", "trainer.loss_wait",
    "io.read", "io.decode", "io.augment", "io.assemble",
    "io.h2d_issue", "io.h2d_wait", "io.queue_full", "io.epoch_wait"}

NET = """
netconfig = start
layer[0->1] = conv:c1
  kernel_size = 3
  nchannel = 8
  pad = 1
layer[1->2] = batch_norm:bn1
layer[2->3] = relu
layer[3->4] = max_pooling:pool1
  kernel_size = 2
  stride = 2
layer[4->5] = flatten
layer[5->6] = fullc:fc1
  nhidden = 10
layer[6->6] = softmax
netconfig = end
input_shape = 3,8,8
batch_size = 16
eta = 0.1
momentum = 0.9
"""


# -- the recorder ------------------------------------------------------------


def test_spans_nest_by_thread_with_parent_and_thread_ids():
    mon = Monitor(MemorySink())
    seen = {}

    def producer():
        with mon.span("io.decode", n=4) as outer:
            with mon.span("io.inner") as inner:
                pass
        seen["producer"] = (outer, inner, threading.get_ident())

    with mon.span("train.round", round=3) as rnd:
        th = threading.Thread(target=producer)
        th.start()
        with mon.span("train.dispatch", round=3) as disp:
            pass
        th.join(10)
        assert not th.is_alive()
    outer, inner, tid = seen["producer"]
    assert rnd.parent == 0 and disp.parent == rnd.id
    # the other thread's spans hang off its own stack, not the main one's
    assert outer.parent == 0 and inner.parent == outer.id
    assert outer.tid == inner.tid == tid != rnd.tid == disp.tid
    assert len({rnd.id, disp.id, outer.id, inner.id}) == 4
    assert rnd.t0_ns <= disp.t0_ns <= disp.t1_ns <= rnd.t1_ns
    assert rnd.dur_ns == rnd.t1_ns - rnd.t0_ns
    mon.flush_spans()
    recs = mon.sink.records
    validate_records(recs)
    assert [r["name"] for r in recs if r["event"] == "span"].count(
        "train.round") == 1
    by_name = {r["name"]: r for r in recs}
    assert by_name["train.round"]["attrs"] == {"round": 3}
    assert by_name["io.decode"]["attrs"] == {"n": 4}
    # t is the span's END, in seconds on the time.time() clock
    r = by_name["train.dispatch"]
    assert r["t"] == pytest.approx((r["t0_ns"] + r["dur_ns"]) / 1e9)


def test_the_ring_is_bounded_and_counts_what_it_drops():
    rec = SpanRecorder(maxlen=4)
    for i in range(7):
        with rec.span("s", n=i):
            pass
    kept = rec.drain()
    assert [s.attrs["n"] for s in kept] == [3, 4, 5, 6]
    assert rec.dropped == 3 and rec.drain() == []


def test_a_wrapped_ring_warns_once_in_the_stream(capsys):
    mon = Monitor(MemorySink())
    mon.spans = SpanRecorder(maxlen=2)
    for _ in range(5):
        with mon.span("s"):
            pass
    mon.flush_spans()
    mon.flush_spans()
    warns = [r for r in mon.sink.records if r["event"] == "warning"]
    assert len(warns) == 1 and warns[0]["code"] == "spans_dropped"
    assert "3 span(s)" in warns[0]["message"]
    assert "spans_dropped" in capsys.readouterr().err


def test_a_null_sink_gives_the_shared_no_op_and_records_nothing():
    mon = Monitor()
    assert isinstance(mon.sink, NullSink) and mon.spans is None
    with mon.span("train.round", round=1) as sp:
        pass
    assert sp is NULL_SPAN is mon.span("other") is no_span("x", n=1)
    assert sp.dur_ns == 0 and sp.t0_ns == 0
    mon.flush_spans()
    mon.close()


def test_spans_go_out_ahead_of_step_and_at_close():
    """A MemorySink read without a close (the resident benchmark driver)
    must hold the spans of every dispatch whose step record it holds."""
    mon = Monitor(MemorySink())
    with mon.span("trainer.stage", step=1):
        pass
    mon.emit("log", text="not a trigger")
    assert [r["event"] for r in mon.sink.records] == ["log"]
    mon.emit("round_end", round=0, examples=1, wall_s=1.0,
             examples_per_sec=1.0)
    assert [r["event"] for r in mon.sink.records] == ["log", "span",
                                                      "round_end"]
    with mon.span("late"):
        pass
    mon.close()
    assert mon.sink.records[-1]["name"] == "late"
    validate_records(mon.sink.records)


def test_span_records_are_held_to_the_schema():
    good = {"event": "span", "t": 2.0, "name": "x", "t0_ns": 1, "dur_ns": 5,
            "tid": 1, "id": 1, "parent": 0, "attrs": {}}
    validate_records([good])
    with pytest.raises(ValueError, match="dur_ns"):
        validate_records([dict(good, dur_ns=-1)])
    with pytest.raises(ValueError, match="parent"):
        validate_records([{k: v for k, v in good.items() if k != "parent"}])


# -- task = train ------------------------------------------------------------


def _raw_rec(path, n=32, size=10):
    from cxxnet_tpu.io.recordio import RecordIOWriter, pack_raw_tensor_record
    rng = np.random.RandomState(7)
    w = RecordIOWriter(path)
    for i in range(n):
        w.write_record(pack_raw_tensor_record(
            i, float(i % 10), rng.randint(0, 256, (size, size, 3))
            .astype(np.uint8)))
    w.close()


def test_a_monitored_train_task_writes_every_span(tmp_path):
    """Every span of the table, and the two records the spans replace:
    ``step.wall_ms`` is its ``trainer.*`` spans' extent and
    ``step.data_wait_ms`` its ``train.data_wait`` spans' sum."""
    from cxxnet_tpu.main import main
    rec = str(tmp_path / "train.rec")
    _raw_rec(rec)
    conf = str(tmp_path / "train.conf")
    with open(conf, "w") as f:
        f.write("""
data = train
iter = imgrec
  path_imgrec = %s
  input_shape = 3,8,8
  rand_crop = 1
  max_random_contrast = 0.2
  silent = 1
iter = threadbuffer
iter = end
%s
model_dir = %s
""" % (rec, NET.replace("batch_size = 16", "batch_size = 8"),
            tmp_path / "models"))
    stream = str(tmp_path / "mon.jsonl")
    assert main([conf, "task=train", "num_round=2", "dispatch_period=2",
                 "precompile=1", "save_model=0", "silent=1",
                 "monitor=jsonl", "monitor_path=" + stream]) == 0
    recs = read_jsonl(stream)
    validate_records(recs)
    # only spans that closed later (the producer thread's last wait,
    # ended by the iterator's close) follow run_end
    assert [r["event"] for r in recs if r["event"] != "span"][-1] == "run_end"
    spans = [r for r in recs if r["event"] == "span"]
    assert {s["name"] for s in spans} == SPAN_NAMES
    steps = sorted((r for r in recs if r["event"] == "step"),
                   key=lambda r: r["step"])
    assert len(steps) == 4                   # 2 rounds x 2 windows of 2

    def of(name, step):
        (s,) = [s for s in spans if s["name"] == name
                and s["attrs"].get("step") == step]
        return s

    stage_t0 = []
    for st in steps:
        stage, wait = of("trainer.stage", st["step"]), \
            of("trainer.loss_wait", st["step"])
        enq = of("trainer.enqueue", st["step"])
        assert stage["t0_ns"] <= enq["t0_ns"] <= wait["t0_ns"]
        extent_ns = wait["t0_ns"] + wait["dur_ns"] - stage["t0_ns"]
        assert st["wall_ms"] == pytest.approx(extent_ns / 1e6, rel=1e-9)
        stage_t0.append(stage["t0_ns"])
    waits = [s for s in spans if s["name"] == "train.data_wait"]
    for st, lo, hi in zip(steps, [0] + stage_t0, stage_t0):
        mine = [w["dur_ns"] for w in waits
                if lo < w["t0_ns"] + w["dur_ns"] <= hi]
        assert mine and st["data_wait_ms"] == pytest.approx(
            sum(mine) / 1e6, rel=1e-9)
    # precompile.wall_ms is the setup.precompile span's duration
    (pre,) = [r for r in recs if r["event"] == "precompile"]
    (pre_span,) = [s for s in spans if s["name"] == "setup.precompile"]
    assert pre["wall_ms"] == pytest.approx(pre_span["dur_ns"] / 1e6)
    # pipeline.h2d_ms is the io.h2d_* spans' sum, a round at a time
    pipes = [r for r in recs if r["event"] == "pipeline"]
    h2d = sum(s["dur_ns"] for s in spans
              if s["name"] in ("io.h2d_issue", "io.h2d_wait"))
    assert sum(p["h2d_ms"] for p in pipes) <= h2d / 1e6 + 1e-3
    assert all(p["h2d_ms"] > 0 and "h2d_overlap_ratio" not in p
               for p in pipes)
    # spans nest: the trainer's lie in a dispatch, that in a round
    by_id = {s["id"]: s for s in spans}
    stage = of("trainer.stage", steps[0]["step"])
    assert by_id[stage["parent"]]["name"] == "train.dispatch"
    assert by_id[by_id[stage["parent"]]["parent"]]["name"] == "train.round"
    # the update_many program said where its instructions come from
    (scopes,) = [r for r in recs if r["event"] == "program_scopes"]
    assert scopes["program"] == "update_many"
    assert scopes["module"] == "jit_many_step"


# -- scopes in the compiled step ---------------------------------------------


def _trainer(monitor=None):
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config
    t = NetTrainer(parse_config(NET))
    t.init_model()
    if monitor is not None:
        t.set_monitor(monitor)
    t.precompile()
    (key,) = [k for k in t.programs.aot
              if k[0] == "update" and k[-3] is True]      # the unmasked one
    return t, key


def _canonical(hlo: str) -> str:
    """A module's text without metadata and with every name (a label:
    the name stack also leaks into the names of inlined calls) replaced
    by its order of first appearance: opcodes, shapes, operands and
    order are what is left."""
    ids = {}
    hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    # the stack-frame tables metadata points into (they hold the line
    # this test called from)
    hlo = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                 r"(.+\n)*", "", hlo, flags=re.M)
    return re.sub(r"%[\w.\-]+",
                  lambda m: "%%%d" % ids.setdefault(m.group(0), len(ids)),
                  hlo)


def test_the_compiled_step_names_its_layers_and_is_otherwise_unchanged(
        monkeypatch):
    import jax
    t, key = _trainer()
    hlo = t.programs.aot[key].as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    parts = {p for n in names for p in n.split(";")[0].split("/")}
    for scope in ("conv.c1", "batch_norm.bn1", "max_pooling.pool1",
                  "fullc.fc1"):
        assert "jvp(%s)" % scope in parts
        assert "transpose(jvp(%s))" % scope in parts
    assert "jvp(loss)" in parts and "update" in parts
    assert t.net.scope_names[:2] == ("conv.c1", "batch_norm.bn1")
    # scopes are metadata only: without them (the parent commit) the
    # program is instruction for instruction the same
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, bare_key = _trainer()
    assert bare_key == key
    bare_hlo = bare.programs.aot[key].as_text()
    assert "conv.c1" not in bare_hlo
    assert "%" in hlo and _canonical(bare_hlo) == _canonical(hlo)


def test_program_scopes_maps_the_compiled_fusions():
    from cxxnet_tpu.io.data import DataBatch
    sink = MemorySink()
    t, key = _trainer(Monitor(sink))
    rng = np.random.RandomState(0)
    b = DataBatch(data=rng.rand(16, 8, 8, 3).astype(np.float32),
                  label=rng.randint(0, 10, (16, 1)).astype(np.float32))
    t.update(b)
    t.update(b)
    validate_records(sink.records)
    (rec,) = [r for r in sink.records if r["event"] == "program_scopes"]
    assert rec["program"] == "update" and rec["module"] == "jit_train_step"
    # the CPU backend rewrites some ops (a convolution, the pooling
    # window, iotas) into instructions that carry no op_name; the TPU's
    # compiler keeps it, and tests/test_chip_compile.py holds the real
    # AlexNet step to 90 %
    assert rec["fusions_mapped"] >= 0.75 * rec["fusions"] > 0
    assert rec["wall_ms"] < 500
    hlo = t.programs.aot[key].as_text()
    # every mapped name is an instruction of the loaded module, and the
    # groups a reader needs are all there
    assert all(re.search(r"^\s+(ROOT )?%?" + re.escape(k) + " = ", hlo, re.M)
               for k in rec["scopes"])
    paths = set(rec["scopes"].values())
    assert {"transpose(jvp(conv.c1))", "jvp(batch_norm.bn1)",
            "transpose(jvp(max_pooling.pool1))", "update"} <= paths
    # it is emitted inside the first dispatch's enqueue, as a span
    by = {r["name"]: r for r in sink.records if r["event"] == "span"}
    assert by["setup.program_scopes"]["dur_ns"] / 1e6 == rec["wall_ms"]


def test_scope_path_keeps_transforms_and_drops_function_names():
    known = {"conv.c1", "update", "loss"}
    assert scope_path("jit(update)/jit(main)/while/body/closed_call/"
                      "transpose(jvp(conv.c1))/conv_general_dilated",
                      known) == "transpose(jvp(conv.c1))"
    assert scope_path("jit(f)/update/mul;jit(f)/other", known) == "update"
    assert scope_path("jit(f)/while/body/dynamic_slice", known) == ""
    module, scopes, fusions, mapped = scope_map(
        "HloModule jit_f, is_scheduled=true\n\n"
        "%fused_computation (p: f32[4]) -> f32[4] {\n"
        '  %inner = f32[4] add(%p, %p), metadata={op_name="jit(f)/update/add"}\n'
        "}\n\n"
        "ENTRY %main (a: f32[4]) -> f32[4] {\n"
        "  %a = f32[4]{0} parameter(0)\n"
        "  %fusion.1 = f32[4]{0:T(8)S(1)} fusion(%a), kind=kLoop, "
        'calls=%fused_computation, metadata={op_name="jit(f)/jvp(loss)/add"}\n'
        "  %copy.2 = f32[4]{0} copy(%fusion.1)\n"
        "  ROOT %fusion.3 = f32[4]{0} fusion(%copy.2), kind=kLoop, "
        'calls=%fused_computation, metadata={op_name="jit(f)/squeeze"}\n'
        "}\n", known)
    assert module == "jit_f" and (fusions, mapped) == (2, 1)
    assert scopes == {"fusion.1": "jvp(loss)"}
    assert set(STEP_SCOPES) >= {"loss", "update", "grad_sync"}


# -- the trace hook ----------------------------------------------------------


def test_the_trace_hook_starts_once_with_the_python_tracer_off(monkeypatch):
    """It used to start again in the round after every stop, and with
    the profiler's defaults (PERF.md, PR 24)."""
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append(("start", d, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    mon = Monitor(MemorySink(), trace_dir="/nowhere", trace_begin=1)
    for r in range(4):
        mon.maybe_start_trace(r)
        mon.maybe_stop_trace(r)
    mon.close()
    assert [c[0] for c in calls] == ["start", "stop"]
    assert calls[0][2]["profiler_options"].python_tracer_level == 0
    events = [(r["event"], r.get("round")) for r in mon.sink.records]
    assert events == [("trace_start", 1), ("trace_stop", 1)]
