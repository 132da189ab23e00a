"""The training driver. The traffic file chooses the input:

``input = pipeline``  the job a user runs: ``task = train`` through
    ``cxxnet_tpu.main.main(argv)`` in-process with a conf file (after
    chip_smoke.py's ``write_conf`` / ``run_task``): imgrec -> augment ->
    threadbuffer over a seeded archive, ``precompile = 1``,
    ``monitor = jsonl``. The task runner stops by rounds and not by
    seconds, so a first call of two rounds warms everything and gives a
    rate (its second round's), from which the measured call's
    ``num_round`` is chosen to fill ``--seconds``; the window runs from the first ``round_start`` after
    round 0 of the measured call to its last completed dispatch.
``input = resident``  ``NetTrainer`` built from the configuration, one
    seeded batch made on the device, ``run_steps`` dispatches back to
    back until ``--seconds`` have passed (the window of bench.py's
    ``measure``, every dispatch counted, none dropped).

Either way a dispatch is closed by its fetched loss (with a monitor on,
the trainer blocks on it), and ``train_img_per_s`` is the images of the
dispatches that completed inside the window over the time from the
window's start to the last completion, for the whole cell.
"""

import json
import math
import os
import subprocess
import threading
import time
from typing import Any, Dict, List

from harness import BenchFailure, Run, trace_options

CONF = """
data = train
iter = imgrec
  path_imgrec = %(rec)s
  input_shape = 3,%(image)d,%(image)d
%(iterator)s
  silent = 1
iter = threadbuffer
iter = end
%(net)s
dtype = %(dtype)s
model_dir = %(models)s
"""


def netconfig(config: Dict[str, Any]) -> str:
    with open(os.path.join(config["_dir"], config["netconfig"])) as f:
        return f.read()


def run(r: Run) -> None:
    if r.traffic["input"] == "pipeline":
        run_pipeline(r)
    elif r.traffic["input"] == "resident":
        run_resident(r)
    else:
        raise BenchFailure("train traffic: input = %r" % r.traffic["input"])


def finish(r: Run, steps: List[Dict[str, Any]], t0: float) -> None:
    """The end-to-end metric and the checks both inputs share."""
    if not steps:
        raise BenchFailure("no dispatch completed inside the window")
    r.window = (t0, steps[-1]["t"])
    r.attempted = len(steps)
    r.failed = sum(1 for s in steps if not math.isfinite(s["loss"]))
    r.check(r.failed == 0, "non-finite loss in %d dispatch(es)" % r.failed)
    r.check(not any(s["compile"] for s in steps),
            "a step record inside the window carries compile = true")
    r.check(not r.in_window("compile"),
            "the program recorded a compile inside the window")
    r.end_to_end["train_img_per_s"] = \
        sum(s["examples"] for s in steps) / r.window_s
    r.notes["dispatches"] = len(steps)
    r.notes["loss_first_last"] = [steps[0]["loss"], steps[-1]["loss"]]


# -- input = pipeline --------------------------------------------------------


def build_native_recordio(root: str) -> None:
    """``lib/`` is a build product no checkout holds: build the RecordIO
    reader with the Makefile's rule BEFORE cxxnet_tpu.io.recordio is
    imported and picks one (chip_smoke.py's build_native_recordio).
    Where the build fails the program reads with its pure-Python twin;
    an earlier line of the run says which reader ran."""
    if os.path.exists(os.path.join(root, "Makefile")):
        subprocess.run(["make", "lib/libcxxnet_io.so"], cwd=root,
                       stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                       check=False, timeout=300)


def run_task(r: Run, conf: str, name: str, **overrides) -> List[Dict]:
    """One CLI task in-process, its telemetry schema-validated."""
    from cxxnet_tpu.main import main as cxxnet_main
    from cxxnet_tpu.monitor.schema import read_jsonl, validate_records
    stream = os.path.join(r.out_dir, name + ".jsonl")
    argv = [conf, "monitor=jsonl", "monitor_path=" + stream]
    argv += ["%s=%s" % kv for kv in overrides.items()]
    rc = cxxnet_main(argv)
    if rc != 0:
        raise BenchFailure("%s: the task runner returned %r" % (name, rc))
    recs = read_jsonl(stream)
    validate_records(recs)
    return recs


def trace_when_steady(r: Run, stream: str) -> threading.Thread:
    """The task runner owns the loop, so the trace is started from
    beside it: once the measured call's stream shows a round after
    round 0, trace the mix's ``trace_seconds``."""
    import jax

    def steady() -> bool:
        try:
            with open(stream) as f:
                return any('"round_start"' in ln and json.loads(ln)["round"] > 0
                           for ln in f)
        except (OSError, ValueError):
            return False          # not there yet, or a line half written

    def body() -> None:
        deadline = time.time() + 600
        while not steady():
            if time.time() > deadline:
                return
            time.sleep(0.25)
        t_ask = time.time()
        jax.profiler.start_trace(r.trace_dir,
                                 profiler_options=trace_options(r.traffic))
        t_on = time.time()
        time.sleep(float(r.traffic["trace_seconds"]))
        t_off = time.time()
        jax.profiler.stop_trace()
        r.trace_span_s = t_off - t_on       # not the profiler's own work
        r.notes["trace"] = {"start_s": t_on - t_ask,
                            "stop_s": time.time() - t_off}

    th = threading.Thread(target=body, name="bench-trace", daemon=True)
    th.start()
    return th


def run_pipeline(r: Run) -> None:
    t, c = r.traffic, r.config
    batch, k, n = int(t["batch_size"]), int(t["dispatch_period"]), \
        int(t["records"])
    if n % (batch * k):
        raise BenchFailure("records (%d) must be whole update_many windows "
                           "of %d x %d" % (n, k, batch))
    build_native_recordio(r.root)
    import datagen
    from cxxnet_tpu.io.recordio import native_available
    rec = os.path.join(r.out_dir, "train.rec")
    t_gen = time.time()
    datagen.make_rec(rec, n, int(t["record_image_size"]), r.seed32(),
                     t["record_format"], int(c["nclass"]))
    print(json.dumps({"phase": "data", "records": n,
                      "bytes": os.path.getsize(rec),
                      "format": t["record_format"],
                      "wall_s": time.time() - t_gen,
                      "recordio": "native" if native_available()
                      else "python"}), flush=True)
    conf = os.path.join(r.out_dir, "train.conf")
    with open(conf, "w") as f:
        f.write(CONF % {
            "rec": rec, "image": int(c["image_size"]),
            "iterator": "\n".join("  %s = %s" % kv
                                  for kv in t["iterator"].items()),
            "net": netconfig(c), "dtype": c["dtype"],
            "models": os.path.join(r.out_dir, "models")})
    keys = dict(task="train", precompile=1, dispatch_period=k,
                batch_size=batch, save_model=0, silent=1, seed=r.seed32())

    # round 0 starts the pipeline's threads and is slower: the rate
    # that sizes the window is round 1's
    probe = run_task(r, conf, "probe", num_round=2, **keys)
    rate = [x["examples_per_sec"] for x in probe
            if x["event"] == "round_end"][-1]
    rounds = max(1, math.ceil(r.seconds * rate / n))
    print(json.dumps({"phase": "probe", "img_per_s_round1": rate,
                      "rounds_in_window": rounds}), flush=True)

    tracer = None
    if r.trace:
        r.trace_dir = os.path.join(r.out_dir, "trace")
        tracer = trace_when_steady(
            r, os.path.join(r.out_dir, "measured.jsonl"))
    r.records = run_task(r, conf, "measured", num_round=rounds + 1, **keys)
    if tracer is not None:
        tracer.join(300)
        if tracer.is_alive() or not r.trace_span_s:
            raise BenchFailure("the trace did not finish inside the run")
    starts = [x["t"] for x in r.records
              if x["event"] == "round_start" and x["round"] > 0]
    t0 = min(starts)
    steps = [x for x in r.records if x["event"] == "step" and x["t"] > t0]
    finish(r, steps, t0)
    images = sum(s["examples"] for s in steps)
    r.check(images == rounds * n,
            "the window's dispatches hold %d images, %d rounds of %d hold %d"
            % (images, rounds, n, rounds * n))
    r.check(all(s["dispatch"] == "update_many" and s["n_batches"] == k
                for s in steps),
            "a dispatch inside the window is not an update_many of %d" % k)


# -- input = resident --------------------------------------------------------


def run_resident(r: Run) -> None:
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.monitor import MemorySink, Monitor
    from cxxnet_tpu.monitor.schema import validate_records
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config

    t, c = r.traffic, r.config
    batch, n_steps = int(t["batch_size"]), int(t["steps_per_dispatch"])
    size, nclass = int(c["image_size"]), int(c["nclass"])
    trainer = NetTrainer(parse_config(netconfig(c)) + [
        ("batch_size", str(batch)), ("dtype", c["dtype"]), ("silent", "1"),
        ("seed", str(r.seed32()))])
    trainer.init_model()
    if trainer.batch_size != batch:
        raise BenchFailure("the trainer took batch_size %d, not %d"
                           % (trainer.batch_size, batch))
    mesh = {k: int(v) for k, v in trainer.mesh.shape.items()}

    # the batch is made on the device(s), in one call, from the seed, in
    # the trainer's own batch sharding: mean-subtracted pixels' range,
    # labels uniform over the classes
    def make(key):
        kd, kl = jax.random.split(key)
        data = jax.random.uniform(kd, (batch, size, size, 3), jnp.float32,
                                  -128.0, 128.0)
        label = jax.random.randint(kl, (batch, 1), 0, nclass)
        return data, label.astype(jnp.float32)

    data, label = jax.jit(make, out_shardings=(
        trainer._b_shard, trainer._b_shard))(jax.random.PRNGKey(r.seed32()))
    b = DataBatch(data=trainer._put_batch_array(data),
                  label=trainer._put_batch_array(label))
    sink = MemorySink()
    trainer.set_monitor(Monitor(sink))         # emits model_info + layout
    trainer.precompile(n_steps=n_steps, per_batch=False)
    (key,) = [k for k in trainer.programs.aot if k[0] == "run_steps"]
    hlo = trainer.programs.aot[key].as_text()
    placed = b.data.addressable_shards
    r.notes.update(mesh=mesh, batch_shards=len(placed),
                   batch_devices=len({s.device for s in placed}),
                   all_reduce="all-reduce" in hlo)
    if not r.rehearse:
        r.check(r.notes["batch_devices"] == r.chips == mesh.get("data"),
                "the batch lies on %d device(s), mesh %r, cell of %d chip(s)"
                % (r.notes["batch_devices"], mesh, r.chips))
        r.check(r.notes["all_reduce"] == (r.chips > 1),
                "all-reduce in the compiled step: %r on %d chip(s)"
                % (r.notes["all_reduce"], r.chips))
    trainer.run_steps(b, n_steps)              # warm-up: the same program
    loss_warm = trainer.last_loss
    setup_records = list(sink.records)

    sink.clear()
    first_traced = -1
    if r.trace:
        r.trace_dir = os.path.join(r.out_dir, "trace")
        first_traced = 2                       # third dispatch on
    t0 = time.time()
    i = 0
    t_trace = 0.0
    while time.time() - t0 < r.seconds:
        if i == first_traced:
            jax.profiler.start_trace(r.trace_dir,
                                     profiler_options=trace_options(t))
            t_trace = time.time()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            trainer.run_steps(b, n_steps)
        i += 1
        if r.trace and i == first_traced + int(t["trace_dispatches"]):
            r.trace_span_s = time.time() - t_trace   # not the profiler's work
            jax.profiler.stop_trace()
    if r.trace and not r.trace_span_s:
        raise BenchFailure("the window held %d dispatches, too few to trace "
                           "dispatches %d..%d" % (i, first_traced,
                                                  first_traced
                                                  + int(t["trace_dispatches"])))
    validate_records(sink.records)
    r.records = setup_records + list(sink.records)
    steps = [x for x in sink.records if x["event"] == "step"]
    finish(r, steps, t0)
    r.notes["loss_warm_up"] = loss_warm
    r.check(steps[-1]["loss"] < loss_warm,
            "the last loss %r is not below the warm-up dispatch's %r: %d "
            "updates on one batch must fit it"
            % (steps[-1]["loss"], loss_warm, len(steps) * n_steps))
