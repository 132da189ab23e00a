"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once on a TPU, through the entry
points a user calls (``cxxnet_tpu.main``'s task runner with a config
file and ``key=value`` overrides), at AlexNet's published width
(227x227, 1000 classes, global batch 256, bfloat16 — the
``example/ImageNet/AlexNet.conf`` net; weights random, from the seed):

1. data     a raw-tensor RecordIO archive made from a fixed seed, read by
            ``iter = imgrec`` -> augment -> ``iter = threadbuffer``
2. train    ``task = train``, ``precompile = 1``, a few windows of
            ``dispatch_period`` batches and one snapshot
3. pred     ``task = pred`` from that snapshot over the same archive
4. serve    ``task = export`` seals a bundle, ``task = serve`` boots FROM
            the bundle and answers a few hundred closed-loop requests

Every check reads what came out (telemetry records, files, arrays), not
what was printed. Any failed check raises, so the script exits non-zero;
nothing is caught to let a run finish. It is a smoke: it prints wall
and compile times per phase, and no rate under a metric's name.

    python chip_smoke.py            # one chip, all four phases
    python chip_smoke.py --chips 4  # ONLY data-parallel training over the
                                    # four chips of one host, against the
                                    # same steps on a one-device mesh

It refuses to run — non-zero, before any work — unless
``jax.devices()[0].platform == "tpu"``. The last line of standard output
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
Compiled programs are cached where ``JAX_COMPILATION_CACHE_DIR`` says,
else in ``<checkout>/.jax_cache``; everything else it writes goes under
``<checkout>/chip_smoke_out`` (both git-ignored).
"""

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chip_smoke_out")


class SmokeFailure(AssertionError):
    """A check on what a phase produced did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Size(NamedTuple):
    """How big one run is. ``REAL`` is what the chip runs; the CPU tests
    rehearse the same phase functions at a tiny size."""
    batch: int              # global batch
    image: int              # net input extent
    src_image: int          # archive image extent (rand_crop source)
    n_images: int           # archive records; a multiple of batch
    dispatch_period: int    # batches per update_many window
    rounds: int
    serve_buckets: str
    serve_clients: int
    serve_requests: int     # per client
    serve_request_rows: int


REAL = Size(batch=256, image=227, src_image=256, n_images=1024,
            dispatch_period=4, rounds=3, serve_buckets="8,32",
            serve_clients=8, serve_requests=32, serve_request_rows=4)

NCLASS = 1000


# -- compile accounting ----------------------------------------------------


class CompileMeter:
    """Seconds jax spent getting executables (compiling, or reading the
    persistent cache) and the cache's hit/miss counts, from jax's own
    monitoring events — so a cold run can be told from a slow one."""

    def __init__(self) -> None:
        import jax.monitoring
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.secs, self.hits, self.misses


def run_phase(name: str, fn: Callable[[], Dict], meter: CompileMeter
              ) -> Dict:
    """Run one phase and print its one JSON line. ``fn`` raises on any
    failed check — deliberately not caught."""
    t0 = time.perf_counter()
    s0, h0, m0 = meter.snapshot()
    detail = fn()
    s1, h1, m1 = meter.snapshot()
    line = {"phase": name, "ok": True,
            "wall_s": round(time.perf_counter() - t0, 3),
            "compile_s": round(s1 - s0, 3),
            "cache_hits": h1 - h0, "cache_misses": m1 - m0}
    line.update(detail)
    print(json.dumps(line), flush=True)
    return line


# -- the config file -------------------------------------------------------


def write_conf(out: str, size: Size) -> str:
    """The AlexNet.conf net and data blocks over the generated archive
    (example/ImageNet/AlexNet.conf with the paths filled in)."""
    from cxxnet_tpu.models import alexnet
    rec = os.path.join(out, "train_raw.rec")
    text = """
data = train
iter = imgrec
  path_imgrec = %(rec)s
  input_shape = 3,%(image)d,%(image)d
  rand_crop = 1
  rand_mirror = 1
  mean_value = 123,117,104
  silent = 1
iter = threadbuffer
iter = end

pred = %(pred)s
iter = imgrec
  path_imgrec = %(rec)s
  input_shape = 3,%(image)d,%(image)d
  mean_value = 123,117,104
  silent = 1
iter = end
%(net)s
dtype = bfloat16
model_dir = %(models)s
""" % {"rec": rec, "image": size.image,
       "pred": os.path.join(out, "pred.txt"),
       "models": os.path.join(out, "models"),
       "net": alexnet(nclass=NCLASS, batch_size=size.batch,
                      image_size=size.image)}
    path = os.path.join(out, "alexnet_smoke.conf")
    with open(path, "w") as f:
        f.write(text)
    return path


def run_task(conf: str, out: str, name: str, **overrides) -> List[Dict]:
    """One CLI task in-process — the code ``python -m cxxnet_tpu.main``
    runs — with its telemetry in ``<out>/<name>.jsonl``; returns the
    schema-validated records."""
    from cxxnet_tpu.main import main as cxxnet_main
    from cxxnet_tpu.monitor.schema import read_jsonl, validate_records
    stream = os.path.join(out, name + ".jsonl")
    argv = [conf, "monitor=jsonl", "monitor_path=" + stream]
    argv += ["%s=%s" % kv for kv in overrides.items()]
    rc = cxxnet_main(argv)
    check(rc == 0, "%s: the task runner returned %r" % (name, rc))
    recs = read_jsonl(stream)
    validate_records(recs)
    return recs


def one(recs: Sequence[Dict], event: str) -> Dict:
    found = [r for r in recs if r["event"] == event]
    check(len(found) == 1, "expected one %r record, found %d"
          % (event, len(found)))
    return found[0]


def check_run_start(recs: Sequence[Dict], platform: str) -> None:
    start = one(recs, "run_start")
    check(start["platform"] == platform,
          "run_start says platform %r, expected %r"
          % (start["platform"], platform))


# -- phases 1-4: the CLI path ----------------------------------------------


def make_raw_rec(path: str, n: int, size: int) -> None:
    """Pack ``n`` seeded RAW uint8 tensors (no JPEG) of ``size`` x
    ``size`` x 3 into a RecordIO archive, labels ``i % 1000``."""
    import numpy as np
    from cxxnet_tpu.io.recordio import (RecordIOWriter,
                                        pack_raw_tensor_record)
    rng = np.random.RandomState(0)
    w = RecordIOWriter(path)
    for i in range(n):
        img = rng.randint(0, 255, (size, size, 3), np.uint8)
        w.write_record(pack_raw_tensor_record(i, float(i % 1000), img))
    w.close()


def phase_data(out: str, size: Size) -> Dict:
    from cxxnet_tpu.io.recordio import native_available
    check(size.n_images % size.batch == 0,
          "n_images must be a multiple of batch")
    path = os.path.join(out, "train_raw.rec")
    make_raw_rec(path, n=size.n_images, size=size.src_image)
    return {"records": size.n_images, "bytes": os.path.getsize(path),
            "recordio": "native" if native_available() else "python"}


def phase_train(conf: str, out: str, size: Size, platform: str,
                pallas_interpret: bool) -> Dict:
    recs = run_task(conf, out, "train", task="train", precompile=1,
                    dispatch_period=size.dispatch_period,
                    num_round=size.rounds, save_model=size.rounds,
                    silent=1)
    check_run_start(recs, platform)
    layout = [r for r in recs if r["event"] == "layout"][-1]
    check(layout["pallas_interpret"] is pallas_interpret,
          "layout record says pallas_interpret=%r"
          % layout["pallas_interpret"])
    pre = one(recs, "precompile")
    check(pre["programs"] > 0, "precompile compiled nothing")
    after = recs[recs.index(pre) + 1:]
    late = [r for r in after if r["event"] == "compile"]
    check(not late, "compile event(s) after the precompile window: %r"
          % [r["signature"] for r in late])
    steps = [r for r in recs if r["event"] == "step"]
    windows = size.rounds * (size.n_images // size.batch
                             // size.dispatch_period)
    check(len(steps) == windows and all(
        s["dispatch"] == "update_many" for s in steps),
        "expected %d update_many dispatches, got %r"
        % (windows, [s["dispatch"] for s in steps]))
    check(not any(s["compile"] for s in steps),
          "a step record carries compile=true")
    losses = [s["loss"] for s in steps]
    check(all(math.isfinite(v) for v in losses),
          "non-finite loss in %r" % losses)
    check(losses[0] != losses[-1],
          "loss did not move: %r" % losses)
    snap = os.path.join(out, "models", "%04d.model.npz" % size.rounds)
    check(os.path.exists(snap), "no snapshot at %s" % snap)
    ck = [r for r in recs if r["event"] == "checkpoint"]
    check(ck and all(c["status"] == "ok" for c in ck),
          "checkpoint records: %r" % ck)
    return {"platform": platform, "steps": len(steps),
            "examples": one(recs, "run_end")["examples"],
            "loss_first": losses[0], "loss_last": losses[-1],
            "precompile_programs": pre["programs"],
            "precompile_s": round(pre["wall_ms"] / 1e3, 3),
            "snapshot": snap}


def phase_pred(conf: str, out: str, size: Size, platform: str,
               snapshot: str) -> Dict:
    recs = run_task(conf, out, "pred", task="pred", model_in=snapshot,
                    silent=1)
    check_run_start(recs, platform)
    with open(os.path.join(out, "pred.txt")) as f:
        vals = [float(line) for line in f]
    check(len(vals) == size.n_images,
          "pred wrote %d rows for %d records"
          % (len(vals), size.n_images))
    check(one(recs, "task_end")["rows"] == size.n_images,
          "task_end row count disagrees")
    check(all(math.isfinite(v) and v == int(v) and 0 <= v < NCLASS
              for v in vals), "a prediction is not a class index")
    return {"rows": len(vals), "classes_seen": len(set(vals))}


def phase_serve(conf: str, out: str, size: Size, platform: str,
                snapshot: str) -> Dict:
    from cxxnet_tpu.serve.bucketing import (parse_buckets,
                                            reachable_variants)
    bundle = os.path.join(out, "models", "smoke.model.bundle")
    serve_kv = dict(serve_dtype="bfloat16",
                    serve_buckets=size.serve_buckets,
                    serve_max_batch=max(
                        int(b) for b in size.serve_buckets.split(",")),
                    silent=1)
    t0 = time.perf_counter()
    exp = run_task(conf, out, "export", task="export",
                   model_in=snapshot, export_out=bundle, **serve_kv)
    export_s = time.perf_counter() - t0
    check_run_start(exp, platform)
    want = len(list(reachable_variants(parse_buckets(
        size.serve_buckets, serve_kv["serve_max_batch"]))))
    sealed = one(exp, "export")["programs"]
    check(sealed == want == one(exp, "precompile")["programs"],
          "export sealed %d programs, the ladder has %d variants"
          % (sealed, want))

    recs = run_task(conf, out, "serve", task="serve", model_in=bundle,
                    serve_clients=size.serve_clients,
                    serve_requests=size.serve_requests,
                    serve_request_rows=size.serve_request_rows,
                    **serve_kv)
    check_run_start(recs, platform)
    art = one(recs, "artifact_load")
    check(art["fingerprint_match"] is True and art["rebuilds"] == 0
          and art["hits"] == sealed,
          "bundle boot: %r (sealed %d)" % (art, sealed))
    check(all(r["programs"] == 0 for r in recs
              if r["event"] == "precompile"),
          "serve warmup compiled programs the bundle should hold")
    compiles = [r for r in recs if r["event"] == "compile"]
    check(not compiles, "compile event(s) while serving from the "
          "bundle: %r" % [r["signature"] for r in compiles])
    summ = one(recs, "serve_summary")
    asked = size.serve_clients * size.serve_requests
    check(summ["requests"] == asked and summ["errors"] == 0
          and summ["timeouts"] == 0 and summ["rejected"] == 0
          and summ["rows"] == asked * size.serve_request_rows,
          "not every request was answered: %r" % summ)
    check(summ["compile_events"] == 0,
          "post-warmup compiles: %d" % summ["compile_events"])
    done = one(recs, "task_end")
    check(done["requests"] == asked, "task_end: %r" % done)
    return {"export_s": round(export_s, 3), "programs": sealed,
            "artifact_hits": art["hits"],
            "artifact_rebuilds": art["rebuilds"],
            "artifact_load_s": round(art["wall_ms"] / 1e3, 3),
            "requests": summ["requests"], "rows": summ["rows"],
            "batches": summ["batches"],
            "compile_events": summ["compile_events"]}


# -- the four-chip path (--chips 4) ------------------------------------------


def phase_data_parallel(devices: Optional[Sequence], batch: int,
                        image: int, window: int = 2, windows: int = 3,
                        tol: float = 1e-2) -> Dict:
    """Data-parallel AlexNet training over ``devices`` (None = the
    trainer's default mesh: every device jax reports) against the same
    steps on a one-device mesh in the same process.

    Tolerance: the per-example arithmetic is the same bf16/f32 program
    on both meshes; what differs is the ORDER of f32 sums — the batch
    mean of the loss and each weight gradient are reduced over a
    quarter of the rows per device and then across devices, not over
    all rows at once — and whichever conv/matmul tilings XLA picks for
    the smaller per-device shard. That perturbs f32 weights in their
    last bits, which flips a bf16 rounding of a weight or an activation
    here and there, and AlexNet at lr 0.01 with no warm-up on random
    labels amplifies it step over step (the CPU rehearsal, on four
    virtual devices, reads 1e-4 after one update and 4e-4 after five).
    1e-2 relative on the loss is well above that, and well below what
    a wrong placement does: losses of two different random batches
    differ by tens of percent here, so rows that reached the wrong
    device, or a gradient that was not reduced, cannot hide under it."""
    import gc

    import jax
    import numpy as np

    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.models import alexnet
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.parallel import make_mesh
    from cxxnet_tpu.utils.config import parse_config

    all_devs = list(jax.devices() if devices is None else devices)
    n = len(all_devs)
    rng = np.random.RandomState(0)
    batches = [
        [DataBatch(
            data=(rng.rand(batch, image, image, 3)
                  - 0.5).astype(np.float32),
            label=rng.randint(0, NCLASS, (batch, 1)).astype(np.float32))
         for _ in range(window)] for _ in range(windows)]
    base = parse_config(alexnet(nclass=NCLASS, batch_size=batch,
                                image_size=image)) \
        + [("dtype", "bfloat16"), ("eval_train", "0"), ("silent", "1"),
           ("grad_sync", "fused")]

    def distinct(arr) -> int:
        return len({s.device for s in arr.addressable_shards})

    def run(mesh, optim_shard: int) -> Dict:
        t = NetTrainer(base + [("optim_shard", str(optim_shard))],
                       mesh=mesh)
        t.init_model()
        ndata = dict(t.mesh.shape)["data"]
        hlo = {}
        dispatch = t._call_step

        def spy(kind, sig, jit_fn, args, **static_kw):
            # the text of the program this dispatch is about to run
            if kind == "update_many" and not hlo:
                hlo["text"] = jit_fn.lower(
                    *args, **static_kw).compile().as_text()
            return dispatch(kind, sig, jit_fn, args, **static_kw)

        t._call_step = spy
        losses = []
        for win in batches:
            t.update_many(win)
            losses.append(t.last_loss)
        check(all(math.isfinite(v) for v in losses),
              "non-finite loss %r" % losses)
        placed = t._put_batch_array(batches[0][0].data)
        info = {"mesh": {k: int(v) for k, v in t.mesh.shape.items()},
                "optim_shard": optim_shard, "losses": losses,
                "batch_shards": len(placed.addressable_shards),
                "batch_devices": distinct(placed),
                "batch_shard_rows": placed.addressable_shards[0]
                .data.shape[0],
                "all_reduce": "all-reduce" in hlo["text"]}
        w = t.params["fc6"]["wmat"]
        mom = jax.tree_util.tree_leaves(t.opt_state["fc6"]["wmat"])[0]
        info["weight_replicas"] = distinct(w)
        info["weight_shard_rows"] = w.addressable_shards[0].data.shape[0]
        info["momentum_shard_rows"] = mom.addressable_shards[0] \
            .data.shape[0]
        info["momentum_devices"] = distinct(mom)
        check(ndata == info["batch_shards"] == info["batch_devices"]
              and info["batch_shard_rows"] * ndata == batch,
              "batch placement: %r" % info)
        check(all(leaf.sharding.is_fully_replicated
                  and distinct(leaf) == ndata
                  for leaf in jax.tree_util.tree_leaves(t.params)),
              "a weight is not replicated on every device")
        want_rows = w.shape[0] // ndata if optim_shard else w.shape[0]
        check(info["momentum_shard_rows"] == want_rows
              and info["momentum_devices"] == ndata,
              "fc6 momentum placement: %r" % info)
        check(info["all_reduce"] == (ndata > 1),
              "all-reduce in the step: %r on a data=%d mesh"
              % (info["all_reduce"], ndata))
        del t
        gc.collect()
        return info

    mesh = None if devices is None else make_mesh(devices=all_devs)
    runs = [run(make_mesh(devices=all_devs[:1]), 0),
            run(mesh, 0), run(mesh, 1)]
    ref = runs[0]["losses"]
    for r in runs[1:]:
        check(r["mesh"]["data"] == n, "mesh %r over %d devices"
              % (r["mesh"], n))
        r["max_rel_loss_diff"] = max(
            abs(a - b) / abs(b) for a, b in zip(r["losses"], ref))
        check(r["max_rel_loss_diff"] <= tol,
              "losses %r vs one-device %r: off by %.3g (tolerance %g)"
              % (r["losses"], ref, r["max_rel_loss_diff"], tol))
    return {"devices": n, "global_batch": batch, "window": window,
            "windows": windows, "loss_tol": tol, "runs": runs}


# -- entry -------------------------------------------------------------------


def build_native_recordio() -> None:
    """``lib/`` is a git-ignored build product: build the RecordIO
    library from source with the Makefile's rule, BEFORE
    ``cxxnet_tpu.io.recordio`` is imported and picks a reader. Where
    the build fails, recordio.py selects its pure-Python twin of the
    same format; the data phase's line says which one ran."""
    subprocess.run(["make", "lib/libcxxnet_io.so"], cwd=REPO,
                   stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                   check=False, timeout=300)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY data-parallel training over the "
                         "four chips of one host, against a "
                         "one-device mesh")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "cxxnet_tpu")):
        print("chip_smoke.py: no cxxnet_tpu package beside this script "
              "(%s) — run it from a checkout of the repository" % REPO,
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print("chip_smoke.py: needs a TPU — jax found platform %r "
              "(%s); nothing was run" % (dev.platform, dev.device_kind),
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print("chip_smoke.py: --chips %d, but jax reports %d device(s); "
              "nothing was run" % (args.chips, len(devices)),
              file=sys.stderr)
        return 2

    sys.path.insert(0, REPO)
    build_native_recordio()
    from cxxnet_tpu.layers import pallas_kernels
    from cxxnet_tpu.utils.compile_cache import (REPO_CACHE_DIR,
                                                enable_compile_cache)
    pallas_kernels.set_interpret(False)   # on a chip, kernels compile
    cache_dir = enable_compile_cache(default_dir=REPO_CACHE_DIR)
    meter = CompileMeter()
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    print(json.dumps({"phase": "setup", "ok": True,
                      "jax": jax.__version__,
                      "compile_cache_dir": cache_dir,
                      "cache_entries_at_start": len(os.listdir(cache_dir))
                      if os.path.isdir(cache_dir) else 0,
                      "out_dir": OUT_DIR}), flush=True)

    if args.chips == 4:
        run_phase("data_parallel", lambda: phase_data_parallel(
            None, REAL.batch, REAL.image), meter)
    else:
        conf = write_conf(OUT_DIR, REAL)
        run_phase("data", lambda: phase_data(OUT_DIR, REAL), meter)
        train = run_phase("train", lambda: phase_train(
            conf, OUT_DIR, REAL, "tpu", pallas_interpret=False), meter)
        run_phase("pred", lambda: phase_pred(
            conf, OUT_DIR, REAL, "tpu", train["snapshot"]), meter)
        run_phase("serve", lambda: phase_serve(
            conf, OUT_DIR, REAL, "tpu", train["snapshot"]), meter)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
