"""benchmarks/run.py — one cell of BENCHMARK.json, one run, one JSON line.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the root of the checkout.
Its configuration is the file the ``configs`` entry names, its traffic is
``benchmarks/traffic/<traffic>.json``, and the traffic file's ``driver``
names the module under ``benchmarks/drivers/`` that runs it. With
``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics, measured with the profiler off; with ``--trace 1``
it carries the per-layer metrics, each read by
``benchmarks/layer_metrics/<name>.py``, and a ``breakdown`` of the
profiler's trace. This file holds no cell's, configuration's, traffic
mix's or metric's name: a later change adds those as files and entries.

The run refuses to start — non-zero, nothing printed as a result —
unless jax reports ``platform == "tpu"`` and exactly the cell's number
of chips. ``--rehearse`` lifts that for the CPU tests; a rehearsal's
line says ``"rehearsal": true`` and carries every metric under
``rehearsal.<name>``, never under its own name.
"""

import time

T_PROCESS_START = time.time()     # set-up is counted from here

import argparse
import importlib.util
import json
import os
import shutil
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH_DIR)

from harness import (BenchFailure, CompileMeter, MissingPeak, Run, by_name,
                     device_line, load_json)


def load_module(path: str):
    """Import one file by path: metric files are named after their
    metric, dots and all, so they are not importable by name."""
    if not os.path.exists(path):
        raise BenchFailure("no such file: %s" % os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def parse_args(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window "
                         "(default: BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on whatever device jax finds; metrics are "
                         "printed as rehearsal.<name> only")
    ap.add_argument("--sweep", action="store_true",
                    help="drivers that serve: step the offered rate up, "
                         "print the table, report no metric")
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = by_name(bench["workloads"], args.workload, "workload")
    cfg_entry = by_name(bench["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    config["_dir"] = os.path.dirname(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     cell["traffic"] + ".json"))
    seconds = float(bench["run_seconds"] if args.seconds is None
                    else args.seconds)
    if not os.path.isdir(os.path.join(ROOT, "cxxnet_tpu")):
        try:
            import cxxnet_tpu  # noqa: F401  (a test's copy finds it on PYTHONPATH)
        except ImportError:
            raise BenchFailure("no cxxnet_tpu package in %s or on the path: "
                               "nothing to measure" % ROOT)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and (platform != "tpu"
                              or len(devices) != cell["chips"]):
        raise BenchFailure(
            "cell %s needs %d TPU chip(s); jax found %d device(s) of "
            "platform %r (%s). Nothing was run."
            % (cell["name"], cell["chips"], len(devices), platform,
               devices[0].device_kind))

    cache_dir = ""
    if platform != "cpu":
        # a persistent cache on the CPU backend breaks later programs of
        # the same process (PERF.md, PR 21 fault 5); a rehearsal needs none
        from cxxnet_tpu.utils.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache(
            default_dir=os.path.join(ROOT, ".jax_cache"))
    meter = CompileMeter()
    out_dir = os.path.join(ROOT, ".bench_out", cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    print(json.dumps({"phase": "start", "workload": cell["name"],
                      "seed": args.seed, "seconds": seconds,
                      "trace": args.trace, "rehearsal": args.rehearse,
                      "jax": jax.__version__, "compile_cache_dir": cache_dir,
                      **device_line(devices)}), flush=True)

    run = Run(cell=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=seconds, trace=bool(args.trace), out_dir=out_dir,
              root=ROOT, rehearse=args.rehearse, devices=devices)
    driver = load_module(os.path.join(BENCH_DIR, "drivers",
                                      traffic["driver"] + ".py"))
    if args.sweep:
        driver.sweep(run)
        return 0
    driver.run(run)               # fills run.window, .records, .end_to_end ...
    secs, hits, misses = meter.snapshot()
    compiled_in_window = meter.seconds_since(run.window[0])
    print(json.dumps({"phase": "measured", "window_s": run.window_s,
                      "compile_s": secs, "cache_hits": hits,
                      "cache_misses": misses,
                      "compile_s_in_window": compiled_in_window,
                      "memory_stats": devices[0].memory_stats(),
                      "notes": run.notes}), flush=True)
    run.check(compiled_in_window == 0,
              "jax compiled %.3f s inside the window" % compiled_in_window)

    metrics: Dict[str, Dict[str, Any]] = {}
    prefix = "rehearsal." if args.rehearse else ""

    def report(m: Dict[str, Any], value: float) -> None:
        metrics[prefix + m["name"]] = {"value": float(value),
                                       "unit": m["unit"]}

    run.end_to_end["setup_s"] = run.window[0] - T_PROCESS_START
    device = device_line(devices)
    line = {"correct": not run.failures, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if args.trace:
        import trace_reduce
        run.trace_summary = trace_reduce.reduce_dir(
            run.trace_dir, len(devices), run.trace_span_s)
        if run.trace_summary is not None:
            device["busy_s"] = run.trace_summary.busy_s
            device["window_s"] = run.trace_summary.window_s
            line["breakdown"] = run.trace_summary.breakdown()
        elif not args.rehearse:
            raise BenchFailure("the trace holds no operation on the device")
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            reader = load_module(os.path.join(
                BENCH_DIR, "layer_metrics", m["name"] + ".py"))
            try:
                v = reader.read(run)
            except MissingPeak:
                if not args.rehearse:
                    raise
                v = None            # a rehearsal's device has no peak
            if v is not None:       # nothing to read: left out of the line
                report(m, v)
    else:
        for m in bench["end_to_end"]:
            if not applies(m, cell["name"]):
                continue
            if m["name"] not in run.end_to_end:
                raise BenchFailure("driver %s gave no %s for cell %s"
                                   % (traffic["driver"], m["name"],
                                      cell["name"]))
            report(m, run.end_to_end[m["name"]])

    if args.rehearse:
        line["rehearsal"] = True
        line["why_incorrect"] = run.failures
    elif run.failures:
        print(json.dumps({"phase": "incorrect", "why": run.failures}),
              flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as e:
        print("benchmarks/run.py: %s" % e, file=sys.stderr)
        sys.exit(2)
