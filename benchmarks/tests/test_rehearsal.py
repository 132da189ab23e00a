"""Every traffic file rehearsed end to end through run.py at a tiny size
on the CPU, in a temporary copy of the benchmark to which the tiny
configuration, the tiny mixes and one more per-layer metric are ADDED as
files and entries — no file that is there is edited, which is how a
later PR adds a cell. Run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY_CONF = """netconfig=start
layer[0->1] = conv:c1
  kernel_size = 3
  nchannel = 8
  init_sigma = 0.001
layer[1->2] = relu
layer[2->3] = max_pooling
  kernel_size = 2
  stride = 2
layer[3->4] = flatten
layer[4->5] = fullc:fc
  nhidden = 10
  init_sigma = 0.1
layer[5->5] = softmax
netconfig=end
input_shape = 3,16,16
batch_size = 8
momentum = 0.9
wmat:lr = 0.00002
bias:lr = 0.00002
random_type = gaussian
metric = error
"""

# what each real mix is cut to for the CPU: sizes only, never its shape
TINY = {
    "train_pipeline": {"batch_size": 8, "dispatch_period": 2, "records": 32,
                       "record_image_size": 20, "trace_seconds": 1},
    "train_resident": {"batch_size": 8, "steps_per_dispatch": 2},
    "train_resident_dp": {"batch_size": 8, "steps_per_dispatch": 2},
    "serve_steady": {"rate_per_s": 40, "pool_rows": 16, "rescore_sample": 4,
                     "trace_seconds": 0.5,
                     "sweep": {"step_seconds": 0.5, "rates_per_s": [20, 40]},
                     "serve": {"serve_dtype": "bfloat16",
                               "serve_max_batch": 16,
                               "serve_buckets": "auto"}},
    "serve_batch_clients": {"clients": 3, "pool_rows": 16,
                            "rescore_sample": 4, "trace_seconds": 0.5,
                            "serve": {"serve_dtype": "bfloat16",
                                      "serve_max_batch": 16,
                                      "serve_buckets": "auto"}},
}

EXTRA_METRIC = '''"""Added by the test: the window's record count."""


def read(run):
    return float(len(run.records))
'''

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark with a tiny configuration, a tiny twin of
    every mix and one more per-layer metric added as new files."""
    top = str(tmp_path_factory.mktemp("bench_copy"))
    shutil.copytree(BENCH, os.path.join(top, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(top, "benchmarks")
    with open(os.path.join(b, "configs", "tiny.conf"), "w") as f:
        f.write(TINY_CONF)
    with open(os.path.join(b, "configs", "tiny.json"), "w") as f:
        json.dump({"name": "tiny", "netconfig": "tiny.conf", "nclass": 10,
                   "image_size": 16, "batch_size": 8,
                   "dtype": "bfloat16"}, f)
    bench["configs"].append({"name": "tiny", "source": "the test's own",
                             "file": "benchmarks/configs/tiny.json",
                             "reduced": [], "why": "CPU rehearsal"})
    e2e = {m["name"] for m in bench["end_to_end"]}
    for want, unit in (("serve_p95_ms", "ms"), ("serve_p50_ms", "ms")):
        if want not in e2e:          # not entered yet: the copy adds them
            bench["end_to_end"].append(
                {"name": want, "unit": unit, "better": "lower",
                 "bound": 0.1, "source": "host_clock",
                 "workloads": ["tiny.serve_steady",
                               "tiny.serve_batch_clients"]})
    for m in bench["end_to_end"]:
        if m["name"] == "train_img_per_s":
            m["workloads"] = m.get("workloads", []) + [
                "tiny.train_pipeline", "tiny.train_resident",
                "tiny.train_resident_dp"]
    for mix, cut in TINY.items():
        with open(os.path.join(b, "traffic", mix + ".json")) as f:
            traffic = json.load(f)
        traffic.update(cut)
        with open(os.path.join(b, "traffic", "tiny_%s.json" % mix), "w") as f:
            json.dump(traffic, f)
        bench["workloads"].append(
            {"name": "tiny." + mix, "config": "tiny",
             "traffic": "tiny_" + mix, "chips": 1, "why": "CPU rehearsal"})
    serve_cells = ["tiny.serve_steady", "tiny.serve_batch_clients"]
    for name in os.listdir(os.path.join(b, "layer_metrics")):
        name = name[:-3]
        if name not in {m["name"] for m in bench["per_layer"]}:
            bench["per_layer"].append(
                {"name": name, "unit": "ms", "better": "lower",
                 "source": "program_span", "layer": "serving",
                 "moves": "serve_p95_ms", "workloads": serve_cells})
    with open(os.path.join(b, "layer_metrics", "record_count.py"), "w") as f:
        f.write(EXTRA_METRIC)
    bench["per_layer"].append(
        {"name": "record_count", "unit": "records", "better": "higher",
         "source": "program_counter", "layer": "the test's own",
         "moves": "setup_s"})
    for m in bench["per_layer"]:
        if "workloads" in m and m["moves"] == "train_img_per_s":
            m["workloads"] = m["workloads"] + ["tiny.train_pipeline"]
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return top


def run_cell(top, workload, *more, devices=1, seconds=1.5):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % devices
    return subprocess.run(
        [sys.executable, os.path.join(top, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(2 ** 31 + 12345),
         "--seconds", str(seconds)] + list(more),
        cwd=top, env=env, capture_output=True, text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_refuses_a_cpu_without_the_rehearsal_flag(copy):
    proc = run_cell(copy, "tiny.train_resident", "--trace", "0")
    assert proc.returncode != 0
    assert "needs 1 TPU chip" in proc.stderr
    assert '"metrics"' not in proc.stdout


def test_refuses_a_directory_without_the_program(tmp_path):
    shutil.copytree(BENCH, str(tmp_path / "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), str(tmp_path))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"),
         "--workload", cell, "--rehearse"], cwd=str(tmp_path), env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout


@pytest.mark.parametrize("mix,devices", [
    ("train_pipeline", 1), ("train_resident", 1), ("train_resident_dp", 4),
    ("serve_steady", 1), ("serve_batch_clients", 1)])
@pytest.mark.parametrize("trace", [0, 1])
def test_every_mix_rehearses_and_prints_the_contract_line(copy, mix,
                                                          devices, trace):
    proc = run_cell(copy, "tiny." + mix, "--trace", str(trace), "--rehearse",
                    devices=devices)
    line = last_line(proc)
    assert KEYS <= set(line) and line["rehearsal"] is True
    if mix.startswith("serve") and line["why_incorrect"] and all(
            "when scored alone" in w for w in line["why_incorrect"]):
        # the re-score check doing its work: on the CPU backend the
        # engine's staging ring sometimes hands a batch another request's
        # rows (its alias probe tests one buffer; PERF.md, section 7)
        pytest.xfail("known CPU-only fault in serve/engine.py's staging "
                     "ring: %s" % line["why_incorrect"])
    assert line["correct"], line["why_incorrect"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    # a rehearsal never prints a metric under its name
    assert line["metrics"] and all(k.startswith("rehearsal.")
                                   for k in line["metrics"])
    names = {k[len("rehearsal."):] for k in line["metrics"]}
    if trace:
        # the metric that exists only as a file added in the copy
        assert "record_count" in names
        if mix.startswith("train"):
            assert "step_ms.train" in names
        else:
            assert {"serve_queue_ms", "serve_device_ms",
                    "serve_pad_share"} <= names
            # a closed loop has no schedule to be late against: its
            # reader finds nothing and the metric is left out
            assert ("gen_late_ms.serve" in names) == (mix == "serve_steady")
    else:
        assert "setup_s" in names
        assert ("train_img_per_s" in names) == mix.startswith("train")
        assert ("serve_p95_ms" in names) == mix.startswith("serve")
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]


def test_the_dp_mix_shards_the_batch_over_four_devices(copy):
    proc = run_cell(copy, "tiny.train_resident_dp", "--rehearse", devices=4)
    last_line(proc)
    (measured,) = [json.loads(ln) for ln in proc.stdout.splitlines()
                   if '"phase": "measured"' in ln]
    notes = measured["notes"]
    assert notes["mesh"]["data"] == 4 and notes["batch_devices"] == 4
    assert notes["all_reduce"] is True


def test_the_sweep_prints_a_table_and_no_metric(copy):
    proc = run_cell(copy, "tiny.serve_steady", "--rehearse", "--sweep")
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(ln) for ln in proc.stdout.splitlines()
            if '"phase": "sweep"' in ln]
    assert rows and all("offered_per_s" in r and "backlog_grew" in r
                        for r in rows)
    assert '"metrics"' not in proc.stdout
