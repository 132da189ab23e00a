"""The language-model cell's own pieces: a tiny twin of its configuration
and of its mix rehearsed end to end through run.py on the CPU (in a
temporary copy of the benchmark, files and entries added, none edited),
and its readers on recorded lines: a recorded trace with a scope map
laid over it, and ``moe`` records recorded from a CPU rehearsal. Run by
hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import importlib.util
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import scope_groups  # noqa: E402
import span_reduce as sr  # noqa: E402
from test_rehearsal import last_line, run_cell  # noqa: E402
from test_span_metrics import make_run  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

TINY_JSON = {
    "name": "tiny_lm", "netconfig": "tiny_lm.conf",
    "reference": "reference/kimi_vl_a3b.py", "dtype": "bfloat16",
    "vocab_size": 64, "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 24, "num_hidden_layers": 3,
    "num_attention_heads": 2, "n_shared_experts": 2, "n_routed_experts": 4,
    "routed_scaling_factor": 2.446, "kv_lora_rank": 16,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "qk_nope_head_dim": 8,
    "num_experts_per_tok": 3, "first_k_dense_replace": 1,
    "norm_topk_prob": True, "rms_norm_eps": 1e-5, "rope_theta": 800000,
    "expert_first": 2, "published": {"n_routed_experts": 8},
    "adam": {"lr": 0.01, "beta1": 0.9, "beta2": 0.95},
    # toy widths and sigma 0.3: bfloat16 reads far from float32 here;
    # the real file's limits come from the chip
    "limits": {"loss_rel": 0.05, "step_rel": 0.9, "held_share_off": 0.5}}
TINY_MIX = {"batch_size": 2, "seq_len": 16, "steps_per_dispatch": 2,
            "trace_dispatches": 2, "reference_q_block": 8}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    from cxxnet_tpu.models import kimi_vl_a3b_tiny
    top = str(tmp_path_factory.mktemp("bench_copy_tokens"))
    shutil.copytree(BENCH, os.path.join(top, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(top, "benchmarks")
    with open(os.path.join(b, "configs", "tiny_lm.conf"), "w") as f:
        f.write(kimi_vl_a3b_tiny(experts_held=4, expert_first=2))
    with open(os.path.join(b, "configs", "tiny_lm.json"), "w") as f:
        json.dump(TINY_JSON, f)
    with open(os.path.join(b, "traffic", "train_tokens_8k.json")) as f:
        mix = json.load(f)
    for name, more in (("tiny_train_tokens", {}),
                       ("tiny_train_tokens_fp8",
                        {"reference_also": {"products": "float8_e4m3fn"}})):
        with open(os.path.join(b, "traffic", name + ".json"), "w") as f:
            json.dump(dict(mix, **TINY_MIX, **more), f)
        bench["workloads"].append(
            {"name": "tiny_lm." + name, "config": "tiny_lm", "traffic": name,
             "chips": 1, "why": "CPU rehearsal"})
    bench["configs"].append({"name": "tiny_lm", "source": "the test's own",
                             "file": "benchmarks/configs/tiny_lm.json",
                             "reduced": [], "why": "CPU rehearsal"})
    cells = ["tiny_lm.tiny_train_tokens", "tiny_lm.tiny_train_tokens_fp8"]
    for m in bench["per_layer"]:
        if "kimi_vl_a3b.train_tokens_8k" in m.get("workloads", []):
            m["workloads"] = m["workloads"] + cells
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return top


def phases(proc, name):
    return [json.loads(ln) for ln in proc.stdout.splitlines()
            if '"phase": "%s"' % name in ln]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_twin_rehearses_and_compares_with_the_reference(copy, trace):
    proc = run_cell(copy, "tiny_lm.tiny_train_tokens", "--trace", str(trace),
                    "--rehearse", seconds=2)
    line = last_line(proc)
    assert line["correct"], line["why_incorrect"]
    assert line["rehearsal"] is True and line["attempted"] > 0
    (ref,) = phases(proc, "reference")
    (cmp_,) = phases(proc, "compared")
    assert len(ref["losses"]) == 2 and ref["losses"][1] < ref["losses"][0]
    assert cmp_["loss_rel"] <= cmp_["loss_rel_limit"]
    assert 0 < cmp_["step_rel"] <= cmp_["step_rel_limit"] < 1
    names = {k[len("rehearsal."):] for k in line["metrics"]}
    if trace:
        assert {"step_ms.train", "moe_load_max_over_mean",
                "host_dispatch_ms.train"} <= names
        assert line["metrics"]["rehearsal.moe_load_max_over_mean"][
            "value"] >= 1.0
    else:
        assert {"setup_s", "train_img_per_s"} <= names
    (measured,) = phases(proc, "measured")
    assert measured["notes"]["tokens_per_s"] > 0
    assert measured["compile_s_in_window"] == 0


def test_the_lower_precision_probe_reads_further_from_the_reference(copy):
    """``reference_also`` prints what float8 products read on the same
    two comparisons: further than the program's bfloat16 on the loss."""
    proc = run_cell(copy, "tiny_lm.tiny_train_tokens_fp8", "--trace", "0",
                    "--rehearse", seconds=1)
    assert last_line(proc)["correct"]
    (ref,) = phases(proc, "reference")
    (cmp_,) = phases(proc, "compared")
    assert ref["also"]["lower"] == {"products": "float8_e4m3fn"}
    assert ref["also"]["loss_rel"] > cmp_["loss_rel"]
    assert ref["also"]["step_rel"] > 0


def test_a_wrong_limit_makes_the_run_incorrect(copy):
    """The comparison decides ``correct``: with the step limit under the
    reading, the line says false and why."""
    path = os.path.join(copy, "benchmarks", "configs", "tiny_lm.json")
    with open(path) as f:
        cfg = json.load(f)
    try:
        with open(path, "w") as f:
            json.dump(dict(cfg, limits=dict(cfg["limits"],
                                            step_rel=1e-4)), f)
        proc = run_cell(copy, "tiny_lm.tiny_train_tokens", "--trace", "0",
                        "--rehearse", seconds=1)
        line = last_line(proc)
        assert line["correct"] is False
        assert any("reference's step" in w for w in line["why_incorrect"])
    finally:
        with open(path, "w") as f:
            json.dump(cfg, f)


# -- the readers on recorded lines -------------------------------------------


@pytest.mark.parametrize("path,kind", [
    ("mla_attention.l0_attn", "mla_attention"),
    ("transpose(jvp(moe.l1_moe))/transpose(jvp(experts))", "moe"),
    ("jvp(moe.l2_moe)/jvp(route)", "moe"),
    ("window/transpose(jvp(fullc.head))", "fullc"),
    ("window/transpose(jvp(moe.l1_moe))/transpose(jvp(shared))", "moe"),
    ("window/loss", "loss"), ("window", "window"),
    ("transpose(jvp(fullc.head))", "fullc"),
    ("jvp(embed.embed)", "embed"), ("loss", "loss"), ("update", "update"),
    ("checkpoint(jvp(swiglu.l0_mlp))", "swiglu")])
def test_outer_kind(path, kind):
    assert scope_groups.outer_kind(path) == kind


def test_device_ms_by_outermost_scope_on_the_recorded_trace():
    """The recorded trace's program (a conv fusion, a copy, a reshape, a
    matmul fusion), its instructions mapped to the new layers' scopes:
    the groups tile what span_reduce's report counts."""
    scopes = {"event": "program_scopes", "t": 1.0, "program": "run_steps",
              "module": "jit_work", "fusions": 2, "fusions_mapped": 2,
              "wall_ms": 1.0,
              "scopes": {
                  "fusion": "transpose(jvp(moe.l1_moe))/transpose(jvp(experts))",
                  "fusion.7": "jvp(mla_attention.l0_attn)",
                  "copy.2": "jvp(moe.l1_moe)/jvp(dispatch)",
                  "reshape.1": "transpose(jvp(fullc.head))"}}
    step = {"event": "step", "t": 1.0, "n_batches": 2}
    run = make_run([scopes, step])
    rep = sr.device_report(run)
    by_kind = scope_groups.ms_by_kind(run)
    assert set(by_kind) == {"moe", "mla_attention", "fullc"}
    assert sum(by_kind.values()) == pytest.approx(
        sum(v for k, v in rep["ms_a_batch"].items() if k != "unscoped"))
    assert scope_groups.device_ms(run, ("moe",)) == by_kind["moe"] \
        > by_kind["mla_attention"] > 0
    assert scope_groups.device_ms(run, ("embed", "fullc", "softmax", "loss")
                                  ) == by_kind["fullc"]
    # a layer type the program does not have reads 0.0, as device_ms.conv
    assert scope_groups.device_ms(run, ("swiglu",)) == 0.0
    # under the guard, or no record (the parent commit): nothing to read
    scopes["scopes"] = {"fusion.7": "jvp(mla_attention.l0_attn)"}
    assert scope_groups.device_ms(run, ("mla_attention",)) is None
    assert scope_groups.device_ms(make_run([step]), ("moe",)) is None


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_moe_reader_on_recorded_records():
    """``moe`` records as a CPU rehearsal of the tiny twin wrote them
    (data/moe_records.jsonl): the median over the window's dispatches of
    the worst layer's max / mean."""
    with open(os.path.join(DATA, "moe_records.jsonl")) as f:
        recs = [json.loads(ln) for ln in f]
    assert len(recs) >= 3 and all(r["event"] == "moe" for r in recs)
    from cxxnet_tpu.monitor.schema import validate_record
    assert not [e for r in recs for e in validate_record(r)]
    run = make_run(recs, window=(min(r["t"] for r in recs) - 1,
                                 max(r["t"] for r in recs)))
    reader = _reader("moe_load_max_over_mean")
    want = sorted(r["load_max_over_mean"] for r in recs)[len(recs) // 2] \
        if len(recs) % 2 else None
    got = reader.read(run)
    assert got >= 1.0 and (want is None or got == pytest.approx(want))
    for r in recs:
        worst = max(v["load_max"] / v["load_mean"]
                    for v in r["layers"].values())
        assert r["load_max_over_mean"] == pytest.approx(worst)
        assert r["dropped"] == 0
    # a program that writes no such record: the metric is left out
    assert reader.read(make_run([])) is None
    for name in ("device_ms.mla_attention", "device_ms.moe",
                 "device_ms.head"):
        assert _reader(name).read(make_run([])) is None
