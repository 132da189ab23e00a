"""PR 32's pieces of the benchmark: a tiny twin of ``trinity_mini`` and of
its mix rehearsed end to end through run.py and ``drivers/
train_tokens_afmoe.py`` on the CPU (in a temporary copy of the benchmark,
files and entries added, none edited), the cell's files against the zoo
builder and the catalog's keys, and the three new readers on recorded
lines. The four-chip mix's rehearsal is test_rehearsal.py's
(``train_resident_dp`` on four virtual devices). Run by hand (not part of
tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

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
from test_rehearsal import last_line, run_cell  # noqa: E402
from test_span_metrics import make_run  # noqa: E402
from test_tokens_cell import _reader, phases  # noqa: E402

TINY_JSON = {
    "name": "tiny_afmoe", "netconfig": "tiny_afmoe.conf",
    "reference": "reference/trinity_mini.py", "dtype": "bfloat16",
    "vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 5,
    "num_dense_layers": 1, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "sliding_window": 6,
    "global_attn_every_n_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "intermediate_size": 48, "moe_intermediate_size": 24, "num_experts": 4,
    "num_experts_per_tok": 3, "num_shared_experts": 1, "route_norm": True,
    "route_scale": 2.826, "mup_enabled": True,
    "n_routed_experts": 4, "expert_first": 2,
    "published": {"num_experts": 8, "n_routed_experts": 8},
    "adam": {"lr": 0.01, "beta1": 0.9, "beta2": 0.95},
    # toy widths and sigma 0.3: bfloat16 reads far from float32 here;
    # the real file's limits come from the chip
    "limits": {"loss_rel": 0.05, "step_rel": 0.9, "held_share_off": 0.5}}
TINY_MIX = {"batch_size": 2, "seq_len": 16, "steps_per_dispatch": 2,
            "trace_dispatches": 2, "reference_q_block": 8}
CELL = "trinity_mini.train_tokens_8k"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    from cxxnet_tpu.models import trinity_mini_tiny
    top = str(tmp_path_factory.mktemp("bench_copy_afmoe"))
    shutil.copytree(BENCH, os.path.join(top, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(top, "benchmarks")
    with open(os.path.join(b, "configs", "tiny_afmoe.conf"), "w") as f:
        f.write(trinity_mini_tiny(experts_held=4, expert_first=2))
    with open(os.path.join(b, "configs", "tiny_afmoe.json"), "w") as f:
        json.dump(TINY_JSON, f)
    with open(os.path.join(b, "traffic", "train_tokens_8k_afmoe.json")) as f:
        mix = json.load(f)
    with open(os.path.join(b, "traffic", "tiny_afmoe_tokens.json"), "w") as f:
        json.dump(dict(mix, **TINY_MIX), f)
    bench["workloads"].append(
        {"name": "tiny_afmoe.tokens", "config": "tiny_afmoe",
         "traffic": "tiny_afmoe_tokens", "chips": 1, "why": "CPU rehearsal"})
    bench["configs"].append({"name": "tiny_afmoe", "source": "the test's own",
                             "file": "benchmarks/configs/tiny_afmoe.json",
                             "reduced": [], "why": "CPU rehearsal"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["tiny_afmoe.tokens"]
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return top


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_twin_rehearses_through_the_afmoe_driver(copy, trace):
    proc = run_cell(copy, "tiny_afmoe.tokens", "--trace", str(trace),
                    "--rehearse", seconds=2)
    line = last_line(proc)
    assert line["correct"], line["why_incorrect"]
    assert line["rehearsal"] is True and line["attempted"] > 0
    (ref,) = phases(proc, "reference")
    (cmp_,) = phases(proc, "compared")
    assert len(ref["losses"]) == 2 and ref["losses"][1] < ref["losses"][0]
    assert cmp_["loss_rel"] <= cmp_["loss_rel_limit"]
    assert 0 < cmp_["step_rel"] <= cmp_["step_rel_limit"] < 1
    assert len(cmp_["held_share"]) == 1 and 0.2 < cmp_["held_share"][0] < 0.8
    names = {k[len("rehearsal."):] for k in line["metrics"]}
    if trace:
        assert {"step_ms.train", "host_dispatch_ms.train"} <= names
    else:
        assert {"setup_s", "train_img_per_s"} <= names
    (measured,) = phases(proc, "measured")
    assert measured["notes"]["tokens_per_s"] > 0
    assert measured["compile_s_in_window"] == 0


def test_the_cells_files_are_the_builders_and_the_catalogs():
    """``configs/trinity_mini.conf`` is the zoo builder's text; the JSON
    carries every key of the catalog row as published but the four
    ``reduced`` ones, states the published values of those, and gives
    the driver the two names it reads."""
    from cxxnet_tpu.models import trinity_mini
    with open(os.path.join(BENCH, "configs", "trinity_mini.conf")) as f:
        assert f.read() == trinity_mini(num_layers=5, num_dense=1,
                                        vocab=25024, experts_held=16)
    with open(os.path.join(BENCH, "configs", "trinity_mini.json")) as f:
        c = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [e for e in json.load(f)["configs"]
                    if e["name"] == "trinity_mini"]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    assert set(c["reduced"]) == {"num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"}
    published = dict(
        global_attn_every_n_layers=4, head_dim=128, hidden_act="silu",
        hidden_size=2048, intermediate_size=6144, load_balance_coeff=0.001,
        max_position_embeddings=131072, model_type="afmoe",
        moe_intermediate_size=1024, mup_enabled=True, n_group=1,
        num_attention_heads=32, num_expert_groups=1, num_experts_per_tok=8,
        num_key_value_heads=4, num_limited_groups=1, num_shared_experts=1,
        rms_norm_eps=1e-5, rope_scaling=None, rope_theta=10000,
        route_norm=True, route_scale=2.826, score_func="sigmoid",
        sliding_window=2048, tie_word_embeddings=False, topk_group=1,
        use_grouped_mm=True)
    for k, v in published.items():
        assert c[k] == v, k
    assert len(c["layer_types"]) == 32 and all(
        kind == ("full_attention" if (i + 1) % 4 == 0
                 else "sliding_attention")
        for i, kind in enumerate(c["layer_types"]))
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["vocab_size"]) == (5, 1, 16, 25024)
    assert c["published"] == {
        "num_hidden_layers": 32, "num_dense_layers": 2, "num_experts": 128,
        "n_routed_experts": 128, "vocab_size": 200192}
    assert (c["n_routed_experts"], c["expert_first"]) == (16, 0)
    with open(os.path.join(BENCH, "reference", "trinity_mini.py")) as f, \
            open(os.path.join(ROOT, "cxxnet_tpu", "reference",
                              "trinity_mini.py")) as g:
        assert f.read() == g.read()
    driver = _reader_of("drivers", "train_tokens_afmoe")
    c["_dir"] = os.path.join(BENCH, "configs")
    cfg, held = driver.reference_config(c)
    assert held == (0, 16) and cfg["num_experts"] == 128
    # the replacement works only while run_reference looks the name up
    # in its module at call time (an early binding would build Kimi's keys)
    assert driver.tokens.reference_config is driver.reference_config
    assert "reference_config" in driver.tokens.run_reference.__code__.co_names
    assert driver.tokens.run_reference.__globals__ is vars(driver.tokens)


def _reader_of(folder, name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(BENCH, folder, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Chip:
    device_kind = "TPU v5 lite"


def _scopes(paths):
    return {"event": "program_scopes", "t": 1.0, "program": "run_steps",
            "module": "jit_work", "fusions": 2, "fusions_mapped": 2,
            "wall_ms": 1.0, "scopes": paths}


STEP = {"event": "step", "t": 1.0, "n_batches": 2}


def test_window_attention_roofline_on_recorded_scope_paths():
    """9.07 TFLOP a trained batch for the cell, by hand: a sliding
    layer's 14,681,088 pairs and the full layer's 33,558,528, x 2
    products x 32 heads x 128 x 2 FLOP, four sliding layers and one full,
    2 sequences, x 3. The reader divides it by the ``core`` ops' time of
    the ``gqa_attention`` layers alone, and says a layer's each; nothing
    to read on the parent (no such scope), for Kimi's or a convnet's
    files, or under scope_groups' guard."""
    reader = _reader("window_attention_roofline")
    with open(os.path.join(BENCH, "configs", "trinity_mini.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           "train_tokens_8k_afmoe.json")) as f:
        traffic = json.load(f)
    sliding = sum(min(i + 1, 2048) for i in range(8192))
    full = 8192 * 8193 // 2
    assert (sliding, full) == (14681088, 33558528)
    assert reader.pairs(8192, 2048) == sliding
    assert reader.pairs(8192, 0) == full == reader.pairs(8192, 9000)
    want = 3 * 2 * (4 * sliding + full) * 2 * 32 * 128 * 2
    flops = reader.useful_flops(config, traffic)
    assert flops == want and flops == pytest.approx(9.07e12, rel=1e-3)
    with open(os.path.join(BENCH, "configs", "kimi_vl_a3b.json")) as f:
        assert reader.useful_flops(json.load(f), traffic) is None
    assert reader.useful_flops({"nclass": 1000}, traffic) is None

    core3 = "window/transpose(jvp(gqa_attention.l3_attn))/transpose(jvp(core))"
    core1 = "checkpoint(jvp(gqa_attention.l1_attn))/jvp(core)"
    paths = {"fusion": core3, "fusion.7": core1,
             "copy.2": "jvp(gqa_attention.l1_attn)",
             "reshape.1": "jvp(moe.l1_moe)/jvp(experts)"}

    def run_of(records, cfg=config):
        run = make_run(records)
        run.config, run.traffic, run.devices = cfg, traffic, [Chip()]
        return run

    run = run_of([_scopes(paths), STEP])
    by_layer = reader.core_ms_by_layer(run)
    assert set(by_layer) == {"gqa_attention.l3_attn", "gqa_attention.l1_attn"}
    in_core = sum(ms for ms, path, _ in scope_groups.walk(run)
                  if scope_groups.inner_part(path) == "core")
    attention = _reader("device_ms.gqa_attention").read(run)
    assert sum(by_layer.values()) == pytest.approx(in_core)
    assert 0 < in_core < attention
    assert reader.read(run) == pytest.approx(
        100.0 * flops / (in_core / 1e3 * 197e12))
    # a convnet's files over the same trace: nothing to read
    assert reader.read(run_of([_scopes(paths), STEP],
                              {"nclass": 1000})) is None
    # Kimi's program: another attention type's core is not this one's
    mla = {k: v.replace("gqa_attention", "mla_attention")
           for k, v in paths.items()}
    assert reader.read(run_of([_scopes(mla), STEP])) is None
    assert _reader("device_ms.gqa_attention").read(
        run_of([_scopes(mla), STEP])) == 0.0
    # under the guard, and with no record at all (the parent commit)
    assert reader.read(run_of([_scopes({"fusion.7": core1}), STEP])) is None
    assert reader.read(run_of([STEP])) is None
    assert _reader("device_ms.gqa_attention").read(run_of([STEP])) is None


def test_device_ms_grad_sync_reads_collectives_and_the_scope(monkeypatch,
                                                             capsys):
    """The exchange is the collective ops under whatever scope (the default
    ``fused`` all-reduce lies under the layers' scopes) and every op under
    the trainer's ``grad_sync`` scope (``overlap``), each counted once; 0.0
    for a step with neither (one chip); nothing to read without the
    program's record. On the recorded trace (no collective in it) the
    scope's ops are scope_groups' own sum."""
    reader = _reader("device_ms.grad_sync")
    paths = {"fusion": "window/transpose(jvp(conv.c1))",
             "fusion.7": "window/grad_sync",
             "copy.2": "window/transpose(jvp(grad_sync))",
             "reshape.1": "update"}
    run = make_run([_scopes(paths), STEP])
    by_kind = scope_groups.ms_by_kind(run)
    assert by_kind["grad_sync"] > 0
    assert reader.read(run) == pytest.approx(by_kind["grad_sync"])
    assert set(by_kind) == {"conv", "grad_sync", "update"}
    none = {k: "window/transpose(jvp(conv.c1))" for k in paths}
    assert reader.read(make_run([_scopes(none), STEP])) == 0.0
    assert reader.read(make_run([STEP])) is None

    fc = "window/transpose(jvp(fullc.fc6))"
    ops = [(2.0, fc, "all-reduce.3"), (0.5, fc, "all-reduce-start.1"),
           (0.25, fc, "all-reduce-done.1"), (1.0, "update", "all-gather.2"),
           (0.125, "window/grad_sync", "reduce-scatter"),
           (4.0, "window/grad_sync", "fusion.9"),
           (8.0, fc, "fusion.2"), (16.0, "update", "fusion.4")]
    monkeypatch.setattr(scope_groups, "walk", lambda run: ops)
    capsys.readouterr()
    assert reader.read(run) == 2.0 + 0.5 + 0.25 + 1.0 + 0.125 + 4.0
    (line,) = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert line["phase"] == "device_exchange"
    assert line["instructions"][0] == ["fusion.9", "window/grad_sync", 4.0]
    assert len(line["instructions"]) == 6
    monkeypatch.setattr(scope_groups, "walk", lambda run: None)
    assert reader.read(run) is None
