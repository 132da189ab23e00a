"""PR 38's pieces of the benchmark: a tiny twin of ``lfm2_24b_a2b`` and of
its mix rehearsed end to end through run.py and ``drivers/
train_tokens_lfm2.py`` on the CPU (in a temporary copy of the benchmark,
files and entries added, none edited), the cell's files against the zoo
builder and the catalog's keys, and the three new readers on recorded
lines, the two rooflines' counts made another way. Run by hand (not part
of tier-1):

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
from test_trinity_cell import STEP, Chip, _reader_of, _scopes  # noqa: E402

KINDS = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv"]
TINY_JSON = {
    "name": "tiny_lfm2", "netconfig": "tiny_lfm2.conf",
    "reference": "reference/lfm2_24b_a2b.py", "dtype": "bfloat16",
    "vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 5,
    "num_dense_layers": 1, "layer_types": KINDS,
    "layers_held": [1, 2, 3, 4, 5], "num_attention_heads": 4,
    "num_key_value_heads": 2, "norm_eps": 1e-5, "conv_L_cache": 3,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "intermediate_size": 48, "moe_intermediate_size": 24,
    "num_experts": 4, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "n_routed_experts": 4, "expert_first": 2,
    "published": {"num_experts": 8, "n_routed_experts": 8},
    "adam": {"lr": 0.01, "beta1": 0.9, "beta2": 0.95},
    # toy widths and sigma 0.3: bfloat16 reads far from float32 here;
    # the real file's limits come from the chip
    "limits": {"loss_rel": 0.1, "step_rel": 0.95, "held_share_off": 0.5}}
TINY_MIX = {"batch_size": 2, "seq_len": 16, "steps_per_dispatch": 2,
            "trace_dispatches": 2, "reference_q_block": 8}
CELL = "lfm2_24b_a2b.train_tokens_8k"
NEW = ("device_ms.gated_conv", "gated_conv_roofline",
       "head64_attention_roofline")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    from cxxnet_tpu.models import lfm2_tiny
    top = str(tmp_path_factory.mktemp("bench_copy_lfm2"))
    shutil.copytree(BENCH, os.path.join(top, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(top, "benchmarks")
    with open(os.path.join(b, "configs", "tiny_lfm2.conf"), "w") as f:
        f.write(lfm2_tiny(experts_held=4, expert_first=2))
    with open(os.path.join(b, "configs", "tiny_lfm2.json"), "w") as f:
        json.dump(TINY_JSON, f)
    with open(os.path.join(b, "traffic", "train_tokens_8k_lfm2.json")) as f:
        mix = json.load(f)
    with open(os.path.join(b, "traffic", "tiny_lfm2_tokens.json"), "w") as f:
        json.dump(dict(mix, **TINY_MIX), f)
    bench["workloads"].append(
        {"name": "tiny_lfm2.tokens", "config": "tiny_lfm2",
         "traffic": "tiny_lfm2_tokens", "chips": 1, "why": "CPU rehearsal"})
    bench["configs"].append(
        {"name": "tiny_lfm2", "source": "the test's own",
         "file": "benchmarks/configs/tiny_lfm2.json", "reduced": [],
         "why": "CPU rehearsal"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["tiny_lfm2.tokens"]
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return top


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_twin_rehearses_through_the_lfm2_driver(copy, trace):
    proc = run_cell(copy, "tiny_lfm2.tokens", "--trace", str(trace),
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
    """``configs/lfm2_24b_a2b.conf`` is the zoo builder's text; the JSON
    carries every key of the catalog row as published but the four
    ``reduced`` ones, states the published values of those, and gives
    the driver the names it reads."""
    from cxxnet_tpu.models import lfm2_24b_a2b
    with open(os.path.join(BENCH, "configs", "lfm2_24b_a2b.json")) as f:
        c = json.load(f)
    held_kinds = [c["layer_types"][i] for i in c["layers_held"]]
    with open(os.path.join(BENCH, "configs", "lfm2_24b_a2b.conf")) as f:
        assert f.read() == lfm2_24b_a2b(
            layer_types=held_kinds, dense_layers=c["num_dense_layers"],
            vocab=c["vocab_size"], experts_held=c["num_experts"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [e for e in bench["configs"] if e["name"] == "lfm2_24b_a2b"]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    assert c["reduced"] == ["num_hidden_layers", "num_dense_layers",
                            "num_experts", "vocab_size"]
    published = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048,
        intermediate_size=11776,
        layer_types=["full_attention" if i % 4 == 2 else "conv"
                     for i in range(40)],
        max_position_embeddings=128000, model_type="lfm2_moe",
        moe_intermediate_size=1536, norm_eps=1e-5, norm_topk_prob=True,
        num_attention_heads=32, num_experts_per_tok=4,
        num_key_value_heads=8,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        routed_scaling_factor=1, use_expert_bias=True)
    for k, v in published.items():
        assert c[k] == v, k
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["vocab_size"]) == (5, 1, 8, 8192)
    assert c["published"] == {
        "num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64,
        "n_routed_experts": 64, "vocab_size": 65536}
    assert c["layers_held"] == [1, 2, 3, 4, 5] and held_kinds == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert (c["n_routed_experts"], c["expert_first"]) == (8, 0)
    assert c["params"] == 469284992 == sum(
        n * (4 if "(x 4)" in k else 1)
        for k, n in c["params_by_tensor"].items())
    # a count of held experts wrong by a factor of two fails the share
    assert c["limits"]["held_share_off"] < 8 / 64 / 2
    assert c["limits"]["loss_rel"] <= 0.002 and c["limits"]["step_rel"] < 1
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2_24b_a2b", "train_tokens_8k_lfm2", 1)
    for name in NEW:
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_img_per_s"
    with open(os.path.join(BENCH, "traffic", "train_tokens_8k_lfm2.json")) \
            as f:
        mix = json.load(f)
    assert {k: mix[k] for k in (
        "driver", "input", "batch_size", "seq_len", "steps_per_dispatch",
        "trace_dispatches", "reference_q_block")} == {
        "driver": "train_tokens_lfm2", "input": "resident", "batch_size": 2,
        "seq_len": 8192, "steps_per_dispatch": 2, "trace_dispatches": 3,
        "reference_q_block": 1024}
    with open(os.path.join(BENCH, "reference", "lfm2_24b_a2b.py")) as f, \
            open(os.path.join(ROOT, "cxxnet_tpu", "reference",
                              "lfm2_24b_a2b.py")) as g:
        assert f.read() == g.read()
    driver = _reader_of("drivers", "train_tokens_lfm2")
    c["_dir"] = os.path.join(BENCH, "configs")
    cfg, held = driver.reference_config(c)
    assert held == (0, 8) and cfg["num_experts"] == 64
    assert cfg["layer_types"] == tuple(held_kinds)
    assert cfg["rope_theta"] == 1e6 and cfg["num_dense_layers"] == 1
    # the replacement works only while run_reference looks the name up
    # in its module at call time (an early binding would build Kimi's keys)
    assert driver.tokens.reference_config is driver.reference_config
    assert "reference_config" in driver.tokens.run_reference.__code__.co_names
    assert driver.tokens.run_reference.__globals__ is vars(driver.tokens)


def _files():
    with open(os.path.join(BENCH, "configs", "lfm2_24b_a2b.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "train_tokens_8k_lfm2.json")) \
            as f:
        return config, json.load(f)


def _run_of(records, config, traffic):
    run = make_run(records)
    run.config, run.traffic, run.devices = config, traffic, [Chip()]
    return run


CONV0 = "window/transpose(jvp(gated_conv.l0_conv))/transpose(jvp(short_conv))"
CONV3 = "checkpoint(jvp(gated_conv.l3_conv))/jvp(short_conv)"
CORE = "window/transpose(jvp(gqa_attention.l1_attn))/transpose(jvp(core))"
# (the recorded trace holds these four instructions)
PATHS = {"fusion": CONV0, "fusion.7": CONV3,
         "copy.2": "jvp(gated_conv.l3_conv)/jvp(in_proj)",
         "reshape.1": "jvp(moe.l1_moe)/jvp(experts)"}
WITH_CORE = dict(PATHS, **{"copy.2": CORE})


def test_gated_conv_roofline_on_recorded_scope_paths():
    """The bytes for the cell, another way: a layer's ``[B | C | x]`` is
    16,384 x 6,144 bfloat16 = 201.3 MB and ``y`` a third of it; forward
    reads the one and writes the other, backward reads both kinds and
    writes the first's gradient: (1 + 1/3 + 1 + 1/3 + 1) x 201.3 MB =
    0.738 GB a layer, 2.95 GB for the four conv layers, 3.6 ms at 819
    GB/s. The reader divides that by the ``short_conv`` ops' time of the
    ``gated_conv`` layers alone; nothing to read on the parent (no such
    scope), for another model's files, or under scope_groups' guard."""
    reader = _reader("gated_conv_roofline")
    config, traffic = _files()
    assert reader.conv_layers(config) == 4
    bcx = 16384 * 6144 * 2
    moved = reader.least_bytes(config, traffic)
    assert moved == 4 * (bcx + bcx // 3 + bcx + bcx // 3 + bcx)
    assert moved / 4 == pytest.approx(0.738e9, rel=2e-3)
    bound_s = moved / 819e9
    assert bound_s == pytest.approx(3.6e-3, rel=2e-2)
    for other in ("kimi_vl_a3b", "trinity_mini", "qwen3_next"):
        with open(os.path.join(BENCH, "configs", other + ".json")) as f:
            assert reader.least_bytes(json.load(f), traffic) is None
    assert reader.least_bytes({"nclass": 1000}, traffic) is None

    run = _run_of([_scopes(PATHS), STEP], config, traffic)
    by_layer = reader.conv_ms_by_layer(run)
    assert set(by_layer) == {"gated_conv.l0_conv", "gated_conv.l3_conv"}
    ops = scope_groups.walk(run)
    in_conv = sum(ms for ms, path, _ in ops
                  if scope_groups.inner_part(path) == "short_conv")
    mixers = _reader("device_ms.gated_conv").read(run)
    assert sum(by_layer.values()) == pytest.approx(in_conv)
    assert 0 < in_conv < mixers
    assert mixers == pytest.approx(scope_groups.device_ms(
        run, ("gated_conv",)))
    # the mixer's convolution is no convolution LAYER: the accepted
    # reader, which goes by an op's innermost scope, counts none of it
    assert _reader("device_ms.conv").read(run) == 0.0
    assert reader.read(run) == pytest.approx(
        100.0 * bound_s / (in_conv / 1e3))
    # a convnet's files over the same trace: nothing to read
    assert reader.read(_run_of([_scopes(PATHS), STEP], {"nclass": 1000},
                               traffic)) is None
    # Qwen3-Next's program: its short_conv lies under gated_delta
    delta = {k: v.replace("gated_conv", "gated_delta")
             for k, v in PATHS.items()}
    other = _run_of([_scopes(delta), STEP], config, traffic)
    assert reader.read(other) is None
    assert _reader("device_ms.gated_conv").read(other) is None
    # under the guard, and with no record at all (the parent commit)
    assert reader.read(_run_of([_scopes({"fusion.7": CONV3}), STEP],
                               config, traffic)) is None
    for name in NEW:
        assert _reader(name).read(_run_of([STEP], config, traffic)) is None


def test_head64_attention_roofline_on_recorded_scope_paths():
    """The FLOPs for the cell, another way: a head's two products over
    the causal triangle are 2 x 2 x 64 FLOP a pair, 8,192 x 8,193 / 2 =
    33.56 M pairs a sequence, 32 heads, 2 sequences, one attention layer,
    3 x forward: 1.65 TFLOP a step, 8.4 ms at 197 TFLOP/s."""
    reader = _reader("head64_attention_roofline")
    config, traffic = _files()
    flops = reader.useful_flops(config, traffic)
    pairs = sum(i + 1 for i in range(8192))
    assert flops == 3 * 2 * 32 * pairs * 2 * 2 * 64
    assert flops == pytest.approx(1.65e12, rel=3e-3)
    for other in ("kimi_vl_a3b", "trinity_mini", "qwen3_next"):
        with open(os.path.join(BENCH, "configs", other + ".json")) as f:
            assert reader.useful_flops(json.load(f), traffic) is None
    run = _run_of([_scopes(WITH_CORE), STEP], config, traffic)
    by_layer = reader.core_ms_by_layer(run)
    assert set(by_layer) == {"gqa_attention.l1_attn"}
    core_ms = by_layer["gqa_attention.l1_attn"]
    assert reader.read(run) == pytest.approx(
        100.0 * flops / (core_ms / 1e3 * 197e12))
    # a program whose attention opens no core scope: nothing to read
    flat = dict(PATHS, **{"copy.2": "jvp(gqa_attention.l1_attn)"})
    assert reader.read(_run_of([_scopes(flat), STEP], config, traffic)) \
        is None
    # trinity's files over the same trace: this reader says nothing
    with open(os.path.join(BENCH, "configs", "trinity_mini.json")) as f:
        assert reader.read(_run_of([_scopes(WITH_CORE), STEP], json.load(f),
                                   traffic)) is None


def test_device_by_layer_line_carries_the_layout_counts(capsys):
    config, traffic = _files()
    layout = {"event": "layout", "t": 1.0, "input_layout": "nhwc",
              "attention_layers": 1, "attention_fused_layers": 1,
              "attention_saved_layers": 1, "attention_window_layers": 0,
              "moe_layers": 4, "moe_grouped_layers": 4,
              "linear_attention_layers": 0, "short_conv_layers": 4,
              "head_tied": True}
    run = _run_of([_scopes(dict(
        WITH_CORE, **{"fusion.7": "jvp(gated_conv.l3_conv)/jvp(in_proj)"})),
        STEP, layout], config, traffic)
    capsys.readouterr()
    assert _reader("device_ms.gated_conv").read(run) > 0
    (line,) = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert line["phase"] == "device_by_layer"
    assert line["layout"] == {
        k: v for k, v in layout.items()
        if k not in ("event", "t", "input_layout", "linear_attention_layers")}
    assert {"gated_conv/short_conv", "gated_conv/in_proj",
            "gqa_attention/core", "moe/experts"} \
        <= set(line["parts_ms_a_batch"])
    assert {"gated_conv", "gqa_attention", "moe"} <= set(line["ms_a_batch"])
