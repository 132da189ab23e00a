"""The expert-axis cell's pieces of the benchmark: a tiny twin of ``mellum2_12b_a2_5b``
and of its mix rehearsed end to end through run.py and ``drivers/
train_tokens_ep.py`` on four CPU devices (in a temporary copy of the
benchmark, files and entries added, none edited), the cell's files against
the zoo builder and the catalog's keys, and the three new readers on
recorded lines, the roofline's bytes counted another way. Run by hand
(not part of tier-1):

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
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import scope_groups  # noqa: E402
from test_rehearsal import last_line, run_cell  # noqa: E402
from test_span_metrics import make_run  # noqa: E402
from test_tokens_cell import _reader, phases  # noqa: E402
from test_trinity_cell import STEP, Chip, _reader_of, _scopes  # noqa: E402

KINDS = ["sliding_attention"] * 3 + ["full_attention"]
TINY_JSON = {
    "name": "tiny_mellum2", "netconfig": "tiny_mellum2.conf",
    "reference": "reference/mellum2_12b_a2_5b.py", "dtype": "bfloat16",
    "vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 4,
    "layer_types": KINDS, "mlp_layer_types": ["sparse"] * 4,
    "layers_held": [0, 1, 2, 3], "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "sliding_window": 6,
    "rms_norm_eps": 1e-6, "moe_intermediate_size": 24, "num_experts": 8,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 100,
                           "factor": 4, "original_max_position_embeddings": 64,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    "adam": {"lr": 0.01, "beta1": 0.9, "beta2": 0.95},
    # toy widths and sigma 0.3: bfloat16 reads far from float32 here;
    # the real file's limits come from the chip
    "limits": {"loss_rel": 0.1, "step_rel": 0.95}}
# two sequences a chip, as the cell (so the reference's gradient runs in two
# parts)
TINY_MIX = {"batch_size": 8, "seq_len": 16, "steps_per_dispatch": 2,
            "trace_dispatches": 2, "reference_q_block": 8}
CELL = "mellum2_12b_a2_5b.train_tokens_8k_ep4"
NEW = ("device_ms.expert_exchange", "expert_exchange_roofline",
       "moe_exchange_max_over_mean")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    from cxxnet_tpu.models import mellum2_tiny
    top = str(tmp_path_factory.mktemp("bench_copy_mellum2"))
    shutil.copytree(BENCH, os.path.join(top, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(top, "benchmarks")
    with open(os.path.join(b, "configs", "tiny_mellum2.conf"), "w") as f:
        f.write(mellum2_tiny(batch_size=8))
    with open(os.path.join(b, "configs", "tiny_mellum2.json"), "w") as f:
        json.dump(TINY_JSON, f)
    with open(os.path.join(b, "traffic",
                           "train_tokens_8k_ep4_mellum2.json")) as f:
        mix = json.load(f)
    with open(os.path.join(b, "traffic", "tiny_mellum2_tokens.json"),
              "w") as f:
        json.dump(dict(mix, **TINY_MIX), f)
    bench["workloads"].append(
        {"name": "tiny_mellum2.tokens", "config": "tiny_mellum2",
         "traffic": "tiny_mellum2_tokens", "chips": 4,
         "why": "CPU rehearsal"})
    bench["configs"].append(
        {"name": "tiny_mellum2", "source": "the test's own",
         "file": "benchmarks/configs/tiny_mellum2.json", "reduced": [],
         "why": "CPU rehearsal"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["tiny_mellum2.tokens"]
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return top


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_twin_rehearses_through_the_expert_axis_driver(copy, trace):
    proc = run_cell(copy, "tiny_mellum2.tokens", "--trace", str(trace),
                    "--rehearse", devices=4, seconds=2)
    line = last_line(proc)
    assert line["correct"], line["why_incorrect"]
    assert line["rehearsal"] is True and line["attempted"] > 0
    (ref,) = phases(proc, "reference")
    (cmp_,) = phases(proc, "compared")
    assert len(ref["losses"]) == 2 and ref["losses"][1] < ref["losses"][0]
    assert cmp_["loss_rel"] <= cmp_["loss_rel_limit"]
    assert 0 < cmp_["step_rel"] <= cmp_["step_rel_limit"] < 1
    # every pick received by the chips' experts, none dropped
    assert cmp_["held_share"] == [1.0]
    assert 1.0 <= cmp_["exchange_max_over_mean"][0] < 2.0
    (measured,) = phases(proc, "measured")
    notes = measured["notes"]
    assert notes["batch_devices"] == 4 and notes["expert_axis_size"] == 4
    assert cmp_["step_rel_tree"] <= cmp_["step_rel"]
    assert notes["all_to_all"] > 0 and notes["all_reduce"] > 0
    assert notes["expert_reduced"] == notes["expert_gathered"] == 0
    assert notes["tokens_per_s"] > 0
    assert measured["compile_s_in_window"] == 0
    names = {k[len("rehearsal."):] for k in line["metrics"]}
    if trace:
        assert {"step_ms.train", "moe_exchange_max_over_mean"} <= names
    else:
        assert {"setup_s", "train_img_per_s"} <= names


def test_a_program_without_an_expert_axis_is_refused_at_once(copy, tmp_path):
    """A checkout whose moe layer knows no expert axis (the parent commit)
    fails with a BenchFailure before the reference or any device work:
    here the layer's ``leading_axes`` is taken away in a copy of the
    package."""
    pkg = tmp_path / "cxxnet_tpu"
    shutil.copytree(os.path.join(ROOT, "cxxnet_tpu"), str(pkg),
                    ignore=shutil.ignore_patterns("__pycache__"))
    seq = pkg / "layers" / "sequence.py"
    text = seq.read_text()
    seq.write_text(text.replace("    def leading_axes(self)",
                                "    def _gone(self)"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    top = str(tmp_path / "top")
    shutil.copytree(copy, top)
    proc = subprocess.run(
        [sys.executable, os.path.join(top, "benchmarks", "run.py"),
         "--workload", "tiny_mellum2.tokens", "--seed", "7", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        cwd=top, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "no expert axis" in proc.stderr
    assert not phases(proc, "reference")


def test_the_cells_files_are_the_builders_and_the_catalogs():
    """``configs/mellum2_12b_a2_5b.conf`` is the zoo builder's text; the
    JSON carries every key of the catalog row as published but the two
    ``reduced`` ones, states the published values of those, and the
    counts of the cut."""
    from cxxnet_tpu.models import mellum2_12b_a2_5b
    with open(os.path.join(BENCH, "configs", "mellum2_12b_a2_5b.json")) as f:
        c = json.load(f)
    held_kinds = [c["layer_types"][i] for i in c["layers_held"]]
    with open(os.path.join(BENCH, "traffic",
                           "train_tokens_8k_ep4_mellum2.json")) as f:
        mix = json.load(f)
    with open(os.path.join(BENCH, "configs", "mellum2_12b_a2_5b.conf")) as f:
        assert f.read() == mellum2_12b_a2_5b(
            layer_types=held_kinds, vocab=c["vocab_size"],
            batch_size=mix["batch_size"])
    # MODEL_CATALOG names a JSONL catalog of published architectures
    # (``source_url`` and ``config`` a row); unset, the check is skipped.
    catalog = os.environ.get("MODEL_CATALOG", "")
    with open(catalog if os.path.isfile(catalog) else os.devnull) as f:
        rows = [json.loads(ln) for ln in f if "Mellum2" in ln]
    for row in rows:
        assert row["source_url"] == c["source"]
        for k, v in row["config"].items():
            if k not in c["reduced"]:
                assert c[k] == v, k
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [e for e in bench["configs"]
                if e["name"] == "mellum2_12b_a2_5b"]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    assert c["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (c["num_hidden_layers"], c["vocab_size"], c["num_experts"]) == (
        4, 12288, 64)
    assert c["published"] == {"num_hidden_layers": 28, "vocab_size": 98304}
    assert held_kinds == KINDS
    counts = c["params_by_tensor"]
    assert c["params"] == 1727616256 == sum(
        n * (4 if "(x 4)" in k else 1) for k, n in counts.items())
    assert c["params_a_chip"] == 538531072
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2_12b_a2_5b", "train_tokens_8k_ep4_mellum2", 4)
    for name in NEW:
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_img_per_s"
    assert {k: mix[k] for k in (
        "driver", "input", "batch_size", "seq_len", "steps_per_dispatch",
        "trace_dispatches", "reference_q_block")} == {
        "driver": "train_tokens_ep", "input": "resident", "batch_size": 8,
        "seq_len": 8192, "steps_per_dispatch": 2, "trace_dispatches": 2,
        "reference_q_block": 1024}
    with open(os.path.join(BENCH, "reference", "mellum2_12b_a2_5b.py")) as f, \
            open(os.path.join(ROOT, "cxxnet_tpu", "reference",
                              "mellum2_12b_a2_5b.py")) as g:
        assert f.read() == g.read()
    driver = _reader_of("drivers", "train_tokens_ep")
    cfg = driver.reference_config(c)
    assert cfg["layer_types"] == tuple(KINDS) and cfg["num_experts"] == 64
    assert driver.expert_shapes(c, 4) == [
        "64,2304,896", "64,896,2304", "16,2304,896", "16,896,2304"]


def _files():
    with open(os.path.join(BENCH, "configs", "mellum2_12b_a2_5b.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           "train_tokens_8k_ep4_mellum2.json")) as f:
        return config, json.load(f)


def _run_of(records, config, traffic, chips=4):
    run = make_run(records)
    run.config, run.traffic, run.devices = config, traffic, [Chip()] * chips
    run.cell = dict(run.cell, chips=chips)
    return run


# (a program's scope paths keep only the scopes it opened: shard_map's
# own name in an op_name is not one)
EX0 = "window/jvp(moe.l0_moe)/jvp(exchange)"
EX3 = "window/transpose(jvp(moe.l3_moe))/transpose(jvp(exchange))"
EXPERTS = "window/jvp(moe.l0_moe)/jvp(experts)"
# (the recorded trace holds these four instructions)
PATHS = {"fusion": EX0, "fusion.7": EX3, "copy.2": EXPERTS,
         "reshape.1": "jvp(gqa_attention.l1_attn)/jvp(core)"}


def test_expert_exchange_roofline_counts_the_bytes_another_way():
    """A chip's 16,384 tokens x 8 picks, three quarters of them to another
    chip: 98,304 rows of 2,304 bfloat16 values; four crossings a layer,
    four layers: 7.25 GB a step (453 MB a crossing), 36.2 ms at 1,600
    Gbit/s."""
    reader = _reader("expert_exchange_roofline")
    config, traffic = _files()
    assert reader.expert_layers(config) == 4
    rows = 8 * 8192 // 4 * 8 * 3 // 4
    assert rows == 98304
    sent = reader.least_bytes(config, traffic, 4)
    assert sent == rows * 2304 * 2 * 4 * 4
    assert sent / 16 == pytest.approx(453e6, rel=2e-3)
    assert sent / (1600e9 / 8) == pytest.approx(36.2e-3, rel=3e-3)
    assert reader.least_bytes(config, traffic, 1) is None
    for other in ("kimi_vl_a3b", "trinity_mini", "lfm2_24b_a2b"):
        with open(os.path.join(BENCH, "configs", other + ".json")) as f:
            assert reader.least_bytes(json.load(f), traffic, 4) is None


def test_the_exchange_readers_on_recorded_scope_paths(capsys):
    config, traffic = _files()
    run = _run_of([_scopes(PATHS), STEP], config, traffic)
    ops = scope_groups.walk(run)
    want = sum(ms for ms, path, _ in ops if path in (EX0, EX3))
    assert 0 < want
    capsys.readouterr()
    got = _reader("device_ms.expert_exchange").read(run)
    assert got == pytest.approx(want)
    (line,) = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert line["phase"] == "device_expert_exchange"
    assert {p for _, p, _ in line["instructions"]} == {EX0, EX3}
    share = _reader("expert_exchange_roofline").read(run)
    sent = _reader("expert_exchange_roofline").least_bytes(config, traffic, 4)
    assert share == pytest.approx(100.0 * sent / 200e9 / (want / 1e3))
    # a program with no exchange scope and no all-to-all: nothing to read
    flat = {k: v.replace("exchange", "experts") for k, v in PATHS.items()}
    for name in NEW[:2]:
        assert _reader(name).read(_run_of([_scopes(flat), STEP], config,
                                          traffic)) is None
        assert _reader(name).read(_run_of([STEP], config, traffic)) is None


def test_moe_exchange_max_over_mean_reads_the_records():
    reader = _reader("moe_exchange_max_over_mean")
    recs = [{"event": "moe", "t": 2.0 + i, "dropped": 0, "held_share": 1.0,
             "layers": {}, "load_max_over_mean": 1.3,
             "exchange_max_over_mean": v} for i, v in enumerate(
                 (1.02, 1.10, 1.04))]
    run = make_run(recs)
    run.window = (1.0, 10.0)
    assert reader.read(run) == pytest.approx(1.04)
    # records without the counter (no expert axis): nothing to read
    for r in recs:
        del r["exchange_max_over_mean"]
    assert reader.read(run) is None
