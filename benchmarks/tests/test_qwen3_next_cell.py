"""PR 34's pieces of the benchmark: a tiny twin of ``qwen3_next`` and of
its mix rehearsed end to end through run.py and ``drivers/
train_tokens_qwen3_next.py`` on the CPU (in a temporary copy of the
benchmark, files and entries added, none edited), the cell's files
against the zoo builder and the catalog's keys, and the two new readers
on recorded lines, the roofline's two counts by hand. Run by hand (not
part of tier-1):

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

TINY_JSON = {
    "name": "tiny_qwen3_next", "netconfig": "tiny_qwen3_next.conf",
    "reference": "reference/qwen3_next.py", "dtype": "bfloat16",
    "vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "partial_rotary_factor": 0.5,
    "rope_theta": 10000000, "rms_norm_eps": 1e-6, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 6, "linear_conv_kernel_dim": 4,
    "moe_intermediate_size": 24, "shared_expert_intermediate_size": 24,
    "num_experts": 4, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "n_routed_experts": 4, "expert_first": 2,
    "published": {"num_experts": 8, "n_routed_experts": 8},
    "adam": {"lr": 0.01, "beta1": 0.9, "beta2": 0.95},
    # toy widths and sigma 0.3: bfloat16 reads far from float32 here;
    # the real file's limits come from the chip
    "limits": {"loss_rel": 0.1, "step_rel": 0.95, "held_share_off": 0.5}}
TINY_MIX = {"batch_size": 2, "seq_len": 16, "steps_per_dispatch": 2,
            "trace_dispatches": 2, "reference_q_block": 8}
CELL = "qwen3_next.train_tokens_8k"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    from cxxnet_tpu.models import qwen3_next_tiny
    top = str(tmp_path_factory.mktemp("bench_copy_qwen3_next"))
    shutil.copytree(BENCH, os.path.join(top, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(top, "benchmarks")
    with open(os.path.join(b, "configs", "tiny_qwen3_next.conf"), "w") as f:
        f.write(qwen3_next_tiny(experts_held=4, expert_first=2))
    with open(os.path.join(b, "configs", "tiny_qwen3_next.json"), "w") as f:
        json.dump(TINY_JSON, f)
    with open(os.path.join(b, "traffic",
                           "train_tokens_8k_qwen3_next.json")) as f:
        mix = json.load(f)
    with open(os.path.join(b, "traffic", "tiny_qwen3_next_tokens.json"),
              "w") as f:
        json.dump(dict(mix, **TINY_MIX), f)
    bench["workloads"].append(
        {"name": "tiny_qwen3_next.tokens", "config": "tiny_qwen3_next",
         "traffic": "tiny_qwen3_next_tokens", "chips": 1,
         "why": "CPU rehearsal"})
    bench["configs"].append(
        {"name": "tiny_qwen3_next", "source": "the test's own",
         "file": "benchmarks/configs/tiny_qwen3_next.json", "reduced": [],
         "why": "CPU rehearsal"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["tiny_qwen3_next.tokens"]
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return top


@pytest.mark.parametrize("trace", [0, 1])
def test_the_tiny_twin_rehearses_through_the_qwen3_next_driver(copy, trace):
    proc = run_cell(copy, "tiny_qwen3_next.tokens", "--trace", str(trace),
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
    """``configs/qwen3_next.conf`` is the zoo builder's text; the JSON
    carries every key of the catalog row as published but the three
    ``reduced`` ones, states the published values of those, and gives
    the driver the two names it reads."""
    from cxxnet_tpu.models import qwen3_next
    with open(os.path.join(BENCH, "configs", "qwen3_next.json")) as f:
        c = json.load(f)
    with open(os.path.join(BENCH, "configs", "qwen3_next.conf")) as f:
        assert f.read() == qwen3_next(num_layers=4, vocab=18992,
                                      experts_held=c["num_experts"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [e for e in bench["configs"] if e["name"] == "qwen3_next"]
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    assert set(c["reduced"]) == {"num_hidden_layers", "num_experts",
                                 "vocab_size"}
    published = dict(
        decoder_sparse_step=1, full_attention_interval=4, head_dim=256,
        hidden_act="silu", hidden_size=2048, intermediate_size=5120,
        linear_conv_kernel_dim=4, linear_key_head_dim=128,
        linear_num_key_heads=16, linear_num_value_heads=32,
        linear_value_head_dim=128, max_position_embeddings=262144,
        mlp_only_layers=[], model_type="qwen3_next",
        moe_intermediate_size=512, norm_topk_prob=True,
        num_attention_heads=16, num_experts_per_tok=10,
        num_key_value_heads=2, partial_rotary_factor=0.25,
        rms_norm_eps=1e-6, rope_scaling=None, rope_theta=10000000,
        shared_expert_intermediate_size=512, tie_word_embeddings=False,
        use_sliding_window=False)
    for k, v in published.items():
        assert c[k] == v, k
    assert (c["num_hidden_layers"], c["vocab_size"]) == (4, 18992)
    assert c["num_experts"] in (16, 32)      # ISSUE 34's two cuts
    assert c["published"] == {
        "num_hidden_layers": 48, "num_experts": 512,
        "n_routed_experts": 512, "vocab_size": 151936}
    assert (c["n_routed_experts"], c["expert_first"]) \
        == (c["num_experts"], 0)
    # a count of held experts wrong by a factor of two fails the share
    assert c["limits"]["held_share_off"] < c["num_experts"] / 512 / 2
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3_next", "train_tokens_8k_qwen3_next", 1)
    with open(os.path.join(BENCH, "reference", "qwen3_next.py")) as f, \
            open(os.path.join(ROOT, "cxxnet_tpu", "reference",
                              "qwen3_next.py")) as g:
        assert f.read() == g.read()
    driver = _reader_of("drivers", "train_tokens_qwen3_next")
    c["_dir"] = os.path.join(BENCH, "configs")
    cfg, held = driver.reference_config(c)
    assert held == (0, c["num_experts"]) and cfg["num_experts"] == 512
    # the replacement works only while run_reference looks the name up
    # in its module at call time (an early binding would build Kimi's keys)
    assert driver.tokens.reference_config is driver.reference_config
    assert "reference_config" in driver.tokens.run_reference.__code__.co_names
    assert driver.tokens.run_reference.__globals__ is vars(driver.tokens)


def test_delta_scan_roofline_on_recorded_scope_paths():
    """The two counts for the cell, by hand: 3 x 6 x 128 x 128 FLOP a
    value head a position x 32 heads x 16,384 tokens x 3 layers = 0.464
    TFLOP; 74,496 bytes a token a layer (q, k, v, o forward; they, dO and
    three gradients backward, bfloat16; g and beta float32) = 3.66 GB: the
    bytes bound (4.47 ms against 2.36). The reader divides that by the
    ``scan`` ops' time of the ``gated_delta`` layers alone, and says a
    layer's each; nothing to read on the parent (no such scope), for
    another model's or a convnet's files, or under scope_groups' guard."""
    reader = _reader("delta_scan_roofline")
    with open(os.path.join(BENCH, "configs", "qwen3_next.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           "train_tokens_8k_qwen3_next.json")) as f:
        traffic = json.load(f)
    assert reader.linear_layers(config) == 3
    flops = reader.useful_flops(config, traffic)
    assert flops == 3 * 6 * 128 * 128 * 32 * 16384 * 3
    assert flops == pytest.approx(0.464e12, rel=2e-3)
    a_token = (2 * 2048 + 2 * 4096) * 2 + (2 * 2048 + 2 * 4096) * 2 \
        + 4096 * 2 + (2 * 2048 + 4096) * 2 + 2 * 32 * 4 * 3
    assert a_token == 74496
    moved = reader.least_bytes(config, traffic)
    assert moved == a_token * 16384 * 3
    assert moved == pytest.approx(3.66e9, rel=2e-3)
    bound_s = max(flops / 197e12, moved / 819e9)
    assert bound_s == moved / 819e9 == pytest.approx(4.47e-3, rel=2e-3)
    for other in ("kimi_vl_a3b", "trinity_mini"):
        with open(os.path.join(BENCH, "configs", other + ".json")) as f:
            assert reader.useful_flops(json.load(f), traffic) is None
    assert reader.least_bytes({"nclass": 1000}, traffic) is None

    scan0 = "window/transpose(jvp(gated_delta.l0_delta))/transpose(jvp(scan))"
    scan2 = "checkpoint(jvp(gated_delta.l2_delta))/jvp(scan)"
    paths = {"fusion": scan0, "fusion.7": scan2,
             "copy.2": "jvp(gated_delta.l2_delta)/jvp(short_conv)",
             "reshape.1": "jvp(moe.l1_moe)/jvp(experts)"}

    def run_of(records, cfg=config):
        run = make_run(records)
        run.config, run.traffic, run.devices = cfg, traffic, [Chip()]
        return run

    run = run_of([_scopes(paths), STEP])
    by_layer = reader.scan_ms_by_layer(run)
    assert set(by_layer) == {"gated_delta.l0_delta", "gated_delta.l2_delta"}
    ops = scope_groups.walk(run)
    in_scan = sum(ms for ms, path, _ in ops
                  if scope_groups.inner_part(path) == "scan")
    mixers = _reader("device_ms.gated_delta").read(run)
    assert sum(by_layer.values()) == pytest.approx(in_scan)
    assert 0 < in_scan < mixers
    assert mixers == pytest.approx(scope_groups.device_ms(
        run, ("gated_delta",)))
    # the mixer's convolution is no convolution LAYER: the accepted reader,
    # which goes by an op's innermost scope, counts none of it
    assert _reader("device_ms.conv").read(run) == 0.0
    assert reader.read(run) == pytest.approx(
        100.0 * bound_s / (in_scan / 1e3))
    # a convnet's files over the same trace: nothing to read
    assert reader.read(run_of([_scopes(paths), STEP],
                              {"nclass": 1000})) is None
    # Trinity's program: an attention core is not the scan
    gqa = {k: v.replace("gated_delta", "gqa_attention").replace("scan", "core")
           for k, v in paths.items()}
    assert reader.read(run_of([_scopes(gqa), STEP])) is None
    assert _reader("device_ms.gated_delta").read(
        run_of([_scopes(gqa), STEP])) == 0.0
    # under the guard, and with no record at all (the parent commit)
    assert reader.read(run_of([_scopes({"fusion.7": scan2}), STEP])) is None
    assert reader.read(run_of([STEP])) is None
    assert _reader("device_ms.gated_delta").read(run_of([STEP])) is None


def test_device_by_layer_line_carries_the_layout_counts(capsys):
    paths = {"fusion": "window/transpose(jvp(gated_delta.l0_delta))"
                       "/transpose(jvp(scan))",
             "fusion.7": "jvp(gated_delta.l1_delta)/jvp(proj)",
             "copy.2": "jvp(gqa_attention.l3_attn)/jvp(core)",
             "reshape.1": "jvp(moe.l1_moe)/jvp(experts)"}
    layout = {"event": "layout", "t": 1.0, "input_layout": "nhwc",
              "attention_layers": 1, "attention_fused_layers": 1,
              "attention_saved_layers": 1, "attention_window_layers": 0,
              "moe_layers": 4, "moe_grouped_layers": 4,
              "linear_attention_layers": 3, "linear_attention_chunk": 64}
    run = make_run([_scopes(paths), STEP, layout])
    capsys.readouterr()
    assert _reader("device_ms.gated_delta").read(run) > 0
    (line,) = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert line["phase"] == "device_by_layer"
    assert line["layout"] == {k: v for k, v in layout.items()
                              if k not in ("event", "t", "input_layout")}
    assert {"gated_delta/scan", "gated_delta/proj", "gqa_attention/core",
            "moe/experts"} <= set(line["parts_ms_a_batch"])
    assert {"gated_delta", "gqa_attention", "moe"} \
        <= set(line["ms_a_batch"])
