"""The fused causal attention's share of the chip's peak
(layer_metrics/causal_attention_roofline.py) on recorded lines: the
recorded trace with a scope map laid over it, and the language-model
cell's own configuration and traffic files. Run by hand (not part of
tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import scope_groups  # noqa: E402
from test_span_metrics import make_run  # noqa: E402
from test_tokens_cell import _reader  # noqa: E402


def test_causal_attention_roofline_on_recorded_scope_paths():
    """The recorded trace's program with its instructions laid under an
    attention layer's ``core`` scope, forward and backward, beside the
    layer's projections and an expert layer: the reader divides the
    FLOPs it counts from the cell's own files by the ``core`` ops' time
    alone. 12.37 TFLOP a trained batch for the cell; nothing to read
    without a ``core`` scope (the parent commit), without an attention
    model's sizes (the convnets), or under scope_groups' guard."""
    reader = _reader("causal_attention_roofline")
    with open(os.path.join(BENCH, "configs", "kimi_vl_a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "train_tokens_8k.json")) as f:
        traffic = json.load(f)
    flops = reader.useful_flops(config, traffic)
    assert flops == pytest.approx(12.37e12, rel=1e-3)
    assert flops == 3 * 2 * 6 * 8192 * 2 * 16 * (128 + 64 + 128) * 8193 / 2
    assert reader.useful_flops({"nclass": 1000}, traffic) is None

    core = "window/transpose(jvp(mla_attention.l0_attn))/transpose(jvp(core))"
    scopes = {"event": "program_scopes", "t": 1.0, "program": "run_steps",
              "module": "jit_work", "fusions": 2, "fusions_mapped": 2,
              "wall_ms": 1.0,
              "scopes": {"fusion": core,
                         "fusion.7": "checkpoint(jvp(mla_attention.l0_attn))"
                                     "/jvp(core)",
                         "copy.2": "jvp(mla_attention.l0_attn)",
                         "reshape.1": "jvp(moe.l1_moe)/jvp(experts)"}}
    step = {"event": "step", "t": 1.0, "n_batches": 2}

    class Chip:
        device_kind = "TPU v5 lite"

    def run_of(records):
        run = make_run(records)
        run.config, run.traffic, run.devices = config, traffic, [Chip()]
        return run

    run = run_of([scopes, step])
    ops = scope_groups.walk(run)
    in_core = sum(ms for ms, path, _ in ops
                  if scope_groups.inner_part(path) == "core")
    attention = scope_groups.device_ms(run, ("mla_attention",))
    assert 0 < in_core == reader.core_ms(run) < attention
    assert reader.read(run) == pytest.approx(
        100.0 * flops / (in_core / 1e3 * 197e12))
    # a convnet's files over the same trace: nothing to read
    run.config = {"nclass": 1000}
    assert reader.read(run) is None
    # the parent commit: attention without a core scope
    scopes["scopes"] = {k: "jvp(mla_attention.l0_attn)"
                        for k in scopes["scopes"]}
    assert reader.read(run_of([scopes, step])) is None
    # under the guard, and with no record at all
    scopes["scopes"] = {"fusion.7": core}
    assert reader.read(run_of([scopes, step])) is None
    assert reader.read(run_of([step])) is None
