"""The routed experts' share of the chip's peak
(layer_metrics/moe_experts_roofline.py) on recorded lines: the recorded
trace with a scope map laid over it, and the language-model cell's own
configuration and traffic files. Run by hand (not part of tier-1):

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


def test_moe_experts_roofline_on_recorded_scope_paths():
    """The recorded trace's program with its instructions laid under an
    expert layer's ``experts`` scope, forward and backward (a loop's
    body or a kernel inside a conditional's branch alike), beside the
    layer's dispatch and an attention layer: the reader divides the
    FLOPs it counts from the cell's own files by the ``experts`` ops'
    time alone. 3.19 TFLOP a trained batch for the cell, whatever
    implements the experts; nothing to read without an ``experts``
    scope, without an expert model's sizes (the convnets), or under
    scope_groups' guard."""
    reader = _reader("moe_experts_roofline")
    with open(os.path.join(BENCH, "configs", "kimi_vl_a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "train_tokens_8k.json")) as f:
        traffic = json.load(f)
    flops = reader.useful_flops(config, traffic)
    assert flops == pytest.approx(3.19e12, rel=1e-3)
    assert flops == 3 * 6 * 2048 * 1408 * (2 * 8192 * 6 * 8 / 64) * 5
    # every expert held: the file's own count is the router's
    whole = dict(config, n_routed_experts=64)
    del whole["published"]
    assert reader.useful_flops(whole, traffic) == 8 * flops
    assert reader.useful_flops({"nclass": 1000}, traffic) is None

    experts = "window/transpose(jvp(moe.l1_moe))/transpose(jvp(experts))"
    scopes = {"event": "program_scopes", "t": 1.0, "program": "run_steps",
              "module": "jit_work", "fusions": 2, "fusions_mapped": 2,
              "wall_ms": 1.0,
              "scopes": {"fusion": experts + "/cond/branch_1_fun",
                         "fusion.7": "checkpoint(jvp(moe.l1_moe))"
                                     "/jvp(experts)/while/body",
                         "copy.2": "jvp(moe.l1_moe)/jvp(dispatch)",
                         "reshape.1": "jvp(mla_attention.l0_attn)/jvp(core)"}}
    step = {"event": "step", "t": 1.0, "n_batches": 2}

    class Chip:
        device_kind = "TPU v5 lite"

    def run_of(records):
        run = make_run(records)
        run.config, run.traffic, run.devices = config, traffic, [Chip()]
        return run

    run = run_of([scopes, step])
    ops = scope_groups.walk(run)
    in_experts = sum(ms for ms, path, _ in ops
                     if scope_groups.inner_part(path).startswith("experts"))
    moe = scope_groups.device_ms(run, ("moe",))
    assert 0 < in_experts == reader.experts_ms(run) < moe
    assert reader.read(run) == pytest.approx(
        100.0 * flops / (in_experts / 1e3 * 197e12))
    # a convnet's files over the same trace: nothing to read
    run.config = {"nclass": 1000}
    assert reader.read(run) is None
    # an expert layer without the scope
    scopes["scopes"] = {k: "jvp(moe.l1_moe)" for k in scopes["scopes"]}
    assert reader.read(run_of([scopes, step])) is None
    # under the guard, and with no record at all
    scopes["scopes"] = {"fusion.7": experts}
    assert reader.read(run_of([scopes, step])) is None
    assert reader.read(run_of([step])) is None
