"""Qwen3-Next's block as a model (models/qwen3_next.py) against its plain
reference (cxxnet_tpu/reference/qwen3_next.py): the whole tiny model's
loss, gradients and two Adam steps through ``NetTrainer``; the shares of
its expert layer adding up to the uncut reference's; the records that
count the new layer; the FLOPs and parameters of the benchmark's cut. The
layers one at a time are tests/test_qwen3_next_layers.py's.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers import seq_shape
from cxxnet_tpu.models import qwen3_next, qwen3_next_tiny
from cxxnet_tpu.nnet.net import FuncNet
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.reference import qwen3_next as ref
from cxxnet_tpu.utils.config import parse_config

from test_qwen3_next_layers import (D, MOE, ROOT, T, TINY, _close, _layer,
                                    _x)


# -- the whole tiny model -------------------------------------------------------


def _trainer(dtype="float32", held=(2, 4), extra=()):
    t = NetTrainer(parse_config(qwen3_next_tiny(
        experts_held=held[1], expert_first=held[0]))
        + [("dtype", dtype), ("seed", "3"), ("silent", "1")] + list(extra))
    t.init_model()
    return t


def _batch(seed=0, batch=2, vocab=64):
    ids = np.random.RandomState(seed).randint(0, vocab, (batch, T + 1))
    return ids[:, :T].astype(np.int32), ids[:, 1:].astype(np.float32)


def _host(t):
    return (jax.tree_util.tree_map(np.asarray, t.params),
            {k: np.asarray(v["bias"]) for k, v in t.net_state.items()
             if "bias" in v})


def test_tiny_model_alternates_two_layer_types_by_the_models_rule():
    t = _trainer(extra=[])
    kinds = [type(l).__name__ for l in t.net.layer_objs
             if hasattr(l, "chunk") or hasattr(l, "fused_core")]
    assert kinds == ["GatedDeltaLayer"] * 3 + ["GQAAttentionLayer"]
    assert [ref.is_full_attention(TINY, i) for i in range(4)] == [
        False, False, False, True]
    deep = NetTrainer(parse_config(qwen3_next_tiny(num_layers=8))
                      + [("silent", "1")])
    deep.init_model()
    assert [i for i, l in enumerate(
        l for l in deep.net.layer_objs
        if hasattr(l, "chunk") or hasattr(l, "fused_core"))
        if hasattr(l, "fused_core")] == [3, 7]


def test_tiny_model_loss_and_gradients_match_the_reference():
    t = _trainer()
    data, lab = _batch()
    params, biases = _host(t)
    l_ref, g_ref = jax.jit(lambda p: ref.loss_and_grad(
        p, biases, jnp.asarray(data), jnp.asarray(lab, jnp.int32), TINY,
        held=(2, 4), q_block=8, remat=True, stretch=4))(params)
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.jit(jax.value_and_grad(
            lambda p: t.net.loss_fn(p, t.net_state, jnp.asarray(data),
                                    jnp.asarray(lab), None),
            has_aux=True))(t.params)
    assert abs(float(loss) - float(l_ref)) < 1e-5
    assert set(g) == set(g_ref)
    # 1e-3, not the siblings' 2e-5: float32 itself is that far from a
    # float64 run of the reference here (the last layer's gradients 1e-6,
    # whatever lies behind a delta layer's convolution and unit-length
    # q, k 3e-4 to 5e-4, the program and the float32 reference alike)
    for lk in g:
        assert set(g[lk]) == set(g_ref[lk]), lk
        for tag in g[lk]:
            _close(g[lk][tag], g_ref[lk][tag],
                   2e-5 if lk.startswith("l3_") else 1e-3)
    # the reference's memory bounds change no value
    l_plain = ref.loss(params, biases, jnp.asarray(data),
                       jnp.asarray(lab, jnp.int32), TINY, held=(2, 4))
    assert abs(float(l_plain) - float(l_ref)) < 1e-6


_REFERENCE_STEPS = {}


def _reference_two_steps(params, biases, data, lab):
    """The reference's two Adam steps, made once for both dtypes (the
    seeded start is the same float32 masters)."""
    if not _REFERENCE_STEPS:
        after, losses = jax.jit(lambda p: ref.train_steps(
            p, biases, jnp.asarray(data), jnp.asarray(lab, jnp.int32), TINY,
            2, lr=0.01, held=(2, 4)))(params)
        _REFERENCE_STEPS.update(after=after, losses=losses, start=params)
    for k, v in _REFERENCE_STEPS["start"].items():
        for tag in v:
            assert np.array_equal(v[tag], params[k][tag])
    return _REFERENCE_STEPS["after"], _REFERENCE_STEPS["losses"]


@pytest.mark.parametrize("dtype,tol_loss,tol_step", [
    ("float32", 1e-4, 2e-2), ("bfloat16", 0.03, 0.8)])
def test_two_adam_steps_through_run_steps_match_the_reference(
        dtype, tol_loss, tol_step):
    """As tests/test_trinity_model.py's: the second step's loss and the
    parameters after it, as a share of how far the reference moved
    (float32 at 1e-4 and not 1e-5: the gradients' float32 noise, see
    above, moves Adam's first step; bfloat16 at toy widths and sigma 0.3
    reads 0.66 of the reference's step, under the 1 of a state left
    unchanged; the published widths' reading is the chip's, PERF.md)."""
    t = _trainer(dtype)
    data, lab = _batch()
    params, biases = _host(t)
    t.run_steps(DataBatch(data=data, label=lab), 2)
    after, losses = _reference_two_steps(params, biases, data, lab)
    assert abs(t.last_loss - float(losses[1])) < tol_loss * float(losses[1])
    assert float(losses[1]) < float(losses[0])
    num = sum(float(jnp.sum((t.params[k][g] - after[k][g]) ** 2))
              for k in after for g in after[k])
    den = sum(float(jnp.sum((params[k][g] - after[k][g]) ** 2))
              for k in after for g in after[k])
    assert (num / den) ** 0.5 < tol_step
    assert t.update_counter == 2


def test_records_count_the_linear_attention_layers_and_name_their_parts():
    from cxxnet_tpu.monitor import MemorySink, Monitor
    from cxxnet_tpu.monitor.schema import validate_records
    t = _trainer("bfloat16")
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    t.precompile(n_steps=2, per_batch=False)
    data, lab = _batch()
    t.run_steps(DataBatch(data=data, label=lab), 2)
    validate_records(sink.records)
    (layout,) = [r for r in sink.records if r["event"] == "layout"]
    # (heads of 8 x 6 in chunks of 4: the gate of the fused scan refuses)
    assert (layout["linear_attention_layers"],
            layout["linear_attention_chunk"],
            layout["linear_attention_fused_layers"],
            layout["linear_attention_fused_conv_layers"]) == (3, 4, 0, 0)
    assert (layout["attention_layers"], layout["attention_fused_layers"],
            layout["attention_window_layers"]) == (1, 0, 0)
    assert (layout["moe_layers"], layout["moe_grouped_layers"]) == (4, 0)
    (scopes,) = [r for r in sink.records if r["event"] == "program_scopes"]
    paths = set(scopes["scopes"].values())
    for want in ("gated_delta.l0_delta", "gated_delta.l2_delta",
                 "gqa_attention.l3_attn", "moe.l0_moe", "moe.l3_moe",
                 "rmsnorm.l1_ffn_norm", "embed.embed", "fullc.head"):
        assert any(want in p for p in paths), want
    for part in ("proj", "short_conv", "scan", "gate_norm", "out"):
        assert any("gated_delta.l1_delta" in p and part in p.split(
            "gated_delta.l1_delta")[1] for p in paths), part
    moes = [r for r in sink.records if r["event"] == "moe"]
    assert moes and moes[0]["dropped"] == 0
    assert set(moes[0]["layers"]) == {"l%d_moe" % i for i in range(4)}
    # a net without such layers counts none
    from cxxnet_tpu.models import mnist_mlp
    plain = NetTrainer(parse_config(mnist_mlp()) + [("silent", "1")])
    plain.init_model()
    sink2 = MemorySink()
    plain.set_monitor(Monitor(sink2))
    (rec,) = [r for r in sink2.records if r["event"] == "layout"]
    assert (rec["linear_attention_layers"], rec["linear_attention_chunk"],
            rec["linear_attention_fused_layers"],
            rec["linear_attention_fused_conv_layers"]) == (0, 0, 0, 0)


def test_records_count_the_layers_whose_scan_is_the_fused_kernels():
    """The same block with a key head serving two value heads of 128 x
    128 over 128 positions in chunks of 64: every linear-attention layer
    takes the kernels (3 of 3) and the step trains through them
    (interpreted here)."""
    from cxxnet_tpu.models.qwen3_next import qwen3_next_lm
    from cxxnet_tpu.monitor import MemorySink, Monitor
    from cxxnet_tpu.monitor.schema import validate_records
    t = NetTrainer(parse_config(qwen3_next_lm(
        vocab=64, hidden=32, num_layers=4, full_attention_interval=4,
        nhead=4, nkvhead=2, head_dim=8, rope_dim=4, rope_theta=1e7,
        linear_nkhead=1, linear_nvhead=2, linear_key_dim=128,
        linear_value_dim=128, linear_conv_kernel=4, linear_chunk=64,
        rms_norm_eps=1e-6, expert_width=24, num_experts=8,
        experts_per_tok=3, shared_width=24, experts_held=4, expert_first=2,
        seq_len=128, batch_size=2, q_block=8, expert_block=4, loss_chunk=8,
        init_sigma=0.3, lr=0.01))
        + [("dtype", "bfloat16"), ("seed", "3"), ("silent", "1")])
    t.init_model()
    assert [(l.fused_scan, l.fused_conv) for l in t.net.layer_objs
            if hasattr(l, "fused_scan")] == [(True, True)] * 3
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    ids = np.random.RandomState(0).randint(0, 64, (2, 129))
    t.run_steps(DataBatch(data=ids[:, :128].astype(np.int32),
                          label=ids[:, 1:].astype(np.float32)), 2)
    first = t.last_loss
    t.run_steps(DataBatch(data=ids[:, :128].astype(np.int32),
                          label=ids[:, 1:].astype(np.float32)), 2)
    assert np.isfinite(first) and t.last_loss < first
    validate_records(sink.records)
    (layout,) = [r for r in sink.records if r["event"] == "layout"]
    assert (layout["linear_attention_layers"],
            layout["linear_attention_chunk"],
            layout["linear_attention_fused_layers"],
            layout["linear_attention_fused_conv_layers"]) == (3, 64, 3, 3)


# -- a chip's share of the block ------------------------------------------------


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_reference():
    """The guide's share test for this family's expert half: sixteen
    experts over eight shares of two; the routed parts the shares give,
    with the gated shared expert (which every chip computes alike)
    counted once, add up to the uncut reference's layer."""
    cfg = dict(TINY, num_experts=16, num_experts_per_tok=4)
    moe_cfg = dict(MOE, nexpert=16, topk=4, score_func="softmax",
                   shared_gate=1, bias_sigma=0)
    z = _x(7)
    full, p, st = _layer("moe", moe_cfg, seq_shape(T, D))
    with jax.default_matmul_precision("highest"):
        flat = z.reshape(-1, D)
        uncut = ref.moe(p, flat, cfg, None, None)
        shared = jax.nn.sigmoid(flat @ p["sharedgate"]) * ref.swiglu(
            flat, p["sgate"], p["sup"], p["sdown"], None)
        total, loads = jnp.zeros_like(uncut), []
        for share in range(8):
            layer, _, _ = _layer("moe", dict(moe_cfg,
                                             expert_first=2 * share,
                                             expert_count=2),
                                 seq_shape(T, D))
            mine = {k: (v[2 * share:2 * share + 2]
                        if k in ("egate", "eup", "edown") else v)
                    for k, v in p.items()}
            (out,), st2 = layer.forward(mine, st, [z], True, None)
            total = total + out.reshape(-1, D) - shared
            loads.append(int(st2["picks_held"]))
            assert int(st2["dropped"]) == 0
            _close(out.reshape(-1, D), ref.moe(mine, flat, cfg,
                                               (2 * share, 2), None))
        _close(total + shared, uncut)
        (whole,), _ = full.forward(p, st, [z], True, None)
        _close(whole.reshape(-1, D), uncut)
    assert sum(loads) == 2 * T * 4          # every pick lands on one share
    assert float(jnp.abs(uncut - shared).max()) > 1e-3


# -- the benchmark's cut ---------------------------------------------------------


def test_analytic_flops_and_parameters_of_the_cut_configuration():
    """The count the MFU metric divides by, at the benchmark's sizes (3 +
    1 layers, 16 of 512 experts, 18,992 rows), from shapes alone: the
    delta rule at the recurrence's three products of 128 x 128 a value
    head a position, routed experts at 10 x 16 / 512 picks a token; and
    ISSUE 34's table of parameters, at the 16 held experts its fallback
    names (424.3 M)."""
    from cxxnet_tpu.graph import NetGraph
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "qwen3_next.conf")) as f:
        text = f.read()
    # the conf is the zoo builder's text
    assert text == qwen3_next(num_layers=4, vocab=18992, experts_held=16)
    with open(os.path.join(ROOT, "benchmarks", "reference",
                           "qwen3_next.py")) as f, \
            open(os.path.join(ROOT, "cxxnet_tpu", "reference",
                              "qwen3_next.py")) as g:
        assert f.read() == g.read()
    g = NetGraph()
    g.configure(parse_config(text))
    net = FuncNet(g, 2)
    t, d = 8192, 2048
    delta = 2 * d * (12288 + 64) + 2 * 4096 * d + 2 * 4 * 8192 \
        + 6 * 32 * 128 * 128
    attn_proj = 2 * (3 * d * 4096 + 2 * d * 512)
    core = 4 * 16 * 256 * (t * (t + 1) / 2)
    moe = 2 * d * 512 + 6 * d * 512 + 2 * d + 6 * d * 512 * 10 * 16 / 512
    want = t * (3 * delta + attn_proj + 4 * moe + 2 * d * 18992) + core
    assert net.analytic_flops_per_example() == pytest.approx(want, rel=1e-9)
    assert 1.34e9 < 3 * want / t < 1.37e9
    assert 0.46 < 3 * delta / (want / t) < 0.48     # the mixers' share
    assert net.tokens_per_example == t
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0))[0]
    count = lambda keys: sum(int(np.prod(w.shape)) for k in keys
                             for w in shapes[k].values())
    assert count(["l0_delta"]) == 33718464
    assert count(["l3_attn"]) == 27263488
    assert count(["l0_moe", "l0_attn_norm", "l0_ffn_norm"]) \
        == 4200448 + 100663296 // 2
    assert count(["embed", "head", "final_norm"]) == 77793280
    assert count(shapes) == 625667136 - 4 * 100663296 // 2 == 424340544
    # the kernels take every layer at these shapes
    attn = [l for l in net.layer_objs if hasattr(l, "fused_core")]
    assert len(attn) == 1 and attn[0].fused_core and attn[0].rope_dim == 64
    assert all(l.grouped for l in net.layer_objs if hasattr(l, "grouped"))
    assert [l.chunk for l in net.layer_objs if hasattr(l, "chunk")] == [64] * 3
