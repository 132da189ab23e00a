"""Mellum2's block as a model (models/mellum2.py) against its plain
reference (cxxnet_tpu/reference/mellum2_12b_a2_5b.py): the builder's
pattern; the whole tiny model's loss, gradients and two Adam steps through
``NetTrainer``, on one device and with its experts over an expert axis of
four; the records that count the new pieces; the FLOPs and parameters of
the benchmark's cut. The layers one at a time are
tests/test_mellum2_layers.py's, the expert axis alone
tests/test_expert_parallel.py's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.graph import NetGraph
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.models import mellum2_12b_a2_5b, mellum2_tiny
from cxxnet_tpu.models.mellum2 import PUBLISHED_LAYER_TYPES
from cxxnet_tpu.monitor import MemorySink, Monitor
from cxxnet_tpu.monitor.schema import validate_records
from cxxnet_tpu.nnet.net import FuncNet
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.parallel import make_mesh
from cxxnet_tpu.reference import mellum2_12b_a2_5b as ref
from cxxnet_tpu.utils.config import parse_config

from test_mellum2_layers import TINY, T, _close

CUT = PUBLISHED_LAYER_TYPES[:4]


def _trainer(dtype="float32", chips=1, extra=()):
    t = NetTrainer(parse_config(mellum2_tiny()) + [
        ("dtype", dtype), ("seed", "3"), ("silent", "1")] + list(extra),
        mesh=make_mesh(chips, 1, jax.devices()[:chips]))
    t.init_model()
    return t


def _batch(seed=0, batch=4, vocab=64):
    ids = np.random.RandomState(seed).randint(0, vocab, (batch, T + 1))
    return ids[:, :T].astype(np.int32), ids[:, 1:].astype(np.float32)


def _host(t):
    return jax.tree_util.tree_map(np.asarray, t.params)


# -- the builder -----------------------------------------------------------------


def test_the_builder_follows_the_published_pattern():
    """Full attention at 3, 7, ..., 27 of 28 layers, every layer with
    experts; a full layer carries YaRN's keys, a sliding one the window;
    what the builder refuses."""
    assert len(PUBLISHED_LAYER_TYPES) == 28
    assert [i for i, k in enumerate(PUBLISHED_LAYER_TYPES)
            if k == "full_attention"] == list(range(3, 28, 4))
    assert tuple(ref.PUBLISHED["layer_types"]) == PUBLISHED_LAYER_TYPES
    g = NetGraph()
    g.configure(parse_config(mellum2_12b_a2_5b()))
    kinds = [l.type for l in g.layers]
    assert kinds.count("gqa_attention") == 28 and kinds.count("moe") == 28
    assert "swiglu" not in kinds and kinds.count("fullc") == 1
    t = _trainer()
    attn = [l for l in t.net.layer_objs if hasattr(l, "fused_core")]
    assert [(l.window, l.rope_type, l.gate) for l in attn] == [
        (6, "default", 0)] * 3 + [(0, "yarn", 0)]
    moe = next(l for l in t.net.layer_objs if hasattr(l, "grouped"))
    assert (moe.nshared, moe.score_func, moe.norm_topk, moe.scale,
            moe.bias_sigma, moe.expert_axis, moe.count) == (
        0, "softmax", 1, 1.0, 0.0, "data", 8)
    with pytest.raises(ValueError, match="layer_types"):
        mellum2_tiny(layer_types=("conv",))


# -- the whole tiny model --------------------------------------------------------


@pytest.mark.parametrize("chips", [1, 4])
def test_tiny_model_loss_and_gradients_match_the_reference(chips):
    """Float32 at highest precision, the experts on one device or over an
    expert axis of four: the program's loss and every gradient against
    the reference's (dense routing over all experts)."""
    t = _trainer(chips=chips)
    data, lab = _batch()
    params = _host(t)
    l_ref, g_ref = jax.jit(lambda p: ref.loss_and_grad(
        p, jnp.asarray(data), jnp.asarray(lab, jnp.int32), TINY,
        q_block=8, remat=True))(params)
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.jit(jax.value_and_grad(
            lambda p: t.net.loss_fn(p, t.net_state, jnp.asarray(data),
                                    jnp.asarray(lab), None),
            has_aux=True))(t.params)
    assert abs(float(loss) - float(l_ref)) < 1e-5
    assert set(g) == set(g_ref)
    for lk in g:
        assert set(g[lk]) == set(g_ref[lk]), lk
        for tag in g[lk]:
            _close(g[lk][tag], g_ref[lk][tag], 5e-5)
    # the reference's memory bounds change no value
    l_plain = ref.loss(params, jnp.asarray(data),
                       jnp.asarray(lab, jnp.int32), TINY)
    assert abs(float(l_plain) - float(l_ref)) < 1e-6


_REFERENCE_STEPS = {}


def _reference_two_steps(params, data, lab):
    """The reference's two Adam steps, made once for every case (the
    seeded start is the same float32 masters)."""
    if not _REFERENCE_STEPS:
        after, losses = jax.jit(lambda p: ref.train_steps(
            p, jnp.asarray(data), jnp.asarray(lab, jnp.int32), TINY, 2,
            lr=0.01))(params)
        _REFERENCE_STEPS.update(after=after, losses=losses, start=params)
    for k, v in _REFERENCE_STEPS["start"].items():
        for tag in v:
            assert np.array_equal(v[tag], params[k][tag])
    return _REFERENCE_STEPS["after"], _REFERENCE_STEPS["losses"]


@pytest.mark.parametrize("dtype,chips,tol_loss,tol_step", [
    ("float32", 1, 1e-4, 2e-2), ("float32", 4, 1e-4, 2e-2),
    ("bfloat16", 4, 0.03, 0.8)])
def test_two_adam_steps_through_run_steps_match_the_reference(
        dtype, chips, tol_loss, tol_step):
    """As the siblings': the second step's loss and the parameters after
    it, as a share of how far the reference moved, on one device and with
    the experts over four (bfloat16 at toy widths and sigma 0.3 reads well
    under the 1 of a state left unchanged; the published widths' reading
    is the chip's, PERF.md)."""
    t = _trainer(dtype, chips)
    data, lab = _batch()
    params = _host(t)
    t.run_steps(DataBatch(data=data, label=lab), 2)
    after, losses = _reference_two_steps(params, data, lab)
    assert abs(t.last_loss - float(losses[1])) < tol_loss * float(losses[1])
    assert float(losses[1]) < float(losses[0])
    num = sum(float(jnp.sum((t.params[k][g] - after[k][g]) ** 2))
              for k in after for g in after[k])
    den = sum(float(jnp.sum((params[k][g] - after[k][g]) ** 2))
              for k in after for g in after[k])
    assert (num / den) ** 0.5 < tol_step
    assert t.update_counter == 2


def test_the_seeded_weights_are_the_same_on_one_device_and_on_four():
    """``FuncNet.init_on`` makes each tensor on its chips in one program:
    the experts' over the axis, the rest replicated, and the values do not
    depend on the mesh."""
    one, four = _trainer(chips=1), _trainer(chips=4)
    assert four.params["l0_moe"]["egate"].sharding.spec[0] == "data"
    assert len(four.params["l0_moe"]["egate"].addressable_shards) == 4
    assert four.params["l0_moe"]["egate"].addressable_shards[0].data.shape \
        == (2, 32, 24)
    assert four.params["l0_attn"]["wq"].sharding.is_fully_replicated
    for a, b in zip(jax.tree_util.tree_leaves(_host(one)),
                    jax.tree_util.tree_leaves(_host(four))):
        assert np.array_equal(a, b)


# -- records ---------------------------------------------------------------------


def test_the_records_count_the_expert_axis_and_the_exchange():
    """The ``layout`` record's expert axis, and a ``moe`` record a
    dispatch whose layers say what crossed: every pick received (held
    share 1), none dropped, each token's row sent once to each other
    chip, picks received a chip, the token rows a chip receives in one
    exchange and the share of them that carry a pick for its experts."""
    t = _trainer("bfloat16", 4)
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    data, lab = _batch()
    t.run_steps(DataBatch(data=data, label=lab), 2)
    validate_records(sink.records)
    (layout,) = [r for r in sink.records if r["event"] == "layout"]
    assert layout["expert_axis_size"] == 4 and layout["moe_layers"] == 4
    (moe,) = [r for r in sink.records if r["event"] == "moe"]
    assert moe["dropped"] == 0 and moe["held_share"] == 1.0
    assert 1.0 <= moe["exchange_max_over_mean"] < 4.0
    picks = 4 * T * 3
    for lk, v in moe["layers"].items():
        assert v["received_mean"] * 4 == picks
        assert v["received_min"] <= v["received_mean"] <= v["received_max"]
        assert v["sent_offchip"] == 4 * T * (4 - 1)
        # a chip's 16 tokens from each of the four chips
        assert v["capacity"] == 4 * 16
        assert 0 < v["exchange_used_share"] <= 1
    # one device: the axis has one chip and nothing travels
    one = _trainer("bfloat16", 1)
    sink = MemorySink()
    one.set_monitor(Monitor(sink))
    one.run_steps(DataBatch(data=data, label=lab), 2)
    (layout,) = [r for r in sink.records if r["event"] == "layout"]
    assert layout["expert_axis_size"] == 1
    (moe,) = [r for r in sink.records if r["event"] == "moe"]
    assert all(v["sent_offchip"] == 0 and v["exchange_used_share"] == 1.0
               for v in moe["layers"].values())
    assert moe["exchange_max_over_mean"] == 1.0


# -- the benchmark's cut -----------------------------------------------------------


def test_the_cuts_parameters_and_flops():
    """The counts of the benchmark's cut (configs/mellum2_12b_a2_5b.json):
    a layer has 21,385,984 parameters outside its experts and 64 x
    6,193,152 in them; the host holds 1,727,616,256 (27.64 GB at 16 B); a
    chip 538,531,072 (8.62 GB at 16 B). On an expert axis every one of a
    token's 8 picks counts: 2.215 GFLOP a token trained."""
    g = NetGraph()
    g.configure(parse_config(mellum2_12b_a2_5b(layer_types=CUT,
                                               vocab=12288, batch_size=4)))
    net = FuncNet(g, 4)
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0))[0]
    size = {lk: {tag: int(np.prod(a.shape)) for tag, a in v.items()}
            for lk, v in shapes.items()}
    experts = {lk: sum(n for tag, n in v.items() if tag.startswith("e"))
               for lk, v in size.items() if lk.endswith("_moe")}
    assert set(experts.values()) == {64 * 6193152}
    for i in range(4):
        outside = sum(size["l%d_attn" % i].values()) \
            + size["l%d_moe" % i]["router"] \
            + size["l%d_attn_norm" % i]["wmat"] \
            + size["l%d_ffn_norm" % i]["wmat"]
        assert outside == 21385984
    total = sum(n for v in size.values() for n in v.values())
    assert total == 1727616256 and total * 16 / 1e9 == pytest.approx(
        27.64, abs=0.01)
    per_chip = total - sum(experts.values()) * 3 // 4
    assert per_chip == 538531072
    assert per_chip * 16 / 1e9 == pytest.approx(8.62, abs=0.01)
    assert per_chip * 12 / 1e9 == pytest.approx(6.46, abs=0.01)
    assert net.leading_axes() == {
        "l%d_moe" % i: {"egate": "data", "eup": "data", "edown": "data"}
        for i in range(4)}
    per_token = 3 * net.analytic_flops_per_example() / 8192
    assert per_token == pytest.approx(2.215e9, rel=1e-3)
    moe = next(l for l in net.layer_objs if hasattr(l, "grouped"))
    assert moe.flops_per_example() / 8192 == pytest.approx(
        2 * 2304 * 64 + 6 * 2304 * 896 * 8)
    assert moe.grouped
