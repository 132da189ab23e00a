"""The expert axis (layers/sequence.py: ``MoELayer`` with ``expert_axis``,
``exchange_plan``; parallel/__init__.py: ``param_sharding``'s leading
axes; nnet/trainer.py: a sequence net's experts spread over the mesh's
data axis) on four virtual devices against the uncut layer on one: the
forward pass and the gradients, one sequence a chip and two in parts,
routing made as uneven as it can be with nothing dropped, where the trainer places the
expert tensors and their Adam moments, which gradients it reduces, and
that one device lowers no all-to-all.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers import create_layer, seq_shape
from cxxnet_tpu.layers import sequence
from cxxnet_tpu.layers.sequence import exchange_plan
from cxxnet_tpu.models import mellum2_tiny
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.parallel import make_mesh
from cxxnet_tpu.utils.config import parse_config

B, T, D, W, E, K = 4, 16, 32, 24, 8, 3


def _close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
        np.abs(a - b).max()


def _layer(chips, score="softmax", block=4, d=D, w=W):
    cfg = [("nexpert", E), ("topk", K), ("nhidden", w), ("nshared", 0),
           ("score_func", score), ("expert_block", block)]
    if chips:
        cfg += [("expert_axis", "data")]
    layer = create_layer("moe", [(k, str(v)) for k, v in cfg])
    layer.infer_shape([seq_shape(T, d)])
    if chips:
        layer.bind_mesh(make_mesh(chips, 1, jax.devices()[:chips]))
    return layer


def _params(seed=0, d=D, w=W):
    layer = _layer(0, d=d, w=w)
    return layer.init_params(jax.random.PRNGKey(seed)), layer.init_state()


def _run(layer, params, state, x, wt):
    """The layer's output, its new state, and the gradients of a weighted
    sum of the output in every parameter and the input, in one program."""
    def f(p, x):
        y, st = layer.forward(p, state, [x], True, None)
        return jnp.sum(wt * y[0].astype(jnp.float32)), (y[0], st)

    with jax.default_matmul_precision("highest"):
        (_, (y, st)), g = jax.jit(jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True))(params, x)
    return y, st, g


def _uneven_state(state, toward):
    """Routing made uneven on purpose: a bias on three experts, which the
    picks follow (the weights stay the scores')."""
    bias = jnp.zeros((E,)).at[jnp.array(toward)].set(10.0)
    return dict(state, bias=bias)


@pytest.mark.parametrize("uneven", [False, True])
def test_four_chips_give_the_uncut_layers_values_and_gradients(uneven):
    """Float32: the output, the input's gradient and every weight's
    (router, the experts') from the layer over four chips equal the uncut
    layer's on one; so do the loads each expert got, and every pick
    arrives. With the routing made uneven (every token picks experts 0,
    2, 4: a pick a token on each of chips 0, 1, 2 and none on chip 3) too.
    """
    params, state = _params()
    if uneven:
        state = _uneven_state(state, (0, 2, 4))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, D))
    wt = jax.random.normal(jax.random.PRNGKey(2), (B, T, D))
    y1, st1, g1 = _run(_layer(0), params, state, x, wt)
    four = _layer(4)
    y4, st4, g4 = _run(four, params, state, x, wt)
    _close(y4, y1, 1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g4),
                    jax.tree_util.tree_leaves(g1)):
        _close(a, b, 1e-5)
    assert np.array_equal(st4["load"], st1["load"])
    assert int(st4["dropped"]) == 0
    assert int(st4["picks_held"]) == B * T * K == int(st1["picks_held"])
    sent, fewest, most = (int(v) for v in st4["exchange"])
    assert fewest + most <= B * T * K and most >= B * T * K // 4
    if uneven:
        # chips 0, 1, 2 each take one pick a token of all 64, chip 3 none
        assert (fewest, most) == (0, B * T)
        assert [int(st4["load"][e]) for e in (0, 2, 4)] == [B * T] * 3
    # each chip keeps the picks for its own experts: the rest cross
    assert 0 < sent < B * T * K


def test_two_sequences_a_chip_in_parts_give_the_uncut_layers_values(
        monkeypatch):
    """Eight sequences on four chips, a chip's 32 tokens exchanged in four
    parts of 8 (``EXCHANGE_ROWS`` cut to 96 rows: 8 tokens x 3 picks from
    each of four chips), each made again in the backward pass: the output,
    every gradient, the loads and the counters are the uncut layer's."""
    monkeypatch.setattr(sequence, "EXCHANGE_ROWS", 4 * 8 * K)
    params, state = _params()
    x = jax.random.normal(jax.random.PRNGKey(1), (2 * B, T, D))
    wt = jax.random.normal(jax.random.PRNGKey(2), (2 * B, T, D))
    y1, st1, g1 = _run(_layer(0), params, state, x, wt)
    four = _layer(4)
    assert four.part(2 * T) == 8 and four.capacity(2 * T) == 8 * K
    y4, st4, g4 = _run(four, params, state, x, wt)
    _close(y4, y1, 1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g4),
                    jax.tree_util.tree_leaves(g1)):
        _close(a, b, 1e-5)
    assert np.array_equal(st4["load"], st1["load"])
    assert int(st4["dropped"]) == 0
    assert int(st4["picks_held"]) == 2 * B * T * K
    sent, fewest, most = (int(v) for v in st4["exchange"])
    assert fewest <= 2 * B * T * K // 4 <= most and 0 < sent < 2 * B * T * K


def test_the_most_uneven_routing_drops_no_pick():
    """A destination's block holds every pick of a part: every token
    picking experts 0, 1, 2 sends two of its three picks to chip 0, 32 of
    a chip's 48, and each lands (the plan would drop past a smaller
    block: ``test_the_exchange_plan_fills_each_destination_in_order``)."""
    params, state = _params()
    state = _uneven_state(state, (0, 1, 2))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, D))
    layer = _layer(4)
    assert layer.capacity(T) == T * K
    y, st, _ = _run(layer, params, state, x, jnp.ones((B, T, D)))
    assert int(st["dropped"]) == 0
    assert int(st["picks_held"]) == B * T * K
    sent, fewest, most = (int(v) for v in st["exchange"])
    assert most == 2 * B * T and fewest == 0
    assert [int(st["load"][e]) for e in (0, 1, 2)] == [B * T] * 3
    y1, _, _ = _run(_layer(0), params, state, x, jnp.ones((B, T, D)))
    _close(y, y1, 1e-5)


def test_the_exchange_plan_fills_each_destination_in_order():
    picks = jnp.array([[0, 3], [2, 1], [3, 0], [1, 2]])   # 2 experts a chip
    slot, src, want = exchange_plan(picks, 0, 2, 2, 3)
    # destination 0 takes experts 0, 1: picks 0, 3, 5, 6 in that order,
    # the fourth past its 3 rows
    assert want.tolist() == [4, 4]
    assert slot.tolist() == [0, 3, 4, 1, 5, 2, 6, 6]
    assert src.tolist() == [0, 3, 5, 1, 2, 4]
    # experts off the axis (a program holding 2 .. 5 of them) go nowhere
    slot, _, want = exchange_plan(picks, 2, 1, 2, 3)
    assert want.tolist() == [2, 2] and slot.tolist()[0] == 6


def test_the_grouped_kernels_run_on_the_received_rows():
    """Widths of whole lanes: the chips' received rows go through the
    grouped kernels (interpreted here), not the loop, and give the uncut
    layer's output."""
    params, state = _params(d=128, w=128)
    layer = _layer(4, block=128, d=128, w=128)
    assert layer.grouped
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, 128))
    y4, st4 = layer.forward(params, dict(state), [x], True, None)
    y1, st1 = _layer(0, block=128, d=128, w=128).forward(
        params, dict(state), [x], True, None)
    _close(y4[0], y1[0], 1e-5)
    assert int(st4["grouped"]) == 1 and int(st4["dropped"]) == 0


def _trainer(chips, dtype="float32"):
    t = NetTrainer(parse_config(mellum2_tiny()) + [
        ("dtype", dtype), ("seed", "3"), ("silent", "1")],
        mesh=make_mesh(chips, 1, jax.devices()[:chips]))
    t.init_model()
    return t


def _hlo(t):
    t.precompile(n_steps=2, per_batch=False)
    (key,) = [k for k in t.programs.aot if k[0] == "run_steps"]
    return t.programs.aot[key].as_text()


def test_the_trainer_shards_the_experts_and_reduces_the_rest():
    """On four devices the expert tensors and both Adam moments lie on the
    data axis, two experts a chip, everything else replicated; the step
    holds all-to-alls and an all-reduce, and no all-reduce over the chips
    carries an expert tensor (a chip owns its experts' gradients). One
    step lands where the one-device trainer's does: the replicated
    leaves' gradients are the sum over the chips."""
    four = _trainer(4)
    for tag in ("egate", "eup", "edown"):
        w = four.params["l0_moe"][tag]
        assert w.sharding.spec[0] == "data"
        assert w.addressable_shards[0].data.shape[0] == 2
        for m in four.opt_state["l0_moe"][tag].values():
            assert m.sharding.spec[0] == "data"
            assert m.addressable_shards[0].data.shape[0] == 2
    for lk, tag in (("l0_moe", "router"), ("l0_attn", "wq"), ("head", "wmat"),
                    ("embed", "wmat")):
        assert four.params[lk][tag].sharding.is_fully_replicated
        assert four.opt_state[lk][tag]["m_w1"].sharding.is_fully_replicated
    hlo = _hlo(four)
    assert re.search(r"all-to-all(-start)?\(", hlo)
    reduces = [ln for ln in hlo.splitlines()
               if re.search(r"all-reduce(-start)?\(", ln)
               and not re.search(r"replica_groups=\{\{\d+\}(,\{\d+\})*\}",
                                 ln)]
    assert reduces
    assert not any(re.search(r"\[(8|2),(32,24|24,32)\]", ln)
                   for ln in reduces)
    one = _trainer(1)
    ids = np.random.RandomState(0).randint(0, 64, (4, T + 1))
    batch = DataBatch(data=ids[:, :T].astype(np.int32),
                      label=ids[:, 1:].astype(np.float32))
    for t in (one, four):
        t.run_steps(batch, 1)
    # (Adam's first step moves an element by about lr = 0.01 whatever its
    # gradient's size, so a gradient that missed the other chips' parts
    # would be off by up to 0.02; float32 sums in another order leave a
    # few 1e-4 where a gradient is near eps)
    for lk in one.params:
        for tag in one.params[lk]:
            _close(four.params[lk][tag], one.params[lk][tag], 1e-3)


def test_one_device_lowers_no_all_to_all():
    """The same net on one device takes the layer's path without the
    exchange: no all-to-all and no all-reduce in its step."""
    hlo = _hlo(_trainer(1))
    assert not re.search(r"all-to-all(-start)?\(", hlo)
    assert not re.search(r"all-reduce(-start)?\(", hlo)
