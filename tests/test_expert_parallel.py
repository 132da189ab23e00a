"""The expert axis (layers/sequence.py: ``MoELayer`` with ``expert_axis``:
a token's row sent once to every chip, the partial sums back;
parallel/__init__.py: ``param_sharding``'s leading axes;
nnet/trainer.py: a sequence net's experts spread over the mesh's data
axis) on four virtual devices against the uncut layer on one: the
forward pass and the gradients, one sequence a chip and two in parts,
routing made as uneven as it can be with nothing dropped, the rows that
carry a pick counted on a routing made by hand, where the trainer places
the expert tensors and their Adam moments, which gradients it reduces,
and that one device lowers no all-to-all.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers import create_layer, seq_shape
from cxxnet_tpu.layers import sequence
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


def _layer(chips, score="softmax", block=4, d=D, w=W, e=E, k=K):
    cfg = [("nexpert", e), ("topk", k), ("nhidden", w), ("nshared", 0),
           ("score_func", score), ("expert_block", block)]
    if chips:
        cfg += [("expert_axis", "data")]
    layer = create_layer("moe", [(k, str(v)) for k, v in cfg])
    layer.infer_shape([seq_shape(T, d)])
    if chips:
        layer.bind_mesh(make_mesh(chips, 1, jax.devices()[:chips]))
    return layer


def _params(seed=0, d=D, w=W, e=E, k=K):
    layer = _layer(0, d=d, w=w, e=e, k=k)
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


def _uneven_state(state, toward, e=E):
    """Routing made uneven on purpose: a bias on ``topk`` experts, which
    the picks follow (the weights stay the scores')."""
    bias = jnp.zeros((e,)).at[jnp.array(toward)].set(10.0)
    return dict(state, bias=bias)


def _same(a, b):
    """Two runs' outputs and every gradient to 1e-5 (float32)."""
    _close(a[0], b[0], 1e-5)
    for x, y in zip(jax.tree_util.tree_leaves(a[2]),
                    jax.tree_util.tree_leaves(b[2])):
        _close(x, y, 1e-5)


@pytest.mark.parametrize("uneven", [False, True])
def test_four_chips_give_the_uncut_layers_values_and_gradients(uneven):
    """Float32: the output, the input's gradient and every weight's
    (router, the experts') from the layer over four chips equal the uncut
    layer's on one; so do the loads each expert got, and every pick
    arrives. With the routing made uneven (every token picks experts 0,
    2, 4: a pick a token on each of chips 0, 1, 2 and none on chip 3) too.
    Every chip sends each of its tokens' rows to the three others, and
    receives every chip's."""
    params, state = _params()
    if uneven:
        state = _uneven_state(state, (0, 2, 4))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, D))
    wt = jax.random.normal(jax.random.PRNGKey(2), (B, T, D))
    one = _run(_layer(0), params, state, x, wt)
    four = _layer(4)
    assert four.capacity(T) == 4 * T
    got = _run(four, params, state, x, wt)
    _same(got, one)
    st1, st4 = one[1], got[1]
    assert np.array_equal(st4["load"], st1["load"])
    assert int(st4["dropped"]) == 0
    assert int(st4["picks_held"]) == B * T * K == int(st1["picks_held"])
    sent, fewest, most, used = (int(v) for v in st4["exchange"])
    assert sent == B * T * 3
    assert fewest + most <= B * T * K and most >= B * T * K // 4
    if uneven:
        # chips 0, 1, 2 each take one pick a token, chip 3 none: of the
        # 4 x B x T rows received, chip 3's B x T carry nothing
        assert (fewest, most) == (0, B * T)
        assert [int(st4["load"][e]) for e in (0, 2, 4)] == [B * T] * 3
        assert used == 3 * B * T
    else:
        assert B * T <= used <= 4 * B * T


def test_two_sequences_a_chip_in_parts_give_the_uncut_layers_values(
        monkeypatch):
    """Eight sequences on four chips, a chip's 32 tokens exchanged in four
    parts of 8 (``EXCHANGE_ROWS`` cut to 96 picks: 8 tokens x 3 picks from
    each of four chips; a chip receives 4 x 8 token rows a part), each
    made again in the backward pass: the output, every gradient, the
    loads and the counters are the uncut layer's."""
    monkeypatch.setattr(sequence, "EXCHANGE_ROWS", 4 * 8 * K)
    params, state = _params()
    x = jax.random.normal(jax.random.PRNGKey(1), (2 * B, T, D))
    wt = jax.random.normal(jax.random.PRNGKey(2), (2 * B, T, D))
    one = _run(_layer(0), params, state, x, wt)
    four = _layer(4)
    assert four.part(2 * T) == 8 and four.capacity(2 * T) == 4 * 8
    got = _run(four, params, state, x, wt)
    _same(got, one)
    st1, st4 = one[1], got[1]
    assert np.array_equal(st4["load"], st1["load"])
    assert int(st4["dropped"]) == 0
    assert int(st4["picks_held"]) == 2 * B * T * K
    sent, fewest, most, used = (int(v) for v in st4["exchange"])
    assert fewest <= 2 * B * T * K // 4 <= most
    assert sent == 2 * B * T * 3 and 2 * B * T <= used <= 4 * 2 * B * T


def test_the_most_uneven_routing_drops_no_pick():
    """Every token picking experts 0, 1, 2 sends two of its three picks to
    chip 0 and one to chip 1: chip 0's experts take 32 of a chip's 48
    picks, and each lands."""
    params, state = _params()
    state = _uneven_state(state, (0, 1, 2))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, D))
    ones = jnp.ones((B, T, D))
    got = _run(_layer(4), params, state, x, ones)
    st = got[1]
    assert int(st["dropped"]) == 0
    assert int(st["picks_held"]) == B * T * K
    sent, fewest, most, used = (int(v) for v in st["exchange"])
    assert most == 2 * B * T and fewest == 0 and used == 2 * B * T
    assert [int(st["load"][e]) for e in (0, 1, 2)] == [B * T] * 3
    _same(got, _run(_layer(0), params, state, x, ones))


def test_every_pick_on_one_chip_drops_nothing():
    """Top-4 of 16 experts, 4 a chip, every token's four picks on chip 0's
    experts: chip 0 takes every pick of every chip (the case a block of one
    row a pick had to be four times the even share for), the others none;
    nothing is dropped, and the output and every gradient are the uncut
    layer's."""
    e, k = 16, 4
    params, state = _params(e=e, k=k)
    state = _uneven_state(state, (0, 1, 2, 3), e=e)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, D))
    wt = jax.random.normal(jax.random.PRNGKey(2), (B, T, D))
    got = _run(_layer(4, e=e, k=k), params, state, x, wt)
    _same(got, _run(_layer(0, e=e, k=k), params, state, x, wt))
    st = got[1]
    assert int(st["dropped"]) == 0
    assert int(st["picks_held"]) == B * T * k
    assert [int(v) for v in st["load"]] == [B * T] * 4 + [0] * 12
    sent, fewest, most, used = (int(v) for v in st["exchange"])
    assert (sent, fewest, most, used) == (B * T * 3, 0, B * T * k, B * T)


def test_the_rows_that_carry_a_pick_are_counted_exactly():
    """A routing made by hand: the router reads features 0 .. 7 as the
    experts' scores, and half the tokens pick experts 0, 1, 2 (chips 0 and
    1), the other half 1, 3, 7 (chips 0, 1 and 3). Of the 4 x B x T token
    rows the chips receive, 2.5 x B x T carry a pick for the receiving
    chip: a share of 0.625, which the trainer reports as
    ``exchange_used_share``."""
    params, state = _params()
    params = dict(params, router=10.0 * jnp.eye(D, E))
    half = jnp.arange(B * T).reshape(B, T) % 2 == 0
    x = jnp.where(half[..., None],
                  jnp.zeros(D).at[jnp.array([0, 1, 2])].set(1.0),
                  jnp.zeros(D).at[jnp.array([1, 3, 7])].set(1.0))
    wt = jax.random.normal(jax.random.PRNGKey(2), (B, T, D))
    got = _run(_layer(4), params, state, x, wt)
    _same(got, _run(_layer(0), params, state, x, wt))
    st = got[1]
    assert [int(v) for v in st["load"]] == \
        [B * T // 2, B * T, B * T // 2, B * T // 2, 0, 0, 0, B * T // 2]
    sent, fewest, most, used = (int(v) for v in st["exchange"])
    assert used == 5 * B * T // 2
    assert used / (4 * B * T) == 0.625
    # per chip: chip 0 and 1 every row, chip 3 half, chip 2 none
    assert (fewest, most) == (0, 3 * B * T // 2)


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
