"""The routed experts as grouped kernels (layers/pallas_kernels.py:
experts_forward / experts_backward) against the loop a block at a time
that stays beside them (layers/sequence.py: grouped_swiglu), in
interpret mode: values and the five gradients under even and uneven
routing, the fallback when a step's routing needs more rows than the
kernels' buffers hold, the shape gate, and an expert layer and a whole
trainer on each schedule. That the kernels compile for the chip at the
language-model cell's shapes is tests/test_chip_compile.py's job.

Interpreted Pallas takes the traced grid bound on the CPU (and fills
what a kernel does not write with NaN), so the kernels run here under
the same ``nb`` as on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.layers import create_layer, pallas_kernels as pk, seq_shape
from cxxnet_tpu.layers.sequence import dispatch_plan, grouped_swiglu
from cxxnet_tpu.models.kimi_vl import decoder_lm
from cxxnet_tpu.monitor import MemorySink, Monitor
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config

TOKENS, D, W, HELD, NEXPERT, TOPK, BLOCK = 256, 128, 256, 4, 16, 2, 128
NAMES = ("out", "dx", "dwgate", "dwup", "dwdown", "dcw")


def _picks(routing):
    """(tokens, topk) expert ids over all ``NEXPERT``; the held ones are
    0..3. ``even``: random. ``one_expert``: every token's first pick is
    expert 0 (two full blocks) and its second lands anywhere.
    ``empty_expert``: no pick on held expert 2. ``exact_multiple``:
    expert 1 gets exactly one block of picks and expert 3 exactly two,
    no padding row in either."""
    rng = np.random.RandomState(5)
    picks = np.stack([rng.permutation(NEXPERT)[:TOPK]
                      for _ in range(TOKENS)])
    if routing == "one_expert":
        picks[:, 0] = 0
        picks[:, 1] = rng.randint(1, NEXPERT, TOKENS)
    if routing == "empty_expert":
        picks[picks == 2] = 9
    if routing == "exact_multiple":
        picks[:, 0] = np.where(np.arange(TOKENS) < BLOCK, 1, 8)
        picks[:, 1] = 3
    return jnp.asarray(picks, jnp.int32)


def _operands(dtype, routing):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (TOKENS, D), jnp.float32).astype(dtype)
    ws = [(jax.random.normal(k, s, jnp.float32) * 0.1).astype(dtype)
          for k, s in zip(ks[1:4], [(HELD, D, W), (HELD, D, W),
                                    (HELD, W, D)])]
    weights = jax.random.uniform(ks[4], (TOKENS, TOPK), jnp.float32)
    g = jax.random.normal(ks[5], (TOKENS, D), jnp.float32)
    plan = dispatch_plan(_picks(routing), weights, 0, HELD, BLOCK)
    return (x, *ws), plan, g


def _value_and_grads(args, plan, g, budget):
    tok, cw, expert, nb, _ = plan
    out, vjp = jax.vjp(
        lambda x, wg, wu, wd, cw: grouped_swiglu(
            x, wg, wu, wd, cw, tok, expert, nb, BLOCK, budget), *args, cw)
    return (out,) + vjp(g)


def _assert_close(got, want, tol):
    for name, a, b in zip(NAMES, got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30), \
            (name, np.abs(a - b).max(), np.abs(b).max())


# float32: the two schedules are the same function of the same rows and
# differ by the order in which an expert's blocks enter its weight
# gradients and by the kernel's ``dh = cw * (g Wdown^T)`` where the loop
# rounds ``cw * g`` first (1e-5 of the largest value holds both).
# bfloat16: each schedule rounds its hidden rows, ``da``, ``du`` and the
# weight gradients to bfloat16 once, 2^-8 relative each, and the kernel
# rounds ``cw * h`` where the loop rounds ``cw * g``: 1 % of the largest
# value holds them. A budget of 6 blocks is under the plan's 8, so the
# schedule is chosen on the device by the blocks in use, as in the cell.
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("routing", ["even", "one_expert", "empty_expert",
                                     "exact_multiple"])
def test_grouped_kernels_match_the_block_loop(routing, dtype, tol):
    args, plan, g = _operands(dtype, routing)
    load = np.asarray(plan[4])
    assert {"even": load.min() > 0, "one_expert": load[0] == TOKENS,
            "empty_expert": load[2] == 0 and load.sum() > 0,
            "exact_multiple": (load[1], load[3]) == (BLOCK, 2 * BLOCK)
            }[routing], load
    assert int(plan[3]) <= 6
    _assert_close(_value_and_grads(args, plan, g, 6),
                  _value_and_grads(args, plan, g, 0), tol)


def _scatter_plan(picks, weights, first, count, block):
    """``dispatch_plan`` as it was before its plan became integers: the
    token ids and the combine weights scattered into the rows."""
    n, k = picks.shape
    rows = (-(-n * k // block) + count) * block
    flat = picks.reshape(-1) - first
    held = (flat >= 0) & (flat < count)
    onehot = (flat[:, None] == jnp.arange(count)[None, :]).astype(jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    load = jnp.sum(onehot, axis=0)
    nblk = (load + block - 1) // block
    ends = jnp.cumsum(nblk)
    start = (ends - nblk) * block
    dest = jnp.where(held, start[jnp.clip(flat, 0, count - 1)] + rank, rows)
    tok = jnp.full((rows,), n, jnp.int32).at[dest].set(
        jnp.arange(n * k, dtype=jnp.int32) // k, mode="drop")
    cw = jnp.zeros((rows,), jnp.float32).at[dest].set(
        weights.reshape(-1).astype(jnp.float32), mode="drop")
    expert = jnp.clip(jnp.searchsorted(
        ends, jnp.arange(rows // block), side="right"), 0, count - 1)
    return tok, cw, expert.astype(jnp.int32), ends[-1].astype(jnp.int32), load


def _routed(routing):
    """(picks, weights, first) of a routing case; ``HELD`` experts from
    ``first``. ``ties``: top-k of scores with three values, so that most
    picks are decided by the lower index, and their weights tie too."""
    rng = np.random.RandomState(9)
    weights = jnp.asarray(rng.uniform(size=(TOKENS, TOPK)), jnp.float32)
    if routing == "ties":
        scores = jnp.asarray(rng.randint(0, 3, (TOKENS, NEXPERT)) / 4.0,
                             jnp.float32)
        weights, picks = jax.lax.top_k(scores, TOPK)
        return picks, weights, 0
    if routing == "off_held":
        return _picks("even") % (NEXPERT - HELD) + HELD, weights, 0
    if routing == "first":
        return _picks("even"), weights, 5
    return _picks(routing), weights, 0


@pytest.mark.parametrize("routing", ["even", "one_expert", "off_held",
                                     "first", "ties"])
def test_the_integer_plan_is_the_scatters_to_the_bit(routing):
    """The plan of a row's pick and a pick's row gives the scatter form's
    token ids, combine weights, blocks, count and loads exactly, and the
    gradient of ``sum(cw * r)`` in the weights is the scatter's own."""
    picks, weights, first = _routed(routing)
    got = dispatch_plan(picks, weights, first, HELD, BLOCK)
    want = _scatter_plan(picks, weights, first, HELD, BLOCK)
    load = np.asarray(want[4])
    assert {"even": load.min() > 0, "one_expert": load[0] == TOKENS,
            "off_held": load.sum() == 0, "first": load.min() > 0,
            "ties": load.sum() > TOKENS}[routing], load
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    r = jax.random.normal(jax.random.PRNGKey(3), got[1].shape)

    def grad(plan):
        return jax.grad(lambda w: jnp.sum(plan(picks, w, first, HELD,
                                               BLOCK)[1] * r))(weights)

    g = grad(dispatch_plan)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(
        grad(_scatter_plan)))
    assert (routing == "off_held") == (not np.asarray(g).any())


def test_the_whole_plan_as_budget_needs_no_branch():
    """A budget of all the plan's blocks (a layer that holds every
    expert) runs the kernels alone, to the same values: the only loops
    with a traced trip count left are the two gathers' (of ``x`` and of
    the cotangent, eight blocks a trip as far as the blocks in use
    reach). Under a smaller budget each direction has more: the kernels'
    schedule and the loop's, each the body of a loop of one trip or none
    (not ``lax.cond``: layers/sequence.py), and the loop over the blocks
    itself."""
    args, plan, g = _operands("float32", "even")
    blocks = plan[2].shape[0]

    def whiles(budget):
        text = jax.make_jaxpr(
            lambda: _value_and_grads(args, plan, g, budget))().pretty_print()
        assert "pallas_call" in text and "cond[" in text   # ``pl.when``
        return text.count("while[")

    assert whiles(6) > whiles(blocks) == 2
    _assert_close(_value_and_grads(args, plan, g, blocks),
                  _value_and_grads(args, plan, g, 0), 1e-5)


@pytest.mark.parametrize("routing", ["even", "one_expert"])
def test_blocks_over_the_budget_take_the_loop(routing):
    """A step whose routing needs more blocks than the kernels' buffers
    hold takes the loop, forward and backward, and loses nothing: the
    values are the loop's own to the bit."""
    args, plan, g = _operands("float32", routing)
    assert int(plan[3]) > 2
    got = _value_and_grads(args, plan, g, 2)
    want = _value_and_grads(args, plan, g, 0)
    for name, a, b in zip(NAMES, got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


@pytest.mark.parametrize("d,w,block,dtype,fits", [
    (2048, 1408, 512, "bfloat16", True),     # the language-model cell's
    (32, 24, 8, "float32", False),           # the tiny model's
    (128, 256, 128, "float32", True),        # this file's
    (2048, 1400, 512, "bfloat16", False),    # a width off the lanes
    (2048, 1408, 64, "bfloat16", False),     # a block off the lanes
    (2048, 1408, 512, "float32", False),     # three float32 matrices
                                             # twice over: past the VMEM
    (0, 1408, 512, "bfloat16", False),
])
def test_applicable_is_a_function_of_the_shapes(d, w, block, dtype, fits):
    assert pk.grouped_experts_applicable(d, w, block, dtype) is fits


MOE = dict(nexpert=NEXPERT, topk=TOPK, nhidden=W, nshared=1,
           routed_scaling_factor=2.0, expert_first=0, expert_count=HELD,
           expert_block=BLOCK, bias_sigma=0.01)


def _moe_layer(**over):
    layer = create_layer("moe", [(k, str(v))
                                 for k, v in dict(MOE, **over).items()])
    layer.infer_shape([seq_shape(TOKENS // 2, D)])
    return layer


def test_the_layer_takes_the_schedule_its_shapes_and_routing_allow():
    """Tiling shapes: the kernels under the layer's ``experts`` scope,
    with buffers for three times the even share plus a block an expert
    (7 of the plan's 8 blocks here, 80 of 200 in the cell); the same layer
    held to the loop gives the same values and gradients. A budget the
    routing outgrows: the loop, the same values, and the counter says
    so. The tiny model's widths: the loop alone."""
    assert not _moe_layer(nhidden=24, expert_block=8).grouped
    assert _moe_layer(nhidden=24, expert_block=8).budget(TOKENS) == 0
    cell = create_layer("moe", [(k, str(v)) for k, v in dict(
        MOE, nexpert=64, topk=6, nhidden=1408, expert_count=8,
        expert_block=512).items()] + [("dtype", "bfloat16")])
    cell.infer_shape([seq_shape(8192, 2048)])
    assert cell.grouped and cell.budget(2 * 8192) == 80
    # every expert held: the plan's own bound
    assert _moe_layer(nexpert=4).budget(TOKENS) == TOKENS * TOPK // BLOCK + 4
    layer = _moe_layer()
    assert layer.grouped and layer.budget(TOKENS) == 7
    params = layer.init_params(jax.random.PRNGKey(1))
    state = layer.init_state()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, TOKENS // 2, D))

    def loss(p, x):
        (y,), st = layer.forward(p, state, [x], True, None)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), st

    text = jax.jit(jax.grad(loss, has_aux=True)).lower(params, x) \
        .as_text(debug_info=True)
    assert "experts" in text
    run = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    with jax.default_matmul_precision("highest"):
        (got, st), got_grads = run(params, x)
        small = layer.budget
        layer.budget = lambda tokens: 1
        (over, st_over), over_grads = run(params, x)
        layer.budget = small
        layer.grouped = False
        (want, st_loop), want_grads = run(params, x)
    assert [int(s["grouped"]) for s in (st, st_over, st_loop)] == [1, 0, 0]
    assert int(st["dropped"]) == 0
    for a, b, c in zip(jax.tree.leaves((got, got_grads)),
                       jax.tree.leaves((want, want_grads)),
                       jax.tree.leaves((over, over_grads))):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.abs(a - b).max() <= 1e-4 * max(1.0, np.abs(b).max())
        assert np.array_equal(np.asarray(c, np.float64), b)


def _lm_trainer(hidden, width, block):
    conf = decoder_lm(
        vocab=32, hidden=hidden, num_layers=3, first_k_dense=1, nhead=2,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        kv_lora_rank=16, rope_theta=800000.0, rms_norm_eps=1e-5,
        dense_width=48, expert_width=width, n_routed_experts=8,
        experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=2.0,
        experts_held=2, expert_first=0, seq_len=64, batch_size=2,
        q_block=32, expert_block=block, loss_chunk=64, bias_sigma=0.01,
        init_sigma=0.1, lr=0.01)
    t = NetTrainer(parse_config(conf) + [("silent", "1"), ("seed", "3")])
    t.init_model()
    return t


def _monitored(t):
    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    return sink


@pytest.mark.parametrize("hidden,width,block,grouped", [
    (128, 128, 128, 2), (32, 24, 8, 0)])
def test_the_records_say_which_schedule_ran(hidden, width, block, grouped):
    """A decoder with two expert layers through the trainer's step and
    ``remat = block``: the ``layout`` record counts the expert layers
    and those on the grouped kernels, and each dispatch's ``moe`` record
    gives the share of its passes that took them: all where the shapes
    tile (and the same loss as the same net held to the loop), none at
    the tiny model's widths."""
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.monitor.schema import validate_record
    t = _lm_trainer(hidden, width, block)
    sink = _monitored(t)
    (layout,) = [r for r in sink.records if r["event"] == "layout"]
    assert not validate_record(layout)
    assert (layout["moe_layers"], layout["moe_grouped_layers"]) == (2, grouped)
    ids = np.random.RandomState(0).randint(0, 32, (2, 65))
    batch = DataBatch(data=ids[:, :-1].astype(np.int32),
                      label=ids[:, 1:].astype(np.float32))
    with jax.default_matmul_precision("highest"):
        t.update(batch)
        t.update(batch)
    records = [r for r in sink.records if r["event"] == "moe"]
    assert len(records) == 2 and not any(map(validate_record, records))
    assert [r["grouped_share"] for r in records] == [grouped / 2.0] * 2
    assert all(r["dropped"] == 0 for r in records)
    if grouped:
        plain = _lm_trainer(hidden, width, block)
        for layer in plain.net.layer_objs:
            if hasattr(layer, "grouped"):
                layer.grouped = False
        with jax.default_matmul_precision("highest"):
            plain.update(batch)
            plain.update(batch)
        assert np.isfinite(t.last_loss) and abs(
            t.last_loss - plain.last_loss) <= 1e-4 * abs(plain.last_loss)


def test_a_convnet_has_no_expert_layer_to_count():
    from cxxnet_tpu.models import alexnet
    t = NetTrainer(parse_config(alexnet(nclass=10, batch_size=2,
                                        image_size=67))
                   + [("silent", "1")])
    t.init_model()
    (layout,) = [r for r in _monitored(t).records if r["event"] == "layout"]
    assert (layout["moe_layers"], layout["moe_grouped_layers"]) == (0, 0)
