"""Qwen3-Next's layers (layers/sequence.py: ``gated_delta``,
``gqa_attention``'s ``rope_dim``, ``moe``'s ``score_func`` and
``shared_gate``) against the plain reference
(cxxnet_tpu/reference/qwen3_next.py): the chunked delta rule against the
recurrence a position at a time, value and every gradient; the triangular
inverse, and that a block's checkpoint keeps it; the whole mixer; RoPE on
part of a head; the softmax router and the gated shared expert, with the
defaults what they were. The whole model is tests/test_qwen3_next_model.py's.
"""


import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.layers import create_layer, seq_shape
from cxxnet_tpu.layers.sequence import _solve_unit_lower, gated_delta_rule
from cxxnet_tpu.reference import qwen3_next as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the reference's names for the sizes qwen3_next_tiny builds
TINY = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=4,
    full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, partial_rotary_factor=0.5, rope_theta=1e7,
    rms_norm_eps=1e-6, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=6, linear_conv_kernel_dim=4,
    moe_intermediate_size=24, shared_expert_intermediate_size=24,
    num_experts=8, num_experts_per_tok=3, norm_topk_prob=True)
T, D = 16, 32


def _layer(kind, cfg, in_shape, seed=0):
    layer = create_layer(kind, [(k, str(v)) for k, v in cfg.items()])
    layer.infer_shape([in_shape])
    return layer, layer.init_params(jax.random.PRNGKey(seed)), \
        layer.init_state()


def _close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
        np.abs(a - b).max()


def _x(seed=1, batch=2, t=T):
    return jax.random.normal(jax.random.PRNGKey(seed), (batch, t, D))


def _both(fn, w):
    """fn's value and its gradients in every argument, jitted."""
    return jax.jit(lambda *a: (fn(*a), jax.grad(
        lambda *a: jnp.sum(w * fn(*a)), argnums=tuple(range(len(a))))(*a)))


# -- the delta rule --------------------------------------------------------------


def _rule_inputs(t, seed=0, b=2, hk=2, hv=4, dk=8, dv=6):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (ref.l2_norm(jax.random.normal(key, (b, t, hk, dk)))
            for key in ks[:2])
    v = jax.random.normal(ks[2], (b, t, hv, dv))
    # decays from almost none to exp(-20) a position, as A_log's start gives
    g = -jnp.exp(jax.random.uniform(ks[3], (b, t, hv), minval=-4, maxval=3))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    return (q / dk ** 0.5, k, v, g, beta), jax.random.normal(
        ks[5], (b, t, hv, dv))


def _recurrence(q, k, v, g, beta):
    r = v.shape[2] // q.shape[2]
    each = lambda a: jnp.repeat(a, r, axis=2)
    return jnp.stack([ref.delta_rule(each(q)[b], each(k)[b], v[b], g[b],
                                     beta[b], None, 0, False)
                      for b in range(q.shape[0])])


@pytest.mark.parametrize("t,chunk", [(16, 4), (14, 4), (128, 64), (40, 64)])
def test_chunked_delta_rule_matches_the_recurrence(t, chunk):
    """Value and the gradients in q, k, v, g and beta, float32: several
    chunks, a length no chunk divides (padded), the published chunk of 64
    (whose solve is made of four blocks of 16) over two chunks and over
    a part of one; the value heads twice the key heads."""
    args, w = _rule_inputs(t)
    with jax.default_matmul_precision("highest"):
        (of, gf), (og, gg) = _both(
            lambda *a: gated_delta_rule(*a, chunk, jnp.float32), w)(*args), \
            _both(_recurrence, w)(*args)
    _close(of, og, 5e-5)
    for a, b in zip(gf, gg):
        _close(a, b, 1e-4)
    assert float(jnp.abs(og).max()) > 0.1


def _near(a, b, tol):
    """``_close`` as a share of ``b``'s largest entry, whatever its size:
    at heads of 128 features the rule's values stay under 0.1."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * np.abs(b).max(), \
        np.abs(a - b).max() / np.abs(b).max()


# bfloat16: the XLA form itself lies 5e-3 to 8e-3 of the largest entry
# from the float32 recurrence on these inputs, value and gradients
_BF16 = 2e-2


@pytest.mark.parametrize("t,hk,hv,dtype", [
    (256, 1, 2, "float32"),      # two tiles of 128, two value heads a key head
    (640, 1, 1, "float32"),      # five tiles, a value head a key head
    (360, 2, 2, "float32"),      # no chunk divides it: padded to 384, 3 tiles
    (512, 1, 2, "float32"),      # one tile of 512: eight chunks in the kernel
    (384, 2, 4, "bfloat16"),     # the cell's dtype, three tiles
    (200, 1, 2, "bfloat16")])    # padded to 256, one tile
def test_fused_delta_rule_matches_the_recurrence(t, hk, hv, dtype):
    """The two kernels (pallas_kernels.gated_delta_scan, interpreted)
    at heads of 128 x 128 in chunks of 64: value and the gradients in q,
    k, v, g and beta. float32 against the recurrence a position at a
    time, held as the XLA form is; bfloat16 against the XLA form (the
    same products on the same roundings: the value to the last bit or
    two) and, like that form, against the recurrence."""
    from cxxnet_tpu.layers import pallas_kernels
    cd = jnp.dtype(dtype)
    args, w = _rule_inputs(t, hk=hk, hv=hv, dk=128, dv=128)
    assert pallas_kernels.gated_delta_applicable(t, 64, 128, 128, hv // hk,
                                                 cd)
    assert -(-t // 64) * 64 // pallas_kernels._delta_tile(
        -(-t // 64) * 64) == {256: 1, 640: 5, 360: 3, 512: 1, 384: 3,
                              200: 1}[t]
    rule = lambda fused: _both(lambda *a: gated_delta_rule(
        *a, 64, cd, fused).astype(jnp.float32), w)
    with jax.default_matmul_precision("highest"):
        (of, gf), (og, gg) = rule(None)(*args), _both(_recurrence, w)(*args)
        if dtype == "bfloat16":
            ox, gx = rule(False)(*args)
    if dtype == "float32":
        _near(of, og, 5e-5)
        for a, b in zip(gf, gg):
            _near(a, b, 1e-4)
    else:
        _near(of, ox, 1e-5)
        _near(of, og, _BF16)
        for a, b, c in zip(gf, gx, gg):
            _near(a, b, _BF16)
            _near(a, c, _BF16)
            _near(b, c, _BF16)
    assert float(jnp.abs(og).max()) > 0.01
    # the kernels are what ran: two calls in the gradient's program
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(gated_delta_rule(
        *a, 64, cd).astype(jnp.float32))))(*args))
    assert text.count("pallas_call") == 2 and "while" not in text


@pytest.mark.parametrize("t,chunk,dk,dv,r,dtype,takes", [
    (8192, 64, 128, 128, 2, "bfloat16", True),    # the cell's
    (8192, 64, 128, 128, 2, "float32", True),
    (8192, 128, 128, 256, 1, "bfloat16", True),
    (8180, 64, 128, 128, 2, "bfloat16", True),    # padded to 8,192
    (100, 64, 128, 128, 2, "bfloat16", True),     # padded to 128
    (128, 256, 128, 128, 1, "float32", True),     # one chunk of 128
    (8192, 64, 64, 128, 2, "bfloat16", False),    # half a lane of key
    (8192, 64, 128, 64, 2, "bfloat16", False),    # half a lane of value
    (15, 64, 128, 128, 2, "bfloat16", False),     # a chunk of 15 positions
    (8192, 48, 128, 128, 2, "bfloat16", False),   # no tile holds whole chunks
    (8192, 32, 128, 128, 2, "bfloat16", False),
    (64, 64, 128, 128, 2, "bfloat16", False),     # no tile so short
    (8000, 64, 128, 128, 2, "bfloat16", False),   # 125 chunks: no tile's
    (8192, 64, 128, 128, 2, "float16", False),
    (8192, 64, 512, 512, 4, "float32", False),    # past the kernels' VMEM
    (16, 4, 8, 6, 2, "float32", False)])          # the tiny model's
def test_gated_delta_gate(t, chunk, dk, dv, r, dtype, takes):
    from cxxnet_tpu.layers.pallas_kernels import gated_delta_applicable
    assert gated_delta_applicable(t, chunk, dk, dv, r,
                                  jnp.dtype(dtype)) is takes


def test_a_refused_shape_runs_the_xla_form_to_the_bit():
    """Where the gate refuses (64-wide heads here) the rule and the
    layer trace the program they traced before the kernels existed: no
    ``pallas_call``, the scan a loop, ``fused`` left out or False the
    same jaxpr; the layer says which form it runs."""
    args, _ = _rule_inputs(128, hk=1, hv=2, dk=64, dv=64)
    import re
    # (a custom rule prints as its functions' addresses)
    one, two = (re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(
        lambda *a: gated_delta_rule(*a, 64, jnp.bfloat16, **kw))(*args)))
        for kw in ({}, {"fused": False}))
    assert one == two and "pallas_call" not in one and "scan[" in one
    cfg = dict(nkhead=1, nvhead=2, conv_kernel=4, chunk=64, init_sigma=0.3)
    for width, fused in ((64, False), (128, True)):
        layer, p, st = _layer("gated_delta", dict(
            cfg, key_dim=width, value_dim=width), seq_shape(128, D))
        assert layer.fused_scan is fused
        assert layer.fused_conv is layer.fused_scan
        text = str(jax.make_jaxpr(lambda x: layer.forward(
            p, st, [x], True, None)[0][0])(_x(t=128)))
        assert ("pallas_call" in text) is fused


def test_a_block_keeps_the_fused_scans_outputs_and_runs_it_once():
    """Under a ``remat = block`` segment's checkpoint the forward kernel's
    three outputs (``o``, a state a chunk, ``u``) are kept by their names
    (``DELTA_SCAN_KEEPS``), so the gradient's program holds one forward
    and one backward kernel; a segment that keeps no name runs the
    forward kernel a second time. The values are the same."""
    from test_block_remat_keeps import _eqns
    from cxxnet_tpu.layers.base import BLOCK_REMAT_KEEPS, DELTA_SCAN_KEEPS
    assert set(DELTA_SCAN_KEEPS) < set(BLOCK_REMAT_KEEPS)
    args, w = _rule_inputs(128, b=1, hk=1, hv=2, dk=128, dv=128)

    def grad_of(names):
        rule = jax.checkpoint(
            lambda *a: gated_delta_rule(*a, 64, jnp.float32),
            policy=jax.checkpoint_policies.save_only_these_names(*names))
        return jax.grad(lambda *a: jnp.sum(w * rule(*a)),
                        argnums=(0, 1, 2, 3, 4))

    def kernels(names):
        return sum(1 for e in _eqns(jax.make_jaxpr(grad_of(names))(
            *args).jaxpr) if e.primitive.name == "pallas_call")

    assert kernels(BLOCK_REMAT_KEEPS) == 2 and kernels(()) == 3
    for a, b in zip(grad_of(BLOCK_REMAT_KEEPS)(*args), grad_of(())(*args)):
        _near(a, b, 1e-6)


def test_fused_gated_delta_layer_matches_the_reference():
    """The whole mixer with the kernels inside, float32, a key head
    serving two value heads of 128 over 128 positions."""
    cfg = dict(TINY, linear_num_key_heads=1, linear_num_value_heads=2,
               linear_key_head_dim=128, linear_value_head_dim=128)
    layer, p, st = _layer("gated_delta", dict(
        nkhead=1, nvhead=2, key_dim=128, value_dim=128, conv_kernel=4,
        chunk=64, eps=1e-6, init_sigma=0.3), seq_shape(128, D))
    assert layer.fused_scan and layer.fused_conv
    x, w = _x(t=128), _x(9, t=128)
    plain = lambda p, x: jnp.stack([ref.gated_delta_net(
        p, x[b], cfg, None, 0, False) for b in range(x.shape[0])])
    with jax.default_matmul_precision("highest"):
        (yf, gf), (yg, gg) = _both(
            lambda p, x: layer.forward(p, st, [x], True, None)[0][0],
            w)(p, x), _both(plain, w)(p, x)
    _close(yf, yg, 2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gg)):
        _close(a, b, 1e-4)


@pytest.mark.parametrize("t,taps,hk,hv,dk,dv,dtype,takes", [
    (8192, 4, 16, 32, 128, 128, "bfloat16", True),    # the cell's
    (8192, 4, 16, 32, 128, 128, "float32", True),
    (128, 4, 1, 2, 128, 128, "float32", True),        # one tile of 128
    (8192, 9, 16, 32, 128, 128, "bfloat16", True),    # nine taps
    (8192, 10, 16, 32, 128, 128, "bfloat16", False),  # past what a step reads
    (8192, 4, 16, 32, 64, 128, "bfloat16", False),    # half a lane of key
    (8192, 4, 16, 32, 128, 64, "bfloat16", False),    # half a lane of value
    (8192, 4, 2, 2, 384, 128, "bfloat16", False),     # no block of whole heads
    (8000, 4, 16, 32, 128, 128, "bfloat16", False),   # no tile divides it
    (8192, 4, 16, 32, 128, 128, "float16", False),
    (16, 4, 2, 4, 8, 6, "float32", False)])           # the tiny model's
def test_gated_delta_conv_gate(t, taps, hk, hv, dk, dv, dtype, takes):
    from cxxnet_tpu.layers.pallas_kernels import gated_delta_conv_applicable
    assert gated_delta_conv_applicable(t, taps, hk, hv, dk, dv,
                                       jnp.dtype(dtype)) is takes


def test_a_block_runs_the_conv_kernels_forward_twice():
    """Under a ``remat = block`` segment's checkpoint the whole mixer's
    gradient holds the convolution's forward kernel twice (the step's
    forward and the segment's recomputation) and its backward kernel
    once, which makes what it needs again itself; the scan's kernels
    once each. As the XLA form under ``jax.checkpoint`` the convolution
    ran forward three times."""
    from test_block_remat_keeps import _eqns
    from cxxnet_tpu.layers.base import BLOCK_REMAT_KEEPS
    layer, p, st = _layer("gated_delta", dict(
        nkhead=1, nvhead=2, key_dim=128, value_dim=128, conv_kernel=4,
        chunk=64, eps=1e-6, init_sigma=0.3), seq_shape(128, D))
    seg = jax.checkpoint(
        lambda p, x: layer.forward(p, st, [x], True, None)[0][0],
        policy=jax.checkpoint_policies.save_only_these_names(
            *BLOCK_REMAT_KEEPS))
    grad = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(seg(p, x)),
                                   argnums=(0, 1)))(p, _x(t=128))
    # a kernel by its outputs: the convolution's forward q, k, v and
    # backward d(qkv), the taps' partials; the scan's forward o, a state
    # a chunk (6-D), u, and backward six gradients
    kind = {(3, 3): "conv forward", (2, 3): "conv backward",
            (3, 6): "scan forward", (6, 5): "scan backward"}
    names = sorted(kind[len(e.params["out_avals"]), max(
        len(a.shape) for a in e.params["out_avals"])]
        for e in _eqns(grad.jaxpr) if e.primitive.name == "pallas_call")
    assert names == ["conv backward", "conv forward", "conv forward",
                     "scan backward", "scan forward"], names


def test_the_solve_inverts_a_unit_lower_triangle():
    for c in (3, 4, 16, 24, 64):
        a = jnp.tril(0.3 * jax.random.normal(jax.random.PRNGKey(c),
                                             (2, c, c)), -1)
        with jax.default_matmul_precision("highest"):
            _close(_solve_unit_lower(a, jnp.float32) @ (jnp.eye(c) + a),
                   jnp.broadcast_to(jnp.eye(c), a.shape), 1e-5)


def test_a_block_keeps_the_solve_and_makes_it_once():
    """Under a ``remat = block`` segment's checkpoint (nnet/net.py: it
    keeps what carries a name of ``BLOCK_REMAT_KEEPS``) the triangular
    inverse's float32 products appear once in the gradient's program, in
    the first forward pass, beside the two of its own backward rule; a
    segment that keeps no name makes the inverse twice (the first pass
    and the segment's recomputation, which keeps it for the rule's own
    backward pass). The values are the same."""
    from test_block_remat_keeps import _eqns
    from cxxnet_tpu.layers.base import BLOCK_REMAT_KEEPS, DELTA_KEEPS
    assert set(DELTA_KEEPS) < set(BLOCK_REMAT_KEEPS)
    args, w = _rule_inputs(32)

    def grad_of(names):
        rule = jax.checkpoint(
            lambda *a: gated_delta_rule(*a, 16, jnp.float32),
            policy=jax.checkpoint_policies.save_only_these_names(*names))
        return jax.grad(lambda *a: jnp.sum(w * rule(*a)),
                        argnums=(0, 1, 2, 3, 4))

    def exact_products(names):
        return sum(1 for e in _eqns(jax.make_jaxpr(grad_of(names))(
            *args).jaxpr) if e.primitive.name == "dot_general"
            and "HIGHEST" in str(e.params["precision"]))

    one = 6         # a block of 16: three squarings, three products
    assert exact_products(BLOCK_REMAT_KEEPS) == one + 2
    assert exact_products(()) == 2 * one + 2
    for a, b in zip(grad_of(BLOCK_REMAT_KEEPS)(*args), grad_of(())(*args)):
        _close(a, b, 1e-5)


@pytest.mark.parametrize("t", [16, 10])
def test_gated_delta_layer_matches_the_reference(t):
    """The whole mixer: projections, the convolution (a channel alone,
    zeros before the sequence), SiLU, the unit-length q and k, the rule,
    the gated norm a head, the output projection."""
    layer, p, st = _layer("gated_delta", dict(
        nkhead=2, nvhead=4, key_dim=8, value_dim=6, conv_kernel=4, chunk=4,
        eps=1e-6, init_sigma=0.3), seq_shape(t, D))
    assert set(p) == {"wqkv", "wz", "wb", "wa", "wo", "conv", "alog",
                      "dtbias", "norm"}
    assert p["conv"].shape == (4, 2 * 2 * 8 + 4 * 6)
    assert float(jnp.abs(p["conv"]).max()) <= 0.5
    assert float(jnp.exp(p["alog"]).max()) <= 16.0
    # scales off one, so that a norm left out shows
    p = dict(p, norm=p["norm"] + 0.1 * _x(3)[0, 0, :6],
             dtbias=p["dtbias"] + 0.2 * _x(4)[0, 0, :4])
    x, w = _x(t=t), _x(9, t=t)
    plain = lambda p, x: jnp.stack([ref.gated_delta_net(
        p, x[b], TINY, None, 0, False) for b in range(x.shape[0])])
    with jax.default_matmul_precision("highest"):
        (yf, gf), (yg, gg) = _both(
            lambda p, x: layer.forward(p, st, [x], True, None)[0][0],
            w)(p, x), _both(plain, w)(p, x)
    _close(yf, yg)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gg)):
        _close(a, b, 2e-5)
    # causal: a later position moves no earlier output
    y2 = layer.forward(p, st, [x.at[:, t - 1].add(1.0)], True, None)[0][0]
    assert float(jnp.abs(y2 - yf)[:, :t - 1].max()) < 1e-5
    assert float(jnp.abs(y2 - yf)[:, t - 1].max()) > 1e-3


def test_gated_delta_refuses_heads_it_cannot_group():
    for bad in (dict(nkhead=3, nvhead=4, key_dim=8, value_dim=6),
                dict(nkhead=2, nvhead=4, key_dim=8),
                dict(nkhead=2, nvhead=4, key_dim=8, value_dim=6, chunk=0)):
        with pytest.raises(ValueError, match="gated_delta"):
            _layer("gated_delta", bad, seq_shape(T, D))


# -- RoPE on part of a head ------------------------------------------------------


@pytest.mark.parametrize("rope_dim", [4, 8])
def test_rope_dim_matches_the_reference(rope_dim):
    """4 of a head's 8 features rotated, the others passed through; 8 of
    8 is the reference's factor 1."""
    layer, p, st = _layer("gqa_attention", dict(
        nhead=4, nkvhead=2, head_dim=8, rope=1, rope_dim=rope_dim,
        rope_theta=1e7, eps=1e-6, q_block=8, init_sigma=0.3),
        seq_shape(T, D))
    p = dict(p, qnorm=p["qnorm"] + 0.1 * _x(3)[0, 0, :8])
    cfg = dict(TINY, partial_rotary_factor=rope_dim / 8)
    x, w = _x(), _x(9)
    plain = lambda p, x: jnp.stack([ref.attention(
        p, x[b], cfg, None, None, False) for b in range(x.shape[0])])
    with jax.default_matmul_precision("highest"):
        (yf, gf), (yg, gg) = _both(
            lambda p, x: layer.forward(p, st, [x], True, None)[0][0],
            w)(p, x), _both(plain, w)(p, x)
    _close(yf, yg)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gg)):
        _close(a, b)


def test_rope_dim_default_is_all_of_a_head_to_the_bit():
    cfg = dict(nhead=4, nkvhead=2, head_dim=8, rope=1, rope_theta=1e4,
               eps=1e-5, init_sigma=0.3)
    old, p, st = _layer("gqa_attention", cfg, seq_shape(T, D))
    full, _, _ = _layer("gqa_attention", dict(cfg, rope_dim=8),
                        seq_shape(T, D))
    part, _, _ = _layer("gqa_attention", dict(cfg, rope_dim=4),
                        seq_shape(T, D))
    a, b, c = (l.forward(p, st, [_x()], True, None)[0][0]
               for l in (old, full, part))
    assert old.rope_dim == 0 and bool(jnp.all(a == b))
    assert float(jnp.abs(a - c).max()) > 1e-3
    # the program the default traces names no slice of a head
    text = str(jax.make_jaxpr(
        lambda x: old.forward(p, st, [x], True, None)[0][0])(_x()))
    assert text == str(jax.make_jaxpr(
        lambda x: full.forward(p, st, [x], True, None)[0][0])(_x()))
    for bad in (3, 10):
        with pytest.raises(ValueError, match="rope_dim"):
            _layer("gqa_attention", dict(cfg, rope_dim=bad), seq_shape(T, D))


# -- the expert layer's two keys -------------------------------------------------

MOE = dict(nexpert=8, topk=3, nhidden=24, nshared=1, expert_block=4,
           init_sigma=0.3)


def test_softmax_router_and_gated_shared_expert_match_the_reference():
    layer, p, st = _layer("moe", dict(
        MOE, score_func="softmax", shared_gate=1, expert_first=2,
        expert_count=4, bias_sigma=0), seq_shape(T, D))
    assert p["sharedgate"].shape == (D, 1)
    assert float(jnp.abs(st["bias"]).max()) == 0.0
    x, w = _x(), _x(9)
    plain = lambda p, x: ref.moe(p, x.reshape(-1, D), TINY, (2, 4), None
                                 ).reshape(x.shape)
    with jax.default_matmul_precision("highest"):
        (yf, gf), (yg, gg) = _both(
            lambda p, x: layer.forward(p, st, [x], True, None)[0][0],
            w)(p, x), _both(plain, w)(p, x)
    _close(yf, yg)
    assert set(gf[0]) == set(gg[0])
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gg)):
        _close(a, b, 2e-5)
    # a token's weights over its picks add up to one
    picks, wts = layer.route(x.reshape(-1, D), p["router"], st["bias"])
    _close(jnp.sum(wts, axis=-1), jnp.ones(2 * T), 1e-6)


def test_moe_defaults_are_the_sigmoid_router_to_the_bit():
    """Neither key set: the parameters, the values and the traced program
    are what ``score_func = sigmoid``, ``shared_gate = 0`` give, and the
    gate's own key leaves the other tensors' start alone."""
    cfg = dict(MOE, routed_scaling_factor=2.446, bias_seed=3, bias_sigma=0.5)
    old, p, st = _layer("moe", cfg, seq_shape(T, D))
    same, p2, _ = _layer("moe", dict(cfg, score_func="sigmoid",
                                     shared_gate=0), seq_shape(T, D))
    gated, p3, _ = _layer("moe", dict(cfg, shared_gate=1), seq_shape(T, D))
    soft, _, _ = _layer("moe", dict(cfg, score_func="softmax"),
                        seq_shape(T, D))
    assert set(p) == set(p2) == set(p3) - {"sharedgate"}
    assert all(bool(jnp.all(p[k] == p3[k])) for k in p)
    run = lambda l, q: l.forward(q, st, [_x()], True, None)[0][0]
    assert bool(jnp.all(run(old, p) == run(same, p)))
    assert str(jax.make_jaxpr(lambda x: old.forward(
        p, st, [x], True, None)[0][0])(_x())) == str(jax.make_jaxpr(
            lambda x: same.forward(p, st, [x], True, None)[0][0])(_x()))
    assert float(jnp.abs(run(old, p) - run(gated, p3)).max()) > 1e-3
    assert float(jnp.abs(run(old, p) - run(soft, p)).max()) > 1e-3
    with pytest.raises(ValueError, match="score_func"):
        _layer("moe", dict(cfg, score_func="tanh"), seq_shape(T, D))
    with pytest.raises(ValueError, match="shared_gate"):
        _layer("moe", dict(cfg, nshared=0, shared_gate=1), seq_shape(T, D))
