"""LFM2's layers (layers/sequence.py: ``gated_conv``, the one causal
depthwise convolution it shares with ``gated_delta``, ``gqa_attention``'s
``gate``, the fused attention core at value heads of 64, ``embed`` as a
tied head) against the plain reference
(cxxnet_tpu/reference/lfm2_24b_a2b.py), and what was there before to the
bit. The whole model is tests/test_lfm2_model.py's.
"""

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.layers import (Shape3, create_layer, pallas_kernels as pk,
                               seq_shape)
from cxxnet_tpu.layers.sequence import causal_depthwise_conv
from cxxnet_tpu.reference import lfm2_24b_a2b as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the reference's names for the sizes lfm2_tiny builds
TINY = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=5,
    layer_types=("conv", "full_attention", "conv", "conv", "conv"),
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    rope_theta=1e6, norm_eps=1e-5, conv_L_cache=3, intermediate_size=48,
    moe_intermediate_size=24, num_experts=8, num_experts_per_tok=3,
    norm_topk_prob=True, routed_scaling_factor=1.0, use_expert_bias=True)
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


def _x(seed=1, batch=2, t=T, d=D):
    return jax.random.normal(jax.random.PRNGKey(seed), (batch, t, d))


def _both(fn, w):
    """fn's value and its gradients in every argument, jitted."""
    return jax.jit(lambda *a: (fn(*a), jax.grad(
        lambda *a: jnp.sum(w * fn(*a)), argnums=tuple(range(len(a))))(*a)))


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a.astype(jnp.float32)).tobytes())
    return h.hexdigest()


# -- the short convolution -------------------------------------------------------


@pytest.mark.parametrize("t,kernel", [(15, 3), (16, 3), (2, 3), (16, 4),
                                      (14, 4)])
def test_the_one_causal_convolution_is_the_librarys(t, kernel):
    """The shifted products against ``lax.conv_general_dilated`` with a
    group a channel (which the program does not use): lengths that are
    and are not multiples of the kernel, one shorter than it, LFM2's
    three taps and Qwen3-Next's four."""
    x = _x(2, t=t)
    taps = jax.random.uniform(jax.random.PRNGKey(3), (kernel, D), jnp.float32,
                              -0.5, 0.5)
    got = causal_depthwise_conv(x, taps, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.causal_conv(x[b], taps, None)
                          for b in range(x.shape[0])])
    _close(got, want, 1e-6)
    # position 0 sees the last tap alone
    _close(got[:, 0], taps[-1] * x[:, 0], 1e-6)
    # bfloat16: operands rounded, the sum float32
    low = causal_depthwise_conv(x.astype(jnp.bfloat16), taps, jnp.bfloat16)
    assert low.dtype == jnp.float32
    _close(low, causal_depthwise_conv(
        x.astype(jnp.bfloat16).astype(jnp.float32),
        taps.astype(jnp.bfloat16).astype(jnp.float32), jnp.float32), 1e-6)


@pytest.mark.parametrize("t", [15, 16, 2])
def test_gated_conv_layer_matches_the_reference(t):
    layer, p, st = _layer("gated_conv", dict(conv_kernel=3, init_sigma=0.3),
                          seq_shape(t, D))
    assert {k: v.shape for k, v in p.items()} == {
        "win": (D, 3 * D), "taps": (3, D), "wout": (D, D)}
    assert float(jnp.abs(p["taps"]).max()) <= 3 ** -0.5
    assert layer.sub_scopes == ("in_proj", "short_conv", "out_proj")
    x, w = _x(t=t), _x(9, t=t)
    plain = lambda p, x: jnp.stack([ref.short_conv(p, x[b], None)
                                    for b in range(x.shape[0])])
    with jax.default_matmul_precision("highest"):
        (yf, gf), (yg, gg) = _both(
            lambda p, x: layer.forward(p, st, [x], True, None)[0][0],
            w)(p, x), _both(plain, w)(p, x)
    _close(yf, yg)
    assert set(gf[0]) == set(gg[0])
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gg)):
        _close(a, b, 2e-5)
    assert layer.flops_per_example() == t * (2 * D * 4 * D + 2 * 3 * D)
    # causal: a later position moves no earlier output
    if t > 2:
        bumped = x.at[:, -1].add(1.0)
        y2 = layer.forward(p, st, [bumped], True, None)[0][0]
        assert bool(jnp.all(y2[:, :-1] == layer.forward(
            p, st, [x], True, None)[0][0][:, :-1]))


def test_gated_conv_names_its_parts_and_refuses_a_matrix():
    layer, p, st = _layer("gated_conv", dict(conv_kernel=3), seq_shape(T, D))
    text = str(jax.make_jaxpr(lambda x: layer.forward(
        p, st, [x], True, None)[0][0])(_x()))
    assert "checkpoint" in text or "remat" in text   # the passes are remade
    with pytest.raises(ValueError, match="sequence node"):
        create_layer("gated_conv", []).infer_shape([Shape3(1, 1, 8)])
    with pytest.raises(ValueError, match="conv_kernel"):
        _layer("gated_conv", dict(conv_kernel=0), seq_shape(T, D))


# sha256 over the float32 bytes of (y, every gradient) of a gated_delta
# layer and (y, every gradient, every parameter) of a default
# gqa_attention layer, at 16 and 14 positions, computed on the tree
# before the convolution was lifted out and the gate key existed
# (1eddf34): both layers are that tree's, to the bit.
@pytest.mark.parametrize("dtype,digest", [
    ("float32",
     "111e0a78ab0ff8179e333eaf049904586992f6bae34a3b15121756ea6c3a7d81"),
    ("bfloat16",
     "812c2564c0ba50f8dc1ff2a350dbd2c3a6514f20903eeb402c767f767c595211")])
def test_gated_delta_and_the_gated_attention_are_unchanged_to_the_bit(
        dtype, digest):
    out = []
    for t in (16, 14):
        layer, p, _ = _layer("gated_delta", dict(
            nkhead=2, nvhead=4, key_dim=8, value_dim=6, conv_kernel=4,
            chunk=4, eps=1e-6, init_sigma=0.3, dtype=dtype), seq_shape(t, D))
        x = _x(t=t)
        run = lambda p, x: layer.forward(p, {}, [x], True, None)[0][0]
        out += [run(p, x)] + jax.tree.leaves(jax.grad(
            lambda p, x: jnp.sum(run(p, x).astype(jnp.float32) ** 2),
            argnums=(0, 1))(p, x))
    layer, p, _ = _layer("gqa_attention", dict(
        nhead=4, nkvhead=2, head_dim=8, rope=1, eps=1e-5, q_block=8,
        init_sigma=0.3, dtype=dtype), seq_shape(T, D))
    assert layer.gate == 1 and "wg" in p
    x = _x()
    run = lambda p, x: layer.forward(p, {}, [x], True, None)[0][0]
    out += [run(p, x)] + jax.tree.leaves(jax.grad(
        lambda p, x: jnp.sum(run(p, x).astype(jnp.float32) ** 2),
        argnums=(0, 1))(p, x)) + jax.tree.leaves(p)
    assert _digest(out) == digest


# -- attention without the gate, and heads of 64 ---------------------------------


def test_gqa_without_the_gate_matches_the_reference():
    cfg = dict(nhead=4, nkvhead=2, head_dim=8, rope=1, rope_theta=1e6,
               eps=1e-5, q_block=8, init_sigma=0.3)
    layer, p, st = _layer("gqa_attention", dict(cfg, gate=0), seq_shape(T, D))
    gated, pg, _ = _layer("gqa_attention", cfg, seq_shape(T, D))
    assert set(p) == set(pg) - {"wg"}
    assert layer.flops_per_example() == gated.flops_per_example() \
        - T * 2 * D * 32
    p = dict(p, qnorm=p["qnorm"] + 0.1 * _x(3)[0, 0, :8])
    x, w = _x(), _x(9)
    plain = lambda p, x: jnp.stack([ref.attention(
        p, x[b], TINY, None, None, False) for b in range(x.shape[0])])
    with jax.default_matmul_precision("highest"):
        (yf, gf), (yg, gg) = _both(
            lambda p, x: layer.forward(p, st, [x], True, None)[0][0],
            w)(p, x), _both(plain, w)(p, x)
        blocked = jnp.stack([ref.attention(p, x[b], TINY, None, 4, True)
                             for b in range(2)])
    _close(yf, yg)
    _close(blocked, yg, 1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gg)):
        _close(a, b, 2e-5)
    # the program without the gate holds no sigmoid
    text = str(jax.make_jaxpr(lambda x: layer.forward(
        p, st, [x], True, None)[0][0])(x))
    assert "logistic" not in text
    assert "logistic" in str(jax.make_jaxpr(lambda x: gated.forward(
        pg, st, [x], True, None)[0][0])(x))


@pytest.mark.parametrize("time,q_block,qk,v,nhead,nkvhead,fits", [
    (8192, 1024, (64,), 64, 32, 8, True),     # LFM2's attention layer
    (8192, 1024, (128, 64), 64, 1, 1, True),  # values of one half-lane
    (8192, 1024, (64,), 128, 32, 8, True),
    (8192, 1024, (64,), 32, 32, 8, False),    # under a half-lane
    (8192, 1024, (64,), 192, 32, 8, False),   # neither 64 nor whole lanes
    (8192, 1024, (64,), 0, 32, 8, False),
    (8192, 1024, (32,), 64, 32, 8, False),    # queries under a half-lane
    (8200, 1024, (64,), 64, 32, 8, False),    # no tile divides the length
    (8192, 1024, (64,), 64, 32, 5, False),    # groups of unequal size
])
def test_the_attention_gate_takes_value_heads_of_64(time, q_block, qk, v,
                                                    nhead, nkvhead, fits):
    assert pk.causal_attention_applicable(time, q_block, qk, v, nhead,
                                          nkvhead) is fits


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 3e-2)])
def test_the_fused_core_at_heads_of_64_matches_the_xla_core(dtype, tol):
    """A ``gqa_attention`` layer of LFM2's kind (no gate, heads of 64, a
    key/value head a pair of query heads) over 256 positions: the fused
    kernels (interpreted) under its ``core`` scope; held to the XLA core
    it gives the same value and gradients."""
    layer, p, st = _layer("gqa_attention", dict(
        nhead=4, nkvhead=2, head_dim=64, window=0, rope=1, gate=0,
        rope_theta=1e6, eps=1e-5, q_block=128, init_sigma=0.1, dtype=dtype),
        seq_shape(256, 64))
    assert layer.fused_core
    x = _x(2, t=256, d=64)
    w = jnp.cos(jnp.arange(x.size, dtype=jnp.float32).reshape(x.shape))

    def loss(p, x):
        (y,), _ = layer.forward(p, st, [x], True, None)
        return jnp.sum(y.astype(jnp.float32) * w)

    text = str(jax.make_jaxpr(loss)(p, x))
    assert "pallas_call" in text
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
        layer.fused_core = False
        assert "pallas_call" not in str(jax.make_jaxpr(loss)(p, x))
        want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max())


# -- the tied head ----------------------------------------------------------------


def test_embed_on_a_sequence_node_is_the_tied_head():
    layer, p, st = _layer("embed", dict(nvocab=10, nhidden=D, init_sigma=0.3),
                          Shape3(1, 1, T))
    assert not layer.tied_head
    assert layer.infer_shape([seq_shape(T, D)]) == [seq_shape(T, 10)]
    assert layer.tied_head
    # the lookup's shapes stay the layer's own
    assert layer.out_shapes == [seq_shape(T, D)]
    h = _x()
    (logits,), _ = layer.forward(p, st, [h], True, None)
    with jax.default_matmul_precision("highest"):
        _close(logits, jnp.einsum("btd,vd->btv", h, p["wmat"]))
    ids = jnp.arange(2 * T).reshape(2, T) % 10
    (rows,), _ = layer.forward(p, st, [ids], True, None)
    assert bool(jnp.all(rows == p["wmat"][ids]))
    with pytest.raises(ValueError, match="features"):
        layer.infer_shape([seq_shape(T, D + 1)])
