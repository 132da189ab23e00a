"""Pairtest-style layer validation (SURVEY.md §4.1).

Every layer runs against an independent oracle — NumPy loop
implementations mirroring the mshadow expression semantics, and torch
(CPU) as the cross-framework oracle for conv (the reference used its
caffe adapter the same way). Gradients are checked where the reference's
backprop has an exact closed form.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.layers import Shape3, create_layer
from cxxnet_tpu.layers.base import as_mat


def run_layer(ltype, cfg, in_shapes, inputs, is_train=False, seed=0,
              rng=None, **kw):
    layer = create_layer(ltype, cfg, **kw)
    layer.infer_shape([Shape3(*s) for s in in_shapes])
    params = layer.init_params(jax.random.PRNGKey(seed))
    state = layer.init_state()
    outs, new_state = layer.forward(
        params, state, [jnp.asarray(x) for x in inputs], is_train, rng)
    return layer, params, state, outs, new_state


# ---------------------------------------------------------------- fullc

def test_fullc_forward_and_grad(rng):
    x = rng.randn(5, 8).astype(np.float32)
    layer, params, _, outs, _ = run_layer(
        "fullc", [("nhidden", "3")], [(1, 1, 8)], [x])
    w, b = np.asarray(params["wmat"]), np.asarray(params["bias"])
    np.testing.assert_allclose(np.asarray(outs[0]), x @ w + b, rtol=1e-5)

    # gradient parity with fullc_layer-inl.hpp:108-130:
    # gwmat(ref layout out,in) = dout^T @ x ; gbias = sum_rows(dout);
    # din = dout @ wmat(ref)
    def f(p, xx):
        y, _ = layer.forward(p, {}, [xx], True, None)
        return jnp.sum(y[0] ** 2)

    gp, gx = jax.grad(f, argnums=(0, 1))(params, jnp.asarray(x))
    dout = 2 * (x @ w + b)
    np.testing.assert_allclose(np.asarray(gp["wmat"]), x.T @ dout,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gp["bias"]), dout.sum(0),
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gx), dout @ w.T, rtol=1e-4)


@pytest.mark.parametrize("nin,nout", [(40, 24), (77, 130)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fullc_matches_x_w_plus_b(rng, nin, nout, dtype):
    """fullc against ``x @ W + b`` forward and gradient, at widths that
    are and are not multiples of 8 or 128, in both compute dtypes (bf16
    operands, f32 accumulation: within one bf16 rounding a product)."""
    x = rng.randn(12, nin).astype(np.float32)
    layer, params, _, outs, _ = run_layer(
        "fullc", [("nhidden", str(nout)), ("init_bias", "0.3"),
                  ("dtype", dtype)], [(1, 1, nin)], [x])
    w, b = np.asarray(params["wmat"]), np.asarray(params["bias"])
    tol = dict(rtol=1e-4, atol=1e-4) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2 * np.abs(x).max())
    assert outs[0].dtype == jnp.dtype(dtype)
    y = np.asarray(outs[0], np.float32)
    np.testing.assert_allclose(y, x @ w + b, **tol)
    g = rng.randn(12, nout).astype(np.float32)

    def f(p, xx):
        o, _ = layer.forward(p, {}, [xx], True, None)
        return jnp.sum(o[0].astype(jnp.float32) * g)

    gp, gx = jax.grad(f, argnums=(0, 1))(params, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(gp["wmat"], np.float32), x.T @ g,
                               **tol)
    np.testing.assert_allclose(np.asarray(gp["bias"]), g.sum(0), **tol)
    np.testing.assert_allclose(np.asarray(gx, np.float32), g @ w.T, **tol)


def test_fullc_no_bias():
    _, params, _, _, _ = run_layer(
        "fullc", [("nhidden", "3"), ("no_bias", "1")], [(1, 1, 8)],
        [np.zeros((2, 8), np.float32)])
    assert "bias" not in params


@pytest.mark.parametrize("rt,extra", [
    ("gaussian", [("init_sigma", "0.05")]), ("xavier", []),
    ("kaiming", [])])
def test_fullc_init_modes(rt, extra):
    _, params, _, _, _ = run_layer(
        "fullc", [("nhidden", "64"), ("random_type", rt)] + extra,
        [(1, 1, 32)], [np.zeros((2, 32), np.float32)], seed=3)
    w = np.asarray(params["wmat"])
    assert w.std() > 0
    if rt == "xavier":
        a = np.sqrt(3.0 / (32 + 64))
        assert np.abs(w).max() <= a + 1e-6


# ---------------------------------------------------------------- conv

def _torch_conv(x_nhwc, w_hwio, b, stride, pad, groups):
    import torch
    xt = torch.tensor(x_nhwc.transpose(0, 3, 1, 2))
    wt = torch.tensor(w_hwio.transpose(3, 2, 0, 1))   # OIHW
    bt = torch.tensor(b) if b is not None else None
    y = torch.nn.functional.conv2d(xt, wt, bt, stride=stride,
                                   padding=pad, groups=groups)
    return y.numpy().transpose(0, 2, 3, 1)


@pytest.mark.parametrize("groups,pad,stride", [(1, 0, 1), (1, 1, 2),
                                               (2, 1, 1)])
def test_conv_vs_torch(rng, groups, pad, stride):
    x = rng.randn(2, 9, 9, 4).astype(np.float32)
    layer, params, _, outs, _ = run_layer(
        "conv", [("nchannel", "6"), ("kernel_size", "3"),
                 ("pad", str(pad)), ("stride", str(stride)),
                 ("ngroup", str(groups))],
        [(4, 9, 9)], [x])
    ref = _torch_conv(x, np.asarray(params["wmat"]),
                      np.asarray(params["bias"]), stride, pad, groups)
    np.testing.assert_allclose(np.asarray(outs[0]), ref, rtol=1e-4,
                               atol=1e-5)
    # shape formula parity (convolution_layer-inl.hpp:178-181)
    assert layer.out_shapes[0] == Shape3(6, (9 + 2 * pad - 3) // stride + 1,
                                         (9 + 2 * pad - 3) // stride + 1)


# ---------------------------------------------------------------- pooling

def _ref_pool(x, k, stride, pad, mode):
    """NumPy mirror of mshadow pool<Reducer>(pad(x)) with truncated
    windows (pooling_layer-inl.hpp:47-56 + mshadow pool semantics)."""
    b, h, w, c = x.shape
    xp = np.zeros((b, h + 2 * pad, w + 2 * pad, c), x.dtype)
    xp[:, pad:pad + h, pad:pad + w] = x
    hp, wp = h + 2 * pad, w + 2 * pad
    oh = min(hp - k + stride - 1, hp - 1) // stride + 1
    ow = min(wp - k + stride - 1, wp - 1) // stride + 1
    out = np.zeros((b, oh, ow, c), x.dtype)
    for i in range(oh):
        for j in range(ow):
            ys, xs = i * stride, j * stride
            win = xp[:, ys:min(ys + k, hp), xs:min(xs + k, wp)]
            if mode == "max":
                out[:, i, j] = win.max(axis=(1, 2))
            else:
                out[:, i, j] = win.sum(axis=(1, 2))
    if mode == "avg":
        out /= (k * k)
    return out


@pytest.mark.parametrize("mode", ["max", "sum", "avg"])
@pytest.mark.parametrize("k,stride,pad,size", [
    (2, 2, 0, 8), (3, 2, 0, 9), (3, 2, 1, 7), (3, 3, 0, 8)])
def test_pooling_matches_reference_semantics(rng, mode, k, stride, pad,
                                             size):
    x = rng.randn(2, size, size, 3).astype(np.float32)
    _, _, _, outs, _ = run_layer(
        "%s_pooling" % mode,
        [("kernel_size", str(k)), ("stride", str(stride)),
         ("pad", str(pad))],
        [(3, size, size)], [x])
    ref = _ref_pool(x, k, stride, pad, mode)
    assert np.asarray(outs[0]).shape == ref.shape
    np.testing.assert_allclose(np.asarray(outs[0]), ref, rtol=1e-5,
                               atol=1e-6)


def _untied(rng, b, h, w, c):
    """(b, h, w, c) with no two equal values in any (image, channel)
    plane, exactly representable in bfloat16: a max pool's backward
    then has one winner a window in either dtype."""
    grid = np.concatenate([(128 + np.arange(128)) * 2.0 ** e
                           for e in range(-8, -3)])
    vals = np.concatenate([grid, -grid])
    x = np.empty((b, c, h * w), np.float32)
    for i in range(b):
        for j in range(c):
            x[i, j] = rng.choice(vals, h * w, replace=False)
    return x.reshape(b, c, h, w).transpose(0, 2, 3, 1)


def _ref_max_pool_grad(x, g, k, stride):
    """NumPy backward of relu -> max pool (pad 0, truncated windows):
    each window's cotangent goes to its one largest input, if that is
    positive (relu's gradient)."""
    b, h, w, c = x.shape
    dx = np.zeros_like(x)
    for i in range(g.shape[1]):
        for j in range(g.shape[2]):
            ys, xs = i * stride, j * stride
            win = x[:, ys:min(ys + k, h), xs:min(xs + k, w)]
            flat = win.reshape(b, -1, c)
            arg = flat.argmax(1)
            for bi in range(b):
                for ci in range(c):
                    if flat[bi, arg[bi, ci], ci] > 0:
                        dy, dxx = divmod(arg[bi, ci], win.shape[2])
                        dx[bi, ys + dy, xs + dxx, ci] += g[bi, i, j, ci]
    return dx


@pytest.mark.parametrize("k,stride,h,w", [
    (2, 2, 8, 8), (3, 2, 9, 9), (3, 1, 9, 9), (3, 1, 30, 13),
    (3, 2, 30, 13)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_relu_max_pooling(rng, k, stride, h, w, dtype):
    """relu_max_pooling forward and gradient against NumPy relu then max
    pool, square windows of 2 and 3, strides 2 and 1, maps of 8 to 30
    rows. Values are exact in both dtypes and the cotangents small
    integers, so every comparison is exact."""
    x = _untied(rng, 2, h, w, 8)
    xd = jnp.asarray(x, dtype)
    layer, params, state, outs, _ = run_layer(
        "relu_max_pooling", [("kernel_size", str(k)),
                             ("stride", str(stride))],
        [(8, h, w)], [xd])
    ref = _ref_pool(np.maximum(x, 0), k, stride, 0, "max")
    assert outs[0].dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(outs[0], np.float32), ref)
    g = rng.randint(1, 8, ref.shape).astype(np.float32)

    def f(xx):
        o, _ = layer.forward(params, state, [xx], True, None)
        return jnp.sum(o[0].astype(jnp.float32) * g)

    np.testing.assert_array_equal(np.asarray(jax.grad(f)(xd), np.float32),
                                  _ref_max_pool_grad(x, g, k, stride))


# ---------------------------------------------------------------- lrn

def test_lrn(rng):
    x = rng.randn(2, 4, 4, 5).astype(np.float32)
    nsize, alpha, beta, knorm = 3, 0.001, 0.75, 1.0
    _, _, _, outs, _ = run_layer(
        "lrn", [("local_size", str(nsize)), ("alpha", str(alpha)),
                ("beta", str(beta)), ("knorm", str(knorm))],
        [(5, 4, 4)], [x])
    # numpy chpool: window [c-h, c+h] clipped (mshadow chpool)
    h = nsize // 2
    sq = x ** 2
    norm = np.zeros_like(x)
    C = x.shape[-1]
    for c in range(C):
        lo, hi = max(0, c - h), min(C, c + h + 1)
        norm[..., c] = sq[..., lo:hi].sum(-1)
    ref = x * (norm * alpha / nsize + knorm) ** (-beta)
    np.testing.assert_allclose(np.asarray(outs[0]), ref, rtol=1e-5)


# ---------------------------------------------------------------- BN

def test_batch_norm_train_and_running(rng):
    x = rng.randn(4, 3, 3, 2).astype(np.float32)
    layer, params, state, outs, new_state = run_layer(
        "batch_norm", [], [(2, 3, 3)], [x], is_train=True)
    mean = x.mean(axis=(0, 1, 2))
    var = x.var(axis=(0, 1, 2))
    ref = (x - mean) / np.sqrt(var + 1e-10)
    np.testing.assert_allclose(np.asarray(outs[0]), ref, rtol=1e-3,
                               atol=1e-5)
    # running stats: 0*0.9 + batch*(1-0.9)
    np.testing.assert_allclose(np.asarray(new_state["running_exp"]),
                               0.1 * mean, rtol=1e-4, atol=1e-6)
    # inference uses running stats
    outs2, _ = layer.forward(params, new_state, [jnp.asarray(x)],
                             False, None)
    rexp, rvar = 0.1 * mean, 0.1 * var
    ref2 = (x - rexp) / np.sqrt(rvar + 1e-10)
    np.testing.assert_allclose(np.asarray(outs2[0]), ref2, rtol=1e-3,
                               atol=1e-4)


@pytest.mark.parametrize("fuse_relu", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_batch_norm_training_matches_numpy(rng, fuse_relu, dtype):
    """A training step of batch_norm (the folded scale/shift, with the
    relu that the net's bn_fuse_relu pass moves into it) against a NumPy
    batch norm with a learned slope and bias; the running statistics
    move by the batch's moments."""
    x = (rng.randn(6, 5, 5, 8) * 2.0 + 0.5).astype(np.float32)
    layer = create_layer("batch_norm", [])
    layer.infer_shape([Shape3(8, 5, 5)])
    layer.fuse_relu = fuse_relu
    params = {"wmat": jnp.asarray(rng.rand(8).astype(np.float32) + 0.5),
              "bias": jnp.asarray(rng.randn(8).astype(np.float32))}
    outs, new_state = layer.forward(params, layer.init_state(),
                                    [jnp.asarray(x, dtype)], True, None)
    xr = np.asarray(jnp.asarray(x, dtype), np.float64)
    mean, var = xr.mean(axis=(0, 1, 2)), xr.var(axis=(0, 1, 2))
    ref = (xr - mean) / np.sqrt(var + 1e-10) * np.asarray(params["wmat"]) \
        + np.asarray(params["bias"])
    if fuse_relu:
        ref = np.maximum(ref, 0)
    assert outs[0].dtype == jnp.dtype(dtype)
    atol = 1e-4 if dtype == "float32" else 0.06
    np.testing.assert_allclose(np.asarray(outs[0], np.float32), ref,
                               atol=atol)
    np.testing.assert_allclose(np.asarray(new_state["running_exp"]),
                               0.1 * mean, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_state["running_var"]),
                               0.1 * var, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("mode", ["train", "eval"])
def test_batch_norm_fold_bf16(rng, mode):
    """bn_fold_affine (default on) applies scale/shift in the compute
    dtype, so under bfloat16 the normalize multiply-add runs in bf16
    while the unfused branch and the eval path promote to f32
    (conv.py forward). This pins the precision contract: folded-bf16
    must agree with unfused-bf16 and with the f32 reference to within
    bf16 rounding (~3 bits on an O(1) normalized tensor), in the train
    step and in the eval pass after it."""
    x32 = rng.randn(8, 5, 5, 6).astype(np.float32)
    x16 = jnp.asarray(x32, jnp.bfloat16)
    outs = {}
    for fold in ("0", "1"):
        # bn_momentum=0: one train step writes the running stats to
        # exactly this batch's moments, so the eval branch is
        # comparable against the same reference
        layer, params, state, o, new_state = run_layer(
            "batch_norm", [("bn_fold_affine", fold),
                           ("bn_momentum", "0")], [(6, 5, 5)],
            [x16], is_train=True)
        assert o[0].dtype == jnp.bfloat16
        if mode == "eval":
            # through the running stats updated by this train step
            o, _ = layer.forward(params, new_state, [x16], False, None)
        outs[fold] = np.asarray(o[0], np.float32)
    mean = x32.mean(axis=(0, 1, 2))
    ref = (x32 - mean) / np.sqrt(x32.var(axis=(0, 1, 2)) + 1e-10)
    for key in outs:
        np.testing.assert_allclose(outs[key], ref, atol=0.06,
                                   err_msg="bf16 BN path %r" % key)
    # fold on/off must agree to bf16 rounding
    np.testing.assert_allclose(outs["1"], outs["0"], atol=0.04)


def test_batch_norm_no_ma_eval_uses_batch_stats(rng):
    x = rng.randn(6, 5).astype(np.float32)
    layer, params, state, outs, _ = run_layer(
        "batch_norm_no_ma", [], [(1, 1, 5)], [x], is_train=False)
    mean, var = x.mean(0), x.var(0)
    ref = (x - mean) / np.sqrt(var + 1e-10)
    np.testing.assert_allclose(np.asarray(outs[0]), ref, rtol=1e-3,
                               atol=1e-5)
    assert state == {}


# ----------------------------------------------------- activations etc.

def test_activations(rng):
    x = rng.randn(3, 7).astype(np.float32)
    refs = {
        "relu": np.maximum(x, 0),
        "sigmoid": 1 / (1 + np.exp(-x)),
        "tanh": np.tanh(x),
        "softplus": np.log1p(np.exp(x)),
    }
    for k, ref in refs.items():
        _, _, _, outs, _ = run_layer(k, [], [(1, 1, 7)], [x])
        np.testing.assert_allclose(np.asarray(outs[0]), ref, rtol=1e-5,
                                   atol=1e-6)


def test_xelu(rng):
    x = rng.randn(3, 7).astype(np.float32)
    _, _, _, outs, _ = run_layer("xelu", [("b", "4")], [(1, 1, 7)], [x])
    ref = np.where(x > 0, x, x / 4.0)
    np.testing.assert_allclose(np.asarray(outs[0]), ref, rtol=1e-6)


def test_insanity_eval_and_train(rng):
    x = rng.randn(3, 7).astype(np.float32)
    layer, params, state, outs, _ = run_layer(
        "insanity", [("lb", "3"), ("ub", "8")], [(1, 1, 7)], [x])
    ref = np.where(x > 0, x, x / 5.5)     # (3+8)/2
    np.testing.assert_allclose(np.asarray(outs[0]), ref, rtol=1e-5)
    outs_t, _ = layer.forward(params, layer.init_state(),
                              [jnp.asarray(x)], True,
                              jax.random.PRNGKey(0))
    y = np.asarray(outs_t[0])
    neg = x < 0
    # negative entries divided by a slope in [3, 8]
    slopes = x[neg] / y[neg]
    assert (slopes >= 3 - 1e-4).all() and (slopes <= 8 + 1e-4).all()
    np.testing.assert_allclose(y[~neg], x[~neg])


def test_prelu_forward_and_ref_grad(rng):
    x = rng.randn(4, 6).astype(np.float32)
    layer, params, _, outs, _ = run_layer(
        "prelu", [("init_slope", "0.25")], [(1, 1, 6)], [x])
    ref = np.where(x > 0, x, 0.25 * x)
    np.testing.assert_allclose(np.asarray(outs[0]), ref, rtol=1e-5)

    # slope grad parity: gslope = sum_over_batch(x<0 ? x : 0) * dout
    def f(p):
        y, _ = layer.forward(p, {}, [jnp.asarray(x)], False, None)
        return jnp.sum(y[0] * 2.0)

    g = jax.grad(f)(params)["bias"]
    ref_g = (np.where(x < 0, x, 0.0) * 2.0).sum(0)
    np.testing.assert_allclose(np.asarray(g), ref_g, rtol=1e-4)


def test_dropout(rng):
    x = np.ones((64, 100), np.float32)
    layer, params, state, outs, _ = run_layer(
        "dropout", [("threshold", "0.5")], [(1, 1, 100)], [x],
        is_train=True, rng=jax.random.PRNGKey(1))
    y = np.asarray(outs[0])
    kept = y != 0
    assert 0.35 < kept.mean() < 0.65
    np.testing.assert_allclose(y[kept], 2.0, rtol=1e-6)   # inverted scale
    outs_e, _ = layer.forward(params, state, [jnp.asarray(x)], False, None)
    np.testing.assert_allclose(np.asarray(outs_e[0]), x)


# ----------------------------------------------------------- structural

def test_flatten_matches_nchw_order(rng):
    x = rng.randn(2, 3, 4, 5).astype(np.float32)   # (b,y,x,ch)
    _, _, _, outs, _ = run_layer("flatten", [], [(5, 3, 4)], [x])
    ref = x.transpose(0, 3, 1, 2).reshape(2, -1)   # NCHW c-order
    np.testing.assert_allclose(np.asarray(outs[0]), ref)


def test_concat_and_ch_concat(rng):
    a = rng.randn(2, 5).astype(np.float32)
    b = rng.randn(2, 3).astype(np.float32)
    _, _, _, outs, _ = run_layer("concat", [], [(1, 1, 5), (1, 1, 3)],
                                 [a, b])
    np.testing.assert_allclose(np.asarray(outs[0]),
                               np.concatenate([a, b], 1))
    xa = rng.randn(2, 4, 4, 3).astype(np.float32)
    xb = rng.randn(2, 4, 4, 2).astype(np.float32)
    layer, _, _, outs, _ = run_layer("ch_concat", [],
                                     [(3, 4, 4), (2, 4, 4)], [xa, xb])
    assert layer.out_shapes[0] == Shape3(5, 4, 4)
    np.testing.assert_allclose(np.asarray(outs[0]),
                               np.concatenate([xa, xb], -1))


def _pool_concat_ref(branches, pos, k, mode):
    """A SAME stride-1 k x k pool of branch ``pos`` (zero padding, as
    mshadow's pad()), then the channel concat of every branch."""
    p = k // 2
    xs = list(branches)
    pad = jnp.pad(xs[pos], ((0, 0), (p, p), (p, p), (0, 0)))
    if mode == "max":
        y = jax.lax.reduce_window(pad, -jnp.inf, jax.lax.max,
                                  (1, k, k, 1), (1, 1, 1, 1), "VALID")
    else:
        y = jax.lax.reduce_window(pad, 0.0, jax.lax.add,
                                  (1, k, k, 1), (1, 1, 1, 1),
                                  "VALID") * (1.0 / (k * k))
    xs[pos] = y
    return jnp.concatenate(xs, axis=3)


@pytest.mark.parametrize("pos", [0, 1, 2])
@pytest.mark.parametrize("mode", ["max", "avg"])
def test_inception_tower_tail_pool_into_ch_concat(rng, mode, pos):
    """An Inception tower's tail: a 3 x 3 stride-1 SAME pool branch into
    ch_concat with two other branches, at each position, forward and
    gradient against the padded reduce_window + concatenate. Random
    float32 data has no ties for the max backward to split."""
    widths = (8, 16, 8)
    bs = [jnp.asarray(rng.randn(2, 8, 8, c).astype(np.float32))
          for c in widths]
    pool = create_layer("%s_pooling" % mode, [("kernel_size", "3"),
                                              ("stride", "1"),
                                              ("pad", "1")])
    pool.infer_shape([Shape3(widths[pos], 8, 8)])
    concat = create_layer("ch_concat", [])
    concat.infer_shape([Shape3(c, 8, 8) for c in widths])

    def tail(*branches):
        xs = list(branches)
        xs[pos] = pool.forward({}, {}, [xs[pos]], True, None)[0][0]
        return concat.forward({}, {}, xs, True, None)[0][0]

    np.testing.assert_allclose(np.asarray(tail(*bs)),
                               np.asarray(_pool_concat_ref(bs, pos, 3,
                                                           mode)),
                               atol=1e-6)
    args = (0, 1, 2)
    g = jax.grad(lambda *a: jnp.sum(tail(*a) ** 2), argnums=args)(*bs)
    gr = jax.grad(lambda *a: jnp.sum(_pool_concat_ref(a, pos, 3, mode)
                                     ** 2), argnums=args)(*bs)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_split_grad_sums(rng):
    x = rng.randn(2, 4).astype(np.float32)
    layer, _, _, outs, _ = run_layer("split", [], [(1, 1, 4)], [x],
                                     n_out=3)
    assert len(outs) == 3

    def f(xx):
        ys, _ = layer.forward({}, {}, [xx], False, None)
        return ys[0].sum() + 2 * ys[1].sum() + 3 * ys[2].sum()

    g = jax.grad(f)(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(g), np.full_like(x, 6.0))


def test_bias_layer(rng):
    x = rng.randn(2, 4).astype(np.float32)
    layer, params, _, outs, _ = run_layer(
        "bias", [("init_bias", "0.5")], [(1, 1, 4)], [x])
    np.testing.assert_allclose(np.asarray(outs[0]), x + 0.5)


def test_fixconn(tmp_path, rng):
    f = tmp_path / "w.txt"
    f.write_text("2 3 2\n0 1 2.0\n1 2 -1.0\n")
    x = rng.randn(4, 3).astype(np.float32)
    _, _, _, outs, _ = run_layer(
        "fixconn", [("nhidden", "2"), ("fixconn_weight", str(f))],
        [(1, 1, 3)], [x])
    w = np.array([[0, 2, 0], [0, 0, -1]], np.float32)
    np.testing.assert_allclose(np.asarray(outs[0]), x @ w.T)


# ---------------------------------------------------------------- losses

def test_softmax_loss_grad_parity(rng):
    """Reference grad: (softmax(x) - onehot) * grad_scale/batch
    (softmax_layer-inl.hpp:25-33 + loss base scaling)."""
    x = rng.randn(6, 4).astype(np.float32)
    labels = rng.randint(0, 4, size=(6, 1)).astype(np.float32)
    layer = create_layer("softmax", [("grad_scale", "2.0")])
    layer.batch_size = 6
    layer.infer_shape([Shape3(1, 1, 4)])
    mask = jnp.ones((6,))
    g = jax.grad(lambda xx: layer.loss_value(xx, jnp.asarray(labels),
                                             mask))(jnp.asarray(x))
    p = np.exp(x - x.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    onehot = np.eye(4, dtype=np.float32)[labels[:, 0].astype(int)]
    np.testing.assert_allclose(np.asarray(g), (p - onehot) * 2.0 / 6,
                               rtol=1e-4, atol=1e-6)
    # forward transform is softmax
    outs, _ = layer.forward({}, {}, [jnp.asarray(x)], False, None)
    np.testing.assert_allclose(np.asarray(outs[0]), p, rtol=1e-5)


def test_softmax_loss_masks_padding(rng):
    x = rng.randn(4, 3).astype(np.float32)
    labels = np.zeros((4, 1), np.float32)
    layer = create_layer("softmax", [])
    layer.batch_size = 4
    layer.infer_shape([Shape3(1, 1, 3)])
    mask = jnp.asarray([1.0, 1.0, 0.0, 0.0])
    g = jax.grad(lambda xx: layer.loss_value(xx, jnp.asarray(labels),
                                             mask))(jnp.asarray(x))
    assert np.all(np.asarray(g)[2:] == 0)


def test_lp_loss_grad(rng):
    x = rng.randn(5, 3).astype(np.float32)
    lab = rng.randn(5, 3).astype(np.float32)
    layer = create_layer("lp_loss", [])
    layer.batch_size = 5
    layer.infer_shape([Shape3(1, 1, 3)])
    g = jax.grad(lambda xx: layer.loss_value(xx, jnp.asarray(lab),
                                             jnp.ones((5,))))(
        jnp.asarray(x))
    # p=2: grad = 2*(x-l)*scale
    np.testing.assert_allclose(np.asarray(g), 2 * (x - lab) / 5,
                               rtol=1e-4)


def test_multi_logistic_grad(rng):
    x = rng.randn(5, 3).astype(np.float32)
    lab = (rng.rand(5, 3) > 0.5).astype(np.float32)
    layer = create_layer("multi_logistic", [])
    layer.batch_size = 5
    layer.infer_shape([Shape3(1, 1, 3)])
    g = jax.grad(lambda xx: layer.loss_value(xx, jnp.asarray(lab),
                                             jnp.ones((5,))))(
        jnp.asarray(x))
    sig = 1 / (1 + np.exp(-x))
    np.testing.assert_allclose(np.asarray(g), (sig - lab) / 5,
                               rtol=1e-4, atol=1e-6)


# ----------------------------------------------------- insanity pooling

def test_insanity_pooling_eval_is_plain_pool(rng):
    x = rng.randn(2, 6, 6, 3).astype(np.float32)
    _, _, _, outs, _ = run_layer(
        "insanity_max_pooling",
        [("kernel_size", "2"), ("stride", "2"), ("keep", "0.8")],
        [(3, 6, 6)], [x])
    np.testing.assert_allclose(np.asarray(outs[0]),
                               _ref_pool(x, 2, 2, 0, "max"), rtol=1e-5)


def test_insanity_pooling_train_bounded(rng):
    x = rng.randn(2, 6, 6, 3).astype(np.float32)
    _, _, _, outs, _ = run_layer(
        "insanity_max_pooling",
        [("kernel_size", "2"), ("stride", "2"), ("keep", "0.5")],
        [(3, 6, 6)], [x], is_train=True, rng=jax.random.PRNGKey(0))
    y = np.asarray(outs[0])
    assert y.shape == (2, 3, 3, 3)
    assert y.max() <= x.max() + 1e-6      # displaced values are inputs


# ----------------------------------------------------------- registry

def test_vestigial_types_rejected():
    with pytest.raises(ValueError):
        create_layer("maxout", [])
    with pytest.raises(ValueError):
        create_layer("nonexistent_layer", [])
