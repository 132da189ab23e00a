"""Pallas kernels validated against XLA reference layers via pairtest —
the reference's hand-CUDA-vs-cuDNN validation flow (SURVEY.md §4.1).
Runs in interpret mode on the CPU test mesh; the same code drives the
MXU on TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.layers import Shape3, create_layer
from cxxnet_tpu.layers.pallas_kernels import matmul


def test_pallas_matmul_matches_xla(rng):
    for m, k, n in [(8, 16, 4), (50, 256, 32), (300, 77, 130)]:
        x = jnp.asarray(rng.randn(m, k).astype(np.float32))
        w = jnp.asarray(rng.randn(k, n).astype(np.float32))
        np.testing.assert_allclose(np.asarray(matmul(x, w)),
                                   np.asarray(x @ w), atol=1e-4)


def test_pallas_matmul_grads(rng):
    x = jnp.asarray(rng.randn(10, 32).astype(np.float32))
    w = jnp.asarray(rng.randn(32, 8).astype(np.float32))

    gx, gw = jax.grad(lambda a, b: jnp.sum(matmul(a, b) ** 2),
                      argnums=(0, 1))(x, w)
    gx_ref, gw_ref = jax.grad(lambda a, b: jnp.sum((a @ b) ** 2),
                              argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               atol=1e-3)


def test_pairtest_pallas_vs_xla_fullc(rng):
    """The reference's kernel-validation flow: pairtest the Pallas layer
    against the XLA layer inside one connection."""
    layer = create_layer("pairtest-pallas_fullc-fullc", [("nhidden", "24")])
    layer.infer_shape([Shape3(1, 1, 40)])
    params = layer.init_params(jax.random.PRNGKey(0))
    state = layer.init_state()
    x = jnp.asarray(rng.randn(12, 40).astype(np.float32))
    outs, new_state = layer.forward(params, state, [x], True, None)
    assert float(new_state["pairtest:max_diff"]) < 1e-4

    # gradient parity through the pairtest tie-in
    def f(p):
        o, _ = layer.forward(p, state, [x], True, None)
        return jnp.sum(o[0] ** 2)

    g = jax.grad(f)(params)
    np.testing.assert_allclose(np.asarray(g["wmat"]),
                               np.asarray(g["slave:wmat"]), atol=1e-3)


def test_pallas_fullc_trains(rng):
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer

    conf = [
        ("input_shape", "1,1,16"),
        ("batch_size", "8"),
        ("netconfig", "start"),
        ("layer[0->1]", "pallas_fullc:fc1"),
        ("nhidden", "16"),
        ("layer[1->2]", "relu"),
        ("layer[2->3]", "fullc:fc2"),
        ("nhidden", "4"),
        ("layer[3->3]", "softmax"),
        ("netconfig", "end"),
        ("eta", "0.1"),
    ]
    t = NetTrainer(conf)
    t.init_model()
    data = rng.rand(8, 16).astype(np.float32)
    label = rng.randint(0, 4, (8, 1)).astype(np.float32)
    losses = []
    for _ in range(5):
        t.update(DataBatch(data=data, label=label))
        losses.append(t.last_loss)
    assert np.isfinite(losses[-1]) and losses[-1] < losses[0]


def test_pallas_relu_max_pool_matches_xla(rng):
    """Fused relu+maxpool kernel vs relu -> reduce_window, fwd + bwd.

    Tie semantics: the Pallas backward credits EVERY input equal to the
    window max (the reference's unpool), XLA's select-and-scatter only
    the first — continuous random data has no positive ties, so both
    paths must agree exactly there; the relu mask zeroes the x<=0
    region where relu-induced ties live.
    """
    from cxxnet_tpu.layers.pallas_kernels import relu_max_pool

    for shape, k in [((2, 9, 9, 8), 3), ((3, 12, 10, 16), 3),
                     ((2, 7, 7, 8), 2)]:
        x = jnp.asarray(rng.randn(*shape).astype(np.float32))

        def ref(a):
            r = jax.nn.relu(a)
            return jax.lax.reduce_window(
                r, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, 1, 1, 1),
                "VALID")

        y = relu_max_pool(x, k)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x)),
                                   atol=1e-6)
        g = jax.grad(lambda a: jnp.sum(relu_max_pool(a, k) ** 2))(x)
        g_ref = jax.grad(lambda a: jnp.sum(ref(a) ** 2))(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   atol=1e-5)


def test_pairtest_pallas_relu_max_pooling(rng):
    """pairtest-relu_max_pooling-pallas_relu_max_pooling: the
    validation flow for the fused stem-pool kernel."""
    layer = create_layer("pairtest-relu_max_pooling-pallas_relu_max_pooling",
                         [("kernel_size", "3"), ("stride", "1")])
    layer.infer_shape([Shape3(8, 11, 11)])
    params = layer.init_params(jax.random.PRNGKey(0))
    state = layer.init_state()
    x = jnp.asarray(rng.randn(4, 11, 11, 8).astype(np.float32))
    outs, new_state = layer.forward(params, state, [x], True, None)
    assert float(new_state["pairtest:max_diff"]) < 1e-6


def test_pallas_relu_max_pool_chunked(rng, monkeypatch):
    """Force the H-chunked halo path (production stems chunk; the small
    shapes above take the single-call path) and check fwd + the
    overlapping-halo bwd accumulation against XLA."""
    from cxxnet_tpu.layers import pallas_kernels as pk

    monkeypatch.setattr(pk, "_chunk_rows", lambda *a, **k: 8)
    x = jnp.asarray(rng.randn(2, 30, 13, 8).astype(np.float32))

    def ref(a):
        r = jax.nn.relu(a)
        return jax.lax.reduce_window(
            r, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 1, 1, 1),
            "VALID")

    y = pk.relu_max_pool(x, 3)
    assert y.shape == (2, 28, 11, 8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x)),
                               atol=1e-6)
    g = jax.grad(lambda a: jnp.sum(pk.relu_max_pool(a, 3) ** 2))(x)
    g_ref = jax.grad(lambda a: jnp.sum(ref(a) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=1e-5)


# ------------------------------------------------ conv epilogue fusion


def test_conv_epilogue_matches_reference(rng):
    """conv_epilogue vs the jnp formulation: fwd (float and int32
    accumulator inputs, NHWC and matrix nodes) + grads on the float
    path — the pairtest-style A/B for the fused dequant/BN epilogue."""
    from cxxnet_tpu.layers.pallas_kernels import conv_epilogue

    s = jnp.asarray(rng.rand(24).astype(np.float32) + 0.5)
    t = jnp.asarray(rng.randn(24).astype(np.float32))

    def ref(a, relu):
        y = a.astype(jnp.float32) * s + t
        return jnp.maximum(y, 0) if relu else y

    for shape in [(2, 6, 10, 24), (5, 24)]:
        x = jnp.asarray(rng.randn(*shape).astype(np.float32))
        for relu in (False, True):
            got = conv_epilogue(x, s, t, relu, jnp.float32)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(ref(x, relu)),
                                       atol=1e-5)
            gx, gs, gt = jax.grad(
                lambda a, b, c: jnp.sum(
                    conv_epilogue(a, b, c, relu, jnp.float32) ** 2),
                argnums=(0, 1, 2))(x, s, t)
            rx, rs, rt = jax.grad(
                lambda a, b, c: jnp.sum(
                    (jnp.maximum(a * b + c, 0) if relu
                     else a * b + c) ** 2),
                argnums=(0, 1, 2))(x, s, t)
            np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                       atol=1e-3)
            np.testing.assert_allclose(np.asarray(gs), np.asarray(rs),
                                       rtol=1e-4, atol=1e-2)
            np.testing.assert_allclose(np.asarray(gt), np.asarray(rt),
                                       rtol=1e-4, atol=1e-2)
    # int32 accumulator input (the native int8 conv dequant path)
    xi = jnp.asarray(rng.randint(-1000, 1000, (2, 6, 10, 24)),
                     jnp.int32)
    got = conv_epilogue(xi, s, t, True, jnp.float32)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref(xi, True)), rtol=1e-6)


def test_conv_epilogue_in_net_matches_weight_fold(rng):
    """conv_pallas_epilogue=1 moves the bn_fold_eval factor from the
    weights to the fused output epilogue — eval outputs must agree with
    the weight-fold formulation to reassociation-level rounding."""
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config

    conf = """
netconfig=start
layer[0->1] = conv:c1
  nchannel = 8
  kernel_size = 3
  pad = 1
  no_bias = 1
layer[1->2] = batch_norm:bn
layer[2->3] = relu
layer[3->4] = flatten
layer[4->5] = fullc:fc
  nhidden = 4
layer[5->5] = softmax
netconfig=end
input_shape = 3,8,8
batch_size = 8
eta = 0.05
bn_fold_eval = 1
bn_fuse_relu = 1
"""
    data = rng.rand(8, 8, 8, 3).astype(np.float32)
    lab = rng.randint(0, 4, (8, 1)).astype(np.float32)
    outs = {}
    for ep in (0, 1):
        t = NetTrainer(parse_config(conf)
                       + [("conv_pallas_epilogue", str(ep))])
        t.init_model()
        for i in range(3):
            t.update(DataBatch(data=data, label=lab))
        (v,) = t._call_pred(t._put_batch_array(data), None, (),
                            (t.graph.num_nodes - 1,))
        outs[ep] = np.asarray(v)
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-5)


# -------------------------------------- fused pool+concat (Inception)


def _pool_concat_ref(branches, pos, k, mode):
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


def test_pool_concat_matches_reference(rng):
    """pool_concat vs zero-padded reduce_window + concatenate: fwd and
    bwd, max and avg, pool branch at every position. Continuous random
    data has no positive ties, so the equality-credit max backward must
    agree with XLA's select-and-scatter exactly (the relu_max_pool
    argument)."""
    from cxxnet_tpu.layers.pallas_kernels import pool_concat

    for mode in ("max", "avg"):
        for pos in (0, 1, 2):
            bs = [jnp.asarray(rng.randn(2, 8, 8, c).astype(np.float32))
                  for c in (8, 16, 8)]
            got = pool_concat(tuple(bs), pos, 3, mode)
            want = _pool_concat_ref(bs, pos, 3, mode)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want), atol=1e-6)
            g = jax.grad(lambda *a: jnp.sum(
                pool_concat(a, pos, 3, mode) ** 2), argnums=(0, 1, 2))(
                    *bs)
            gr = jax.grad(lambda *a: jnp.sum(
                _pool_concat_ref(a, pos, 3, mode) ** 2),
                argnums=(0, 1, 2))(*bs)
            for a, b in zip(g, gr):
                np.testing.assert_allclose(np.asarray(a),
                                           np.asarray(b), atol=1e-4)


def test_pool_concat_net_fusion_parity(rng):
    """pool_concat_pallas=1 on an Inception-tower-shaped concat net:
    the fusion pass engages (pool layer passes through, concat runs the
    fused kernel) and training + eval stay numerically on top of the
    unfused graph."""
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config

    conf = """
netconfig=start
layer[0->1] = conv:c1
  nchannel = 8
  kernel_size = 3
  pad = 1
layer[1->2] = relu
layer[2->3,4] = split
layer[3->5] = conv:b1
  nchannel = 8
  kernel_size = 1
layer[4->6] = %s_pooling
  kernel_size = 3
  stride = 1
  pad = 1
layer[5,6->7] = ch_concat
layer[7->8] = flatten
layer[8->9] = fullc:fc
  nhidden = 4
layer[9->9] = softmax
netconfig=end
input_shape = 3,8,8
batch_size = 8
eta = 0.05
"""
    data = rng.rand(8, 8, 8, 3).astype(np.float32)
    lab = rng.randint(0, 4, (8, 1)).astype(np.float32)
    for mode in ("avg", "max"):
        preds, weights = {}, {}
        for fuse in (0, 1):
            t = NetTrainer(parse_config(conf % mode)
                           + [("pool_concat_pallas", str(fuse))])
            t.init_model()
            assert bool(t.net._pool_concat) == bool(fuse)
            if fuse:
                (pos, k, m) = list(t.net._pool_concat.values())[0]
                assert (pos, k, m) == (1, 3, mode)
                assert len(t.net._pool_passthrough) == 1
            for i in range(3):
                t.update(DataBatch(data=data, label=lab))
            (v,) = t._call_pred(t._put_batch_array(data), None, (),
                                (t.graph.num_nodes - 1,))
            preds[fuse] = np.asarray(v)
            weights[fuse] = t.get_weight("c1", "wmat")
        # same data, same seeds: the fused graph must train on top of
        # the unfused one (rounding-level drift only)
        np.testing.assert_allclose(weights[0], weights[1], atol=1e-5)
        np.testing.assert_allclose(preds[0], preds[1], atol=1e-5)


def test_pool_concat_fusion_gates(rng):
    """The pass must NOT fuse: non-SAME pools, stride-2 reduction
    modules, pools with a second consumer, channel_pad graphs (the
    alignment pass owns concat layout there), or with the knob off."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config

    base = """
netconfig=start
layer[0->1] = conv:c1
  nchannel = 8
  kernel_size = 3
  pad = 1
layer[1->2] = relu
layer[2->3,4] = split
layer[3->5] = conv:b1
  nchannel = 8
  kernel_size = 1
layer[4->6] = avg_pooling
  kernel_size = 3
  stride = %s
  pad = %s
layer[5,6->7] = ch_concat
layer[7->8] = flatten
layer[8->9] = fullc:fc
  nhidden = 4
layer[9->9] = softmax
netconfig=end
input_shape = 3,8,8
batch_size = 8
eta = 0.05
pool_concat_pallas = 1
"""
    # VALID pad (not SAME) must not fuse
    t = NetTrainer(parse_config(base % ("1", "0")))
    with np.testing.assert_raises(Exception):
        # pad 0 changes the spatial size -> the concat itself rejects
        # the mismatched branches; build fails either way
        t.init_model()
    # channel_pad disables the pass outright
    from cxxnet_tpu.utils.config import parse_config as pc
    t2 = NetTrainer(pc(base % ("1", "1"))
                    + [("channel_pad", "128"),
                       ("channel_pad_max_overhead", "10")])
    t2.init_model()
    assert not t2.net._pool_concat
    # SAME avg pool with pool_concat_pallas=0 never fuses
    t3 = NetTrainer(pc((base % ("1", "1"))
                       .replace("pool_concat_pallas = 1",
                                "pool_concat_pallas = 0")))
    t3.init_model()
    assert not t3.net._pool_concat
    # a SECOND consumer of the pool output (the pool branch re-enters
    # a later concat, like an aux head) kills the fusion for both
    # concats: the pass-through would change what the other reader sees
    second = (base % ("1", "1")).replace(
        """layer[7->8] = flatten""",
        """layer[7,6->7b] = ch_concat
layer[7b->8] = flatten""")
    t4 = NetTrainer(pc(second))
    t4.init_model()
    assert not t4.net._pool_concat
    # stride-2 reduction module (all branches stride 2, k=2 so the
    # floor/ceil output sizes agree): strided pools never fuse
    reduction = (base % ("1", "1")).replace(
        """layer[3->5] = conv:b1
  nchannel = 8
  kernel_size = 1""",
        """layer[3->5] = conv:b1
  nchannel = 8
  kernel_size = 2
  stride = 2""").replace(
        """layer[4->6] = avg_pooling
  kernel_size = 3
  stride = 1
  pad = 1""",
        """layer[4->6] = avg_pooling
  kernel_size = 2
  stride = 2""")
    t5 = NetTrainer(pc(reduction))
    t5.init_model()
    assert not t5.net._pool_concat


def test_pool_concat_applicability_probe():
    from cxxnet_tpu.layers.pallas_kernels import pool_concat_applicable

    assert pool_concat_applicable(8, 8, 32, 3, 4)
    assert pool_concat_applicable(28, 28, 1024, 3, 2)
    assert not pool_concat_applicable(112, 112, 1024, 3, 4)  # stem size
    assert not pool_concat_applicable(8, 8, 32, 2, 4)   # even kernel
    assert not pool_concat_applicable(8, 8, 32, 1, 4)   # no window


@pytest.mark.parametrize("hk,hv,dk,dv,t,tiles,dtype", [
    (2, 4, 128, 128, 256, (128,), "float32"),     # two time tiles
    (2, 4, 128, 128, 256, (128,), "bfloat16"),
    (2, 4, 128, 128, 256, None, "float32"),       # one tile of 256
    (3, 6, 128, 128, 256, (128,), "float32"),     # three column blocks a part
    (1, 2, 256, 128, 384, (128,), "float32")])    # a head of 256, three tiles
def test_gated_delta_conv_matches_the_xla_form(hk, hv, dk, dv, t, tiles,
                                               dtype, monkeypatch):
    """gated_delta's short convolution as the fused kernels
    (pallas_kernels.gated_delta_conv, interpreted) against its XLA form
    (layers/sequence.py: short_conv): q, k and v, and the gradients in
    ``qkv`` and the taps through a weighted sum. A time tile shorter
    than the sequence carries the taps' positions across tiles, forward
    (the positions before a tile) and backward (after it). float32 to
    its rounding; bfloat16 within one rounding, where the XLA form rounds
    each tap's term of the input's gradient before it adds them."""
    from cxxnet_tpu.layers import pallas_kernels as pk
    from cxxnet_tpu.layers.sequence import short_conv
    if tiles:
        monkeypatch.setattr(pk, "_CONV_TILES", tiles)
    cd = jnp.dtype(dtype)
    kw, vw, taps_n = hk * dk, hv * dv, 4
    assert pk.gated_delta_conv_applicable(t, taps_n, hk, hv, dk, dv, cd)
    assert pk._conv_tile(t, pk._conv_lanes(kw, vw, dk), cd) == (tiles or (t,))[0]
    ks = jax.random.split(jax.random.PRNGKey(hk + t), 5)
    qkv = jax.random.normal(ks[0], (2, t, 2 * kw + vw)).astype(cd)
    taps = jax.random.uniform(ks[1], (taps_n, 2 * kw + vw), minval=-0.5,
                              maxval=0.5)
    ws = [jax.random.normal(k, (2, t, n)) for k, n in zip(ks[2:],
                                                         (kw, kw, vw))]
    forms = {"kernel": lambda a, b: pk.gated_delta_conv(a, b, kw, dk),
             "xla": lambda a, b: [o.reshape(2, t, -1) for o in short_conv(
                 a, b, hk, dk, dv, cd)]}
    got = {}
    for name, f in forms.items():
        got[name] = f(qkv, taps), jax.grad(lambda a, b: sum(
            jnp.sum(w * o.astype(jnp.float32)) for w, o in zip(ws, f(a, b))),
            argnums=(0, 1))(qkv, taps)
    (outs, grads), (outs_x, grads_x) = got["kernel"], got["xla"]
    tol = {"float32": 2e-6, "bfloat16": 1e-2}[dtype]
    for a, b in list(zip(outs, outs_x)) + list(zip(grads, grads_x)):
        assert a.shape == b.shape and a.dtype == b.dtype
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.abs(a - b).max() <= tol * np.abs(b).max()
    # unit length a head (q over sqrt(dk)), and causal: the first
    # positions see zeros before the sequence
    q = np.asarray(outs[0], np.float64).reshape(2, t, hk, dk)
    np.testing.assert_allclose((q ** 2).sum(-1), 1.0 / dk, rtol=2e-2)
    later = pk.gated_delta_conv(qkv.at[:, t // 2].add(1.0), taps, kw, dk)
    for a, b in zip(later, outs):
        assert np.array_equal(np.asarray(a[:, :t // 2]),
                              np.asarray(b[:, :t // 2]))
