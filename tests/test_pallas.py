"""The short-convolution kernels of ``gated_delta``
(``pallas_kernels.gated_delta_conv``) against the XLA form they stand in
for, in interpret mode on the CPU; the same code compiles for the chip
(tests/test_chip_compile.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


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
