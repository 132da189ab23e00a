"""NHWC vs NCHW conv layout microbench (VERDICT r3 'What's weak' §1).

Measures representative Inception-BN conv shapes (fwd + bwd) under both
``dimension_numbers`` conventions on the real chip, to answer whether a
whole-net NCHW port could move the 15%-MFU wall — without porting the
net. Run: ``python doc/layout_microbench.py`` (TPU, ~3 min).

Measurement discipline: the timed dispatch uses DIFFERENT arguments
than the warmup, and all N iterations run inside ONE jitted fori_loop
whose input depends on the loop carry (no loop-invariant hoisting, one
dispatch).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

N = 30


def bench_conv(b, h, w, cin, cout, k, stride, pad, layout):
    rng = np.random.RandomState(0)
    if layout == "NHWC":
        xs = [jnp.asarray(rng.rand(b, h, w, cin), jnp.bfloat16)
              for _ in range(2)]
        kern = jnp.asarray(rng.rand(k, k, cin, cout), jnp.bfloat16)
        dn = ("NHWC", "HWIO", "NHWC")
    else:
        xs = [jnp.asarray(rng.rand(b, cin, h, w), jnp.bfloat16)
              for _ in range(2)]
        kern = jnp.asarray(rng.rand(cout, cin, k, k), jnp.bfloat16)
        dn = ("NCHW", "OIHW", "NCHW")

    def loss(x, kern):
        y = jax.lax.conv_general_dilated(
            x, kern, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=dn)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    g = jax.grad(loss, argnums=(0, 1))

    @jax.jit
    def many(x, kern):
        def body(i, acc):
            gx, gk = g(x + acc.astype(x.dtype), kern)
            return acc + jnp.sum(gk.astype(jnp.float32)) * 1e-30
        return jax.lax.fori_loop(0, N, body, jnp.float32(0.0))

    float(many(xs[0], kern))        # compile + warm (fetch = true sync:
    #                                 block_until_ready returns before
    #                                 remote execution completes here)
    t0 = time.perf_counter()
    float(many(xs[1], kern))        # different args: no terminal memo
    return (time.perf_counter() - t0) / N * 1e3


if __name__ == "__main__":
    # representative Inception-BN interior shapes (batch 128):
    # 3x3 conv at 28^2, 1x1 reductions at 28^2/14^2, 3x3 at 14^2
    shapes = [
        (128, 28, 28, 96, 128, 3, 1, 1),
        (128, 28, 28, 320, 128, 1, 1, 0),
        (128, 14, 14, 576, 192, 1, 1, 0),
        (128, 14, 14, 160, 192, 3, 1, 1),
        (128, 7, 7, 1024, 352, 1, 1, 0),
    ]
    print("shape (b,h,w,cin,cout,k,s,p)      NHWC ms   NCHW ms")
    for s in shapes:
        nhwc = bench_conv(*s, layout="NHWC")
        nchw = bench_conv(*s, layout="NCHW")
        print("%-32s  %7.3f   %7.3f" % (s, nhwc, nchw))
