"""Record the small trace that test_trace_reduce.py checks the reduction
against: a few dispatches of a conv and a matmul on the device, under
``bench.dispatch`` annotations, with sleeps between them so that the
device is idle for a known share. Run on the chip:

    python benchmarks/tests/record_trace.py <out_dir>
"""

import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    @jax.jit
    def work(x, w, k):
        y = jax.lax.conv_general_dilated(
            x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.tanh(y.reshape(y.shape[0], -1)[:, :1024] @ w).sum()

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (32, 56, 56, 64), jnp.bfloat16)
    k = jax.random.normal(key, (3, 3, 64, 64), jnp.bfloat16)
    w = jax.random.normal(key, (1024, 1024), jnp.bfloat16)
    work(x, w, k).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    t0 = time.time()
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(4):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            work(x, w, k).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    print("span_s", time.time() - t0)


if __name__ == "__main__":
    main(sys.argv[1])
