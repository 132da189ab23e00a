"""Where jax's persistent compilation cache lives — one rule, one place.

The directory is part of the cache key, so a cache that moves never
hits. The rule every entry point shares (trainer,
``benchmarks/run.py``, ``chip_smoke.py``):

- ``JAX_COMPILATION_CACHE_DIR`` set in the environment wins. jax reads
  it into ``jax_compilation_cache_dir`` itself at import; the program
  sets no directory in code, and a ``compile_cache_dir`` key that names
  another place is ignored with one warning.
- otherwise the ``compile_cache_dir`` key, when the run carries one;
- otherwise the caller's fixed default (``chip_smoke.py`` passes
  :data:`REPO_CACHE_DIR`, ``benchmarks/run.py`` the same path; the
  trainer passes none, so a plain run without the key caches nothing).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# the fixed in-checkout default (git-ignored): never a tempfile / pid /
# time path — the path is part of the key
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(cfg_dir: str = "", default_dir: str = "") -> str:
    """Turn the persistent compilation cache on for this process and
    return the directory in effect ("" = no cache asked for)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    env_dir = os.environ.get(ENV_VAR, "")
    if env_dir and cfg_dir \
            and os.path.abspath(cfg_dir) != os.path.abspath(env_dir):
        from ..monitor import warn_once
        warn_once("compile_cache_dir_ignored",
                  "compile_cache_dir = %s is ignored: %s=%s is set in "
                  "the environment and wins" % (cfg_dir, ENV_VAR, env_dir))
    asked = cfg_dir or default_dir
    if not asked:
        return env_dir                   # jax's own defaults apply
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", asked)
    # cache every program, however small or quick to compile: a chip
    # run's cold start is the sum of all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # drop the 'cache disabled' state memoized by any compile that ran
    # before the dir was configured (library init, net.init) — without
    # this the dir is set but never written
    compilation_cache.reset_cache()
    return env_dir or asked


# -- arrays in a pinned device layout ---------------------------------------
#
# jax 0.9.0 makes every array with a non-default device layout the
# same way — ``device_put(x, Format(layout, sharding))`` runs a jitted
# identity whose OUTPUT layout is pinned — and an executable read back
# from the persistent compilation cache has lost its output layouts:
# the first process gets the layout it asked for, every later process
# (cache hit) silently gets the default one, and a program compiled
# for the pinned input then refuses the array. Seen on the v5e and
# reproduced on the CPU backend (tests/test_pipeline.py). Input pins
# of cached programs survive. So the relayout program must never be
# READ from the cache: it carries a per-process salt as a constant
# second output, which gives it a cache key no earlier process wrote.
# It compiles in milliseconds, once per input shape.

_SALT = int.from_bytes(os.urandom(4), "little")


def _salted_identity(x):
    import numpy as np
    return x, np.uint32(_SALT)


_relayout_programs: dict = {}


def put_with_layout(x, fmt):
    """``jax.device_put(x, fmt)`` for a ``Format`` with a pinned
    layout, proof against the persistent cache (see above). Checks what
    came out: the array carries the layout, or this raises."""
    import jax
    fn = _relayout_programs.get(fmt)
    if fn is None:
        fn = _relayout_programs[fmt] = jax.jit(
            _salted_identity, out_shardings=(fmt, None))
    out, _ = fn(x)
    got = out.format.layout.major_to_minor
    if tuple(got) != tuple(fmt.layout.major_to_minor):
        raise RuntimeError(
            "asked for device layout major_to_minor=%s, the array came "
            "out as %s" % (tuple(fmt.layout.major_to_minor), tuple(got)))
    return out
