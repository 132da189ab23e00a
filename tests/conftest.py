"""Test harness: force an 8-device virtual CPU platform so multi-device
sharding paths run without TPU hardware — the moral equivalent of the
reference's ps-lite local mode (SURVEY.md §4.5) — and choose Pallas
interpret mode explicitly, since no kernel can compile for the CPU.
"""

import os

# jax.config (force_virtual_cpu below) settles THIS process; the env
# vars are for the subprocesses some tests spawn (the embedded-CPython
# C wrapper test, CLI children), which inherit them
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

from cxxnet_tpu.parallel import force_virtual_cpu

force_virtual_cpu(8)

from cxxnet_tpu.layers import pallas_kernels

# the tests' own choice, not a fallback the program takes in silence:
# kernels are checked for their arithmetic here; that they compile for
# the chip is tests/test_chip_compile.py's job
pallas_kernels.set_interpret(True)

import numpy as np
import pytest

assert jax.default_backend() == "cpu"

# the reference checkout is not mounted in every container; suites
# that parse its actual example configs mark themselves with this and
# skip (not fail) without it
REFERENCE_DIR = "/root/reference"
needs_reference = pytest.mark.skipif(
    not os.path.isdir(REFERENCE_DIR),
    reason="reference mount %s is absent in this container"
    % REFERENCE_DIR)


def pytest_configure(config):
    # tier-1 runs -m 'not slow' (ROADMAP verify line): anything over
    # the budget — e.g. the H=4 dryrun overlap sweep — marks itself
    # slow and runs in the full suite only
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 budgeted run")


@pytest.fixture
def rng():
    return np.random.RandomState(0)
