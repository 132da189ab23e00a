"""The benchmark's plain references are copies of the package's.

``benchmarks/reference/<model>.py`` is what a cell's ``correct`` holds
the program to, and it lives under ``benchmarks/`` so that a change to
the program cannot move the yardstick; ``cxxnet_tpu/reference/`` holds
the same file for the package's own tests. The two must not drift
apart unseen."""

import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("model", ["kimi_vl_a3b", "trinity_mini",
                                   "qwen3_next", "lfm2_24b_a2b",
                                   "mellum2_12b_a2_5b"])
def test_benchmark_reference_is_the_packages(model):
    def read(*parts):
        with open(os.path.join(REPO, *parts, model + ".py"), "rb") as f:
            return f.read()

    assert read("benchmarks", "reference") == read("cxxnet_tpu",
                                                   "reference")
