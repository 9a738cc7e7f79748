"""Shared helpers of the benchmark's own CPU tests.  Nothing here (or in
any test module of this directory) touches a TPU topology or JAX at import
time: the workers of the tier-1 run collect every file."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session")
def root():
    return ROOT


@pytest.fixture(scope="session")
def bench():
    from benchmark.lib.spec import Benchmark
    return Benchmark(ROOT)
