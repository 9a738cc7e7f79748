"""Test env: CPU backend with 8 virtual devices so mesh/sharding tests run
without TPU hardware (mirrors the reference's strategy of testing distributed
paths in one process — SURVEY.md §4(d)).

Note: an interpreter may start with jax already imported and another
platform latched into jax.config, so setting os.environ here can be too
late — jax.config is updated directly as well.  XLA_FLAGS is still read at
first CPU-client creation, which happens after conftest, so the env route
works for the device count.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses tests spawn

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# tests/benchmark/test_cell_nemotron_h.py is PR 41's and lies under
# BENCHMARK.json's `paths`, so only a `benchmark` PR may edit it.  One of its
# tests asserts that ITS cell and configuration are the LAST entries of
# BENCHMARK.json's lists — which lists that may only grow at their end cannot
# keep once any cell follows (PR 43's did).  Until a `benchmark` PR unpins it
# (ROADMAP B17, PERF.md section 7 row 20) that one test is expected to fail,
# strictly: the day it passes, this marker goes.  Everything else it asserts
# is held, with the index of the cell and the order of what lies before it
# pinned in place of "last", by tests/benchmark/test_cell_jamba.py::
# test_the_cell_before_this_one_is_declared_as_its_own_test_says.
PINNED_LAST = ("test_cell_nemotron_h.py::"
               "test_cell_and_its_metrics_are_declared_as_the_issue_names_them")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid.endswith(PINNED_LAST):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="pins its cell as BENCHMARK.json's last; a cell was "
                       "appended behind it and the file is a benchmark "
                       "PR's to edit"))
