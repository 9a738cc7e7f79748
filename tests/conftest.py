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
