"""Persistent XLA compile cache placement for the CLI entry points.

Every cold process on the chip otherwise pays every compile.  The rule:
`JAX_COMPILATION_CACHE_DIR` in the environment wins and this module sets
nothing (JAX reads the variable itself); without it the cache lives at ONE
fixed path inside the checkout.  The path is part of the cache key, so it
never carries a tempdir, pid or timestamp.

Called from each entry point's `main()` — never at import time of a package
module, so importing paddle_tpu has no side effect on `jax.config`.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
