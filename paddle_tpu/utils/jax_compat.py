"""The few jax entry points this repo calls through one name.

Written against the installed jax (0.9): `jax.shard_map` with `check_vma`,
`jax.enable_x64`, `lax.axis_size`, `pltpu.CompilerParams`.  Each function
is the direct call; the module stays so the import sites have one place to
change when an API moves.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None):
    """`jax.shard_map`; `check_vma=False` opts out of the varying-axes
    check for bodies — pallas calls, hand-rolled ppermute rings — the
    checker cannot type."""
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def enable_x64():
    """`jax.enable_x64()` context manager (f64 checkgrad/test paths)."""
    return jax.enable_x64()


def axis_size(axis_name) -> int:
    """`lax.axis_size(name)` inside a shard_map/pmap body."""
    return jax.lax.axis_size(axis_name)


def pallas_tpu_compiler_params(**kw):
    """`pltpu.CompilerParams`."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kw)
