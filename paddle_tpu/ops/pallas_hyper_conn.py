"""The hyper-connections' stream pass in Pallas (TPU): `mhc_mix`,

    X'[j] = sum_i H_res[j, i] X[i] + H_post[j] y        (ops/hyper_conn.py)

over a step's rows.  The jnp form is a [rows, n, n] x [rows, n, C] batched
product of 4 x 4 matrices — no MXU shape — which XLA lowers to a loop over
the streams that passes over X once a result stream; here a tile of rows
brings its n streams and the sublayer's output into VMEM ONCE, takes the
n (n + 1) scaled sums on the VPU in float32 (a map's entry is one value a
row, broadcast along the lanes of its stream), and writes the n new
streams once: (2 n + 1) C values a row are moved, which is what the
roofline reader counts (benchmark/lib/mhc_latent_moe.py).

  grid (row tiles,): a step holds `tile_rows` rows of X [rows, n C] whole
  (the flat layer the graph carries: stream i is columns [i C, (i + 1) C),
  lane-aligned where C is a multiple of 128), of y [rows, C] and of the
  maps [rows, 2 n + n^2] float32 as ops/hyper_conn.py packs them.  Rows are
  independent: the axis is parallel, and a row count that no tile divides
  is padded by the caller's rows of zeros (their result is cut off).

The maps themselves (a norm, one [rows, n C] x [n C, 2 n + n^2] product,
20 Sinkhorn iterations on [rows, n, n]) and the read stay XLA ops under
the scope `mhc.map`.  Forward only: a differentiated graph keeps the jnp
form (graph/layers_hc.py), as `kda_seg` does.

Interpret-mode parity with ops/hyper_conn.py `mix` is the CPU oracle
(tests/test_hyper_conn.py); tests/test_mosaic_compile.py asks the chip's
compiler at the cell's shapes (1,088 and 48 rows, 4 streams of 3,584,
bfloat16).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.utils.jax_compat import pallas_tpu_compiler_params

Array = jax.Array


def supported(backend: Optional[str] = None) -> bool:
    """Whether the pallas stream pass may be used."""
    if os.environ.get("PADDLE_TPU_PALLAS", "1") == "0":
        return False
    backend = backend or jax.default_backend()
    if backend == "tpu":
        return True
    # off-TPU the kernel only runs in (slow) interpret mode — opt-in
    return os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def tile_rows(rows: int) -> int:
    """Rows a grid step holds: 32 where that divides the rows (a mixed
    step's 1,088), else 16 (a decode step's 48; the bfloat16 tile's
    sublanes).  At 32 rows of 4 x 3,584 bfloat16 the streams in and out,
    double-buffered, are 3.7 MiB of VMEM."""
    return 32 if rows % 32 == 0 else 16


def _kernel(n: int, x_ref, y_ref, m_ref, o_ref):
    c = y_ref.shape[1]
    f32 = jnp.float32
    y = y_ref[...].astype(f32)
    xs = [x_ref[:, i * c:(i + 1) * c].astype(f32) for i in range(n)]
    for j in range(n):
        acc = m_ref[:, n + j:n + j + 1] * y                  # H_post[j] y
        for i in range(n):
            k = 2 * n + j * n + i
            acc = acc + m_ref[:, k:k + 1] * xs[i]            # H_res[j, i]
        o_ref[:, j * c:(j + 1) * c] = acc.astype(o_ref.dtype)


def mhc_mix(x: Array, y: Array, m: Array, n: int) -> Array:
    """x [rows, n C], y [rows, C] (x's dtype), m [rows, 2 n + n^2] float32
    -> X' [rows, n C] in x's dtype."""
    rows, c = y.shape
    assert x.shape == (rows, n * c) and m.shape == (rows, 2 * n + n * n)
    tr = tile_rows(rows)
    pad = -rows % tr
    if pad:
        x, y, m = (jnp.pad(a, ((0, pad), (0, 0))) for a in (x, y, m))
    by_row = lambda r: (r, 0)
    out = pl.pallas_call(
        functools.partial(_kernel, n),
        name="mhc_mix",         # the device op's name in a profiler trace
        grid=((rows + pad) // tr,),
        in_specs=[pl.BlockSpec((tr, n * c), by_row),
                  pl.BlockSpec((tr, c), by_row),
                  pl.BlockSpec((tr, m.shape[1]), by_row)],
        out_specs=pl.BlockSpec((tr, n * c), by_row),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(x, y.astype(x.dtype), m.astype(jnp.float32))
    return out[:rows] if pad else out
