"""The Mamba-1 selective scan in Pallas (TPU): the recurrence of
ops/selective_scan.py with THE TIME LOOP INSIDE THE KERNEL.  A run is a
slot's consecutive tokens in one step — one token for a decode row, a
prompt chunk's rows (its `prefill_chunk` share of the step and the rows the
step had free); the run's slot rides the scalar-prefetch
channel and addresses its state block, which comes into VMEM once, is moved
through every token of the run

    h[n, c] = exp(dt[c] A[n, c]) h[n, c] + dt[c] x[c] B[n]
    y[c]    = sum_n h[n, c] C[n]

on the VPU (float32, elementwise products and an add of vregs along
sublanes: no MXU pass, nothing rounded), and goes back once — the state
operand is aliased to the state result, so nothing else of the pool moves.

A file of its own, not a third switch of ops/pallas_kda.py's step body:
that body moves a [dk, dv] state a HEAD by a rank-1 product whose vectors
are lanes of a packed operand; here there are no heads, the decay is a
tensor of the state's own shape, and the body is a loop over tokens.  What
the two share is the calling convention (slot and live mask prefetched, the
state block aliased in place, dead rows aimed at the trash row).

The state lies N along sublanes and d_in along lanes ([16, 5120] float32:
two tiles deep, forty wide) and is walked 128 lanes at a time, so a
[16, 128] piece of it is two vregs and a run's piece stays in registers
across its tokens.  B and C differ a token and are wanted along SUBLANES
(one value a state row): the caller hands them already spread along 128
lanes, `bc` [tokens, 2 N, 128] — 16 KiB a token where the state is 320 KiB
a run.

Two calls, two names in a device trace:

  `selective_scan_step`  grid (rows,): one token a run, the whole d_in a
      block (320 KiB in, 320 KiB back a live row).  The decode step (row r
      = slot r) and the decode rows of the mixed step (row r = slot
      row_slot[r]).
  `selective_scan_seg`   grid (d_in blocks, RUNS_PER_CALL): the chunk runs
      of a mixed step, `RUNS_PER_CALL` a call and as many calls as the runs
      need (a loop with a dynamic trip count: a step usually holds two to
      four runs).  The tokens' operands of ALL the chunk rows are one block
      a d_in block, fetched once; a run walks its own rows of it, from
      `start` for `length` tokens — a loop with a dynamic trip count inside
      the kernel.  A dead run (length 0) is aimed at the trash row and
      copies it.

A token whose dt is 0 is the identity (exp(0) = 1, input 0): padding inside
a run needs no mask.

Interpret-mode parity with ops/selective_scan.py `recurrent` is the CPU
oracle (tests/test_jamba.py); tests/test_mosaic_compile.py asks the chip's
compiler at the cell's shapes (256 rows of one token; runs of 128 in 256
chunk rows; d_in 5,120, N 16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import pallas_kda
from paddle_tpu.ops.selective_scan import segment_table
from paddle_tpu.utils.jax_compat import pallas_tpu_compiler_params

Array = jax.Array

RUNS_PER_CALL = 4       # runs a `selective_scan_seg` call holds
_SEG_BLOCK = 1280       # d_in lanes a grid step of the segment call holds:
                        # ten [16, 128] pieces of state = 20 vregs carried
                        # across a run's tokens
_VMEM_LIMIT = 64 * 2 ** 20


def _lanes(d: int) -> int:
    """Lanes a piece of the state holds: a vreg's 128, or a narrow test
    width whole."""
    return 128 if d % 128 == 0 else d


def _spread(Bm: Array, Cm: Array, L: int) -> Array:
    """B and C [T, N] a token as `bc` [T, 2 N, L]: one value a state row,
    spread along the L lanes of a piece."""
    bc = jnp.concatenate([Bm, Cm], axis=1)
    return jnp.broadcast_to(bc[..., None], bc.shape + (L,))


def _step_kernel(N: int, L: int, slot_ref, live_ref, tok_ref, bc_ref, a_ref,
                 s_ref, y_ref, s_out_ref):
    del slot_ref                        # it addressed the state block
    live = live_ref[pl.program_id(0)] != 0
    n_pieces = s_ref.shape[2] // L

    @pl.when(jnp.logical_not(live))
    def _():
        # a dead row: finite zeros go on to the next layers, and the trash
        # row keeps what it held
        y_ref[...] = jnp.zeros_like(y_ref)
        s_out_ref[...] = s_ref[...]

    @pl.when(live)
    def _():
        Bm, Cm = bc_ref[0, 0:N, :], bc_ref[0, N:2 * N, :]
        for j in range(n_pieces):
            sl = slice(j * L, (j + 1) * L)
            h = s_ref[0, :, sl] * jnp.exp(tok_ref[0, 0:1, sl] * a_ref[:, sl]) \
                + tok_ref[0, 1:2, sl] * Bm
            y_ref[0, 0:1, sl] = jnp.sum(h * Cm, axis=0, keepdims=True)
            s_out_ref[0, :, sl] = h


def selective_scan_step(state: Array, slot: Array, live: Array, x: Array,
                        Bm: Array, Cm: Array, dt: Array, A: Array):
    """One token a row (ops/selective_scan.py `step`): state [S+1, N, d_in]
    float32; slot [R] int32 (a dead row's is the trash row S), live [R]
    bool; x dt [R, d_in], Bm Cm [R, N], A [N, d_in], all float32 -> (y
    [R, d_in] without the D x term, state)."""
    R, d_in = x.shape
    N = Bm.shape[1]
    L = _lanes(d_in)
    tok = jnp.stack([dt, dt * x], axis=1)                     # [R, 2, d_in]
    bc = _spread(Bm, Cm, L)
    by_row = lambda r, slot, live: (r, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # slot, live
        grid=(R,),
        in_specs=[pl.BlockSpec((1, 2, d_in), by_row),
                  pl.BlockSpec((1, 2 * N, L), by_row),
                  pl.BlockSpec((N, d_in), lambda r, slot, live: (0, 0)),
                  pl.BlockSpec((1, N, d_in),
                               lambda r, slot, live: (slot[r], 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, d_in), by_row),
                   pl.BlockSpec((1, N, d_in),
                                lambda r, slot, live: (slot[r], 0, 0))])
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, N, L),
        name="selective_scan_step",     # the device op's name in a trace
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, 1, d_in), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the two prefetched scalars: the state is the 6th
        input_output_aliases={5: 1},
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_kda._interpret(),
    )(slot.astype(jnp.int32), live.astype(jnp.int32), tok, bc, A, state)
    return y[:, 0], state


_GROUP = 8              # tokens a pass of a run's loop holds: one
                        # float32 tile of sublanes


def _seg_kernel(N: int, L: int, slot_ref, start_ref, len_ref, zero_ref,
                dt_ref, dx_ref, bc_ref, a_ref, s_ref, y_ref, s_out_ref):
    """A run's tokens in passes of `_GROUP` rows, the rows of a pass whole
    tiles at an aligned offset (Mosaic loads no single row at a dynamic
    sublane): the rows of a pass outside the run — before its first token,
    past its last — get dt = dx = 0, the identity, and keep the y they
    had."""
    del slot_ref                        # it addressed the state block
    g = pl.program_id(1)
    n_pieces = s_ref.shape[2] // L

    @pl.when(g == 0)
    def _():
        # rows no run of this call holds (padding, other calls' runs)
        y_ref[...] = jnp.zeros_like(y_ref)

    t0, n = start_ref[g], len_ref[g]
    keep = jnp.where(zero_ref[g] != 0, 0.0, 1.0)    # a run from position 0
    hs = tuple(s_ref[0, :, j * L:(j + 1) * L] * keep
               for j in range(n_pieces))
    sub = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, L), 0)

    def group(p, hs):
        base = pl.multiple_of(p * _GROUP, _GROUP)
        rows = pl.ds(base, _GROUP)
        mine = (sub + base >= t0) & (sub + base < t0 + n)
        out = []
        for j in range(n_pieces):
            sl = slice(j * L, (j + 1) * L)
            dt = jnp.where(mine, dt_ref[rows, sl], 0.0)
            dx = jnp.where(mine, dx_ref[rows, sl], 0.0)
            h, y = hs[j], y_ref[rows, sl]
            for i in range(_GROUP):
                h = h * jnp.exp(dt[i:i + 1] * a_ref[:, sl]) \
                    + dx[i:i + 1] * bc_ref[base + i, 0:N, :]
                y_i = jnp.sum(h * bc_ref[base + i, N:2 * N, :], axis=0,
                              keepdims=True)
                y = jnp.where(mine & (sub == i), y_i, y)
            y_ref[rows, sl] = y
            out.append(h)
        return tuple(out)

    hs = jax.lax.fori_loop(t0 // _GROUP, (t0 + n + _GROUP - 1) // _GROUP,
                           group, hs)
    for j in range(n_pieces):
        s_out_ref[0, :, j * L:(j + 1) * L] = hs[j]


def _seg_call(state, slot, start, length, zero, dt, dx, bc, A):
    """One `selective_scan_seg` call: RUNS_PER_CALL runs over the P chunk
    rows -> (y [P, d_in], zeros outside the runs; state)."""
    P, d_in = dt.shape
    N = A.shape[0]
    L = bc.shape[-1]
    db = _SEG_BLOCK if d_in % _SEG_BLOCK == 0 else d_in
    G = slot.shape[0]
    tokens = lambda j, g, *_: (0, j)
    by_slot = lambda j, g, slot, *_: (slot[g], 0, j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,                   # slot, start, length, zero
        grid=(d_in // db, G),
        in_specs=[pl.BlockSpec((P, db), tokens),
                  pl.BlockSpec((P, db), tokens),
                  pl.BlockSpec((P, 2 * N, L), lambda j, g, *_: (0, 0, 0)),
                  pl.BlockSpec((N, db), tokens),
                  pl.BlockSpec((1, N, db), by_slot)],
        out_specs=[pl.BlockSpec((P, db), tokens),
                   pl.BlockSpec((1, N, db), by_slot)])
    return pl.pallas_call(
        functools.partial(_seg_kernel, N, L),
        name="selective_scan_seg",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((P, d_in), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the four prefetched scalars: the state is the 9th
        input_output_aliases={8: 1},
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pallas_kda._interpret(),
    )(slot, start, length, zero, dt, dx, bc, A, state)


def selective_scan_segments(state: Array, seg_slot: Array, seg_pos: Array,
                            x: Array, Bm: Array, Cm: Array, dt: Array,
                            A: Array):
    """The chunk rows of a ragged mixed step (ops/selective_scan.py
    `segment_rows`): state [S+1, N, d_in] float32; seg_slot seg_pos [P]
    int32 (padding aims at trash row S); x dt [P, d_in], Bm Cm [P, N], A
    [N, d_in], all float32 -> (y [P, d_in] without D x, state,
    n_segments)."""
    rows, trash = x.shape[0], state.shape[0] - 1
    if rows % _GROUP:               # whole passes: rows of dt = 0 at trash
        grow = lambda a, v=0: jnp.pad(
            a, ((0, -rows % _GROUP),) + ((0, 0),) * (a.ndim - 1),
            constant_values=v)
        seg_slot = grow(seg_slot, trash)
        seg_pos, x, Bm, Cm, dt = map(grow, (seg_pos, x, Bm, Cm, dt))
    P, d_in = x.shape
    N = Bm.shape[1]
    L = _lanes(d_in)
    G = RUNS_PER_CALL
    start, length, slot, zero, n_seg = segment_table(seg_slot, seg_pos, trash)
    pad = -P % G
    start, length, zero = (jnp.pad(a.astype(jnp.int32), (0, pad))
                           for a in (start, length, zero))
    slot = jnp.pad(slot.astype(jnp.int32), (0, pad), constant_values=trash)
    dx = dt * x
    bc = _spread(Bm, Cm, L)

    def body(i, carry):
        state, y = carry
        runs = (jax.lax.dynamic_slice_in_dim(a, i * G, G)
                for a in (slot, start, length, zero))
        y_i, state = _seg_call(state, *runs, dt, dx, bc, A)
        return state, y + y_i

    state, y = jax.lax.fori_loop(
        0, (n_seg + G - 1) // G, body,
        (state, jnp.zeros((P, d_in), jnp.float32)))
    return y[:rows], state, n_seg
