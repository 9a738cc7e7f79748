"""The recurrent layers' decode steps in Pallas (TPU): one token a row
against the slot states, the state moved in place — `kda_step` (the KDA
delta rule, below), `gdn_step` (the same body for Gated DeltaNet's decay a
head: `a` is one number a head repeated down its column, the state a
rectangular [96, 192] at Olmo-Hybrid's widths; its own name so that a trace
tells the two rules apart) and `ssd_step` (Mamba-2's scalar-decay step,
ops/ssd.py: the same body without the correction term, one static switch;
the trace shows each under its own name).

The jnp form (ops/kda.py `step`) is a reduction over the state (S'^T k)
followed by an update that needs its result, so XLA passes over the 2 MiB a
row a layer twice.  Here a head's [dk, dv] float32 state (64 KiB at 128 x
128) comes into VMEM once, takes the whole rank-1 step

    S' = Diag(a) S;  u = b (v - S'^T k);  S = S' + k u^T;  o = S^T q

on the VPU (elementwise products and sublane reductions, float32: no MXU
pass to round anything), and goes back once — the state operand is aliased
to the state result, so nothing else of the pool moves.

  grid (rows, head blocks): a step holds `head_block` heads of one row.
  The row's slot rides the scalar-prefetch channel and addresses the state
  block, so the same kernel serves the decode step (row r = slot r) and the
  decode rows of the ragged mixed step (row r = slot row_slot[r]).  A row
  whose mask is false (paused, empty, padding) is aimed at the pool's
  trash row by its caller and its body is skipped: consecutive dead rows
  name the same block, which the pipeline does not fetch again.

  The per-row vectors come in two small packed operands, laid out by the
  caller so that no transpose happens in the kernel: `cols` [R, H/hb, dk,
  3 hb] holds a = exp(g), k and q (scaled) with dk along sublanes — a
  head's vector is one lane of it, broadcast along lanes against the
  state; `rows` [R, H/hb, 2 hb, dv] holds v and b (broadcast) with dv
  along lanes.

Interpret-mode parity with ops/kda.py is the CPU oracle
(tests/test_kimi_linear.py); tests/test_mosaic_compile.py asks the chip's
compiler at the cell's shape (128 rows, 32 heads, 128 x 128 float32).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.utils.jax_compat import pallas_tpu_compiler_params

Array = jax.Array


def supported(backend: Optional[str] = None) -> bool:
    """Whether the pallas step kernel may be used."""
    if os.environ.get("PADDLE_TPU_PALLAS", "1") == "0":
        return False
    backend = backend or jax.default_backend()
    if backend == "tpu":
        return True
    # off-TPU the kernel only runs in (slow) interpret mode — opt-in
    return os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def head_block(num_heads: int) -> int:
    """Heads a grid step holds: the largest of 16 and 8 that divides the
    head count (1 MiB of state a block at 16 heads of 128 x 128; in and out
    double-buffered: 4 MiB of VMEM), else its largest divisor under 16 (15
    of Olmo-Hybrid's 30 heads of 96 x 192: 1.4 MiB a block as VMEM lays it
    out).  On the chip at 128 rows x 32 heads (PERF.md section 6, PR 33): 4
    heads a step 1.13 ms a call, 8 0.95, 16 0.90, 32 0.89 against 0.66 at
    the HBM's rate."""
    for hb in (16, 8):
        if num_heads % hb == 0:
            return hb
    return max(hb for hb in range(1, 16) if num_heads % hb == 0)


def _kernel(hb: int, delta: bool, slot_ref, live_ref, cols_ref, rows_ref,
            s_ref, o_ref, s_out_ref):
    """One body, two recurrences (`delta`, static): the KDA delta rule, and
    the Mamba-2 scalar-decay step — the same rank-1 move of a state held
    sublanes x lanes, without the correction term, its `k` the row's own
    column (dt x), its `u` and `q` the group's B and C rows, read out along
    lanes."""
    del slot_ref                        # it addressed the state block
    live = live_ref[pl.program_id(0)] != 0

    @pl.when(jnp.logical_not(live))
    def _():
        # a dead row: finite zeros go on to the next layers (a NaN out of
        # unwritten VMEM would reach live rows through the experts' grouped
        # products), and the trash row keeps what it held
        o_ref[...] = jnp.zeros_like(o_ref)
        s_out_ref[...] = s_ref[...]

    @pl.when(live)
    def _():
        n_g = rows_ref.shape[2] // 2     # groups this block holds (ssd)
        for i in range(hb):
            a = cols_ref[0, 0, :, i:i + 1]                   # [dk, 1]
            k = cols_ref[0, 0, :, hb + i:hb + i + 1]
            S = s_ref[0, i].astype(jnp.float32) * a          # [dk, dv]
            if delta:
                q = cols_ref[0, 0, :, 2 * hb + i:2 * hb + i + 1]
                v = rows_ref[0, 0, i:i + 1, :]               # [1, dv]
                b = rows_ref[0, 0, hb + i:hb + i + 1, :]
                u = b * (v - jnp.sum(S * k, axis=0, keepdims=True))
                S = S + k * u
                o_ref[0, 0, i:i + 1, :] = jnp.sum(S * q, axis=0,
                                                  keepdims=True)
            else:
                g = i // (hb // n_g)
                S = S + k * rows_ref[0, 0, g:g + 1, :]       # B [1, N]
                o_ref[0, 0, :, i:i + 1] = jnp.sum(
                    S * rows_ref[0, 0, n_g + g:n_g + g + 1, :], axis=1,
                    keepdims=True)                           # C -> [P, 1]
            s_out_ref[0, i] = S.astype(s_out_ref.dtype)


def _step_call(name: str, delta: bool, hb: int, state, slot, live, cols, rows,
               out_block):
    """The wrapper both steps share: rows x head blocks, the slot and the
    live mask on the scalar-prefetch channel, the state block addressed by
    the slot and aliased to its result.  `cols` [R, nb, dk, .] and `rows`
    [R, nb, ., dv] are the per-row operands, `out_block` the output's block
    a grid step ([hb, dv] rows of heads, or [dk, hb] a head a lane)."""
    R, nb = cols.shape[:2]
    dk, dv = state.shape[2:]
    by_row = lambda r, h, slot, live: (r, h, 0, 0)
    by_slot = lambda r, h, slot, live: (slot[r], h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # slot, live
        grid=(R, nb),
        in_specs=[pl.BlockSpec((1, 1) + cols.shape[2:], by_row),
                  pl.BlockSpec((1, 1) + rows.shape[2:], by_row),
                  pl.BlockSpec((1, hb, dk, dv), by_slot)],
        out_specs=[pl.BlockSpec((1, 1) + out_block, by_row),
                   pl.BlockSpec((1, hb, dk, dv), by_slot)])
    return pl.pallas_call(
        functools.partial(_kernel, hb, delta),
        name=name,              # the device op's name in a profiler trace
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, nb) + out_block, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the two prefetched scalars: the state is the 5th
        input_output_aliases={4: 1},
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(slot.astype(jnp.int32), live.astype(jnp.int32), cols, rows, state)


def kda_step(state: Array, slot: Array, live: Array, q: Array, k: Array,
             v: Array, g: Array, beta: Array, scale: float):
    """state [S+1, H, dk, dv] float32; slot [R] int32 (a dead row's is the
    trash row S), live [R] bool; q k [R, H, dk], g [R, H, dk] — or [R, H],
    one decay a head: the call is then named `gdn_step` —, v [R, H, dv],
    beta [R, H], all float32 -> (o [R, H, dv], state)."""
    R, H, dk = q.shape
    dv = v.shape[-1]
    hb = head_block(H)
    nb = H // hb
    per_head = g.ndim == 2
    a = jnp.exp(g)
    if per_head:
        a = jnp.broadcast_to(a[..., None], q.shape)
    # [R, H, dk] -> [R, nb, dk, hb]: dk along sublanes, a head a lane
    col = lambda x: jnp.swapaxes(x.reshape(R, nb, hb, dk), 2, 3)
    cols = jnp.concatenate([col(a), col(k), col(q * scale)], -1)
    rows = jnp.concatenate(
        [v.reshape(R, nb, hb, dv),
         jnp.broadcast_to(beta.reshape(R, nb, hb, 1), (R, nb, hb, dv))], 2)
    o, state = _step_call("gdn_step" if per_head else "kda_step", True, hb,
                          state, slot, live, cols, rows, (hb, dv))
    return o.reshape(R, H, dv), state


def ssd_head_block(num_heads: int, heads_per_group: int) -> int:
    """Heads a grid step of `ssd_step` holds: whole groups (their heads
    share B and C), the largest of 32 and 16 heads that divides — 1 MiB of
    state a block at 32 heads of 64 x 128 float32, in and out
    double-buffered 4 MiB of VMEM, as `head_block` —, else one group.  On
    the chip at 256 rows x 64 heads (my chip run, PR 41): 8 heads a step
    2.66 ms a call, 16 2.32, 32 2.22, 64 2.17 against 1.31 at the HBM's
    rate and 2.54 for the jnp step: past 16 heads the readout's lane
    reductions, not the blocks, hold it (PERF.md section 7)."""
    for hb in (32, 16):
        if num_heads % hb == 0 and hb % heads_per_group == 0:
            return hb
    return heads_per_group


def ssd_step(state: Array, slot: Array, live: Array, x: Array, Bm: Array,
             Cm: Array, dt: Array, A: Array):
    """The Mamba-2 step (ops/ssd.py `step`): state [S+1, H, P, N] float32;
    slot [R] int32 (a dead row's is the trash row S), live [R] bool; x
    [R, H, P], Bm Cm [R, G, N], dt [R, H], A [H], all float32 -> (y
    [R, H, P] without the D x term, state).  The state lies P along
    sublanes and N along lanes; a head's decay and dt x are one lane each
    of `cols` [R, nb, P, 2 hb], its group's B and C rows of `rows`
    [R, nb, 2 groups, N]; y comes back a head a lane."""
    R, H, P = x.shape
    G, N = Bm.shape[1:]
    hb = ssd_head_block(H, H // G)
    nb, gb = H // hb, hb * G // H
    col = lambda v: jnp.swapaxes(v.reshape(R, nb, hb, P), 2, 3)
    decay = jnp.broadcast_to(jnp.exp(dt * A)[..., None], (R, H, P))
    cols = jnp.concatenate([col(decay), col(dt[..., None] * x)], -1)
    rows = jnp.concatenate([Bm.reshape(R, nb, gb, N),
                            Cm.reshape(R, nb, gb, N)], 2)
    y, state = _step_call("ssd_step", False, hb, state, slot, live, cols,
                          rows, (P, hb))
    return jnp.swapaxes(y, 2, 3).reshape(R, H, P), state
