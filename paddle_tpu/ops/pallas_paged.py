"""Ragged paged decode attention in Pallas (TPU).

The serving engine's decode hot path (the Ragged Paged Attention shape,
arXiv:2604.15464): every slot's KV context lives in fixed-size pages of a
shared HBM pool, mapped by a per-slot page table, and each step attends ONE
query token per slot over its 0..pos positions.  The jnp fallback
(ops/attention.py:paged_attention_step) gathers the mapped pages into a
contiguous [S, max_pages*page_size] view every step — a transient HBM copy
of the whole context.  This kernel reads pages straight from the pool:

  grid (S, max_pages), pages innermost sequential: the page table rides a
  scalar-prefetch ref (pltpu.PrefetchScalarGridSpec) so the k/v BlockSpec
  index maps resolve `table[s, p]` BEFORE the DMA is issued — the pool
  page streams into VMEM with no gathered intermediate.  Per page, fold
  scores into a running online-softmax (max, sum, acc) VMEM scratch (the
  same recurrence as pallas_attention.py's flash kernel); pages past the
  slot's length are skipped entirely via pl.when (the "ragged" part — a
  slot holding 40 tokens reads 3 pages, not max_pages).

Grouped-query heads are handled in-kernel (per-kv-head score/weight dots,
a static python loop), so the pool stays at H_kv heads and no expanded
copy is ever materialized.  Sliding-window decode stays on the jnp
fallback.  Interpret-mode parity with the fallback is the CPU oracle
(tests/test_serving.py); on-TPU timing rides tools/bench_serving.py.

MIXED prefill/decode (chunked prefill): the optional `row_slot` operand
generalizes the query dimension from one-token-per-slot to a packed
ragged row list — row r attends table row `row_slot[r]` up to
`lengths[r]` tokens, so a prompt chunk (several consecutive rows, same
slot) and live decode rows share one grid.  `row_slot` rides the same
scalar-prefetch channel as the page table; everything else (online
softmax over live pages, pl.when page skipping, in-kernel GQA) is
unchanged.

SPECULATIVE verify rows (the engine's `--spec-k` draft chains) are the
same row-indirected shape from this kernel's point of view: a chain is
several consecutive rows of one slot at positions pos..pos+k, each
attending that slot's pages up to its own row — identical to a prompt
chunk except the K/V it reads at pos+1..pos+k was scattered
optimistically by the caller.  Rejection needs nothing from the
kernel: rejected positions sit beyond the slot's committed length,
masked for every later query and overwritten by the next chain before
pos can reach them (the rollback-safe-scatter contract documented on
ops/attention.py:ragged_paged_attention_step).

TENSOR PARALLELISM (the serving engine's `--mesh model=N` sharded
decode): this kernel is always invoked on LOCAL head shards — the
shard_map wrapper in ops/attention.py partitions q over its head axis
and the pools over their kv-head axis before calling in, so H and h_kv
here are the per-device counts (H/N and h_kv/N of the model; the engine
validates divisibility, and the grouped-query ratio H/h_kv is shard-
invariant).  The kernel itself needs no collective and no change: page
tables and lengths arrive replicated, every DMA stays on-chip, and the
head padding below (`max(H, 8)`) applies to the LOCAL count.

MULTI-STEP decode (the engine's `--decode-steps K` scanned dispatch):
the kernel is scan-body safe — pure in its operands with no host
callbacks, no side channels, and no per-call state, so `lax.scan`
tracing it K times produces ONE kernel instance in the loop body (the
body appears once in the HLO).  Positions/lengths arriving as scan
carries instead of host-staged arrays change nothing here: each body's
DMA addressing reads whatever `table`/`lengths` values the carry holds,
and under shard_map the same holds per shard (hlo_shard_check lowers
the scanned program and proves the collective set matches one body).
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

_NEG_INF = -1e30


def supported(backend: Optional[str] = None) -> bool:
    """Whether the pallas ragged-paged kernel may be used."""
    if os.environ.get("PADDLE_TPU_PALLAS", "1") == "0":
        return False
    backend = backend or jax.default_backend()
    if backend == "tpu":
        return True
    # off-TPU the kernel only runs in (slow) interpret mode — opt-in
    return os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _kernel(H, h_kv, ps, scale, table_ref, len_ref, row_ref, q_ref, k_ref,
            v_ref, o_ref, m_s, l_s, acc_s):
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)
    s = pl.program_id(0)

    @pl.when(p == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    length = len_ref[s]

    @pl.when(p * ps < length)
    def _():
        rep = H // h_kv
        q = q_ref[0].astype(jnp.float32)                 # [Hp, Dp]
        k = k_ref[0].astype(jnp.float32)                 # [ps, h_kv, Dp]
        v = v_ref[0].astype(jnp.float32)
        # grouped-query scores: each kv head serves its rep query heads
        # (static python loop — h_kv is a compile-time constant)
        parts = []
        for g in range(h_kv):
            qg = q[g * rep:(g + 1) * rep, :]             # [rep, Dp]
            sg = jax.lax.dot_general(
                qg, k[:, g, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # [rep, ps]
            parts.append(sg)
        sc = jnp.concatenate(parts, axis=0) * scale      # [H, ps]
        tpos = p * ps + jax.lax.broadcasted_iota(jnp.int32, (H, ps), 1)
        valid = tpos < length
        sc = jnp.where(valid, sc, _NEG_INF)

        m_prev = m_s[:H, :1]
        l_prev = l_s[:H, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        w = jnp.where(valid, jnp.exp(sc - m_new), 0.0)   # [H, ps]
        corr = jnp.exp(m_prev - m_new)
        l_s[:H, :1] = corr * l_prev + jnp.sum(w, axis=-1, keepdims=True)
        pv = []
        for g in range(h_kv):
            wg = w[g * rep:(g + 1) * rep, :]             # [rep, ps]
            pv.append(jax.lax.dot_general(
                wg, v[:, g, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))     # [rep, Dp]
        acc_s[:H] = acc_s[:H] * corr + jnp.concatenate(pv, axis=0)
        m_s[:H, :1] = m_new

    @pl.when(p == n_pages - 1)
    def _():
        l = jnp.maximum(l_s[:, :1], 1e-30)
        o_ref[0] = (acc_s[:] / l).astype(o_ref.dtype)


def paged_attention(
    q: Array,               # [R, H, D] one query token per ROW
    k_pages: Array,         # [P, page_size, H_kv, D]
    v_pages: Array,         # [P, page_size, H_kv, D]
    page_table: Array,      # [S, max_pages] int32 (0 = unmapped)
    lengths: Array,         # [R] int32 valid tokens per row (incl. the
                            # just-written one: attend t < lengths[r])
    scale: Optional[float] = None,
    row_slot: Optional[Array] = None,   # [R] int32 page-table row each
                            # query row reads; None = rows ARE slots
                            # (the classic one-token-per-slot decode)
) -> Array:
    """Ragged paged attention -> [R, H, D].  Same math as the jnp
    fallback's gather path (online softmax re-association aside).

    `row_slot` is the MIXED prefill/decode generalization (the full
    ragged-query shape of arXiv:2604.15464): the query rows are no longer
    one-per-slot — a chunk-prefilling prompt packs several consecutive
    rows against the same page-table row, a decode slot keeps its single
    row, and padding rows aim at an all-zero table row.  The indirection
    rides the scalar-prefetch channel next to the page table, so the k/v
    BlockSpec index map resolves `table[row_slot[r], p]` before the page
    DMA is issued — same zero-copy pool streaming as the decode-only
    kernel, one compiled program for any prefill/decode mix."""
    R, H, D = q.shape
    P, ps, h_kv, _ = k_pages.shape
    maxp = page_table.shape[1]
    assert H % h_kv == 0, f"heads {H} not divisible by kv heads {h_kv}"
    if scale is None:
        scale = D ** -0.5
    if row_slot is None:
        row_slot = jnp.arange(R, dtype=jnp.int32)

    Hp = _round_up(max(H, 8), 8)
    Dp = _round_up(D, 128)
    qp = jnp.pad(q, ((0, 0), (0, Hp - H), (0, Dp - D)))
    kp = jnp.pad(k_pages, ((0, 0), (0, 0), (0, 0), (0, Dp - D)))
    vp = jnp.pad(v_pages, ((0, 0), (0, 0), (0, 0), (0, Dp - D)))

    kernel = functools.partial(_kernel, H, h_kv, ps, scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,               # page_table, lengths, row_slot
        grid=(R, maxp),
        in_specs=[
            pl.BlockSpec((1, Hp, Dp),
                         lambda s, p, tbl, lens, rows: (s, 0, 0)),
            pl.BlockSpec((1, ps, h_kv, Dp),
                         lambda s, p, tbl, lens, rows:
                         (tbl[rows[s], p], 0, 0, 0)),
            pl.BlockSpec((1, ps, h_kv, Dp),
                         lambda s, p, tbl, lens, rows:
                         (tbl[rows[s], p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hp, Dp),
                               lambda s, p, tbl, lens, rows: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hp, 128), jnp.float32),   # running max (lane 0)
            pltpu.VMEM((Hp, 128), jnp.float32),   # running sum (lane 0)
            pltpu.VMEM((Hp, Dp), jnp.float32),    # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_attn",      # the device op's name in a profiler trace
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, Hp, Dp), q.dtype),
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      row_slot.astype(jnp.int32), qp, kp, vp)
    return out[:, :H, :D]
