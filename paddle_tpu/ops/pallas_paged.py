"""Ragged paged decode attention in Pallas (TPU).

The serving engine's decode hot path (the Ragged Paged Attention shape,
arXiv:2604.15464): every slot's KV context lives in fixed-size pages of a
shared HBM pool, mapped by a per-slot page table, and each step attends ONE
query token per row over its 0..pos positions.  The jnp fallback
(ops/attention.py:paged_attention_step) gathers the mapped pages into a
contiguous [S, max_pages*page_size] view every step — a transient HBM copy
of the whole context.  This kernel reads LIVE KV only, straight from the
pool:

  grid (tiles,): a TILE of consecutive query rows a grid step (`tile_rows`
  derives how many from the heads, the score columns a block, the row's
  width and the dtype against a VMEM budget: 8 at most, 1 where nothing
  more fits).  The pools stay in HBM (memory_space=pl.ANY) in the layout
  the engine holds them, [P, page_size, H_kv, D]; the page table, the
  lengths and the row->slot indirection ride the scalar-prefetch channel.
  Inside a step the kernel WALKS a row's own KV in BLOCKS of several pages
  (128-512 tokens; `block_tokens` derives the count from page_size, H_kv,
  D and the dtype's bytes against a VMEM budget — one algorithm adapted by
  shape, nothing selects it), with the trip count cdiv(lengths[r], block)
  a run-time value: a page past the row's length is never stepped, a dead
  or padding row costs one block of the trash page, and one compiled
  program serves any fill of the pool.  A block's pages are fetched by the
  kernel's own async copies addressed through table[row_slot[r], ·],
  double-buffered: while block i is folded into the running online softmax
  (max, sum, accumulator: float32 loop carries, the recurrence of
  pallas_attention.py's flash kernel), block i+1's copies — or the NEXT
  walk's first block — are in flight.  A tile whose rows read different
  table rows (decode rows, the ragged ends of a prompt chunk's run) walks
  one row after another; a tile whose rows all read ONE table row walks
  that slot's blocks once, every block scored against all the tile's rows
  (MIXED, below).

A BLOCK IN VMEM is the matmul operand itself: a double buffer
[2, pages*page_size*H_kv, D] a pool, each page's copy landing in its own
page_size*H_kv rows, so K and V enter the two dots as dense tiles read
once (a page of 16 x 2 x 128 bf16 is 2 + 2 vregs) and nothing is stored
but the output.  The pool keeps its stored shape [P, page_size, H_kv, D]:
Mosaic tiles its minor [H_kv, D] (2,128)(2,1) — a token's two bf16 heads
are ONE 32-bit sublane row, a page 8 contiguous KB — and that is byte for
byte a [page_size*H_kv, D] operand tiled (8,128)(2,1): rows 2t, 2t+1 are
token t under heads 0, 1, the column order the mask below assumes.  The
copy's source is the page reshaped to those rows (a view: the DMA crosses
the two tilings, exact on the chip — tools/tpu_parity.py --only=paged).
A buffer shaped like the pool, [pages, page_size, H_kv, D], keeps the
(2,128) tiling, and `reshape(block*H_kv, D)` of it is then a relayout that
moves no information: at 16 pages a block 512 loads of one live sublane
and 512 strided stores through Mosaic's internal scratch, 1.43 us a block
where this form takes 0.88 (my chip run, PR 42; tools/kernel_lowering.py
counts the loads and stores, tests/test_mosaic_compile.py holds them).

Grouped-query heads are handled in-kernel without a per-head gather: a
block is one dense [block*H_kv, D] operand as the pool stores it, every
query head is scored against every (token, kv head) column, and the
columns of the other groups are masked — scores and weights are
[H, block*H_kv], 128 or more lanes wide where a page gave 16.  q, k and v enter the dots in
the dtype they are stored in (bf16 in the cells), accumulated in
float32, the weights cast to v's dtype: the jnp fallback's precision.
A sliding-window layer runs the same kernel under the name
`window_attn` (`paged_attention(first=)`): over a table of each row's
own that names only the pages its window intersects, with the window's
lower edge in the mask.  Interpret-mode parity
with the fallback is the CPU oracle (tests/test_serving.py,
tests/test_chunked_prefill.py); tests/test_mosaic_compile.py asks the
chip's compiler at the serve cells' shapes.

MIXED prefill/decode (chunked prefill): the optional `row_slot` operand
generalizes the query dimension from one-token-per-slot to a packed
ragged row list — row r attends table row `row_slot[r]` up to
`lengths[r]` tokens, so a prompt chunk (several consecutive rows, same
slot) and live decode rows share one grid.  `row_slot` rides the same
scalar-prefetch channel as the page table.  A chunk's rows differ in
nothing but where their causal mask ends, so a tile that lies inside a
chunk's run SHARES ONE WALK: each fetched block enters the two dots once,
as [Bq*Hp, D] x [C, D]^T against the whole tile's heads, one online-softmax
carry a row, each row masked at its own `lengths[r]`, the loop run to the
tile's longest row — Bq rows for the copies (and the loop step) of one.
Which walk a tile takes is read from `row_slot` itself (`_tile_walks`: a
few comparisons under the step's jit, one more prefetched operand, one
branch a tile — never one a page); the arithmetic of a row is the same
either way.  At the Laguna full layer (48 heads over 8 KV heads of 128,
1,024 score columns a block) a shared block of 8 rows costs 2.2 us where
8 rows' own walks cost 8 x 0.83, and the 64 decode rows of a step cost
what they did (my chip run, PR 53: tools/bench_paged.py --shapes
laguna-mixed --tile-rows 1,8 — 6.91 -> 3.42 ms a call).  A windowed call's
rows each read a table row of their own, so its tiles all walk row by row;
`walked_blocks` is the count the engine keeps of all this
(kv_tokens_fetched, serving_kv_shared_rows_total).

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
head padding, the block size and the tile below follow the LOCAL counts.

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


# VMEM a block's K and V may take together, both buffers counted.  On the
# v5e the loop costs about 0.28 us a block and 0.038 us a page of 16 KB of
# K and V (0.020 at the HBM's rate), 0.33 and 0.040 a page of 32 KB (the
# HBM's own 0.040) — my chip run, PR 42: tools/bench_paged.py --fills at
# 512 KiB and 1 MiB, 0.88 / 1.48 us a block of 16 / 32 pages; read through
# a (2,128) tiled buffer it was 0.23 and 0.075 (PR 28), 1.43 a block.  So a
# wider block amortizes the step and a narrower one fetches fewer dead
# tokens past a short row's end (the block fill the engine counts:
# kv_tokens_attended / kv_tokens_fetched).  At the serve cells' fills, ms a
# call at 256 KiB / 512 KiB / 1 MiB (same run): the mixed step of 128 rows
# 0.312 / 0.240 / 0.268; 64 rows of which 62 are dead 0.046 / 0.063 /
# 0.100; decode rows of 300-1,500 tokens tie between 512 KiB and 1 MiB —
# 512 KiB stays.  A pool row of [4, 128] (heads of 64, packed) holds 8
# pages a block at this budget and would take 22% less at 16 (ROADMAP S1).
_KV_VMEM_BUDGET = 512 << 10
_BLOCK_TOKENS = (128, 512)       # floor and ceiling of a block, in tokens
# A row of MORE THAN 8 stored heads: the kernel scores a tile's query heads
# against every head's rows of a block (`cols` = tokens x heads, the other
# groups masked), so at the 128-token floor a row of 32 heads is 4,096
# score columns, 1 MB of float32 scores a query row — `tile_rows` then
# holds 2 rows of its 8 and a prompt chunk's run re-walks its context every
# second row (61.6% of the Olmo-Hybrid cell's busy time, at the HBM's rate:
# PERF.md section 6, PR 59).  Such a row's block holds the score columns
# of the 8-head cells' instead: 1,024 // heads tokens, whole pages.
_WIDE_ROW_COLS = 1024


def kv_row_shape(h_kv: int, head_dim: int) -> tuple[int, int]:
    """The shape a token's K (or V) row is STORED in, in a pool's last two
    dimensions.  A head of 128 lanes or more is a row of the pool as it
    is: (h_kv, head_dim).  A narrower head that divides 128 is packed,
    128 // head_dim heads a lane tile: (h_kv * head_dim // 128, 128) — the
    same bytes in the same order (a row-major reshape of [h_kv, head_dim]),
    so nothing is moved to read it either way.  Stored [.., 8, 64] a bf16
    pool's HBM tiles pad each head to 128 lanes, or XLA lays the pool out
    pages-minor and the kernel's page copy is refused; stored [.., 4, 128]
    it is lane-dense (T(4,128)(2,1): the bytes of its elements).  Where
    the heads do not fill whole tiles (h_kv * head_dim not a multiple of
    128) the row stays as it is and the kernel pads its lanes.  MORE THAN
    8 heads of 128 lanes or more are stored in whole tiles of 8 heads (30
    as 32): a bf16 pool's HBM tile `(8,128)(2,1)` holds them so in any case
    — [.., 30, 128] TAKES the bytes of [.., 32, 128] — and Mosaic refuses
    the page's copy out of a row that is no whole number of tiles ("Slice
    shape along dimension 2 must be aligned to tiling (8), but is 30", PR
    59); the heads past the model's own hold zeros that no query reads."""
    if head_dim < 128 and 128 % head_dim == 0 and \
            (h_kv * head_dim) % 128 == 0:
        return h_kv * head_dim // 128, 128
    if head_dim >= 128 and h_kv > 8:
        return _round_up(h_kv, 8), head_dim
    return h_kv, head_dim


def heads_padded(h_kv: int, head_dim: int, pool_shape: tuple) -> bool:
    """Whether a pool of this shape stores a token's `h_kv` heads in whole
    tiles of 8 (`kv_row_shape`'s last rule): its row holds heads of zeros
    past the model's own."""
    return tuple(pool_shape[-2:]) == kv_row_shape(h_kv, head_dim) and \
        pool_shape[-2] > h_kv


def kv_page_shape(page_size: int, h_kv: int, head_dim: int,
                  itemsize: int) -> tuple[int, int, int]:
    """The shape a PAGE of K (or V) is stored in, a pool's dimensions after
    its first: (page_size,) + `kv_row_shape` — but for multi-query
    attention's ONE KV head of 128 lanes in a 2-byte dtype, where a bf16
    pool's HBM tile `(2,128)(2,1)` would pad the lone row to two and the
    pool to twice its bytes (and Mosaic refuses the page's copy: a slice of
    one row is not aligned to the tiling).
    There TWO TOKENS share a sublane row: (page_size // 2, 2, 128), the
    same bytes in the same order (a row-major reshape of [page_size, 1,
    128]) and exactly the tiles of the kernel's dense operand."""
    g, lanes = kv_row_shape(h_kv, head_dim)
    if h_kv == 1 and (g, lanes) == (1, 128) and itemsize == 2 \
            and page_size % 2 == 0:
        return page_size // 2, 2, lanes
    return page_size, g, lanes


def block_tokens(page_size: int, h_kv: int, head_dim: int, itemsize: int,
                 max_pages: int) -> int:
    """Tokens of KV one loop step of the kernel folds: a whole number of
    pages, derived from the operands' shapes alone — as many as
    `_KV_VMEM_BUDGET` holds (K and V, two buffers each) within
    `_BLOCK_TOKENS`, never more than the table maps.  `h_kv` and
    `head_dim` are the pool's STORED row (`kv_row_shape`).  The engine
    calls this with the pool's shapes to count what the kernel fetches."""
    per_token = 2 * 2 * h_kv * _round_up(head_dim, 128) * itemsize
    lo, hi = _BLOCK_TOKENS
    tokens = max(lo, min(hi, _KV_VMEM_BUDGET // per_token))
    if h_kv > 8:
        tokens = min(tokens, _WIDE_ROW_COLS // h_kv)
    pages = max(1, min(tokens // page_size, max_pages))
    return pages * page_size


def _head_rows(H: int, dtype) -> int:
    """q's rows fill whole sublane tiles of its dtype (8 rows of 32 bits)."""
    return _round_up(H, 8 * max(1, 4 // jnp.dtype(dtype).itemsize))


#: VMEM a tile of query rows may take beside the K/V buffers: its float32
#: scores and their weights, q and the output (two buffers each, the grid's
#: pipeline) and the float32 accumulator.  At the Laguna full layer (48
#: heads against 1,024 score columns) 8 rows are 3.7 MB, and a shared block
#: then costs 2.2 us of vector and MXU work against 0.64 of copies (4 rows:
#: 1.3 — my chip run, PR 53), so a wider tile buys nothing: the ceiling.
_TILE_VMEM_BUDGET = 4 << 20
_TILE_ROWS = 8


def tile_rows(rows: int, heads: int, cols: int, width: int, dtype) -> int:
    """Query rows one grid step holds (a TILE): a power of two derived from
    the shapes alone, as `block_tokens` is — `heads` query heads (padded to
    q's sublane tiles) against `cols` score columns a block and a row of
    `width` lanes of `dtype`, within `_TILE_VMEM_BUDGET`, at most
    `_TILE_ROWS` and never more than the call's `rows` rounded up.  The engine calls this to count what the kernel
    fetches (`walked_blocks`)."""
    a_row = _head_rows(heads, dtype) * (
        2 * 4 * cols
        + _round_up(width, 128) * (4 + 4 * jnp.dtype(dtype).itemsize))
    bq = 1
    while 2 * bq <= min(_TILE_ROWS, _TILE_VMEM_BUDGET // a_row) \
            and bq < rows:
        bq *= 2
    return bq


def _tile_walks(xp, lengths, row_slot, bq: int):
    """[tiles] the length a tile's SHARED walk runs to — its longest row's —
    where all its rows read one table row, else -1: the tile walks row by
    row.  `xp` is jnp under the step's jit and numpy on the host."""
    slots = row_slot.reshape(-1, bq)
    uniform = (slots == slots[:, :1]).all(axis=1)
    return xp.where(uniform, lengths.reshape(-1, bq).max(axis=1), -1)


def walked_blocks(lengths, row_slot, bq: int, bt: int):
    """(blocks fetched, rows on a shared walk) of one call over host arrays:
    a tile whose rows read one table row folds each block once, to its
    longest row; every other row walks its own, a dead one a block.
    `row_slot` None: the rows are the slots, and none shares."""
    import numpy as np
    lengths = np.asarray(lengths).reshape(-1)
    alone = np.maximum(-(-lengths // bt), 1)
    if bq == 1 or row_slot is None:
        return int(alone.sum()), 0
    pad = -lengths.size % bq            # as `_call` pads: whole tiles
    walk = _tile_walks(np, np.pad(lengths, (0, pad)),
                       np.pad(np.asarray(row_slot), (0, pad), mode="edge"),
                       bq)
    shared = np.repeat(walk >= 0, bq)[:lengths.size]
    return int(alone[~shared].sum() + pad * (walk[-1] < 0)
               + np.maximum(-(-walk[walk >= 0] // bt), 1).sum()), \
        int(shared.sum())


def _kernel(H, h_kv, scale, v_width, windowed, tiled, table_ref, len_ref,
            row_ref, *rest):
    """One TILE of query rows against their slots' live KV.  `v_width` None:
    K and V pools of [P, ps, h_kv, Dp] (grouped-query heads).  `v_width`
    set: ONE latent pool of [P, ps, W] whose rows are both — every query
    head scores the whole row and weighs its first `v_width` columns
    (ops/mla.py), so a block is fetched once and there are no groups to
    mask.  `tiled`: a fourth prefetched operand, `_tile_walks` — where it
    is not negative the tile's rows read one table row and walk its blocks
    ONCE, together.  `windowed`: a prefetched operand more, the first token
    of the row's table row that the query may see (the window's lower
    edge, below the row's length: the mask is first <= t < length)."""
    walk_ref = first_ref = None
    if tiled:
        walk_ref, *rest = rest
    if windowed:
        first_ref, *rest = rest
    q_ref, *rest = rest
    if v_width is None:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, slot_ref = rest
        pools = ((k_hbm, kbuf), (v_hbm, vbuf))
    else:
        k_hbm, o_ref, kbuf, sems, slot_ref = rest
        vbuf = None
        pools = ((k_hbm, kbuf),)
    t = pl.program_id(0)
    Bq, Hp, _ = q_ref.shape
    n_rows = pl.num_programs(0) * Bq
    # a page's rows of the operand, however the pool folds them (a lone
    # head's tokens are stored two a row: `kv_page_shape`)
    rows = k_hbm.shape[1] if len(k_hbm.shape) == 3 \
        else k_hbm.shape[1] * k_hbm.shape[2]
    ps = rows // h_kv                   # tokens a page
    C = kbuf.shape[1]                   # score columns: (token, kv head)
    bt = C // h_kv                      # tokens a block
    npb = bt // ps
    maxp = table_ref.shape[1]
    Dv = o_ref.shape[-1]
    rep = H // h_kv

    def start_fetch(row, blk, slot):
        """Start the page copies of block `blk` of row `row` into buffer
        `slot` (npb a pool).  A page past the table's end re-reads its last
        entry; a logical page past the row's length is mapped (or 0, the
        trash page) and masked below."""
        s = row_ref[row]
        for i in range(npb):
            page = table_ref[s, jnp.minimum(blk * npb + i, maxp - 1)]
            for j, (hbm, buf) in enumerate(pools):
                # the page as the operand's rows: the same bytes
                pltpu.make_async_copy(
                    hbm.at[page].reshape(rows, buf.shape[-1]),
                    buf.at[slot, pl.ds(i * rows, rows)],
                    sems.at[j, slot]).start()

    def wait_fetch(slot):
        # one wait a buffer: a descriptor of the whole buffer's size takes
        # what its npb page copies signalled together
        for j, (_, buf) in enumerate(pools):
            pltpu.make_async_copy(
                buf.at[slot], buf.at[slot], sems.at[j, slot]).wait()

    @pl.when(t == 0)
    def _():
        slot_ref[0] = 0
        start_fetch(0, 0, 0)

    def live(r):
        # never past what the table maps, whatever `lengths` holds: the
        # trip count is a run-time value, and a corrupted one would be
        # device time
        return jnp.minimum(len_ref[r], maxp * ps)

    def walk(q, r, nxt, length, longest, first, slot):
        """Fold the blocks of row `r`'s table row into q's rows [M, Dp] —
        M = Hp, one query row, or a whole tile's Bq * Hp — each masked at
        its own `length` (a scalar, or [M, 1]), the loop run to `longest`;
        the last block prefetches row `nxt`'s first.  -> (out [M, Dv], the
        buffer that prefetch lands in)."""
        M = q.shape[0]
        # every walk folds at least one block (a dead or padding row: one
        # block of the trash page), so its last block can always prefetch
        # the next walk's first
        nblk = jnp.maximum(pl.cdiv(longest, bt), 1)
        # column c of a block is token c // h_kv under kv head c % h_kv;
        # query head h reads kv head h // rep.  Scoring every head against
        # every column and masking the other groups keeps the block one
        # dense [bt*h_kv, Dp] operand as the pool stores it — no per-head
        # gather — and costs the MXU nothing it was not already paying to
        # load K.
        col = jax.lax.broadcasted_iota(jnp.int32, (M, C), 1)
        tok = col // h_kv
        # a tile's rows are Hp heads a query row
        head = jax.lax.broadcasted_iota(jnp.int32, (M, C), 0) if M == Hp \
            else jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0) % Hp
        # one KV head under unpadded query heads: every column is every
        # head's own, nothing to mask but the row's length
        own_group = None if (h_kv == 1 and Hp == H) else \
            (col % h_kv) == head // rep

        def fold(b, carry):
            m_prev, l_prev, acc, slot = carry
            last = b == nblk - 1
            nrow = jnp.where(last, nxt, r)
            nb = jnp.where(last, 0, b + 1)

            @pl.when(nrow < n_rows)
            def _():
                start_fetch(nrow, nb, 1 - slot)

            wait_fetch(slot)
            k = kbuf[slot]                                    # [C, Dp]
            v = k[:, :Dv] if vbuf is None else vbuf[slot]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [M, C]
            valid = tok < length - b * bt
            if first is not None:
                valid = jnp.logical_and(valid, tok >= first - b * bt)
            if own_group is not None:
                valid = jnp.logical_and(own_group, valid)
            sc = jnp.where(valid, sc, _NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            w = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = corr * l_prev + jnp.sum(w, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                w.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [M, Dv]
            return m_new, l_new, acc * corr + pv, 1 - slot

        _, l, acc, slot = jax.lax.fori_loop(
            0, nblk, fold,
            (jnp.full((M, 1), _NEG_INF, jnp.float32),
             jnp.zeros((M, 1), jnp.float32),
             jnp.zeros((M, Dv), jnp.float32),
             slot))
        return (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype), slot

    r0 = t * Bq

    def one_row(i, slot):
        r = r0 + i
        length = live(r)
        o_ref[i], slot = walk(
            q_ref[i], r, r + 1, length, length,
            None if first_ref is None else first_ref[r], slot)
        return slot

    def by_row():
        slot_ref[0] = jax.lax.fori_loop(0, Bq, one_row, slot_ref[0])

    if not tiled:
        by_row()
        return
    pl.when(walk_ref[t] < 0)(by_row)

    @pl.when(walk_ref[t] >= 0)
    def _():
        # the tile's rows as one operand; each row's length down its heads
        at = jax.lax.broadcasted_iota(jnp.int32, (Bq * Hp, 1), 0)
        length = jnp.zeros((Bq * Hp, 1), jnp.int32)
        for i in range(Bq):
            length = jnp.where(at >= i * Hp, live(r0 + i), length)
        out, slot_ref[0] = walk(
            q_ref[...].reshape(Bq * Hp, q_ref.shape[-1]), r0, r0 + Bq,
            length, jnp.minimum(walk_ref[t], maxp * ps), None, slot_ref[0])
        o_ref[...] = out.reshape(Bq, Hp, Dv)


@functools.lru_cache(maxsize=None)
def _program(name: str, kernel_args: tuple, bq: int, tiles: int,
             q_row: tuple, pools: tuple, buf_shape: tuple, out_width: int,
             dtype, interpret: bool):
    """The family's one pallas_call for one set of shapes, built ONCE: the
    layers of a step (and the steps of a process) that call with the same
    shapes share the callable, so jit traces the kernel's two walks once for
    all of them (a trace and a lowering a layer was 5.6 s of the Laguna
    cell's warm start-up: my chip runs, PR 53).  `kernel_args` are
    `_kernel`'s statics; `pools` the pools' dtypes."""
    _, _, _, _, windowed, tiled = kernel_args
    Hp, Dq = q_row
    index = lambda t, *prefetched: (t, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + tiled + windowed,
        grid=(tiles,),
        in_specs=[pl.BlockSpec((bq, Hp, Dq), index)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),  # stay in HBM
        out_specs=pl.BlockSpec((bq, Hp, out_width), index),
        scratch_shapes=[pltpu.VMEM((2,) + buf_shape, p) for p in pools]
        + [pltpu.SemaphoreType.DMA((len(pools), 2)),      # (pool, buffer)
           pltpu.SMEM((1,), jnp.int32)],         # buffer the next walk reads
    )
    return pl.pallas_call(
        functools.partial(_kernel, *kernel_args),
        name=name,              # the device op's name in a profiler trace
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles * bq, Hp, out_width), dtype),
        # tiles run in order: each prefetches its successor's first block
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )


def _call(name: str, kernel_args: tuple, bq: int, qp: Array, pools: tuple,
          buf_shape: tuple, out_width: int, page_table: Array,
          lengths: Array, row_slot: Array,
          first: Optional[Array] = None) -> Array:
    """The one pallas_call of the family: grid over TILES of `bq` query
    rows, the pools in HBM, table / lengths / row->slot (with the tiles'
    shared walks, or a windowed call's `first`) on the scalar-prefetch
    channel, a double buffer of `buf_shape` a pool.  `kernel_args`:
    `_kernel`'s H, h_kv, scale and v_width."""
    R, Hp, Dq = qp.shape
    pad = -R % bq
    if pad:     # whole tiles: dead rows of the last row's slot
        qp = jnp.pad(qp, ((0, pad), (0, 0), (0, 0)))
        lengths = jnp.pad(lengths, (0, pad))
        row_slot = jnp.pad(row_slot, (0, pad), mode="edge")
    scalars = (page_table, lengths, row_slot)
    tiled = first is None and bq > 1
    if tiled:
        scalars += (_tile_walks(jnp, lengths, row_slot, bq),)
    if first is not None:
        scalars += (jnp.pad(first, (0, pad)),)
    program = _program(
        name, kernel_args + (first is not None, tiled), bq, (R + pad) // bq,
        (Hp, Dq), tuple(p.dtype for p in pools), buf_shape, out_width,
        qp.dtype, _interpret())
    return program(*(a.astype(jnp.int32) for a in scalars), qp, *pools)[:R]


def paged_attention(
    q: Array,               # [R, H, D] one query token per ROW
    k_pages: Array,         # [P, page_size, H_kv, D], or packed
    v_pages: Array,         # [P, page_size, G, 128] (`kv_row_shape`)
    page_table: Array,      # [S, max_pages] int32 (0 = unmapped)
    lengths: Array,         # [R] int32 valid tokens per row (incl. the
                            # just-written one: attend t < lengths[r])
    scale: Optional[float] = None,
    row_slot: Optional[Array] = None,   # [R] int32 page-table row each
                            # query row reads; None = rows ARE slots
                            # (the classic one-token-per-slot decode)
    kv_heads: Optional[int] = None,     # the model's KV heads; None = the
                            # pool's third dimension holds a token's rows
    first: Optional[Array] = None,      # [R] int32: a WINDOWED call — row r
                            # attends first[r] <= t < lengths[r] of its
                            # table row
) -> Array:
    """Ragged paged attention -> [R, H, D].  Same math as the jnp
    fallback's gather path (online softmax re-association aside): q, k
    and v enter the dots in the dtype they are stored in, scores and the
    running max / sum / accumulator are float32, the weights are cast to
    v's dtype.

    `row_slot` is the MIXED prefill/decode generalization (the full
    ragged-query shape of arXiv:2604.15464): the query rows are no longer
    one-per-slot — a chunk-prefilling prompt packs several consecutive
    rows against the same page-table row, a decode slot keeps its single
    row, and padding rows aim at an all-zero table row.  The table, the
    lengths and the indirection ride the scalar-prefetch channel, so the
    kernel addresses `table[row_slot[r], ·]` itself and bounds each row's
    loop by `lengths[r]` at run time — one compiled program for any
    prefill/decode mix and any fill of the pool.

    A PACKED pool (heads under 128 lanes, `kv_row_shape`: `pack` heads a
    128-lane row, G rows a token) runs the same kernel by shape: a query
    head's D values sit in the lanes its KV head has in the row and zeros
    in the others, so its score against a packed row is its own head's
    dot product; the H // G heads that share a row are one group of the
    in-kernel mask; the weighted row comes back 128 wide and the head's
    own lanes are taken from it here.  A pool that stores a lone head's
    tokens TWO A ROW (`kv_page_shape`; told from `kv_heads`) is the same
    kernel with one row a token: a page's copy lands in the same operand
    rows.  No pool is copied or padded.

    A WINDOWED call (`first`, the sliding-window layers' decode and chunk
    rows) is the same program with one more prefetched operand and one more
    comparison in the mask: the caller (ops/attention.py:_window_view)
    hands a table of each row's OWN — the pages that intersect its window,
    window // page_size + 1 columns at most, `row_slot` the identity — so
    the block loop, bounded by `lengths`, fetches at most window + page_size
    tokens a row whatever the context.  It is named `window_attn` in
    a device trace, so a reader tells it from the full layers' calls."""
    R, H, D = q.shape
    P, ps, G, L = k_pages.shape
    maxp = page_table.shape[1]
    pack = L // D                       # KV heads a stored row: 1 unpacked
    if kv_heads is not None and heads_padded(kv_heads, D, k_pages.shape):
        # the query heads of the heads that are not there are zeros, dropped
        # from the result
        pad = (G - kv_heads) * (H // kv_heads)
        out = paged_attention(
            jnp.pad(q, ((0, 0), (0, pad), (0, 0))), k_pages, v_pages,
            page_table, lengths, scale if scale is not None else D ** -0.5,
            row_slot, G, first)
        return out[:, :H]
    if kv_heads is not None and kv_heads // pack != G:
        # rows a page / rows a token: the lone head stored two tokens a row
        ps, G = ps * G * pack // kv_heads, kv_heads // pack
    assert L == pack * D and H % (G * pack) == 0, \
        f"heads {H} x {D} do not read a pool row of {G} x {L}"
    if scale is None:
        scale = D ** -0.5
    if row_slot is None:
        row_slot = jnp.arange(R, dtype=jnp.int32)

    itemsize = jnp.dtype(k_pages.dtype).itemsize
    Hp = _head_rows(H, q.dtype)
    Dp = _round_up(L, 128)
    npb = block_tokens(ps, G, L, itemsize, maxp) // ps
    if first is not None:
        # a window's few pages in blocks of one size: as many blocks, and
        # fewer copies past the view's end (33 pages: 5 x 7, not 5 x 8)
        npb = -(-maxp // -(-maxp // npb))
    if pack > 1:
        # head h reads KV head h // rep, lane tile (h // rep) % pack
        rep = H // (G * pack)
        lane = jax.nn.one_hot((jnp.arange(H) // rep) % pack, pack,
                              dtype=q.dtype)                 # [H, pack]
        q = (q[:, :, None, :] * lane[None, :, :, None]).reshape(R, H, L)
    qp = jnp.pad(q, ((0, 0), (0, Hp - H), (0, Dp - L)))
    if Dp != L:
        k_pages, v_pages = (jnp.pad(p, ((0, 0),) * 3 + ((0, Dp - L),))
                            for p in (k_pages, v_pages))
    out = _call("paged_attn" if first is None else "window_attn",
                (H, G, float(scale), None),
                tile_rows(R, H, npb * ps * G, Dp, q.dtype),
                qp, (k_pages, v_pages), (npb * ps * G, Dp), Dp, page_table,
                lengths, row_slot, first)[:, :H, :L]
    if pack > 1:
        out = jnp.sum(out.reshape(R, H, pack, D) *
                      lane[None, :, :, None].astype(out.dtype), axis=2)
    return out


def latent_paged_attention(
    q: Array,               # [R, H, W] absorbed queries, one token per ROW
    kv_pages: Array,        # [P, page_size, W] latent pool: a row is a
                            # token's [c_kv, k_pe], key AND value
    page_table: Array,      # [S, max_pages] int32 (0 = unmapped)
    lengths: Array,         # [R] int32 valid tokens per row
    scale: float,
    row_slot: Optional[Array] = None,
    v_width: Optional[int] = None,      # the value is the row's first
                            # v_width columns (kv_lora_rank); default W
) -> Array:
    """Ragged paged attention over a LATENT pool (ops/mla.py's absorbed
    form) -> weighted latents [R, H, v_width].  The same grid, block loop
    bounded by each row's length, double-buffered page copies and online
    softmax as `paged_attention`, adapted by shape: one pool instead of
    two (a block is fetched once and serves as K and, its first v_width
    columns, as V), all H query heads against the one latent row, no group
    mask.  W need not be a multiple of 128: the row is the pool's full
    last dimension, copied and multiplied as it is stored (padding it
    would copy the pool every step).  Named `mla_paged_attn` in a trace."""
    R, H, W = q.shape
    P, ps, Wp = kv_pages.shape
    assert W == Wp, f"query width {W} != latent row width {Wp}"
    v_width = W if v_width is None else int(v_width)
    maxp = page_table.shape[1]
    if row_slot is None:
        row_slot = jnp.arange(R, dtype=jnp.int32)
    Hp = _head_rows(H, q.dtype)
    itemsize = jnp.dtype(kv_pages.dtype).itemsize
    npb = block_tokens(ps, 1, W, itemsize, maxp) // ps
    qp = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))
    out = _call("mla_paged_attn", (H, 1, float(scale), v_width),
                tile_rows(R, H, npb * ps, W, q.dtype), qp,
                (kv_pages,), (npb * ps, W), v_width, page_table, lengths,
                row_slot)
    return out[:, :H]
