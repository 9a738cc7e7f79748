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
  derives how many from the heads, the score columns a dot, the row's
  width and the dtype against a VMEM budget: 8, or where a run's walk is a
  dot a stored head the rows that fill a dot's 128, 1 where nothing more
  fits).  The pools stay in HBM (memory_space=pl.ANY) in the layout
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
  walk's first block — are in flight.  A row that reads a table row of its
  own (a decode row) walks alone; a RUN — the consecutive rows of a tile
  that read ONE table row: a prompt chunk's, a draft chain's, the padding's
  — walks that slot's blocks once, every block scored against all the
  run's rows (MIXED, below).

A BLOCK IN VMEM is the matmul operand itself: a double buffer
[2, pages*page_size*H_kv, D] a pool, each page's copy landing in its own
page_size*H_kv rows, so K and V enter a row's two dots as dense tiles read
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

Grouped-query heads, two forms by what the walk serves (PR 60).  A ROW
ALONE scores its heads against the block as the one dense [block*H_kv, D]
operand the pool stores — every query head against every (token, kv head)
column, the columns of the other groups masked: scores and weights are
[H, block*H_kv], and at one query row the masked columns cost about what
the copies do (0.78 us a block of 32 tokens x 32 heads at Olmo-Hybrid
against 0.64 of copies: my chip run, PR 59).  A RUN of rows over a pool
whose row holds MORE THAN FOUR heads reads the
block ONE STORED HEAD AT A TIME instead (`split_heads`, `heads_of`): head
g's rows are every H_kv-th row of the buffer, read by sublane-strided
loads of its uint32 view (two bf16 heads a 32-bit row: a load serves heads
2j and 2j + 1, the halves of an even and an odd token's rows exchanged so
a head's tokens are packed rows again), and contracted with that head's
query rows of the whole run alone — [rows*rep, D] x [block, D]^T, online
softmax on [rows*rep, block], [rows*rep, block] x [block, D] — no group
mask, no score column of another group (the mask form pays for 31 columns
in 32 at Olmo-Hybrid's 32 stored heads, 7 in 8 at 8 KV heads).  A strided
load costs a cycle a tile it touches — 8 at 8 stored heads or more, so
reading a block head by head is 4.5 us a block of 128 tokens x 32 heads
(1.2 at 8 heads) where its copies take 2.6 (0.64), WHATEVER the rows it
then serves: it pays for a run (the mask form's 8 rows cost 5.1 us there)
and the more the more rows a tile holds — `tile_rows` gives such a pool
the rows that fill a dot, 32 at group size one — and it loses at a row
alone (4.6 us against 3.1: my chip runs, PR 60, tools/bench_paged.py), which
is why a row keeps the dense operand — and a run over FOUR stored heads
or fewer too, where the chip reads the two forms alike (`split_heads`).  The loop over a block's heads is
unrolled: Mosaic takes a strided read at a dynamic first row, and a
`fori_loop` over the pairs of heads, their carries in VMEM, is a program
of 19.7 k lines where this is 26.8 k at Olmo-Hybrid's 32 heads — and
runs the mixed call 22-24% slower (6.25 ms against 5.12 there, 2.81
against 2.26 at Laguna's 8: a pair's loads no longer overlap the last
pair's dots; my chip runs, PR 60).  q goes in twice where runs split: a
row's heads together [Bq, Hp, D], and a group's rows together [H_kv,
Bq*rep, D]; so does the output, and the caller takes a row from the one
its walk wrote.  q, k and v enter the dots in the dtype they are stored in
(bf16 in the cells), accumulated in float32, the weights cast to v's
dtype: the jnp fallback's precision.
A sliding-window layer runs the same kernel under the name
`window_attn` (`paged_attention(first=)`): over a table of each row's
own that names only the pages its window intersects, with the window's
lower edge in the mask.  Interpret-mode parity
with the fallback is the CPU oracle (tests/test_serving.py,
tests/test_chunked_prefill.py, tests/test_paged_tiles.py);
tests/test_mosaic_compile.py asks the chip's compiler at the serve cells'
shapes.

MIXED prefill/decode (chunked prefill): the optional `row_slot` operand
generalizes the query dimension from one-token-per-slot to a packed
ragged row list — row r attends table row `row_slot[r]` up to
`lengths[r]` tokens, so a prompt chunk (several consecutive rows, same
slot) and live decode rows share one grid.  `row_slot` rides the same
scalar-prefetch channel as the page table.  A chunk's rows differ in
nothing but where their causal mask ends, so the rows of a tile that lie
in one chunk SHARE ONE WALK (a run: `_runs`): each fetched block enters
the dots once against the whole run's heads, one online-softmax carry a
row, each row masked at its own `lengths[r]` (the tile's other rows at 0,
their outputs not stored), the loop run to the run's longest row — the
run's rows for the copies (and the loop step, and the block's reading) of
one.  Which rows run together is read from `row_slot` itself (`_runs`: a
few comparisons under the step's jit, two more prefetched operands, one
branch a row — never one a page); a tile holds any mix of runs and rows
alone, so a run that starts or ends off a tile's edge shares what lies in
each tile, and the arithmetic of a row is the same either way.
`walked_blocks` is the count the engine keeps of all this
(kv_tokens_fetched, serving_kv_shared_rows_total).  A windowed call's
rows each read a table row of their own, and the rows of a decode step
ARE the slots: those programs hold the row's walk alone.

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

SCAN-BODY SAFE: the kernel is pure in its operands with no host
callbacks, no side channels, and no per-call state, so a `lax.scan`
tracing it K times produces ONE kernel instance in the loop body (the
body appears once in the HLO).  Positions/lengths arriving as scan
carries instead of host-staged arrays change nothing here: each body's
DMA addressing reads whatever `table`/`lengths` values the carry holds
(tests/test_chunked_prefill.py holds it to that).
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
    pages = max(1, min(tokens // page_size, max_pages))
    return pages * page_size


def _head_rows(H: int, dtype) -> int:
    """q's rows fill whole sublane tiles of its dtype (8 rows of 32 bits)."""
    return _round_up(H, 8 * max(1, 4 // jnp.dtype(dtype).itemsize))


def split_heads(h_kv: int, itemsize: int) -> bool:
    """Whether a RUN's walk (below) reads a block ONE STORED HEAD AT A TIME
    — a dot a head against that head's query rows alone — where a token's
    row holds MORE THAN FOUR heads and the strided read of a head's rows
    exists: rows of 32 bits as they are, rows of 16 bits two a sublane row
    through a uint32 view (so an even count of them).  Any other row (an
    odd count of 16-bit heads, 8-bit heads: no cell's) stays one dense
    operand, the other heads' columns masked — and so does a row of FOUR
    heads or fewer, where the mask wastes three columns in four at most and
    the chip reads a wash (ms a mixed call, dense against head by head, my
    chip runs, PR 60, tools/bench_paged.py): 24 / 2 heads, 64 chunk rows
    beside 64 decode rows, 0.1913 against 0.1942; 32 / 2 heads, 256 + 256
    rows, 1.386 against 1.391; 32 heads on 4 packed rows, 256 + 256 rows,
    1.917 against 1.869 in tiles of 16 — but 0.039 against 0.092 of q's
    second layout and the output's select outside the kernel.  At 8 stored
    heads it is 3.42 against 2.27 (Laguna's full layer).  Such a pool keeps
    one q and one output."""
    return h_kv > 4 and (itemsize == 4 or (itemsize == 2 and h_kv % 2 == 0))


#: VMEM a tile of query rows may take beside the K/V buffers: its float32
#: scores and their weights, q and the output (two buffers each, the grid's
#: pipeline) and the float32 accumulator.
_TILE_VMEM_BUDGET = 4 << 20
#: the tile's ceiling: 8 rows where a block is one dense operand (at the
#: Laguna full layer's 48 heads against 1,024 masked score columns a shared
#: block of 8 rows cost 2.2 us of vector and MXU work against 0.64 of
#: copies, 4 rows 1.3 — my chip run, PR 53: a wider tile bought nothing);
#: where a run's walk is a dot a stored head, as many rows as fill the
#: MXU's 128 rows a dot — reading a block head by head costs the same
#: whatever the rows it then serves (`_kernel.heads_of`)
_TILE_ROWS = 8
_DOT_ROWS = 128


def tile_rows(rows: int, heads: int, cols: int, width: int, dtype,
              groups: int = 1) -> int:
    """Query rows one grid step holds (a TILE): a power of two derived from
    the shapes alone, as `block_tokens` is — `heads` query heads (padded to
    q's sublane tiles) against `cols` score columns a dot a block and a row
    of `width` lanes of `dtype`, within `_TILE_VMEM_BUDGET`; at most
    `_TILE_ROWS`, or where a block is read in `groups` dots, one a stored
    head, the rows that fill `_DOT_ROWS` of a dot; never more than the
    call's `rows` rounded up.  The engine calls this to count what the
    kernel fetches (`walked_blocks`)."""
    a_row = _head_rows(heads, dtype) * (
        2 * 4 * cols
        + _round_up(width, 128) * (4 + 4 * jnp.dtype(dtype).itemsize))
    most = _TILE_ROWS if groups == 1 else \
        max(_TILE_ROWS, _DOT_ROWS * groups // heads)
    bq = 1
    while 2 * bq <= min(most, _TILE_VMEM_BUDGET // a_row) and bq < rows:
        bq *= 2
    return bq


def query_tile(heads: int, kv_heads: int, row: tuple, block: int,
               dtype) -> tuple:
    """What `tile_rows` takes after the call's rows at a K/V pool whose
    stored row is `row` (`kv_row_shape`) and whose block holds `block`
    tokens (`block_tokens`): the query heads — with those a padded row's
    zero heads add, 30 KV heads stored as 32 —, the score columns of one
    dot, the row's lanes, the pool's dtype and the dots a run's walk reads a
    block in: one a stored head (`split_heads`), else one over every head's
    columns."""
    g, lanes = row
    dots = g if split_heads(g, jnp.dtype(dtype).itemsize) \
        and block % 2 == 0 else 1
    return (heads // kv_heads * max(kv_heads, g), block * g // dots, lanes,
            dtype, dots)


def _runs(xp, lengths, row_slot, bq: int):
    """The RUNS of a call's tiles: a run is the consecutive rows of one
    tile that read one table row, and walks its blocks ONCE, to its longest
    row.  -> ([R] a run's rows at its first row, 0 at its others; [R] its
    longest row's length there; [R] whether the row's run has more rows
    than one).  `xp` is jnp under the step's jit and numpy on the host."""
    slots, lens = row_slot.reshape(-1, bq), lengths.reshape(-1, bq)
    first = xp.concatenate([xp.ones_like(slots[:, :1], dtype=bool),
                            slots[:, 1:] != slots[:, :-1]], axis=1)
    run = xp.cumsum(first.astype(xp.int32), axis=1) - 1  # its run, in a tile
    of = run[:, :, None] == xp.arange(bq)[None, None, :]  # [tile, row, run]
    rows = xp.take_along_axis(of.sum(axis=1), run, axis=1)
    longest = xp.take_along_axis(
        xp.where(of, lens[:, :, None], 0).max(axis=1), run, axis=1)
    return (xp.where(first, rows, 0).reshape(-1),
            xp.where(first, longest, 0).reshape(-1), (rows > 1).reshape(-1))


def walked_blocks(lengths, row_slot, bq: int, bt: int):
    """(blocks fetched, rows on a shared walk) of one call over host arrays:
    a run of one slot's rows in a tile folds each block once, to its
    longest row; a row alone walks its own, a dead one a block — the rows
    `_call` pads the last tile with among them.  `row_slot` None: the rows
    are the slots and none shares."""
    import numpy as np
    lengths = np.asarray(lengths)
    alone = int(np.maximum(-(-lengths // bt), 1).sum())
    if row_slot is None:
        return alone + -lengths.size % bq, 0
    if bq == 1:
        return alone, 0
    pad = -lengths.size % bq            # as `_call` pads: whole tiles
    rows, longest, shared = _runs(
        np, np.pad(lengths, (0, pad)),
        np.pad(np.asarray(row_slot), (0, pad), mode="edge"), bq)
    return int(np.maximum(-(-longest[rows > 0] // bt), 1).sum()), \
        int(shared[:lengths.size].sum())


def _kernel(H, h_kv, scale, v_width, split, windowed, tiled, table_ref,
            len_ref, row_ref, *rest):
    """One TILE of query rows against their slots' live KV, a RUN of one
    slot's rows at a time (`_runs`).  `v_width` None: K and V pools of [P,
    ps, h_kv, Dp] (grouped-query heads).  `v_width` set: ONE latent pool of
    [P, ps, W] whose rows are both — every query head scores the whole row
    and weighs its first `v_width` columns (ops/mla.py), so a block is
    fetched once and there are no groups.  `tiled`: two prefetched operands
    more, a run's rows and its longest row's length at its first row — a
    run of several rows walks its slot's blocks ONCE, together; else every
    row walks alone.  `split` (`split_heads`): such a run reads a block one
    stored head at a time, a dot a head against its group's rows of the
    run, from q a second time as [h_kv, Mg, Dp] — a group's rows together —
    into a second output of that shape.  `windowed`: a prefetched operand
    more, the first token of the row's table row that the query may see
    (the window's lower edge, below the row's length: the mask is first <=
    t < length)."""
    run_ref = longest_ref = first_ref = None
    if tiled:
        run_ref, longest_ref, *rest = rest
    if windowed:
        first_ref, *rest = rest
    # q (twice where runs split), the pools, the outputs likewise, a buffer a
    # pool, the copies' semaphores, the buffer the next walk reads
    n = 2 if v_width is None else 1
    q_ref, *rest = rest
    qg_ref = rest.pop(0) if split else None
    hbms, (o_ref, *rest) = rest[:n], rest[n:]
    og_ref = rest.pop(0) if split else None
    bufs, (sems, slot_ref) = rest[:n], rest[n:]
    pools = tuple(zip(hbms, bufs))
    k_hbm, kbuf = pools[0]
    vbuf = bufs[1] if v_width is None else None
    t = pl.program_id(0)
    Bq, Hp, _ = q_ref.shape
    n_rows = pl.num_programs(0) * Bq
    # a page's rows of the operand, however the pool folds them (a lone
    # head's tokens are stored two a row: `kv_page_shape`)
    rows = k_hbm.shape[1] if len(k_hbm.shape) == 3 \
        else k_hbm.shape[1] * k_hbm.shape[2]
    ps = rows // h_kv                   # tokens a page
    bt = kbuf.shape[1] // h_kv          # tokens a block
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

    def heads_of(buf, slot):
        """A block of `buf` as the h_kv operands [bt, Dp] of its stored
        heads: row t * h_kv + g of the buffer is token t under head g, so
        head g is a read of every h_kv-th row.  Rows of 16 bits lie two a
        32-bit sublane row (token t's heads 2j and 2j + 1 are ONE uint32
        row, t * h_kv/2 + j, the even head its low half), and a strided
        read moves whole sublane rows: the even tokens' and the odd tokens'
        rows of a PAIR of heads are read as uint32, and their halves
        exchanged — head 2j of tokens 2s, 2s + 1 is one row of 32 bits
        again, the operand's own tiling.  The eight rows of such a load lie
        in eight tiles of the buffer (one, at 2 heads: two), and the load
        takes a cycle a tile: 4,096 cycles a block of 128 tokens x 32 heads
        where its copies take 2,400 — whatever the rows it then serves (my
        chip run, PR 60: tools/bench_paged.py, 4.5 us a block at Olmo's 32
        heads, 1.2 at Laguna's 8).  So a run of rows reads a block so, and
        a row alone keeps the dense operand."""
        if buf.dtype.itemsize == 4:
            return [buf[slot, pl.ds(g, bt, stride=h_kv), :]
                    for g in range(h_kv)]
        u = buf.bitcast(jnp.uint32)                # [2, bt * h_kv/2, Dp]
        out = []
        for j in range(h_kv // 2):
            even = u[slot, pl.ds(j, bt // 2, stride=h_kv), :]
            odd = u[slot, pl.ds(j + h_kv // 2, bt // 2, stride=h_kv), :]
            out += [pltpu.bitcast(halves, buf.dtype) for halves in (
                (even & 0xFFFF) | (odd << 16),
                (even >> 16) | (odd & jnp.uint32(0xFFFF0000)))]
        return out

    @pl.when(t == 0)
    def _():
        slot_ref[0] = 0
        start_fetch(0, 0, 0)

    def live(r):
        # never past what the table maps, whatever `lengths` holds: the
        # trip count is a run-time value, and a corrupted one would be
        # device time
        return jnp.minimum(len_ref[r], maxp * ps)

    def walk(q, r, nxt, length, longest, first):
        """Fold the blocks of row `r`'s table row into q's rows: ONE
        operand [M, Dp] — a query row's Hp heads, or a tile's Bq * Hp —
        against the block as one dense operand, or h_kv operands, a
        group's rows of a run each, against one stored head each; every
        row masked at its own `length` (a scalar, or [M, 1]), the loop run
        to `longest`; the last block prefetches row `nxt`'s first, into the
        buffer `slot_ref` then names.  -> an output [M, Dv] an operand."""
        M, G = q[0].shape[0], len(q)
        dense = h_kv // G               # stored heads in one dot's operand
        C = bt * dense                  # score columns a dot
        # every walk folds at least one block (a dead or padding row: one
        # block of the trash page), so its last block can always prefetch
        # the next walk's first
        nblk = jnp.maximum(pl.cdiv(longest, bt), 1)
        # column c of a dot is token c // dense (under stored head c %
        # dense where the operand is dense: query head h reads kv head
        # h // rep, and the other groups' columns are masked — but for one
        # KV head under unpadded query heads: every column is every head's)
        tok = jax.lax.broadcasted_iota(jnp.int32, (M, C), 1)
        own_group = None
        if G == 1 and not (h_kv == 1 and Hp == H):
            head = jax.lax.broadcasted_iota(jnp.int32, (M, C), 0) \
                if M == Hp else \
                jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0) % Hp
            own_group = (tok % h_kv) == head // rep
        if dense > 1:
            tok = tok // dense

        def fold(b, carry):
            *state, slot = carry
            last = b == nblk - 1
            nrow = jnp.where(last, nxt, r)
            nb = jnp.where(last, 0, b + 1)

            @pl.when(nrow < n_rows)
            def _():
                start_fetch(nrow, nb, 1 - slot)

            wait_fetch(slot)
            ks = [kbuf[slot]] if G == 1 else heads_of(kbuf, slot)
            vs = [k[:, :Dv] for k in ks] if vbuf is None else \
                [vbuf[slot]] if G == 1 else heads_of(vbuf, slot)
            valid = tok < length - b * bt
            if first is not None:
                valid = jnp.logical_and(valid, tok >= first - b * bt)
            if own_group is not None:
                valid = jnp.logical_and(own_group, valid)
            new = []
            for g in range(G):
                m_prev, l_prev, acc = state[3 * g:3 * g + 3]
                sc = jax.lax.dot_general(
                    q[g], ks[g], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale   # [M, C]
                sc = jnp.where(valid, sc, _NEG_INF)
                m_new = jnp.maximum(
                    m_prev, jnp.max(sc, axis=-1, keepdims=True))
                w = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
                corr = jnp.exp(m_prev - m_new)
                l_new = corr * l_prev + jnp.sum(w, axis=-1, keepdims=True)
                pv = jax.lax.dot_general(
                    w.astype(vs[g].dtype), vs[g], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)       # [M, Dv]
                new += [m_new, l_new, acc * corr + pv]
            return (*new, 1 - slot)

        *state, slot_ref[0] = jax.lax.fori_loop(
            0, nblk, fold,
            (jnp.full((M, 1), _NEG_INF, jnp.float32),
             jnp.zeros((M, 1), jnp.float32),
             jnp.zeros((M, Dv), jnp.float32)) * G + (slot_ref[0],))
        return [(acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
                for _, l, acc in zip(*[iter(state)] * 3)]

    r0 = t * Bq

    def alone(i):
        r = r0 + i
        length = live(r)
        o_ref[i], = walk(
            [q_ref[i]], r, r + 1, length, length,
            None if first_ref is None else first_ref[r])

    def together(i, n):
        """The run of rows i .. i + n - 1 of the tile: the tile's rows as
        the operands, each row's length down its heads — 0 at the rows of
        other runs, whose outputs are not stored."""
        hq, M = (rep, qg_ref.shape[1]) if split else (Hp, Bq * Hp)
        at = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0)
        length = jnp.zeros((M, 1), jnp.int32)
        for j in range(Bq):
            length = jnp.where(
                at >= j * hq,
                jnp.where(jnp.logical_and(j >= i, j < i + n), live(r0 + j),
                          0), length)
        out = walk(
            [qg_ref[g] for g in range(h_kv)] if split else
            [q_ref[...].reshape(M, q_ref.shape[-1])], r0 + i, r0 + i + n,
            length, jnp.minimum(longest_ref[r0 + i], maxp * ps), None)
        if split:
            refs = [og_ref.at[g] for g in range(h_kv)]
            mine = jnp.logical_and(at >= i * hq, at < (i + n) * hq)
        else:
            refs, out = [o_ref], [out[0].reshape(Bq, Hp, Dv)]
            row = jax.lax.broadcasted_iota(jnp.int32, (Bq, 1, 1), 0)
            mine = jnp.logical_and(row >= i, row < i + n)

        for ref, o in zip(refs, out):   # the tile's other runs keep theirs
            ref[...] = jnp.where(mine, o, ref[...])

    def a_row(i, _):
        if not tiled:
            alone(i)
            return 0
        n = run_ref[r0 + i]             # 0 inside a run: its first row's
        pl.when(n == 1)(lambda: alone(i))
        pl.when(n > 1)(lambda: together(i, n))
        return 0

    jax.lax.fori_loop(0, Bq, a_row, 0)


@functools.lru_cache(maxsize=None)
def _program(name: str, kernel_args: tuple, bq: int, tiles: int,
             q_row: tuple, q_groups: Optional[tuple], pools: tuple,
             buf_shape: tuple, out_width: int, dtype, interpret: bool):
    """The family's one pallas_call for one set of shapes, built ONCE: the
    layers of a step (and the steps of a process) that call with the same
    shapes share the callable, so jit traces the kernel's two walks once for
    all of them (a trace and a lowering a layer was 5.6 s of the Laguna
    cell's warm start-up: my chip runs, PR 53).  `kernel_args` are
    `_kernel`'s statics; `q_groups` a tile of q by stored head, [G, Mg]
    (None: the runs' walks read q as the rows' do); `pools` the pools'
    dtypes."""
    *_, windowed, tiled = kernel_args
    Hp, Dq = q_row
    row = lambda t, *prefetched: (t, 0, 0)
    q_specs = [pl.BlockSpec((bq, Hp, Dq), row)]
    out_specs = [pl.BlockSpec((bq, Hp, out_width), row)]
    out_shape = [jax.ShapeDtypeStruct((tiles * bq, Hp, out_width), dtype)]
    if q_groups:
        group = lambda t, *prefetched: (t, 0, 0, 0)
        q_specs.append(pl.BlockSpec((None,) + q_groups + (Dq,), group))
        out_specs.append(
            pl.BlockSpec((None,) + q_groups + (out_width,), group))
        out_shape.append(jax.ShapeDtypeStruct(
            (tiles,) + q_groups + (out_width,), dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + 2 * tiled + windowed,
        grid=(tiles,),
        in_specs=q_specs
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),  # stay in HBM
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((2,) + buf_shape, p) for p in pools]
        + [pltpu.SemaphoreType.DMA((len(pools), 2)),      # (pool, buffer)
           pltpu.SMEM((1,), jnp.int32)],         # buffer the next walk reads
    )
    return pl.pallas_call(
        functools.partial(_kernel, *kernel_args),
        name=name,              # the device op's name in a profiler trace
        grid_spec=grid_spec,
        out_shape=out_shape,
        # tiles run in order: each prefetches its successor's first block
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )


def _call(name: str, kernel_args: tuple, bq: int, groups: int, qp: Array,
          pools: tuple, buf_shape: tuple, out_width: int, page_table: Array,
          lengths: Array, row_slot: Array,
          first: Optional[Array] = None) -> Array:
    """The one pallas_call of the family: grid over TILES of `bq` query
    rows, the pools in HBM, table / lengths / row->slot (with the tiles'
    runs, or a windowed call's `first`) on the scalar-prefetch channel, a
    double buffer of `buf_shape` a pool.  `groups` > 1: the runs' walks
    read a block in that many dots (`split_heads`), and q goes in a second
    time with a group's rows together — [tiles, groups, bq * H // groups,
    Dq], padded to whole sublane tiles — and their rows come back so.
    `kernel_args`: `_kernel`'s H, h_kv, scale and v_width."""
    R, Hp, Dq = qp.shape
    H = kernel_args[0]
    # rows that ARE the slots (the one-token-per-slot decode) hold no run
    tiled = first is None and bq > 1 and row_slot is not None
    if row_slot is None:
        row_slot = jnp.arange(R, dtype=jnp.int32)
    pad = -R % bq
    if pad:     # whole tiles: dead rows of the last row's slot
        qp = jnp.pad(qp, ((0, pad), (0, 0), (0, 0)))
        lengths = jnp.pad(lengths, (0, pad))
        row_slot = jnp.pad(row_slot, (0, pad), mode="edge")
    tiles = (R + pad) // bq
    scalars = (page_table, lengths, row_slot)
    split = tiled and groups > 1
    q, q_groups = (qp,), None
    if tiled:
        *runs, shared = _runs(jnp, lengths, row_slot, bq)
        scalars += tuple(runs)
    if first is not None:
        scalars += (jnp.pad(first, (0, pad)),)
    if split:
        rep = H // groups
        Mg = _head_rows(bq * rep, qp.dtype)
        qg = qp[:, :H].reshape(tiles, bq, groups, rep, Dq).swapaxes(1, 2)
        q += (jnp.pad(qg.reshape(tiles, groups, bq * rep, Dq),
                      ((0, 0), (0, 0), (0, Mg - bq * rep), (0, 0))),)
        q_groups = (groups, Mg)
    program = _program(
        name, kernel_args + (split, first is not None, tiled), bq, tiles,
        (Hp, Dq), q_groups, tuple(p.dtype for p in pools), buf_shape,
        out_width, qp.dtype, _interpret())
    out, *by_group = program(*(a.astype(jnp.int32) for a in scalars), *q,
                             *pools)
    if split:       # a row of a run comes back with its group's rows
        og = by_group[0][:, :, :bq * rep].reshape(
            tiles, groups, bq, rep, out_width).swapaxes(1, 2)
        out = jnp.where(shared[:, None, None],
                        og.reshape(tiles * bq, H, out_width), out[:, :H])
    return out[:R]


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

    itemsize = jnp.dtype(k_pages.dtype).itemsize
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
    Hp = _head_rows(H, q.dtype)
    qp = jnp.pad(q, ((0, 0), (0, Hp - H), (0, Dp - L)))
    if Dp != L:
        k_pages, v_pages = (jnp.pad(p, ((0, 0),) * 3 + ((0, Dp - L),))
                            for p in (k_pages, v_pages))
    tile = query_tile(H, G * pack, (G, Dp), npb * ps, k_pages.dtype)
    out = _call("paged_attn" if first is None else "window_attn",
                (H, G, float(scale), None), tile_rows(R, *tile), tile[-1],
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
    Hp = _head_rows(H, q.dtype)
    itemsize = jnp.dtype(kv_pages.dtype).itemsize
    npb = block_tokens(ps, 1, W, itemsize, maxp) // ps
    qp = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))
    out = _call("mla_paged_attn", (H, 1, float(scale), v_width),
                tile_rows(R, H, npb * ps, W, q.dtype), 1, qp,
                (kv_pages,), (npb * ps, W), v_width, page_table, lengths,
                row_slot)
    return out[:, :H]
