"""The KDA layers' prompt chunks in Pallas (TPU): `kda_seg`, the chunkwise
(WY / UT-transform) form of ops/kda.py over the chunk rows of a ragged mixed
step, WITH THE RUN LOOP AND THE CHUNK LOOP INSIDE THE KERNEL.  A run is a
slot's consecutive chunk rows in one step; its slot, first row, length and
whether it starts at position 0 ride the scalar-prefetch channel
(ops/selective_scan.py `segment_table`), and so does the number of runs:
one call a layer serves any number of runs of any length.

  grid (head blocks,): a grid step holds `head_block` heads (16 of 32 or of
  64, as `pallas_kda.head_block`; fewer where a long row list would not
  fit).  The rows' operands of ALL the chunk rows — q (scaled), k, g, b k
  and b v, each [H, P', d], a head's rows contiguous and one lane tile
  wide (beta rides in on k and v: the kernel needs it nowhere else) — are a
  block a head block each, fetched once a call by the pipeline.  The state
  pool stays in HBM (`pl.ANY`), aliased to its result: a run copies its
  slot's [hb, d, d] float32 block into VMEM once — or zeroes the buffer
  where the run starts at position 0 —, folds cdiv(length, CHUNK) chunks
  into it, and copies it back once.  Nothing else of the pool moves; the
  trash row and the slots with no run are never touched.

  A chunk is CHUNK rows from the run's OWN first row (any row of the packed
  list: a dynamic sublane offset), its ragged tail masked by g = 0, b = 0
  as the jnp form masks padding; the operand carries CHUNK rows of zeros
  past the list so the last window stays inside it.  A chunk of one head,
  rows on sublanes and d on lanes, every reduction over d a product on the
  MXU:

    G = cumsum(g)                    a product with lower-triangular ones
    kk, qk [t, j] = sum_d k|q[t, d] k[j, d] exp(G[t, d] - G[j, d]),  j < t
        level by level of the rows' binary tree: the pair (t, j) belongs
        to the one level s at which t and j part — the same block of 2s
        rows, j in its lower half, t in its upper.  With m the block's
        middle row, j < m <= t and
            exp(G_t - G_j) = exp(G_t - G_m) exp(G_m - G_j)
        BOTH EXPONENTS <= 0 (G falls): a level scales every row by
        F = exp(-|G - G_m|) <= 1 and its pairs are one [2C, d] x [d, C]
        product, the other blocks' entries (products of factors <= 1:
        finite) masked away.  No exponent is ever positive and nothing is
        rescaled by a reference a pair does not straddle, so nothing can
        overflow however long the decay; at s = 1 the factor is exp(g_t),
        the adjacent pair's own ratio.  The G_m of every level come out of
        the cumsum's product (more rows of ones).  On the chip the 64 rows
        a row of exponentials cost 5.4 us a head a chunk, the six levels
        0.8 (my chip runs, PR 56).  None of it is ever in HBM;
    (I + A) u = b (v - (k e^G) S)    A = b kk, strictly lower: forward
        substitution by columns, exact — once row j is final, the rows
        below take A[., j] u_j off, whole tiles of a VMEM scratch (held
        as vregs it ran 3% faster and traced 1,303 equations where this
        traces 771: a second of every start-up, my chip runs, PR 56);
    o = (q e^G) S + (qk + diag(q . k)) u
    S = e^G_last S + (k e^(G_last - G))^T u

  float32 throughout, the products at full float32 precision
  (`Precision.HIGHEST`: Mosaic's fp32 contraction), CHUNK = 64 as the jnp
  form.  What differs from ops/kda.py `chunkwise` is the order of sums:
  the right-hand side takes S_0 before the solve (one solve of d columns,
  not of 2 d), G is a product, not a scan, and a pair's decay is two
  factors <= 1 about a row between them, not one exponential.

A file of its own, not a section of ops/pallas_kda.py: that file's body is
ONE rank-1 step of a state a row with its vectors as lanes of two packed
operands, a grid over rows, the state block addressed by the pipeline;
here the body is a chunk of 64 rows with a solve and ten matmuls, the grid
is over head blocks only, and the state moves by the kernel's own copies
because the runs are a run-time loop.  What the two share is the calling
convention (the run table prefetched, the pool aliased in place, the trash
row left alone), `supported()` and `_interpret()`, which this file asks
ops/pallas_kda.py for.

Interpret-mode parity with ops/kda.py `recurrent` and `chunkwise` is the CPU
oracle (tests/test_kimi_linear.py, tests/test_solar_open2.py);
tests/test_mosaic_compile.py asks the chip's compiler at both cells' shapes
(192 chunk rows x 32 and x 64 heads, 129 slot states of 128 x 128 float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import pallas_kda
from paddle_tpu.ops.kda import CHUNK
from paddle_tpu.ops.selective_scan import segment_table
from paddle_tpu.utils.jax_compat import pallas_tpu_compiler_params

Array = jax.Array

_HI = jax.lax.Precision.HIGHEST
_TILE = 8               # float32 rows a vreg holds
_VMEM_LIMIT = 64 * 2 ** 20


def head_block(num_heads: int, rows: int, lanes: int) -> int:
    """Heads a grid step holds: `pallas_kda.head_block`'s (16 of 32 or 64
    heads, 15 of 30), or the largest divisor of the head count under it
    whose rows' blocks and output's, double-buffered (`lanes` float32 a row
    a head as VMEM lays them out — 6 d for KDA's five operands and its
    output: 25 MiB at 16 heads of 256 rows), stay inside half the kernel's
    VMEM.  On the chip at 192 chunk rows x 32 heads (my chip runs, PR 56):
    4 heads a step 0.343 ms a call, 8 0.334, 16 0.331, 32 0.330 — the
    block is not what binds it."""
    fits = lambda hb: 2 * lanes * rows * 4 * hb <= _VMEM_LIMIT // 2
    top = pallas_kda.head_block(num_heads)
    return max([hb for hb in range(1, top + 1)
                if num_heads % hb == 0 and fits(hb)] or [1])


def chunk_rows(rows: int) -> int:
    """Rows a chunk of `kda_seg` holds in a list of `rows` chunk rows:
    `CHUNK`, or a short list whole (to a tile of 8)."""
    return min(CHUNK, -(-rows // _TILE) * _TILE)


def folded_chunks(run_lengths, rows: int) -> int:
    """Chunks one call folds: cdiv(length, chunk_rows) a run — the count
    the engine keeps on the host, where the step is packed."""
    C = chunk_rows(rows)
    return sum(-(-int(n) // C) for n in run_lengths)


def _dot(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a [M, d] x b [N, d] -> [M, N]: contracted over the lanes of both."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a [C, M] x b [C, N] -> [M, N]: contracted over the rows of both."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _levels(C: int) -> int:
    """Bits a row index of a chunk holds: the levels the pairs part at."""
    return (C - 1).bit_length()


def _sums(C: int):
    """[(1 + levels) C, C] of ones and zeros whose product with g [C, d]
    is G = cumsum(g) (the first C rows: lower-triangular ones) and, a level,
    G at each row's middle row m (rows c <= m; zeros where the block has no
    upper half inside the chunk)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    parts = [col <= row]
    for bit in range(_levels(C)):
        mid = ((row >> (bit + 1)) << (bit + 1)) + (1 << bit)
        parts.append((col <= mid) & (mid < C))
    return jnp.concatenate(parts, axis=0).astype(jnp.float32)


def _solve(C: int, A, rhs, a_ref, u_ref):
    """(I + A) u = rhs by columns, A [C, C] zero on and above the diagonal:
    once row j is final, every later row takes A[., j] u_j off — so the
    rows of j's own tile that come before it are left as they were."""
    a_ref[...] = A
    u_ref[...] = rhs
    for j in range(C - 1):
        low = slice((j + 1) // _TILE * _TILE, C)    # whole tiles from j + 1
        u_ref[low, :] = u_ref[low, :] - a_ref[low, j:j + 1] * u_ref[j:j + 1, :]
    return u_ref[...]


def _chunk_head(C: int, x, S, scratch, n):
    """One chunk of one head under a decay a HEAD (`gdn_seg`): x = (q, k,
    vx) with q (scaled) and k [C, dk], vx [C, dv + 2] holding v, then g and
    beta a column each (they ride in on v's last lane tile), S [dk, dv] the
    state it starts from, n the rows that are the run's -> (o [C, dv],
    S_new).  The pairwise decays are ONE [C, C] matrix, E[t, j] = exp(G_t -
    G_j) for j <= t: the exponent sum_{j < i <= t} g_i is a product of two
    triangles of ones with g on the second's rows (never positive, and no
    row of g ever lies along lanes), and E multiplies K K^T and Q K^T
    entrywise — no tree of levels, no exponential a channel."""
    a_ref, u_ref = scratch
    q, k, vx = x
    dv = S.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    valid = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) < n
    v = vx[:, :dv]
    # the ragged tail: g = 0 and b = 0 leave the state as it was
    g = jnp.where(valid, vx[:, dv:dv + 1], 0.0)              # [C, 1]
    b = jnp.where(valid, vx[:, dv + 1:dv + 2], 0.0)
    D = _dot((col <= row).astype(jnp.float32), jnp.where(row > col, g, 0.0))
    E = jnp.where(col <= row, jnp.exp(jnp.minimum(D, 0.0)), 0.0)
    G = D[:, 0:1] + g[0:1, :]                                # cumsum, [C, 1]
    eG = jnp.exp(G)
    bk = b * k
    # both products against the state the chunk starts from, one pass of S
    kq_S = _dot(jnp.concatenate([bk * eG, q * eG], axis=0), S)
    P = _dot_nt(jnp.concatenate([bk, q], axis=0), k)         # [2C, C]
    u = _solve(C, jnp.where(row > col, P[:C] * E, 0.0), b * v - kq_S[:C],
               a_ref, u_ref)
    o = kq_S[C:] + _dot(P[C:] * E, u)
    g_last = G[C - 1:C]                                      # [1, 1]
    # (a [1, 1] goes along lanes first: Mosaic broadcasts one way at a time)
    S = jnp.exp(jnp.broadcast_to(g_last, (1, dv))) * S + \
        _dot_tn(k * jnp.exp(g_last - G), u)
    return o, S


def _chunk(C: int, x, S, scratch, n):
    """One chunk of one head under a decay a CHANNEL (`kda_seg`): x = (q,
    k, g, bk, bv) [C, d] each (q scaled, bk = b k, bv = b v), S [d, d] the
    state it starts from, n the rows of it that are the run's -> (o [C, d],
    S_new).  `sums_ref` holds `_sums(C)`; `a_ref` [C, C] is VMEM scratch."""
    sums_ref, a_ref, u_ref = scratch
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    valid = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) < n
    q, k, g, bk, bv = x
    # the ragged tail: g = 0 and b = 0 leave the state as it was
    g, bk, bv = (jnp.where(valid, a, 0.0) for a in (g, bk, bv))
    GG = _dot(sums_ref[...], g)         # cumsums, <= 0: G and G_m a level
    G = GG[:C]
    eG = jnp.exp(G)
    # both products against the state the chunk starts from, one pass of S
    kq_S = _dot(jnp.concatenate([bk * eG, q * eG], axis=0), S)
    rhs = bv - kq_S[:C]                 # b (v - (k e^G) S_0)

    # kk, qk [t, j] = sum_d k|q[t, d] k[j, d] exp(G[t, d] - G[j, d]), j < t.
    # The pair (t, j) belongs to the one level (bit) at which t and j part:
    # same block of 2s rows, j in its lower half, t in its upper.  With m
    # the block's middle row (the upper half's first), j < m <= t and
    #   exp(G_t - G_j) = exp(G_t - G_m) exp(G_m - G_j),  both exponents <= 0
    # so every row is scaled by F = exp(-|G - G_m|) <= 1 and the level's
    # pairs are one product on the MXU; entries of other blocks are
    # products of factors <= 1 too (finite) and masked away.  At s = 1 the
    # factor IS exp(g_t): the adjacent pair's own decay.
    A = jnp.zeros((C, C), jnp.float32)
    qk = jnp.where(row == col, jnp.sum(q * k, axis=1, keepdims=True), 0.0)
    for bit in range(_levels(C)):
        s = 1 << bit
        block = lambda i: i >> (bit + 1)
        F = jnp.exp(-jnp.abs(G - GG[(bit + 1) * C:(bit + 2) * C]))
        P = _dot_nt(jnp.concatenate([bk * F, q * F], axis=0), k * F)
        mine = (block(row) == block(col)) & ((row & s) != 0) \
            & ((col & s) == 0)
        A = jnp.where(mine, P[:C], A)   # b_t kk[t, j]
        qk = jnp.where(mine, P[C:], qk)
    u = _solve(C, A, rhs, a_ref, u_ref)     # A: zero on and above the diag

    o = kq_S[C:] + _dot(qk, u)
    g_last = G[C - 1:C]                                      # [1, d]
    S = jnp.exp(g_last).T * S + _dot((k * jnp.exp(g_last - G)).T, u)
    return o, S


def _kernel(C: int, hb: int, n_ref, slot_ref, start_ref,
            len_ref, zero_ref, q_ref, k_ref, g_ref, bk_ref, bv_ref, s_in,
            o_ref, s_out, s_buf, sums_ref, a_ref, u_ref, sem):
    h0 = pl.program_id(0) * hb
    sums_ref[...] = _sums(C)
    # rows no run holds (padding) read zeros, as the jnp form's
    o_ref[...] = jnp.zeros_like(o_ref)
    valid_rows = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)

    def run(r, _):
        slot, row0, n = slot_ref[r], start_ref[r], len_ref[r]
        from_zero = zero_ref[r] != 0

        @pl.when(from_zero)
        def _():
            s_buf[...] = jnp.zeros_like(s_buf)

        @pl.when(jnp.logical_not(from_zero))
        def _():
            fetch = pltpu.make_async_copy(
                s_in.at[slot, pl.ds(h0, hb)], s_buf, sem.at[0])
            fetch.start()
            fetch.wait()

        def chunk(c, _):
            at = row0 + c * C
            left = n - c * C
            rows = pl.ds(at, C)

            def head(i, _):
                # a window one lane tile wide: the only load Mosaic takes
                # at a sublane offset it cannot prove a multiple of 8
                x = tuple(ref[i, rows, :] for ref in
                          (q_ref, k_ref, g_ref, bk_ref, bv_ref))
                o, S = _chunk(C, x, s_buf[i], (sums_ref, a_ref, u_ref), left)
                s_buf[i] = S
                o_ref[i, rows, :] = jnp.where(valid_rows < left, o,
                                              o_ref[i, rows, :])
                return 0

            return jax.lax.fori_loop(0, hb, head, 0)

        jax.lax.fori_loop(0, (n + C - 1) // C, chunk, 0)
        store = pltpu.make_async_copy(
            s_buf, s_out.at[slot, pl.ds(h0, hb)], sem.at[1])
        store.start()
        store.wait()
        return 0

    jax.lax.fori_loop(0, n_ref[0], run, 0)


def _tiles(d: int) -> list:
    """[(first lane, lanes)] of a row of d: pieces one lane tile wide.  A
    rows' operand of `gdn_seg` wider than a tile goes in (and its output
    comes out) one operand a piece: Mosaic takes a load or a store at a
    sublane offset it cannot prove a multiple of 8 — a chunk starts at its
    run's own first row — only on a buffer ONE lane tile wide (asked at
    1,024 chunk rows of 194 lanes, PR 59: "cannot statically prove that
    index in dimension 1 is a multiple of 8")."""
    return [(at, min(128, d - at)) for at in range(0, d, 128)]


def _kernel_head(C: int, hb: int, widths: tuple, dv: int, n_ref, slot_ref,
                 start_ref, len_ref, zero_ref, *refs):
    """`gdn_seg`: grid (head blocks, runs).  Where `kda_seg` copies a run's
    state itself, here the PIPELINE moves it, a [1, hb, dk, dv] block
    addressed by the run's slot as `gdn_step`'s is by its row's: a pool
    whose rows are not whole lane tiles (96 x 192) cannot be sliced for a
    copy of the kernel's own (Mosaic, PR 59: "Slice shape along dimension 3
    must be aligned to tiling (128), but is 192"), and a block of the whole
    last two dims can.  A step is one run of one head block: the state's
    block is the working buffer, zeroed where the run starts at position 0;
    the runs past the table's live ones have no rows and leave the trash
    row as it was.  The rows' operands and the output are blocks a head
    block, resident across its runs."""
    del n_ref, slot_ref                 # the slot addressed the state block
    refs, x_refs = list(refs), []
    for d in widths:
        x_refs.append([refs.pop(0) for _ in _tiles(d)])
    s_ref = refs.pop(0)
    o_refs = [refs.pop(0) for _ in _tiles(dv)]
    s_out, *scratch = refs
    r = pl.program_id(1)
    row0, n = start_ref[r], len_ref[r]

    @pl.when(r == 0)
    def _():
        # rows no run holds (padding) read zeros, as the jnp form's
        for o_ref in o_refs:
            o_ref[...] = jnp.zeros_like(o_ref)

    from_zero = zero_ref[r] != 0

    @pl.when(from_zero)
    def _():
        s_out[...] = jnp.zeros_like(s_out)

    @pl.when(jnp.logical_not(from_zero))
    def _():
        s_out[...] = s_ref[...]

    valid_rows = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)

    def chunk(c, _):
        left = n - c * C
        rows = pl.ds(row0 + c * C, C)

        def head(i, _):
            x = tuple(group[0][i, rows, :] if len(group) == 1 else
                      jnp.concatenate([ref[i, rows, :] for ref in group],
                                      axis=1) for group in x_refs)
            o, S = _chunk_head(C, x, s_out[0, i], scratch, left)
            s_out[0, i] = S
            for o_ref, (lane, w) in zip(o_refs, _tiles(dv)):
                o_ref[i, rows, :] = jnp.where(
                    valid_rows < left, o[:, lane:lane + w], o_ref[i, rows, :])
            return 0

        return jax.lax.fori_loop(0, hb, head, 0)

    jax.lax.fori_loop(0, (n + C - 1) // C, chunk, 0)


@functools.lru_cache(maxsize=None)
def _program(H: int, hb: int, rows: int, C: int, d: int, state_shape: tuple,
             interpret: bool):
    """The one pallas_call for one set of shapes, built ONCE: the KDA layers
    of a step share it, so jit traces the kernel's body once for all of
    them (ops/pallas_paged.py `_program`: a trace a layer was seconds of a
    cell's start-up)."""
    by_head = lambda h, *_: (h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,          # n_runs, slot, start, length, zero
        grid=(H // hb,),
        in_specs=[pl.BlockSpec((hb, rows, d), by_head)] * 5
        + [pl.BlockSpec(memory_space=pl.ANY)],              # the pool: HBM
        out_specs=[pl.BlockSpec((hb, rows, d), by_head),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((hb, d, d), jnp.float32),
                        pltpu.VMEM(((1 + _levels(C)) * C, C), jnp.float32),
                        pltpu.VMEM((C, C), jnp.float32),
                        pltpu.VMEM((C, d), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        functools.partial(_kernel, C, hb),
        name="kda_seg",         # the device op's name in a profiler trace
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((H, rows, d), jnp.float32),
                   jax.ShapeDtypeStruct(state_shape, jnp.float32)],
        # operands count the five prefetched scalars: the state is the 11th
        input_output_aliases={10: 1},
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=None)
def _program_head(H: int, hb: int, rows: int, runs: int, C: int,
                  state_shape: tuple, interpret: bool):
    """`gdn_seg`'s pallas_call for one set of shapes, built once as
    `_program`: q, k and v (g and beta a column each behind it) and the
    output in pieces one lane tile wide, the state a block a run."""
    dk, dv = state_shape[2:]
    widths = (dk, dk, dv + 2)
    by_head = lambda h, r, *_: (h, 0, 0)
    by_slot = lambda h, r, n, slot, *_: (slot[r], h, 0, 0)
    pieces = lambda d: [pl.BlockSpec((hb, rows, w), by_head)
                        for _, w in _tiles(d)]
    ins = [spec for d in widths for spec in pieces(d)]
    state = pl.BlockSpec((1, hb, dk, dv), by_slot)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,          # n_runs, slot, start, length, zero
        grid=(H // hb, runs),
        in_specs=ins + [state],
        out_specs=pieces(dv) + [state],
        scratch_shapes=[pltpu.VMEM((C, C), jnp.float32),
                        pltpu.VMEM((C, dv), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel_head, C, hb, widths, dv),
        name="gdn_seg",         # the device op's name in a profiler trace
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((H, rows, w), jnp.float32)
                   for _, w in _tiles(dv)]
        + [jax.ShapeDtypeStruct(state_shape, jnp.float32)],
        # operands count the five prefetched scalars: the state is the last
        input_output_aliases={5 + len(ins): len(_tiles(dv))},
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )


def kda_segments(state: Array, seg_slot: Array, seg_pos: Array, q: Array,
                 k: Array, v: Array, g: Array, beta: Array, scale: float):
    """The chunk rows of a ragged mixed step (ops/kda.py `segment_rows`):
    state [S+1, H, dk, dv] float32; seg_slot seg_pos [P] int32 (padding aims
    at trash row S); q k [P, H, dk], v [P, H, dv], beta [P, H], and g
    [P, H, dk] (a decay a channel: `kda_seg`, dk = dv as KDA's state is
    square) or [P, H] (a decay a head: `gdn_seg`, any dk and dv; a slot
    holds ONE run a step, the packing contract's), all float32 ->
    (o [P, H, dv], zeros outside the runs; state; n_segments)."""
    P, H, dk = q.shape
    dv = v.shape[-1]
    trash = state.shape[0] - 1
    start, length, slot, zero, n_seg = segment_table(seg_slot, seg_pos, trash)
    table = (n_seg.reshape(1).astype(jnp.int32),
             *(a.astype(jnp.int32) for a in (slot, start, length, zero)))
    C = chunk_rows(P)
    rows = -(-P // _TILE) * _TILE + C   # a chunk's window stays inside
    b = beta[..., None]
    # [P, H, d] -> [H, rows, d]: a head's rows contiguous, zeros past P
    by_head = lambda a: jnp.swapaxes(
        jnp.pad(a, ((0, rows - P), (0, 0), (0, 0))), 0, 1)
    if g.ndim == 2:
        xs = (q * scale, k, jnp.concatenate([v, g[..., None], b], axis=-1))
        xs = [by_head(a[..., lane:lane + w]) for a in xs
              for lane, w in _tiles(a.shape[-1])]
        hb = head_block(H, rows, 128 * (len(xs) + len(_tiles(dv))))
        program = _program_head(H, hb, rows, min(P, trash), C, state.shape,
                                pallas_kda._interpret())
        *o, state = program(*table, *xs, state)
        o = o[0] if len(o) == 1 else jnp.concatenate(o, axis=-1)
    else:
        assert v.shape == q.shape, (q.shape, v.shape)
        program = _program(H, head_block(H, rows, 6 * dk), rows, C, dk,
                           state.shape, pallas_kda._interpret())
        o, state = program(*table, *map(by_head, (q * scale, k, g, b * k,
                                                  b * v)), state)
    return jnp.swapaxes(o[:, :P], 0, 1), state, n_seg
