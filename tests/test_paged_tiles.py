"""The paged kernel's TILES (ops/pallas_paged.py): the grid runs over tiles
of consecutive query rows, and a tile whose rows all read one table row
walks that slot's blocks once for all of them.  Interpret-mode parity of
both walks against the jnp gather (`use_kernel=False`), over row lists that
put a run's ends on and off a tile boundary, and over every kind of pool.
"""

import numpy as np
import pytest

S = 4                                   # live table rows; row S is all-zero


def _table(rng, n_pages, maxp, slot_tokens, ps):
    table = np.zeros((len(slot_tokens) + 1, maxp), np.int32)
    free = rng.permutation(np.arange(1, n_pages)).tolist()
    for s, n in enumerate(slot_tokens):
        for j in range(-(-n // ps)):
            table[s, j] = free.pop()
    return table


def _run(slot, start, n):
    return [(slot, start + i) for i in range(n)]


# rows as (slot, position); slot 4 is the virtual trash row.  At the dense
# pools' shapes a tile is 8 rows (where a block is read a head at a time as
# many rows as the call has, up to what fills a dot) and a block 128 tokens
# (8 pages of 16; the lone head's holds the whole table): the second number
# of a case is the rows the kernel must put on a shared walk in tiles of 8 —
# the rows of the RUNS of one slot's rows inside a tile
_ROW_CASES = {
    # 3 decode rows, then a run of 20: rows 3-22, so tile 0 holds 5 of its
    # rows behind the decode rows, tile 1 is its own and tile 2 holds 7
    "run-starts-and-ends-off-a-tile": (
        [(0, 200), (2, 40), (3, 7)] + _run(1, 100, 20) + [(0, 201)], 20),
    # the 5 padding rows read one (trash) row: a run, one block for all
    "run-of-one-row": (
        [(0, 200), (2, 40)] + _run(1, 5, 1) + [(4, 0)] * 5, 5),
    "two-runs-of-neighbours-in-one-tile": (
        _run(1, 10, 4) + _run(2, 50, 4) + _run(0, 130, 8), 16),
    # rows 8-15 at positions 124-131: the block's and a page's last token
    # and the next one's first, inside one shared tile
    "run-crosses-a-block-and-a-page-boundary": (
        [(s, 300 + i) for i in range(3) for s in (0, 2, 3)][:8]
        + _run(1, 124, 8) + _run(2, 9, 16), 24),
    "padding-rows-after-the-last-run": (
        _run(3, 250, 11) + [(4, 0)] * 13, 24),
    # a speculative chain is a run of one slot at pos..pos+k: three short
    # ones in one tile walk a slot's blocks each, a chain of 8 has its own
    "speculative-chains": (
        _run(0, 126, 3) + _run(1, 9, 3) + _run(2, 260, 2) + _run(3, 121, 8),
        16),
    # rows that are no multiple of a tile: the call pads itself, and the
    # two padding rows' run takes the call's padding in
    "rows-short-of-a-whole-tile": (
        _run(1, 120, 10) + [(0, 255), (4, 0), (4, 0)], 12),
}
_POOLS = {
    # name: (H, H_kv, D, dtype, tolerance[, window]).  A row of more than
    # four heads is read a head at a time (`split_heads`): rows of 32 bits
    # by a strided load, rows of 16 bits through the uint32 view, two heads
    # a load; four stored heads or fewer are one dense operand
    "unpacked": (4, 2, 128, "float32", 2e-5),
    "packed-8x128-groups-of-2": (32, 16, 64, "float32", 2e-5),
    "packed-4x128": (16, 8, 64, "float32", 2e-5),
    "lone-head-two-tokens-a-row": (4, 1, 128, "bfloat16", 2e-2),
    "one-query-a-head-of-8": (8, 8, 128, "float32", 2e-5),
    "2-heads-bf16-groups-of-12": (24, 2, 128, "bfloat16", 2e-2),
    "8-heads-bf16-groups-of-6": (48, 8, 128, "bfloat16", 2e-2),
    "packed-4x128-bf16-groups-of-4": (32, 8, 64, "bfloat16", 2e-2),
    "30-heads-stored-as-32-groups-of-1": (30, 30, 128, "bfloat16", 2e-2),
    # an odd count of 16-bit heads has no uint32 view: one dense operand,
    # the other heads' columns masked
    "3-heads-bf16-masked": (6, 3, 128, "bfloat16", 2e-2),
    "window-40-over-8-heads-bf16": (16, 8, 128, "bfloat16", 2e-2, 40),
}
_CASES = [(rows, "unpacked") for rows in _ROW_CASES] + \
    [("run-crosses-a-block-and-a-page-boundary", pool)
     for pool in list(_POOLS)[1:] + ["latent"]] + \
    [("run-starts-and-ends-off-a-tile", pool)
     for pool in ("latent", "8-heads-bf16-groups-of-6",
                  "30-heads-stored-as-32-groups-of-1",
                  "window-40-over-8-heads-bf16")] + \
    [("padding-rows-after-the-last-run",
      "30-heads-stored-as-32-groups-of-1"),
     ("rows-short-of-a-whole-tile", "2-heads-bf16-groups-of-12"),
     ("rows-short-of-a-whole-tile", "packed-8x128-groups-of-2"),
     ("run-of-one-row", "packed-8x128-groups-of-2"),
     ("padding-rows-after-the-last-run", "packed-8x128-groups-of-2"),
     ("speculative-chains", "8-heads-bf16-groups-of-6"),
     ("two-runs-of-neighbours-in-one-tile", "packed-4x128-bf16-groups-of-4")]


def _shared_rows(pp, rows, tile, bt):
    """(rows on a shared walk by `walked_blocks`, the engine's count; the
    tile's rows) — held to a plain count of the runs, tile by tile."""
    lengths = np.asarray([p + 1 for _, p in rows])
    slots = np.asarray([s for s, _ in rows])
    bq = pp.tile_rows(len(rows), *tile)
    blocks, shared = pp.walked_blocks(lengths, slots, bq, bt)
    alone, none = pp.walked_blocks(lengths, slots, 1, bt)
    assert none == 0 and (blocks < alone) == (shared > 0)
    want = want_blocks = 0
    padded = list(slots) + [slots[-1]] * (-len(slots) % bq)
    for t in range(0, len(padded), bq):
        i = t
        while i < t + bq:
            j = i
            while j < t + bq and padded[j] == padded[i]:
                j += 1
            want += (min(j, len(slots)) - i) * (j - i > 1)
            want_blocks += max(1, -(-max(lengths[i:j]) // bt))
            i = j
    assert (blocks, shared) == (want_blocks, want)
    return shared, bq


@pytest.mark.parametrize("rows,pool", _CASES,
                         ids=[f"{r}-{p}" for r, p in _CASES])
def test_tiles_match_the_gather(rows, pool):
    """The whole step (scatter, then the kernel's read) against
    use_kernel=False, and the tiles the case was written for: the rows on a
    shared walk are what `walked_blocks` — the engine's count — says."""
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_paged as pp
    from paddle_tpu.ops.attention import ragged_paged_attention_step
    from paddle_tpu.ops.mla import paged_latent_step

    rows, want_shared = _ROW_CASES[rows]
    rng = np.random.default_rng(3)
    ps, maxp = 16, 24
    P = 1 + S * maxp
    table = jnp.asarray(_table(rng, P, maxp, [330] * S, ps))
    row_slot = jnp.asarray([s for s, _ in rows], jnp.int32)
    row_pos = jnp.asarray([p for _, p in rows], jnp.int32)
    T = len(rows)
    if pool == "latent":
        H, W, rank, tol = 8, 160, 128, 2e-5
        pages = jnp.asarray(rng.normal(size=(P, ps, W)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(T, H, W)), jnp.float32)
        new = jnp.asarray(rng.normal(size=(T, W)), jnp.float32)
        got, want = (paged_latent_step(q, new, pages, table, row_slot,
                                       row_pos, 0.1, rank, use_kernel=use)[0]
                     for use in (True, False))
        bt = pp.block_tokens(ps, 1, W, 4, maxp)
        shared, bq = _shared_rows(pp, rows, (H, bt, W, "float32"), bt)
    else:
        H, Hkv, D, dtype, tol, *window = _POOLS[pool]
        dtype = jnp.dtype(dtype)
        page = pp.kv_page_shape(ps, Hkv, D, dtype.itemsize)
        kp, vp = (jnp.asarray(rng.normal(size=(P,) + page), dtype)
                  for _ in range(2))
        q = jnp.asarray(rng.normal(size=(T, H, D)), dtype)
        kn, vn = (jnp.asarray(rng.normal(size=(T, Hkv, D)), dtype)
                  for _ in range(2))
        got, want = (ragged_paged_attention_step(
            q, kn, vn, kp, vp, table, row_slot, row_pos, use_kernel=use,
            **(dict(window=window[0]) if window else {}))[0]
            for use in (True, False))
        row = pp.kv_row_shape(Hkv, D)
        bt = pp.block_tokens(ps, *row, dtype.itemsize, maxp)
        # a windowed call's rows each read a table row of their own
        shared, bq = (want_shared, 8) if window else _shared_rows(
            pp, rows, pp.query_tile(H, Hkv, row, bt, dtype), bt)
    assert bq > 8 or shared == want_shared
    real = np.asarray(row_slot) < S
    np.testing.assert_allclose(np.asarray(got, np.float32)[real],
                               np.asarray(want, np.float32)[real],
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("ring", [False, True], ids=["table", "ring"])
def test_a_windowed_call_runs_row_by_row_as_before(ring, monkeypatch):
    """A window layer's call hands each row a table row of its own, so no
    tile is shared: in tiles of 8 it gives bit for bit what it gives one
    row a grid step (`_TILE_ROWS` 1: the grid before the tiles), and both
    are the gather's."""
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_paged as pp
    from paddle_tpu.ops.attention import ragged_paged_attention_step

    rng = np.random.default_rng(4)
    ps, window = 16, 40
    maxp = 5 if ring else 24
    P = 1 + S * maxp
    table = np.zeros((S + 1, maxp), np.int32)
    table[:S] = (rng.permutation(S * maxp) + 1).reshape(S, maxp)
    rows = [(0, 200), (2, 33)] + _run(1, 100, 12) + [(3, 7), (4, 0)]
    row_slot = jnp.asarray([s for s, _ in rows], jnp.int32)
    row_pos = jnp.asarray([p for _, p in rows], jnp.int32)
    T = len(rows)
    kp, vp = (jnp.asarray(rng.normal(size=(P, ps, 2, 128)), jnp.float32)
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(T, 4, 128)), jnp.float32)
    kn, vn = (jnp.asarray(rng.normal(size=(T, 2, 128)), jnp.float32)
              for _ in range(2))

    def step(use):
        return np.asarray(ragged_paged_attention_step(
            q, kn, vn, kp, vp, jnp.asarray(table), row_slot, row_pos,
            window=window, ring=ring, use_kernel=use)[0])

    tiles, want = step(True), step(False)
    monkeypatch.setattr(pp, "_TILE_ROWS", 1)
    monkeypatch.setattr(pp, "_DOT_ROWS", 0)
    np.testing.assert_array_equal(tiles, step(True))
    real = np.asarray(row_slot) < S
    np.testing.assert_allclose(tiles[real], want[real], rtol=2e-5, atol=2e-5)


def test_a_tile_comes_from_the_shapes():
    """`tile_rows` at the serve cells' layers (bf16): where a run's walk
    reads a block a stored head at a time, as many rows as fill the 128
    rows of a dot within the budget — 32 at Olmo-Hybrid's group size one,
    16 at 6 or 8 query heads a KV head —; 8 where a block is one dense
    operand (four stored rows or fewer), fewer under GigaChat's 64 heads of
    640 lanes;
    and never more than the call has rows."""
    from paddle_tpu.ops.pallas_paged import (block_tokens, kv_row_shape,
                                             query_tile, tile_rows)

    def at(rows, heads, kv_heads, head_dim, maxp=512):
        row = kv_row_shape(kv_heads, head_dim)
        tile = query_tile(heads, kv_heads, row,
                          block_tokens(16, *row, 2, maxp), "bfloat16")
        return tile, tile_rows(rows, *tile)

    assert at(320, 48, 8, 128) == ((48, 128, 128, "bfloat16", 8), 16)  # Laguna
    assert at(320, 64, 8, 128) == ((64, 128, 128, "bfloat16", 8), 16)  # Solar
    # four stored rows or fewer: one dense operand, as one (`split_heads`)
    assert at(128, 24, 2, 128) == ((24, 512, 128, "bfloat16", 1), 8)  # sc2-3b
    assert at(512, 32, 2, 128) == ((32, 512, 128, "bfloat16", 1), 8)  # Nemotron
    assert at(512, 32, 8, 64) == ((32, 512, 128, "bfloat16", 1), 8)  # LFM2
    # Olmo-Hybrid: 30 heads on 30 stored as 32, a block of 128 tokens
    assert at(280, 30, 30, 128, 576) == ((32, 128, 128, "bfloat16", 32), 32)
    # one stored head: one dense operand (Jamba; the latent rows)
    assert at(512, 20, 1, 128) == ((20, 512, 128, "bfloat16", 1), 8)
    assert tile_rows(128, 64, 128, 640, "bfloat16") == 4       # GigaChat
    assert tile_rows(320, 32, 128, 640, "bfloat16") == 8       # Kimi
    assert [tile_rows(r, 4, 256, 128, "float32")
            for r in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 8]
    assert [tile_rows(r, 4, 128, 128, "float32", 2)
            for r in (3, 9, 40, 400)] == [4, 16, 64, 64]


@pytest.mark.parametrize("layer", [
    # name: rows, query heads, KV heads, head size, pages a table row
    ("olmo-hybrid", 280, 30, 30, 128, 576),
    ("laguna-full", 320, 48, 8, 128, 512),
    ("lfm2-packed", 512, 32, 8, 64, 256),
    ("decode-saturated", 128, 24, 2, 128, 256),
    ("jamba-lone-head", 512, 20, 1, 128, 256),
    # a decode step's calls: the rows ARE the slots
    ("olmo-hybrid-decode", 24, 30, 30, 128, 576),
    ("odd-slots-decode", 12, 24, 2, 128, 256)], ids=lambda l: l[0])
def test_the_engines_count_is_the_kernels_fetch(layer, monkeypatch):
    """`serving_kv_tokens_fetched_total` is `walked_blocks` x
    `block_tokens` over the tile `tile_rows` gives for `query_tile` of the
    pool's shapes (serving/engine.py): at the cells' layers those are the
    tile and the block the kernel's program is really built with.  A
    decode step's call, whose rows each walk alone, is built in the same
    tiles — 32 for Olmo-Hybrid's 24 slots — and the dead rows that fill the
    last one walk a block each: `walked_blocks` counts them."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_paged as pp

    name, R, H, Hkv, D, maxp = layer
    decode = name.endswith("-decode")
    built = []

    def program(name, kernel_args, bq, tiles, q_row, q_groups, pools,
                buf_shape, width, dtype, interpret):
        built.append((bq, tiles, buf_shape))
        assert not (decode and q_groups)
        return lambda *a: [
            jnp.zeros(shape + (width,), dtype) for shape in (
                [(tiles * bq, q_row[0])] + [(tiles,) + (q_groups or ())] *
                bool(q_groups))]

    monkeypatch.setattr(pp, "_program", program)
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = jax.ShapeDtypeStruct((1 + 4 * maxp,) + pp.kv_page_shape(
        16, Hkv, D, 2), bf16)
    out = jax.eval_shape(
        lambda q, kp, vp, table, lengths, slot: pp.paged_attention(
            q, kp, vp, table, lengths, row_slot=None if decode else slot,
            kv_heads=Hkv),
        jax.ShapeDtypeStruct((R, H, D), bf16), pool, pool,
        jax.ShapeDtypeStruct((5, maxp), i32),
        jax.ShapeDtypeStruct((R,), i32), jax.ShapeDtypeStruct((R,), i32))
    assert out.shape == (R, H, D)
    (tile, tiles, buf_shape), = built
    # the engine's side: the pool's stored row, the model's heads
    row = pp.kv_row_shape(Hkv, D)
    block = pp.block_tokens(16, *row, 2, maxp)
    bq = pp.tile_rows(R, *pp.query_tile(H, Hkv, row, block, bf16))
    assert buf_shape[0] == block * row[0]
    assert (tile, tiles) == (bq, -(-R // bq))
    if decode:      # rows of 300 tokens: 3 blocks each, one a dead row
        dead = tile * tiles - R
        assert dead == {24: 8, 12: 4}[R]
        assert pp.walked_blocks(np.full(R, 300), None, bq, block) == (
            R * -(-300 // block) + dead, 0)


def test_layers_of_one_shape_trace_the_kernel_once(monkeypatch):
    """A step's layers call with the same shapes, and the family's
    pallas_call is built once a set of shapes (`_program`): jit traces the
    kernel's two walks once for all of them, and again only for other
    shapes (a decode step's rows beside a mixed step's)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_paged as pp

    traced = []
    kernel = pp._kernel
    monkeypatch.setattr(pp, "_kernel",
                        lambda *a: (traced.append(1), kernel(*a))[1])
    pp._program.cache_clear()
    rng = np.random.default_rng(5)
    ps, maxp, P = 16, 24, 1 + S * 24
    table = jnp.asarray(_table(rng, P, maxp, [330] * S, ps))
    kp, vp = (jnp.asarray(rng.normal(size=(P, ps, 2, 128)), jnp.float32)
              for _ in range(2))

    def layers(q, lengths, row_slot):
        out = q
        for _ in range(3):
            out = pp.paged_attention(out, kp, vp, table, lengths,
                                     row_slot=row_slot)
        return out

    for rows in (16, 16, 8):
        q = jnp.asarray(rng.normal(size=(rows, 4, 128)), jnp.float32)
        jax.jit(layers).lower(q, jnp.full((rows,), 40, jnp.int32),
                              jnp.arange(rows, dtype=jnp.int32) // 8)
    assert len(traced) == 2, traced     # 16 rows once, 8 rows once
    pp._program.cache_clear()
