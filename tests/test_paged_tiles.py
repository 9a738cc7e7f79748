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


# rows as (slot, position); slot 4 is the virtual trash row.  At these
# shapes a tile is 8 rows and a block 128 tokens (8 pages of 16; the lone
# head's holds the whole table): the second number of a case is the rows
# the kernel must put on a shared walk
_ROW_CASES = {
    # 3 decode rows, then a run of 20: rows 3-22, so tiles 0 and 2 are
    # ragged and tile 1 alone is the run's
    "run-starts-and-ends-off-a-tile": (
        [(0, 200), (2, 40), (3, 7)] + _run(1, 100, 20) + [(0, 201)], 8),
    "run-of-one-row": (
        [(0, 200), (2, 40)] + _run(1, 5, 1) + [(4, 0)] * 5, 0),
    "two-runs-of-neighbours-in-one-tile": (
        _run(1, 10, 4) + _run(2, 50, 4) + _run(0, 130, 8), 8),
    # rows 8-15 at positions 124-131: the block's and a page's last token
    # and the next one's first, inside one shared tile
    "run-crosses-a-block-and-a-page-boundary": (
        [(s, 300 + i) for i in range(3) for s in (0, 2, 3)][:8]
        + _run(1, 124, 8) + _run(2, 9, 16), 24),
    "padding-rows-after-the-last-run": (
        _run(3, 250, 11) + [(4, 0)] * 13, 16),
    # a speculative chain is a run of one slot at pos..pos+k: three short
    # ones share a tile with their neighbours, a chain of 8 has its own
    "speculative-chains": (
        _run(0, 126, 3) + _run(1, 9, 3) + _run(2, 260, 2) + _run(3, 121, 8),
        8),
    # rows that are no multiple of a tile: the call pads itself
    "rows-short-of-a-whole-tile": (
        _run(1, 120, 10) + [(0, 255), (4, 0), (4, 0)], 8),
}
_POOLS = {
    # name: (H, H_kv, D, dtype, tolerance)
    "unpacked": (4, 2, 128, "float32", 2e-5),
    "packed-4x128": (16, 8, 64, "float32", 2e-5),
    "lone-head-two-tokens-a-row": (4, 1, 128, "bfloat16", 2e-2),
}
_CASES = [(rows, "unpacked") for rows in _ROW_CASES] + \
    [("run-crosses-a-block-and-a-page-boundary", pool)
     for pool in ("packed-4x128", "lone-head-two-tokens-a-row", "latent")] + \
    [("run-starts-and-ends-off-a-tile", "latent")]


def _shared_rows(pp, rows, heads, cols, width, dtype, bt):
    import jax.numpy as jnp

    lengths = np.asarray([p + 1 for _, p in rows])
    slots = np.asarray([s for s, _ in rows])
    bq = pp.tile_rows(len(rows), heads, cols, width, jnp.dtype(dtype))
    assert bq == 8
    blocks, shared = pp.walked_blocks(lengths, slots, bq, bt)
    alone, none = pp.walked_blocks(lengths, slots, 1, bt)
    assert none == 0 and (blocks < alone) == (shared > 0)
    return shared


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
        shared = _shared_rows(pp, rows, H, bt, W, "float32", bt)
    else:
        H, Hkv, D, dtype, tol = _POOLS[pool]
        dtype = jnp.dtype(dtype)
        page = pp.kv_page_shape(ps, Hkv, D, dtype.itemsize)
        kp, vp = (jnp.asarray(rng.normal(size=(P,) + page), dtype)
                  for _ in range(2))
        q = jnp.asarray(rng.normal(size=(T, H, D)), dtype)
        kn, vn = (jnp.asarray(rng.normal(size=(T, Hkv, D)), dtype)
                  for _ in range(2))
        got, want = (ragged_paged_attention_step(
            q, kn, vn, kp, vp, table, row_slot, row_pos, use_kernel=use)[0]
            for use in (True, False))
        g, lanes = pp.kv_row_shape(Hkv, D)
        bt = pp.block_tokens(ps, g, lanes, dtype.itemsize, maxp)
        shared = _shared_rows(pp, rows, H, bt * g, lanes, dtype, bt)
    assert shared == want_shared
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
    np.testing.assert_array_equal(tiles, step(True))
    real = np.asarray(row_slot) < S
    np.testing.assert_allclose(tiles[real], want[real], rtol=2e-5, atol=2e-5)


def test_a_tile_comes_from_the_shapes():
    """`tile_rows` at the serve cells' layers (bf16): 8 rows wherever the
    scores, q, the output and the accumulator fit the budget — Laguna's
    full layer at exactly 8 — fewer under GigaChat's 64 heads of 640
    lanes, and never more than the call has rows."""
    from paddle_tpu.ops.pallas_paged import tile_rows

    assert tile_rows(320, 48, 1024, 128, "bfloat16") == 8      # Laguna full
    assert tile_rows(320, 64, 1024, 128, "bfloat16") == 4      # its window
    assert tile_rows(128, 24, 512, 128, "bfloat16") == 8       # sc2-3b
    assert tile_rows(512, 20, 512, 128, "bfloat16") == 8       # Jamba
    assert tile_rows(128, 64, 128, 640, "bfloat16") == 4       # GigaChat
    assert tile_rows(320, 32, 128, 640, "bfloat16") == 8       # Kimi
    assert [tile_rows(r, 4, 256, 128, "float32")
            for r in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 8]


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
