"""Cross-replica KV page transfer (ISSUE 19): the allocator seam under
the disaggregated prefill/decode plane — `export_pages` serializes live
committed pages to host bytes (the spill tier's per-layer layout),
`import_pages` scatters them into freshly-taken pages with one bucketed
dispatch, and `ServingEngine.import_prefix` mounts the run through the
prefix tree so the next admission is a prefix hit.

The contracts pinned here: marker K/V survives the wire round-trip
bit-exactly, refcounts balance (`check()`/`check_reclaimed()` green after
every path), a malformed blob or a dry pool rolls the allocator back
EXACTLY (free-list order included), and a re-import of an already-mounted
run frees the duplicate pages instead of leaking them.  The end-to-end
cross-REPLICA oracles (router + kv_push wire plane) live in
tests/test_fleet.py; this file is the in-process allocator/engine half.
"""

import numpy as np
import pytest

from paddle_tpu.config.parser import parse_config
from paddle_tpu.graph.lm_decode import lm_generate
from paddle_tpu.serving import PagedKVCache, Request, ServingEngine
from paddle_tpu.trainer.trainer import Trainer

BIG = 1 << 20


@pytest.fixture(scope="module")
def tr():
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=23,dim=16,layers=2,heads=2,batch_size=4")
    return Trainer(cfg, seed=7)


def _oracle(tr, req: Request):
    toks, lens = lm_generate(
        tr.executor, tr.params, req.prompt_ids[None, :],
        max_new=req.max_new, temperature=req.temperature, top_k=req.top_k,
        top_p=req.top_p, eos_id=req.eos_id, rng=req.rng, use_cache=True)
    return np.asarray(toks)[0, :int(np.asarray(lens)[0])]


def _kv(tr, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("pages_per_slot", 3)
    kw.setdefault("num_pages", 8)
    return PagedKVCache(tr.executor, **kw)


def _committed_pages(kv, n_tokens=12):
    """Grow slot 0, mark the pages prefix-cached, release the slot —
    refcount-zero cached pages, the exportable state donation leaves."""
    assert kv.try_grow(0, n_tokens)
    pages = [int(kv.table[0, j]) for j in range(kv.pages_for(n_tokens))]
    for p in pages:
        kv.cache_page(p)
    kv.release(0)
    return pages


# ---------------------------------------------------------------------------
# allocator unit: export/import round trip + exact rollback
# ---------------------------------------------------------------------------

def test_export_import_roundtrip_unit(tr):
    """Marker K/V planted in a source pool survives export -> bytes ->
    import into a SEPARATE pool bit-exactly, refcounts balance on both
    sides, and both allocators end check()/check_reclaimed() green."""
    src, dst = _kv(tr), _kv(tr)
    pages = _committed_pages(src)
    name = next(iter(src.pools))
    src.pools[name]["k"] = \
        src.pools[name]["k"].at[pages[0], 1, 0, 2].set(7.5)
    src.pools[name]["v"] = \
        src.pools[name]["v"].at[pages[2], 3, 1, 1].set(-2.25)

    meta, payload = src.export_pages(pages)
    assert meta["n_pages"] == 3 and meta["page_size"] == src.page_size
    assert [l["name"] for l in meta["layers"]] == sorted(src.pools)
    assert len(payload) == 3 * src.page_nbytes
    assert src.n_exported == 3
    src.check()                                     # export mutates nothing

    taken = dst.take_pages(3)
    dst.import_pages(meta, payload, taken)
    dst.adopt_restored(taken)
    assert float(dst.pools[name]["k"][taken[0], 1, 0, 2]) == 7.5, \
        "imported page lost its K contents"
    assert float(dst.pools[name]["v"][taken[2], 3, 1, 1]) == -2.25, \
        "imported page lost its V contents"
    assert dst.n_imported == 3
    dst.check()
    assert dst.cached_page_count == 3

    # full reclaim on both sides: the transfer leaked nothing
    for p in pages:
        src.uncache_page(p)
    for p in taken:
        dst.uncache_page(p)
    src.check_reclaimed()
    dst.check_reclaimed()


def test_import_validates_before_touching_device(tr):
    """Every malformed-blob class raises ValueError BEFORE any device
    mutation, so untake_pages restores the allocator exactly — free-list
    ORDER included."""
    src, dst = _kv(tr), _kv(tr)
    pages = _committed_pages(src)
    meta, payload = src.export_pages(pages)

    free0 = list(dst._free)
    cases = [
        (dict(meta, n_pages=2), payload, "page-count mismatch"),
        (dict(meta, page_size=8), payload, "page-size mismatch"),
        (dict(meta, layers=meta["layers"][:1]), payload, "layer set"),
        (dict(meta, layers=[dict(meta["layers"][0], h_kv=99)]
              + [dict(l) for l in meta["layers"][1:]]),
         payload, "layer shape"),
        (meta, payload[:-1], "truncated payload"),
        (meta, payload + b"\x00", "oversized payload"),
    ]
    for bad_meta, bad_payload, why in cases:
        taken = dst.take_pages(3)
        with pytest.raises(ValueError):
            dst.import_pages(bad_meta, bad_payload, taken)
        dst.untake_pages(taken)
        assert dst._free == free0, \
            f"{why}: rollback did not restore the exact free list"
        assert dst.n_imported == 0
        dst.check()
    dst.check_reclaimed()


def test_export_rejects_free_pages(tr):
    """Exporting a page nobody holds would ship garbage — asserted."""
    kv = _kv(tr)
    with pytest.raises(AssertionError):
        kv.export_pages([int(kv._free[-1])])


# ---------------------------------------------------------------------------
# engine seam: import_prefix mounts, dedups, rolls back
# ---------------------------------------------------------------------------

def _engine(tr, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_context", 16)
    return ServingEngine(tr.executor, tr.params, **kw)


def test_import_prefix_mounts_and_next_admission_hits(tr):
    """The disagg tentpole in-process: engine A retires a request (pages
    donated), export_prefix serializes the committed prompt prefix,
    engine B import_prefix-mounts it, and B's admission of the SAME
    prompt is a prefix HIT whose tokens bit-match both the cold oracle
    and A's run."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(2, 23, 9).astype(np.int32)
    a, b = _engine(tr), _engine(tr)

    ra = Request("a", prompt.copy(), max_new=4)
    out_a = a.run([ra])["a"]
    exp = a.export_prefix(prompt)
    assert exp is not None, "retire donated nothing exportable"
    toks, meta, payload = exp
    full = (prompt.size // a.kv.page_size) * a.kv.page_size
    assert toks.size == full and meta["n_pages"] == full // a.kv.page_size
    np.testing.assert_array_equal(toks, prompt[:full])

    hits0, saved0 = b.n_prefix_hits, b.prefill_tokens_saved
    added = b.import_prefix(toks, meta, payload)
    assert added == meta["n_pages"]
    assert b.n_kv_mounts == 1 and b.kv_pages_mounted == meta["n_pages"]
    b.kv.check()
    rb = Request("b", prompt.copy(), max_new=4)
    out_b = b.run([rb])["b"]
    assert b.n_prefix_hits - hits0 == 1, \
        "mounted run did not turn the admission into a prefix hit"
    assert b.prefill_tokens_saved - saved0 >= full - b.kv.page_size
    np.testing.assert_array_equal(out_a, out_b)
    np.testing.assert_array_equal(_oracle(tr, rb), out_b)


def test_import_prefix_dedups_already_mounted_runs(tr):
    """Importing a blob whose runs are already DEVICE-resident frees the
    duplicate pages immediately (no donor slot ever releases them) —
    node count and retention stay flat, nothing leaks."""
    rng = np.random.default_rng(4)
    prompt = rng.integers(2, 23, 9).astype(np.int32)
    a, b = _engine(tr), _engine(tr)
    a.run([Request("a", prompt.copy(), max_new=4)])
    toks, meta, payload = a.export_prefix(prompt)

    assert b.import_prefix(toks, meta, payload) == meta["n_pages"]
    nodes0, cached0 = b.prefix.n_nodes, b.kv.cached_page_count
    free0 = b.kv.free_page_count
    assert b.import_prefix(toks, meta, payload) == 0, \
        "re-import must add no nodes"
    assert b.prefix.n_nodes == nodes0
    assert b.kv.cached_page_count == cached0
    assert b.kv.free_page_count == free0, \
        "duplicate imported pages leaked"
    b.kv.check()


def test_import_prefix_rolls_back_on_dry_pool(tr):
    """Page starvation mid-import raises with the allocator exactly as
    before — and a partial-failure check() stays green."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(2, 23, 13).astype(np.int32)
    a = _engine(tr, max_context=16)
    a.run([Request("a", prompt[:9].copy(), max_new=4)])
    toks, meta, payload = a.export_prefix(prompt[:9])

    # 3 usable pages total: a 2-page import cannot fit after 2 are pinned
    b = _engine(tr, num_slots=1, num_pages=4, max_context=12,
                prefix_cache=True)
    assert b.kv.try_grow(0, 12)                     # pin every page
    with pytest.raises(ValueError, match="cannot cover"):
        b.import_prefix(toks, meta, payload)
    b.kv.check()
    b.kv.release(0)
    b.kv.check_reclaimed()

    # malformed blob after a successful take: exact rollback through
    # import_prefix's untake path
    c = _engine(tr)
    free0 = list(c.kv._free)
    with pytest.raises(ValueError):
        c.import_prefix(toks, meta, payload[:-1])
    assert c.kv._free == free0
    c.kv.check_reclaimed()


def test_import_prefix_requires_prefix_cache(tr):
    rng = np.random.default_rng(6)
    prompt = rng.integers(2, 23, 9).astype(np.int32)
    a = _engine(tr)
    a.run([Request("a", prompt.copy(), max_new=4)])
    toks, meta, payload = a.export_prefix(prompt)
    b = _engine(tr, prefix_cache=False)
    with pytest.raises(ValueError, match="prefix cache"):
        b.import_prefix(toks, meta, payload)
    assert b.export_prefix(prompt) is None


def test_export_and_import_refuse_window_rings_by_name():
    """Pushed pages carry no ring: both ends of the transfer plane refuse a
    model whose window layers hold rings, by name."""
    from paddle_tpu.serving.paged_kv import RING_REFUSALS
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=23,dim=16,layers=2,heads=2,batch_size=4,"
                       "window=6")
    wtr = Trainer(cfg, seed=7)
    eng = ServingEngine(wtr.executor, wtr.params, num_slots=2, page_size=4,
                        max_context=32)
    with pytest.raises(ValueError) as e:
        eng.export_prefix([1, 2, 3, 4])
    assert RING_REFUSALS["export"] in str(e.value)
    with pytest.raises(ValueError) as e:
        eng.import_prefix([1, 2, 3, 4], {"n_pages": 1}, b"")
    assert RING_REFUSALS["import"] in str(e.value)
    eng.kv.check()
