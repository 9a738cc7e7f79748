"""Host-RAM KV spill tier (ISSUE 17): two-level eviction for the prefix
cache — cold refcount-zero cached pages spill to host RAM under page
pressure instead of being destroyed, and an admission that prefix-hits a
spilled run faults the pages back with one batched scatter.

The exactness contract is the prefix cache's, extended across the tier
boundary: a request whose prefix restores from host RAM produces tokens
BIT-IDENTICAL to a cold `lm_generate(use_cache=True)` run — greedy and
seeded sampling, through COW divergence mid-restored-page, preemption
replay, budget-pressure host evictions, and checkpoint migration — while
`_decode_step._cache_size() == 1` stays asserted (restores ride their own
bucketed admission-boundary jit; the decode/mixed signatures never see
the tier).

Most tests here recycle ONE module-scoped engine via
`reset_prefix_cache()` + `set_spill_budget()` — both idle-engine
allocator-exact knobs, and reset reproducibility is itself pinned by
test_reset_prefix_cache_drains_host_tier_and_reproduces — so the jit
compiles are paid once, not per test.  Counters are lifetime (a reset's
drains land in `_host_drained`, keeping the conservation ledger closed),
so recycled tests assert count DELTAS, never absolutes.

The fast gate (`-m "not slow"`) keeps the tentpole restore oracle, the
zero-budget back-compat guard, the budget-flip seam, reset
reproducibility, and the allocator unit; the heavier interaction
oracles (sampling, COW, preemption, LRU pressure, drain knobs,
checkpoint migration) carry `slow` like the repo's other heavy e2e
oracles and run in the full suite."""

import numpy as np
import pytest

import jax

from paddle_tpu.config.parser import parse_config
from paddle_tpu.graph.lm_decode import lm_generate
from paddle_tpu.serving import PagedKVCache, Request, ServingEngine
from paddle_tpu.trainer.trainer import Trainer

BIG = 1 << 20                       # "never the binding constraint" budget


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


def _assert_exact(tr, reqs, results):
    for r in reqs:
        np.testing.assert_array_equal(
            _oracle(tr, r), results[r.req_id],
            err_msg=f"request {r.req_id!r} diverged from the cold "
                    f"lm_generate oracle")


def _tight_engine(tr, budget, **kw):
    """1 slot over a 5-usable-page pool: one retired 12-token sequence
    donates 3 pages, so the SECOND distinct sequence already forces
    eviction pressure — the spill trigger every test here builds on."""
    kw.setdefault("num_slots", 1)
    return ServingEngine(tr.executor, tr.params, page_size=4,
                         max_context=16, num_pages=6,
                         spill_bytes_budget=budget, **kw)


@pytest.fixture(scope="module")
def tight(tr):
    return _tight_engine(tr, BIG)


def _recycle(eng, budget=BIG):
    """Cold-cache the shared engine: both tiers drained, free list
    canonical, budget reset — only the jit caches survive."""
    eng.set_prefix_cache(True)
    eng.reset_prefix_cache()
    eng.set_spill_budget(budget)
    return eng


def _pressure_abb(tr, eng, rng, max_new=5):
    """a, then b, then b2 — three distinct 12-token sequences through the
    tight pool.  Each retired run donates its 2 fully-committed pages, so
    b2's admission overflows the 5-page pool and (with a big budget)
    spills a's chain to host instead of destroying it.  Returns the
    requests and the results dict (results hold prompt + generated
    tokens, so callers can build follow-on prompts that reach a's
    SPILLED pages)."""
    reqs = [Request(n, rng.integers(2, 23, 7).astype(np.int32),
                    max_new=max_new) for n in ("a", "b", "b2")]
    results = {}
    for r in reqs:
        results.update(eng.run([r]))
    return reqs, results


# ---------------------------------------------------------------------------
# the token-exactness oracle, extended across the spill/restore boundary
# ---------------------------------------------------------------------------

def test_spill_then_restore_hit_stays_oracle_exact(tr, tight):
    """The tentpole path end to end: pressure spills a retired run to
    host RAM (device pages freed, tokens retained), a later admission
    prefix-hits the spilled run, restores the pages with the batched
    scatter, and its tokens bit-match the cold oracle.  The tokens-saved
    counter reconciles against restored pages and the decode step stays
    ONE signature."""
    rng = np.random.default_rng(0)
    eng = _recycle(tight)
    spilled0, hits0 = eng.kv.n_spilled, eng.n_restore_hits
    restored0, saved0 = eng.kv.n_restored, eng.restore_tokens_saved
    reqs, results = _pressure_abb(tr, eng, rng)
    assert eng.kv.n_spilled - spilled0 >= 2, \
        "pressure never reached the host tier"
    assert eng.kv.host_page_count >= 2
    assert eng.kv.free_page_count + eng.kv.cached_page_count == \
        eng.kv.num_pages - 1, \
        "spilled pages must FREE their device page (that is the point)"
    seq_a = np.asarray(results["a"]).astype(np.int32)
    # c extends a's sequence past its first two (now host-resident)
    # pages: the hit must fault them back, not re-prefill
    c = Request("c", seq_a[:9].copy(), max_new=4)
    results.update(eng.run([c]))
    assert eng.n_restore_hits - hits0 >= 1, \
        "hit on a spilled run never restored"
    restored = eng.kv.n_restored - restored0
    assert restored >= 2
    assert 0 < eng.restore_tokens_saved - saved0 <= \
        restored * eng.kv.page_size, \
        "restored-token accounting out of band"
    _assert_exact(tr, reqs + [c], results)
    assert eng._decode_step._cache_size() == 1
    # restores bucket by power-of-two page count: a handful of jits,
    # never one per batch size
    assert 1 <= len(eng.kv._restore_fns) <= 3
    eng.kv.check_reclaimed()


@pytest.mark.slow
def test_sampled_restore_hit_stays_oracle_exact(tr, tight):
    """Seeded sampling through a restored prefix: the spilled pages'
    K/V round-trips host RAM bit-exactly, so the sampled continuation
    (its own key schedule, temperature/top-p knobs) matches the cold
    oracle the same way greedy does."""
    rng = np.random.default_rng(1)
    eng = _recycle(tight)
    spilled0, hits0 = eng.kv.n_spilled, eng.n_restore_hits
    a = Request("a", rng.integers(2, 23, 7).astype(np.int32), max_new=5,
                temperature=0.8, top_k=5, rng=jax.random.PRNGKey(11))
    results = eng.run([a])
    fillers = [Request(n, rng.integers(2, 23, 7).astype(np.int32),
                       max_new=5) for n in ("b", "b2")]
    for f in fillers:                       # pressure: spill a's chain
        results.update(eng.run([f]))
    assert eng.kv.n_spilled - spilled0 >= 1
    seq_a = np.asarray(results["a"]).astype(np.int32)
    c = Request("c", seq_a[:9].copy(), max_new=4,
                temperature=0.7, top_p=0.9, rng=jax.random.PRNGKey(12))
    results.update(eng.run([c]))
    assert eng.n_restore_hits - hits0 >= 1
    _assert_exact(tr, [a, c] + fillers, results)
    eng.kv.check_reclaimed()


@pytest.mark.slow
def test_cow_divergence_mid_restored_page(tr, tight):
    """d's prompt follows a's sequence INTO a restored page and then
    diverges: admission restores the spilled run, COWs the boundary
    page, and d's suffix overwrites only its own copy — d is exact, and
    a later request replaying a's exact sequence is exact too (the
    restored original was never written)."""
    rng = np.random.default_rng(2)
    eng = _recycle(tight)
    hits0 = eng.n_restore_hits
    reqs, results = _pressure_abb(tr, eng, rng)
    seq_a = np.asarray(results["a"]).astype(np.int32)
    cow0 = eng.kv.n_cow
    # matches 6 of a's tokens (1 full spilled page + 2 into the second),
    # then diverges mid-page: the boundary page restores AND COWs
    d_prompt = np.concatenate([seq_a[:6],
                               (seq_a[6:8] + 1) % 21 + 2,
                               rng.integers(2, 23, 2)]).astype(np.int32)
    d = Request("d", d_prompt, max_new=3)
    results.update(eng.run([d]))
    assert eng.n_restore_hits - hits0 >= 1
    assert eng.kv.n_cow > cow0, \
        "mid-restored-page divergence never copied-on-write"
    e = Request("e", seq_a[:9].copy(), max_new=3)
    results.update(eng.run([e]))
    _assert_exact(tr, reqs + [d, e], results)
    assert eng._decode_step._cache_size() == 1
    eng.kv.check_reclaimed()


@pytest.mark.slow
def test_preempt_replay_with_spill_tier_on_stays_exact(tr):
    """Overcommitted slots over the spilling pool: preemptions, device
    evictions, spills and restores all interleave, and every request of
    both waves still matches its cold oracle with refcounts back to
    zero — the tier adds no scheduling state the replay can trip on."""
    rng = np.random.default_rng(3)
    eng = _tight_engine(tr, BIG, num_slots=2)
    reqs, results = _pressure_abb(tr, eng, rng)
    seq_a = np.asarray(results["a"]).astype(np.int32)
    seq_b = np.asarray(results["b"]).astype(np.int32)
    wave = [Request("r1", seq_a[:9].copy(), max_new=6),
            Request("r2", seq_b[:9].copy(), max_new=6),
            Request("r3", rng.integers(2, 23, 6).astype(np.int32),
                    max_new=6)]
    results.update(eng.run(wave))
    assert eng.n_preemptions > 0, "pool was never overcommitted"
    assert eng.kv.n_spilled > 0
    _assert_exact(tr, reqs + wave, results)
    assert (eng.kv._ref == 0).all()
    assert eng._decode_step._cache_size() == 1
    eng.kv.check_reclaimed()


# ---------------------------------------------------------------------------
# budget discipline: LRU inside the host tier, zero-budget == old behavior
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_host_tier_budget_evicts_lru_and_never_overflows(tr, tight):
    """A ONE-page budget under two pages of spill pressure: the tier
    drops its least-recently-used host leaf to admit the second spill
    (kv.check() asserts the byte bound), and a hit on the run whose page
    was dropped simply admits the missing part cold — still exact."""
    rng = np.random.default_rng(4)
    budget = tight.kv.page_nbytes
    eng = _recycle(tight, budget)
    spilled0, evicted0 = eng.kv.n_spilled, eng.kv.n_host_evicted
    reqs, results = _pressure_abb(tr, eng, rng)
    assert eng.kv.n_spilled - spilled0 >= 2
    assert eng.kv.n_host_evicted - evicted0 > 0, \
        "over-budget spills never displaced the host LRU"
    assert eng.kv.host_bytes <= budget
    seq_a = np.asarray(results["a"]).astype(np.int32)
    c = Request("c", seq_a[:9].copy(), max_new=4)
    results.update(eng.run([c]))
    _assert_exact(tr, reqs + [c], results)
    eng.kv.check_reclaimed()


def test_zero_budget_is_the_pre_spill_engine(tr, tight):
    """spill_bytes_budget=0 (the default): the same pressure workload
    destroys victims exactly as before the tier existed — nothing
    spills, no NEW restore jit compiles, eviction still relieves
    pressure, outputs stay exact."""
    rng = np.random.default_rng(0)
    eng = _recycle(tight, 0)
    spilled0, hits0 = eng.kv.n_spilled, eng.n_restore_hits
    ev0, fns0 = eng.prefix.n_evictions, len(eng.kv._restore_fns)
    reqs, results = _pressure_abb(tr, eng, rng)
    assert eng.prefix.n_evictions > ev0, "no pressure — workload too loose"
    assert eng.kv.n_spilled == spilled0 and eng.kv.host_page_count == 0
    seq_a = np.asarray(results["a"]).astype(np.int32)
    c = Request("c", seq_a[:9].copy(), max_new=4)
    results.update(eng.run([c]))
    assert eng.n_restore_hits == hits0
    assert len(eng.kv._restore_fns) == fns0
    _assert_exact(tr, reqs + [c], results)
    eng.kv.check_reclaimed()


# ---------------------------------------------------------------------------
# cache-management seams: reset / disable / budget flips / stale generations
# ---------------------------------------------------------------------------

def test_reset_prefix_cache_drains_host_tier_and_reproduces(tr, tight):
    """reset_prefix_cache drains BOTH tiers (a host entry left behind
    would hold budget bytes no node can ever name again) and bumps the
    spill generation; re-running the workload afterwards reproduces the
    same tokens — a restart is bit-indistinguishable from a fresh
    engine, host tier included."""
    eng = _recycle(tight)

    def mk():
        r2 = np.random.default_rng(50)
        return [Request(n, r2.integers(2, 23, 7).astype(np.int32),
                        max_new=5) for n in ("a", "b", "b2")]

    first = {}
    for r in mk():
        first.update(eng.run([r]))
    assert eng.kv.host_page_count > 0
    gen0 = eng.kv._host_gen
    eng.reset_prefix_cache()
    assert eng.kv.host_page_count == 0 and eng.kv.host_bytes == 0
    assert eng.kv._host_gen > gen0
    assert eng.kv.free_page_count == eng.kv.num_pages - 1
    again = {}
    for r in mk():
        again.update(eng.run([r]))
    for rid in first:
        np.testing.assert_array_equal(first[rid], again[rid])
    eng.kv.check_reclaimed()


@pytest.mark.slow
def test_set_prefix_cache_off_drains_host_tier(tr, tight):
    """Disabling the prefix cache (the A/B knob) walks the index down —
    spilled nodes drain the HOST tier, device nodes drop their cached
    retention — and re-enabling serves cold-but-exact."""
    rng = np.random.default_rng(6)
    eng = _recycle(tight)
    reqs, results = _pressure_abb(tr, eng, rng)
    assert eng.kv.host_page_count > 0
    eng.set_prefix_cache(False)
    assert eng.kv.host_page_count == 0 and eng.kv.host_bytes == 0
    assert eng.kv.cached_page_count == 0
    eng.set_prefix_cache(True)
    hits0 = eng.n_restore_hits
    c = Request("c", np.asarray(results["a"])[:9].astype(np.int32),
                max_new=4)
    results.update(eng.run([c]))
    assert eng.n_restore_hits == hits0   # nothing survived the drain
    _assert_exact(tr, reqs + [c], results)
    eng.kv.check_reclaimed()


def test_set_spill_budget_shrink_drops_lru_grow_reenables(tr, tight):
    """The idle-engine budget knob: shrinking below residency drops LRU
    host leaves until the tier fits, zero drains it entirely, and
    growing it back re-enables spilling — without ever touching device
    state (the free list is unchanged across the flips)."""
    rng = np.random.default_rng(7)
    eng = _recycle(tight)
    _pressure_abb(tr, eng, rng)
    assert eng.kv.host_page_count >= 2
    free0 = list(eng.kv._free)
    one_page = eng.kv.page_nbytes
    eng.set_spill_budget(one_page)
    assert eng.kv.host_bytes <= one_page
    assert eng.kv.host_page_count == 1
    eng.set_spill_budget(0)
    assert eng.kv.host_page_count == 0 and eng.kv.host_bytes == 0
    assert eng.kv._free == free0, "budget flips must not touch the pool"
    eng.set_spill_budget(BIG)
    spilled0 = eng.kv.n_spilled
    r = Request("again", rng.integers(2, 23, 7).astype(np.int32),
                max_new=5)
    res = eng.run([r])
    assert eng.kv.n_spilled > spilled0, "re-enabled budget never spilled"
    _assert_exact(tr, [r], res)
    eng.kv.check_reclaimed()


@pytest.mark.slow
def test_stale_generation_never_restores(tr, tight):
    """The zombie guard: host entries stamped by a dead generation (the
    kv.reset-without-tree-clear seam) must never restore — the hit drops
    the stale subtree and admits COLD, tokens still exact, and the
    conservation ledger accounts the drops as drains."""
    rng = np.random.default_rng(8)
    eng = _recycle(tight)
    reqs, results = _pressure_abb(tr, eng, rng)
    assert eng.kv.host_page_count == 2      # exactly a's spilled chain
    eng.kv._host_gen += 1                   # simulate the dead generation
    drained0 = eng.kv._host_drained
    hits0, restored0 = eng.n_restore_hits, eng.kv.n_restored
    seq_a = np.asarray(results["a"]).astype(np.int32)
    c = Request("c", seq_a[:9].copy(), max_new=3)
    results.update(eng.run([c]))
    assert eng.n_restore_hits == hits0 and eng.kv.n_restored == restored0, \
        "a dead-generation entry was resurrected"
    # both zombies drained on the failed hit; anything resident now is a
    # CURRENT-generation entry (c's cold admission re-pressured the pool)
    assert eng.kv._host_drained == drained0 + 2
    assert all(eng.kv.host_entry_live(h) for h in eng.kv._host)
    _assert_exact(tr, reqs + [c], results)
    eng.kv.check_reclaimed()


# ---------------------------------------------------------------------------
# checkpoint migration: the host tier serializes INTO the bundle
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_save_load_state_migrates_host_resident_pages(tr, tight):
    """A snapshot taken while pages sit in host RAM carries them in the
    bundle (the documented choice: a migrated replica keeps its whole
    effective cache); the restored engine holds the same host residency,
    and a hit on the migrated run restores from the migrated bytes —
    tokens identical to the donor engine's."""
    rng = np.random.default_rng(9)
    eng_a = _recycle(tight)
    hits0 = eng_a.n_restore_hits
    reqs, results_a = _pressure_abb(tr, eng_a, rng)
    h0 = eng_a.kv.host_page_count
    assert h0 > 0
    import os
    import tempfile
    fd, path = tempfile.mkstemp(suffix=".pkl")
    os.close(fd)
    try:
        eng_a.save_state(path)
        seq_a = np.asarray(results_a["a"]).astype(np.int32)
        c = Request("c", seq_a[:9].copy(), max_new=4)
        results_a.update(eng_a.run([c]))
        assert eng_a.n_restore_hits - hits0 >= 1

        eng_b = _tight_engine(tr, BIG)
        eng_b.load_state(path)
        assert eng_b.kv.host_page_count == h0
        eng_b.kv.check()
        restored0 = eng_b.kv.n_restored
        c2 = Request("c", seq_a[:9].copy(), max_new=4)
        results_b = eng_b.run([c2])
        assert eng_b.kv.n_restored > restored0, \
            "the migrated host pages never served a restore"
        np.testing.assert_array_equal(
            results_a["c"], results_b["c"],
            err_msg="restore-from-migrated-host-tier diverged from donor")
        eng_b.kv.check_reclaimed()
    finally:
        os.unlink(path)


# ---------------------------------------------------------------------------
# allocator unit: spill/restore round-trip, budget bound, rollback, ledger
# ---------------------------------------------------------------------------

def test_allocator_spill_restore_roundtrip_unit(tr):
    """PagedKVCache-level contract: spill_page frees the device page and
    banks exact bytes, restore_pages moves the K/V back bit-for-bit
    (marker round-trip), take/untake is an exact rollback, the budget
    bound rejects over-spill, and reset() kills the generation."""
    kv = PagedKVCache(tr.executor, num_slots=2, page_size=4,
                      pages_per_slot=3, num_pages=8,
                      spill_bytes_budget=BIG)
    assert kv.try_grow(0, 12)                       # 3 private pages
    pages = [int(kv.table[0, j]) for j in range(3)]
    name = next(iter(kv.pools))
    kv.pools[name]["k"] = kv.pools[name]["k"].at[pages[0], 1, 0, 2].set(7.5)
    for p in pages:
        kv.cache_page(p)
    kv.release(0)                                   # refcounts to zero
    free0 = kv.free_page_count
    hid = kv.spill_page(pages[0])
    assert hid is not None
    assert kv.host_page_count == 1
    assert kv.host_bytes == kv.page_nbytes
    assert kv.free_page_count == free0 + 1, "spill must free the device page"
    assert not kv._cached[pages[0]]
    # the budget bound: no room -> None, caller makes room first
    kv.spill_bytes_budget = kv.page_nbytes
    assert kv.spill_page(pages[1]) is None
    kv.spill_bytes_budget = BIG
    # take/untake is an exact rollback
    free_list0 = list(kv._free)
    taken = kv.take_pages(2)
    kv.untake_pages(taken)
    assert kv._free == free_list0
    # restore: marker survives the host round-trip
    (dst,) = kv.take_pages(1)
    kv.restore_pages([hid], [dst])
    kv.adopt_restored([dst])
    assert float(kv.pools[name]["k"][dst, 1, 0, 2]) == 7.5, \
        "restored page lost its K/V contents"
    assert kv.host_page_count == 0 and kv.n_restored == 1
    assert not kv.host_entry_live(hid)
    kv.drop_host_page(hid)                          # idempotent on gone
    kv.check()
    # conservation ledger across a reset: wholesale drain, gen bump
    hid2 = kv.spill_page(pages[1])
    assert hid2 is not None and kv.host_entry_live(hid2)
    gen0 = kv._host_gen
    kv.reset()
    assert kv._host_gen == gen0 + 1
    assert kv.host_page_count == 0 and kv.host_bytes == 0
    assert not kv.host_entry_live(hid2)
    assert kv.host_page_count == kv.n_spilled - kv.n_restored - \
        kv.n_host_evicted - kv._host_drained
    kv.check()


def test_the_spill_tier_refuses_window_rings_by_name():
    """A spilled page could not bring the slot's ring back: the budget is
    refused where it is set, at construction and later."""
    from paddle_tpu.serving.paged_kv import RING_REFUSALS
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=23,dim=16,layers=2,heads=2,batch_size=4,"
                       "window=6")
    wtr = Trainer(cfg, seed=7)
    kw = dict(num_slots=2, page_size=4, max_context=32)
    for make in (lambda: ServingEngine(wtr.executor, wtr.params,
                                       spill_bytes_budget=BIG, **kw),
                 lambda: ServingEngine(wtr.executor, wtr.params,
                                       **kw).set_spill_budget(BIG)):
        with pytest.raises(ValueError) as e:
            make()
        assert "the KV spill tier" in str(e.value)
        assert RING_REFUSALS["spill"] in str(e.value)
    # a cache built by hand without rings spills as ever
    PagedKVCache(wtr.executor, 2, 4, 8, spill_bytes_budget=BIG).check()
