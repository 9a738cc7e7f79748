"""Prefix caching: refcounted copy-on-write page sharing in the paged KV
cache (serving/prefix_tree.py + the PR-7 allocator/engine changes).

The exactness contract is unchanged and non-negotiable: a prefix-HIT
request's tokens are bit-identical to a cold `lm_generate(use_cache=True)`
run — including under LRU eviction, COW divergence mid-page, and
preemption-with-replay — while `_decode_step._cache_size() == 1` stays
asserted (all sharing is host-side table/allocator state; the decode jit
signature never changes)."""

import numpy as np
import pytest

import jax

from paddle_tpu.config.parser import parse_config
from paddle_tpu.serving import (PagedKVCache, PrefixTree, Request,
                                ServingEngine)
from paddle_tpu.trainer.trainer import Trainer
from tests.conftest import lm_oracle


@pytest.fixture(scope="module")
def tr():
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=23,dim=16,layers=2,heads=2,batch_size=4")
    return Trainer(cfg, seed=7)


def _oracle(tr, req: Request):
    return lm_oracle(tr.executor, tr.params, req)


def _roomy(engines, tr):
    """The module's one engine with room to spare (pages of 8, the whole
    pool), for the tests whose subject is the sharing and not the pool: the
    index and the allocator cold, as a fresh engine's; counters are read as
    differences."""
    eng = engines(tr.executor, tr.params, num_slots=2, page_size=8,
                  max_context=64, prefill_chunk=-1)
    eng.reset_prefix_cache()
    return eng


def _assert_exact(tr, reqs, results):
    for r in reqs:
        np.testing.assert_array_equal(
            _oracle(tr, r), results[r.req_id],
            err_msg=f"request {r.req_id!r} diverged from the cold "
                    f"lm_generate oracle")


def _pool_reclaimed(eng):
    eng.kv.check_reclaimed()


# ---------------------------------------------------------------------------
# the token-exactness oracle, extended to the sharing paths
# ---------------------------------------------------------------------------

def test_shared_prefix_hits_stay_oracle_exact(tr, engines):
    """A pool of requests sharing one system-prompt prefix with distinct
    suffixes and mixed sampling knobs: the first pays full prefill, the
    rest prefix-hit (mapping the committed pages read-only + suffix-only
    prefill) — every output bit-matches its own cold run, tokens-saved
    accumulates, and the decode step stays ONE signature."""
    rng = np.random.default_rng(0)
    system = rng.integers(2, 23, 19).astype(np.int32)   # spans 2+ pages
    knobs = [dict(), dict(temperature=0.8, top_k=5),
             dict(temperature=0.7, top_p=0.9), dict(temperature=1.1)]
    reqs = [Request(f"r{i}",
                    np.concatenate([system,
                                    rng.integers(2, 23, 3 + i)
                                    .astype(np.int32)]),
                    max_new=5, rng=jax.random.PRNGKey(40 + i), **kw)
            for i, kw in enumerate(knobs)]
    eng = _roomy(engines, tr)
    hits0, saved0 = eng.n_prefix_hits, eng.prefill_tokens_saved
    results = {}
    for r in reqs:                        # sequential: each later request
        results.update(eng.run([r]))      # sees the earlier donations
    _assert_exact(tr, reqs, results)
    assert eng.n_prefix_hits - hits0 >= len(reqs) - 1
    assert eng.prefill_tokens_saved - saved0 >= (len(reqs) - 1) * 16, \
        "hits did not skip the shared full pages"
    assert eng._decode_step._cache_size() == 1
    _pool_reclaimed(eng)


def test_concurrent_same_prefix_requests_share_pages(tr, engines):
    """Two live slots mapping the same cached prefix simultaneously:
    shared pages show refcount > 1 (shared_pages_in_use), neither slot
    writes them (COW gave each a private boundary), and both outputs stay
    exact."""
    rng = np.random.default_rng(1)
    system = rng.integers(2, 23, 17).astype(np.int32)
    warm = Request("warm", system.copy(), max_new=9)
    eng = _roomy(engines, tr)
    hits0 = eng.n_prefix_hits
    res = eng.run([warm])
    a = Request("a", np.concatenate([system, [3, 4, 5]]).astype(np.int32),
                max_new=6)
    b = Request("b", np.concatenate([system, [6, 7]]).astype(np.int32),
                max_new=6)
    eng.add_request(a)
    eng.add_request(b)
    eng.step()                            # both admitted, both hit
    assert eng.n_prefix_hits - hits0 == 2
    assert eng.kv.shared_pages_in_use >= 2, \
        "concurrent hits did not actually share physical pages"
    eng.kv.check()
    res.update(eng.run())
    _assert_exact(tr, [warm, a, b], res)
    assert eng._decode_step._cache_size() == 1
    _pool_reclaimed(eng)


def test_cow_divergence_mid_page_and_donor_page_intact(tr, engines):
    """B's prompt follows A's sequence INTO a page and diverges mid-run:
    admission maps the boundary page, COWs it, and B's suffix overwrites
    only its own copy — B is oracle-exact, and a third request repeating
    A's exact prompt still hits the ORIGINAL page and stays exact (the
    shared original was never written)."""
    rng = np.random.default_rng(2)
    base = rng.integers(2, 23, 13).astype(np.int32)     # 13 = 1.625 pages
    eng = _roomy(engines, tr)
    hits0 = eng.n_prefix_hits
    a = Request("a", base.copy(), max_new=6)
    results = eng.run([a])
    cow0 = eng.kv.n_cow
    # B: matches page 0 fully, then tokens 8..10 of page 1, then diverges
    b_prompt = np.concatenate([base[:11],
                               (base[11:13] + 1) % 23 + 2,
                               rng.integers(2, 23, 4)]).astype(np.int32)
    b = Request("b", b_prompt, max_new=6)
    results.update(eng.run([b]))
    assert eng.kv.n_cow > cow0, "mid-page divergence never copied-on-write"
    assert eng.n_prefix_hits - hits0 >= 1
    # C repeats A's prompt exactly: the original boundary page must still
    # hold A's committed K/V bit-for-bit
    c = Request("c", base.copy(), max_new=6)
    results.update(eng.run([c]))
    _assert_exact(tr, [a, b, c], results)
    assert eng._decode_step._cache_size() == 1
    _pool_reclaimed(eng)


def test_eviction_runs_before_pausing_and_stays_exact(tr):
    """A tree fat with retired prefixes + a pool with no free pages left:
    admission and decode growth reclaim via LRU eviction (free list was
    dry) WITHOUT any preemption, and outputs stay exact."""
    rng = np.random.default_rng(3)
    eng = ServingEngine(tr.executor, tr.params, num_slots=1, page_size=4,
                        max_context=16, num_pages=5)    # 4 real pages
    filler = [Request(f"f{i}", rng.integers(2, 23, 7).astype(np.int32),
                      max_new=5) for i in range(2)]
    results = {}
    for r in filler:
        results.update(eng.run([r]))
    assert eng.kv.cached_page_count > 0
    assert eng.kv.free_page_count < eng.kv.pages_for(9 + 7 - 1), \
        "pool not tight enough to force eviction"
    big = Request("big", rng.integers(2, 23, 9).astype(np.int32), max_new=7)
    results.update(eng.run([big]))
    assert eng.prefix.n_evictions > 0, "free list never pressured the tree"
    assert eng.n_preemptions == 0, \
        "eviction should have satisfied pressure before any preemption"
    _assert_exact(tr, filler + [big], results)
    _pool_reclaimed(eng)


def test_eviction_racing_admission_of_the_same_prefix(tr):
    """The admission that HITS a prefix also triggers eviction for its
    suffix pages: the matched pages are mapped (refcount > 0) before the
    pressure hook runs, so LRU eviction must reclaim OTHER nodes and can
    never steal the prefix out from under the admission using it."""
    rng = np.random.default_rng(4)
    eng = ServingEngine(tr.executor, tr.params, num_slots=1, page_size=4,
                        max_context=16, num_pages=7)    # 6 real pages
    keep = Request("keep", rng.integers(2, 23, 8).astype(np.int32),
                   max_new=5)                            # donates 2+ pages
    other = Request("other", rng.integers(2, 23, 7).astype(np.int32),
                    max_new=4)
    results = eng.run([keep])
    results.update(eng.run([other]))
    nodes_before = eng.prefix.n_nodes
    assert eng.kv.cached_page_count >= 4
    # rerun keep's prompt with a long suffix: hits keep's pages, and the
    # suffix allocation must evict from `other`'s nodes
    hit = Request("hit", np.concatenate(
        [keep.prompt_ids, rng.integers(2, 23, 5)]).astype(np.int32),
        max_new=3)
    ev0 = eng.prefix.n_evictions
    results.update(eng.run([hit]))
    assert eng.n_prefix_hits >= 1
    assert eng.prefix.n_evictions > ev0, "no eviction pressure occurred"
    assert eng.prefix.n_nodes <= nodes_before + 3
    _assert_exact(tr, [keep, other, hit], results)
    _pool_reclaimed(eng)


def test_preempt_replay_prefix_hits_and_refcounts_balance(tr):
    """Preemption donates the victim's committed pages; the deterministic
    replay re-admission prefix-hits its own prompt (skipping the prefill
    it already paid for), outputs stay exact, and slot-mapping refcounts
    drop back to zero everywhere at the end."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 23, n).astype(np.int32) for n in (6, 4, 5)]
    reqs = [Request(i, p, max_new=8) for i, p in enumerate(prompts)]
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=16, num_pages=6)
    results = eng.run(reqs)
    assert eng.n_preemptions > 0, "pool was never overcommitted"
    assert eng.n_prefix_hits > 0, \
        "preempt replay never hit the victim's own donated prefix"
    _assert_exact(tr, reqs, results)
    assert (eng.kv._ref == 0).all()
    assert eng._decode_step._cache_size() == 1
    _pool_reclaimed(eng)


def test_overcommit_pool_with_hits_stays_exact_under_churn(tr):
    """Sustained churn over a small pool with repeated prompts: hits,
    evictions, COWs, and preemptions all interleave — every request of
    every wave still matches its cold oracle."""
    rng = np.random.default_rng(6)
    bases = [rng.integers(2, 23, 9).astype(np.int32) for _ in range(2)]
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=16, num_pages=8)
    reqs = []
    for w in range(3):
        for i, base in enumerate(bases):
            suffix = rng.integers(2, 23, 1 + w).astype(np.int32)
            reqs.append(Request(f"w{w}b{i}",
                                np.concatenate([base, suffix]),
                                max_new=4))
    results = {}
    for r in reqs:
        eng.add_request(r)
        eng.step()
    results.update(eng.run())
    results.update({k: eng.results.pop(k) for k in list(eng.results)})
    _assert_exact(tr, reqs, results)
    assert eng.n_prefix_hits > 0
    assert eng._decode_step._cache_size() == 1
    _pool_reclaimed(eng)


# ---------------------------------------------------------------------------
# allocator satellites: double-release guard, deterministic reset, COW unit
# ---------------------------------------------------------------------------

def test_release_is_idempotent_and_guards_double_free(tr):
    """Releasing a slot twice (or after reset()) must NOT append its pages
    to the free list twice — the double-free would hand one physical page
    to two slots and silently corrupt the allocator."""
    kv = PagedKVCache(tr.executor, num_slots=2, page_size=4,
                      pages_per_slot=3, num_pages=8)
    assert kv.try_grow(0, 9)                 # 3 pages
    assert kv.try_grow(1, 4)                 # 1 page
    free_before = kv.free_page_count
    kv.release(0)
    assert kv.free_page_count == free_before + 3
    kv.release(0)                            # double release: no-op
    assert kv.free_page_count == free_before + 3
    kv.check()
    kv.reset()
    kv.release(0)                            # release after reset: no-op
    kv.release(1)
    assert kv.free_page_count == kv.num_pages - 1
    assert len(set(kv._free)) == len(kv._free), "free list holds duplicates"
    kv.check()


def test_reset_rebuilds_canonical_free_list(tr):
    """After arbitrary grow/release churn, reset() restores the free list
    to construction order, so page placement is reproducible across
    restarts (exactness tests and engine.json snapshots stay stable)."""
    kv = PagedKVCache(tr.executor, num_slots=2, page_size=4,
                      pages_per_slot=3, num_pages=8)
    pristine = list(kv._free)
    assert kv.try_grow(0, 12) and kv.try_grow(1, 7)
    kv.release(1)
    kv.cache_page(int(kv.table[0, 0]))       # prefix retention survives...
    kv.reset()                               # ...until reset forgets it
    assert kv._free == pristine, \
        f"reset() free list {kv._free} != canonical {pristine}"
    assert kv.cached_page_count == 0 and (kv._ref == 0).all()
    # allocation after reset is bit-reproducible: same pages, same order
    assert kv.try_grow(0, 12)
    first = kv.table[0, :3].tolist()
    kv.reset()
    assert kv.try_grow(0, 12)
    assert kv.table[0, :3].tolist() == first
    kv.check()


def test_engine_reset_prefix_cache_restores_cold_start(tr, engines):
    """ServingEngine.reset_prefix_cache is the engine-level cold start:
    the index empties, the free list returns to canonical order, and
    re-running the same workload reproduces the same page placement AND
    the same tokens (a restart is bit-indistinguishable from a fresh
    engine)."""
    rng = np.random.default_rng(5)
    system = rng.integers(2, 23, 18).astype(np.int32)
    mk = lambda: [Request(f"r{i}", np.concatenate(
        [system, rng2.integers(2, 23, 2 + i).astype(np.int32)]), max_new=4)
        for i, rng2 in ((j, np.random.default_rng(50 + j))
                        for j in range(3))]
    eng = _roomy(engines, tr)
    hits0 = eng.n_prefix_hits
    first = eng.run(mk())
    cached1 = np.flatnonzero(eng.kv._cached).tolist()
    eng.reset_prefix_cache()
    assert eng.prefix.n_nodes == 0 and eng.kv.cached_page_count == 0
    assert eng.kv.free_page_count == eng.kv.num_pages - 1
    assert eng.kv._free == eng.kv._canonical_free()
    assert eng.n_prefix_hits > hits0              # first pass did share
    again = eng.run(mk())
    for rid in first:
        np.testing.assert_array_equal(first[rid], again[rid])
    # same physical pages ended up prefix-cached: placement reproduced
    assert np.flatnonzero(eng.kv._cached).tolist() == cached1
    _pool_reclaimed(eng)


def test_map_shared_refcounts_and_cow_unit(tr):
    """Allocator-level sharing: map_shared bumps refcounts, writes to a
    shared page COW through ensure_writable (contents preserved), and the
    last release frees everything exactly once."""
    import jax.numpy as jnp

    kv = PagedKVCache(tr.executor, num_slots=3, page_size=4,
                      pages_per_slot=2, num_pages=8)
    assert kv.try_grow(0, 8)                 # slot 0 owns 2 private pages
    donor = [int(kv.table[0, 0]), int(kv.table[0, 1])]
    name = next(iter(kv.pools))
    kv.pools[name]["k"] = kv.pools[name]["k"].at[donor[0], 0, 0, 0].set(7.5)
    kv.cache_page(donor[0])
    kv.cache_page(donor[1])
    kv.map_shared(1, donor)
    kv.map_shared(2, donor[:1])
    assert kv._ref[donor[0]] == 3 and kv._ref[donor[1]] == 2
    assert kv.shared_pages_in_use == 2 and kv.private_pages_in_use == 0
    assert not kv.page_writable(donor[0])
    assert kv.ensure_writable(1, 0) is True            # COW copies
    fresh = int(kv.table[1, 0])
    assert fresh != donor[0] and kv.page_writable(fresh)
    assert float(kv.pools[name]["k"][fresh, 0, 0, 0]) == 7.5, \
        "COW did not copy the page contents"
    assert kv._ref[donor[0]] == 2
    assert kv.ensure_writable(1, 0) is False           # already private
    kv.check()
    kv.release(0)
    kv.release(1)
    kv.release(2)
    # cached pages stay out of the free list until uncached
    assert kv.cached_page_count == 2
    kv.uncache_page(donor[0])
    kv.uncache_page(donor[1])
    assert kv.free_page_count == kv.num_pages - 1
    kv.check()


def test_cow_returns_none_when_pool_dry(tr):
    """ensure_writable on a shared page with an empty free list and no
    reclaimer reports None (caller rolls back) instead of corrupting."""
    kv = PagedKVCache(tr.executor, num_slots=2, page_size=4,
                      pages_per_slot=2, num_pages=3)    # 2 real pages
    assert kv.try_grow(0, 8)
    kv.cache_page(int(kv.table[0, 0]))
    kv.map_shared(1, [int(kv.table[0, 0])])
    assert kv.ensure_writable(1, 0) is None
    kv.check()


# ---------------------------------------------------------------------------
# radix tree unit behavior
# ---------------------------------------------------------------------------

def test_prefix_tree_match_insert_evict(tr):
    kv = PagedKVCache(tr.executor, num_slots=1, page_size=4,
                      pages_per_slot=4, num_pages=12)
    tree = PrefixTree(kv)
    kv.on_page_pressure = tree.evict_for
    toks = np.arange(2, 18, dtype=np.int32)              # 4 full runs
    assert kv.try_grow(0, 16)
    pages = [int(kv.table[0, j]) for j in range(4)]
    assert tree.insert(toks, pages) == 4
    assert tree.insert(toks, pages) == 0                 # dedupe: no new nodes
    kv.release(0)
    assert kv.cached_page_count == 4

    full, partial = tree.match(toks[:11])                # 2 runs + 3 partial
    assert full == pages[:2]
    assert partial == (pages[2], 3)
    full, partial = tree.match(np.asarray([99, 98], np.int32))
    assert full == [] and partial is None

    # eviction is LRU leaf-first: deepest node goes first, the prefix
    # property (parents outlive children) holds throughout
    assert tree.evict_for(1) == 1
    assert kv.cached_page_count == 3
    full, partial = tree.match(toks)
    assert full == pages[:3], "eviction removed a non-leaf node"
    # a page mapped by a live slot is never evicted
    kv.map_shared(0, pages[:3])
    assert tree.evict_for(99) == 0
    kv.release(0)
    assert tree.evict_for(99) == 3
    assert kv.free_page_count == kv.num_pages - 1
    kv.check()


def test_page_pressure_frees_a_batch_a_walk_in_a_large_pool(tr):
    """A full pool asks for one page at a time; the pressure hook frees
    1/256 of the pool a call (the coldest leaves first; the floor dates
    from when a call walked the whole tree, and stays) — and exactly what
    was asked in a small pool."""
    big = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=16, num_pages=1025)
    rng = np.random.default_rng(5)
    for i in range(4):
        big.run([Request(f"b{i}", rng.integers(2, 23, 9).astype(np.int32),
                         max_new=3)])
    cached = big.kv.cached_page_count
    assert cached >= 8
    assert big._evict_for(1) == 1025 // 256 == 4
    assert big.kv.cached_page_count == cached - 4
    big.kv.check()
    small = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                          max_context=16, num_pages=9)
    small.run([Request("s", rng.integers(2, 23, 9).astype(np.int32),
                       max_new=3)])
    assert small._evict_for(1) == 1


# ---------------------------------------------------------------------------
# the kept eviction frontier (ISSUE 44): the heap against the walk
# ---------------------------------------------------------------------------

def _forbid_walk(monkeypatch):
    def no_walk(self):
        raise AssertionError("the step path walked the whole tree")
    monkeypatch.setattr(PrefixTree, "_evictable_leaves", no_walk)


def _walk_victims(tree, n):
    """The pages `evict_for(n)` freed, in order, when every call walked:
    the frontier by `_evictable_leaves()` into a heap on last_use, a
    victim's parent entering it once its last DEVICE child went and no
    slot maps it.  Reads only; the oracle of the kept frontier's order."""
    import heapq

    heap = [(nd.last_use, i, nd)
            for i, nd in enumerate(tree._evictable_leaves())]
    heapq.heapify(heap)
    seq, gone, pages = len(heap), set(), []
    while len(pages) < n and heap:
        _, _, victim = heapq.heappop(heap)
        pages.append(victim.page)
        gone.add(id(victim))
        parent = victim.parent
        if parent is not tree.root and \
                not any(c.host_id is None and id(c) not in gone
                        for c in parent.children.values()) and \
                tree.kv._ref[parent.page] == 0:
            heapq.heappush(heap, (parent.last_use, seq, parent))
            seq += 1
    return pages


class _Traffic:
    """Seeded random allocator/index traffic at the unit level, the way
    the engine drives the two: admissions that match, map, grow and COW,
    retirements that donate and release, aborts, direct pressure calls,
    imports of adopted pages, and — with a spill budget — restores of a
    spilled tail.  EVERY eviction, asked for or raised by the allocator,
    is held to the walking oracle's pages in its order; after every
    operation the kept frontier is held to the walk."""

    SLOTS, PS, PER_SLOT = 3, 2, 6

    def __init__(self, tr, seed, spill, num_pages=20):
        self.rng = np.random.default_rng(seed)
        self.kv = PagedKVCache(tr.executor, num_slots=self.SLOTS,
                               page_size=self.PS,
                               pages_per_slot=self.PER_SLOT,
                               num_pages=num_pages)
        if spill:
            self.kv.spill_bytes_budget = 5 * self.kv.page_nbytes
        self.tree = PrefixTree(self.kv)
        self.kv.on_page_pressure = self.evict
        self.live = {}                       # slot -> its token sequence
        self.evictions = self.compared = 0

    def evict(self, n):
        want = _walk_victims(self.tree, n)
        tail = len(self.kv._free)
        freed = self.tree.evict_for(n)
        assert self.kv._free[tail:] == want and freed == len(want), \
            f"evict_for({n}) freed {self.kv._free[tail:]}, the walk {want}"
        self.evictions += freed
        self.compared += 1
        return freed

    def _tokens(self):
        # a vocabulary of 3 over runs of 2: prefixes collide all the time
        n = int(self.rng.integers(2, self.PS * self.PER_SLOT + 1))
        return self.rng.integers(0, 3, n).astype(np.int32)

    def _restore(self, path):
        """The engine's `_restore_spilled`, host tail of a matched path."""
        kv, tree = self.kv, self.tree
        tail = [nd for nd in path if nd.host_id is not None]
        if not tail:
            return True
        tree._spill_inhibit = True
        try:
            pages = kv.take_pages(len(tail))
        finally:
            tree._spill_inhibit = False
        if pages is None:
            return False
        if any(nd.page <= 0 for nd in path if nd not in tail) or \
                not all(nd.host_id is not None and
                        kv.host_entry_live(nd.host_id) for nd in tail):
            kv.untake_pages(pages)
            return False
        kv.restore_pages([nd.host_id for nd in tail], pages)
        kv.adopt_restored(pages)
        tree.promote(tail, pages)
        tree.check_invariants()              # before anything maps them
        return True

    def admit(self, s):
        kv, tree = self.kv, self.tree
        toks = self._tokens()
        nodes, partial = tree.match_nodes(toks[:-1])
        path = nodes + ([partial[0]] if partial else [])
        if path and not self._restore(path):
            path, partial = [], None
        ok = True
        if path:
            kv.map_shared(s, [nd.page for nd in path])
            ok = kv.try_grow(s, toks.size)
            if ok and partial:
                ok = kv.ensure_writable(s, len(path) - 1) is not None
        else:
            ok = kv.try_grow(s, toks.size)
        if ok:
            self.live[s] = toks
        else:
            kv.release(s)

    def retire(self, s, donate):
        toks = self.live.pop(s)
        full = (toks.size - 1) // self.PS
        if donate and full:
            self.tree.insert(toks[:full * self.PS],
                             [int(self.kv.table[s, j])
                              for j in range(full)])
        self.kv.release(s)

    def mount(self):
        """An import of adopted pages (`ServingEngine.import_prefix`)."""
        toks = self._tokens()
        n = toks.size // self.PS
        pages = self.kv.take_pages(n)
        if pages is not None:
            self.kv.adopt_restored(pages)
            self.tree.insert(toks[:n * self.PS], pages, adopted=True)

    def step(self):
        op = self.rng.choice(["admit", "retire", "abort", "evict", "match",
                              "mount"], p=[.3, .3, .05, .15, .1, .1])
        free = [s for s in range(self.SLOTS) if s not in self.live]
        if op == "admit" and free:
            self.admit(free[0])
        elif op in ("retire", "abort") and self.live:
            s = list(self.live)[int(self.rng.integers(len(self.live)))]
            self.retire(s, donate=op == "retire")
        elif op == "evict":
            self.evict(int(self.rng.integers(1, 5)))
        elif op == "match":
            self.tree.match(self._tokens())
        elif op == "mount":
            self.mount()
        self.tree.check_invariants()
        self.kv.check()


@pytest.mark.parametrize("spill", [False, True], ids=["destroy", "spill"])
@pytest.mark.parametrize("seed", range(6))
def test_kept_frontier_is_the_walks_under_random_traffic(tr, seed, spill):
    """Completeness, the bound of one entry a node, and the parent's
    victims to the page and in its order: 400 seeded operations, the
    host tier off and on."""
    t = _Traffic(tr, seed, spill)
    for _ in range(400):
        t.step()
    assert t.compared >= 20 and t.evictions >= 20, \
        "the traffic never came under page pressure"
    assert t.tree.frontier_size <= t.tree.n_nodes
    pops = t.tree.frontier_pops
    assert pops["victim"] == t.tree.n_evictions == t.evictions
    assert pops["stale"] > 0 and pops["ineligible"] > 0, \
        f"the lazy validation was never exercised: {pops}"
    if spill:
        assert t.kv.n_spilled > 0 and t.kv.n_restored > 0
    for s in list(t.live):
        t.retire(s, donate=False)
    t.evict(t.kv.num_pages)                  # everything left is evictable
    assert t.tree.n_nodes == t.kv.host_page_count
    assert t.tree.frontier_size == 0
    t.kv.check_reclaimed()


def _engine_under_pressure(tr, **kw):
    """A tight engine whose index holds retired prefixes, some evicted."""
    rng = np.random.default_rng(11)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=16, num_pages=9, **kw)
    for i in range(5):
        eng.run([Request(f"p{i}", rng.integers(2, 23, 7).astype(np.int32),
                         max_new=4)])
    assert eng.prefix.n_evictions > 0 and eng.prefix.n_nodes > 0
    eng.prefix.check_invariants()
    return eng, rng


@pytest.mark.parametrize("path", ["clear", "kv_reset", "toggle",
                                  "checkpoint"])
def test_kept_frontier_survives_every_rebuild_path(tr, path):
    """Where the tree is put together from other state the frontier is
    rebuilt by the walk, and eviction goes on from it as from a kept one:
    `clear`, `kv.reset` (with the clear that must follow it),
    `set_prefix_cache(False / True)`, and a checkpoint loaded into a
    second engine."""
    eng, rng = _engine_under_pressure(tr)
    if path == "clear":
        for nd in list(eng.prefix._by_page.values()):
            eng.kv.uncache_page(nd.page)     # what clear leaves to its caller
        eng.prefix.clear()
    elif path == "kv_reset":
        eng.reset_prefix_cache()
    elif path == "toggle":
        eng.set_prefix_cache(False)
        assert eng.kv.on_cached_unmapped is None
        eng.set_prefix_cache(True)
        assert eng.kv.on_cached_unmapped is not None
    else:
        snap = eng.checkpoint_state()
        kept = sorted(nd.page for nd in eng.prefix._evictable_leaves())
        assert kept, "nothing evictable to carry over"
        eng = ServingEngine(tr.executor, tr.params, num_slots=2,
                            page_size=4, max_context=16, num_pages=9)
        eng.restore_state(snap)              # runs check_invariants itself
        assert sorted(nd.page for _, _, nd in eng.prefix._frontier) == kept
    if path != "checkpoint":
        assert eng.prefix.n_nodes == eng.prefix.frontier_size == 0
        assert not eng.prefix._by_page
    eng.prefix.check_invariants()
    eng.kv.check()
    # and the index goes on working from the rebuilt frontier
    ev0 = eng.prefix.n_evictions
    reqs = [Request(f"q{i}", rng.integers(2, 23, 7).astype(np.int32),
                    max_new=4) for i in range(5)]
    results = {}
    for r in reqs:
        results.update(eng.run([r]))
        eng.prefix.check_invariants()
    assert eng.prefix.n_evictions > ev0
    _assert_exact(tr, reqs, results)
    _pool_reclaimed(eng)


def test_eviction_cost_is_its_victims_not_the_tree(tr, monkeypatch):
    """A count, not a timing: at 16 k nodes a call of 64 pops its 64
    victims plus the entries it finds stale or ineligible on the way —
    read from `frontier_pops` — and the walk is never taken, neither by
    the call nor by the events that feed the frontier."""
    ps, per = 4, 16
    kv = PagedKVCache(tr.executor, num_slots=2, page_size=ps,
                      pages_per_slot=per, num_pages=16 * 1024 + 65)
    tree = PrefixTree(kv)
    kv.on_page_pressure = tree.evict_for
    rng = np.random.default_rng(0)
    seqs = []
    for _ in range(1024):                    # 1,024 chains of 16 nodes
        toks = rng.integers(0, 1 << 30, ps * per).astype(np.int32)
        assert kv.try_grow(0, toks.size)
        tree.insert(toks, [int(kv.table[0, j]) for j in range(per)])
        kv.release(0)
        seqs.append(toks)
    assert tree.n_nodes == 16 * 1024 and tree.frontier_size == 1024
    tree.check_invariants()
    _forbid_walk(monkeypatch)
    # the 8 coldest chains are hit again (their entries go stale), and the
    # next 4 are mapped by a slot (theirs ineligible)
    for toks in seqs[:8]:
        tree.match(toks)
    kv.map_shared(1, tree.match(seqs[8])[0])
    pops0 = dict(tree.frontier_pops)
    nodes0 = tree.n_nodes
    assert tree.evict_for(64) == 64
    pops = {k: v - pops0[k] for k, v in tree.frontier_pops.items()}
    assert pops == {"victim": 64, "stale": 8, "ineligible": 1}, pops
    assert tree.n_nodes == nodes0 - 64
    # whole chains went, coldest first: four chains of 16, leaf to top
    for toks in seqs[9:13]:
        assert tree.match(toks) == ([], None)
    assert len(tree.match(seqs[13])[0]) == per
    # a second call pays for nothing the first already settled
    kv.release(1)
    assert tree.evict_for(64) == 64
    pops2 = {k: v - pops0[k] for k, v in tree.frontier_pops.items()}
    assert pops2["victim"] == 128 and \
        pops2["stale"] + pops2["ineligible"] <= 9 + 1, pops2
    assert tree.frontier_size <= tree.n_nodes
    assert tree.n_evict_calls == 2


def test_step_path_under_page_pressure_never_walks(tr, monkeypatch):
    """The engine's own steps, admission and growth under a dry free
    list, with the walk patched to raise: evictions happen, outputs stay
    exact."""
    _forbid_walk(monkeypatch)
    rng = np.random.default_rng(3)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=16, num_pages=7)
    reqs = [Request(f"r{i}", rng.integers(2, 23, 6 + i % 3).astype(np.int32),
                    max_new=5) for i in range(6)]
    results = {}
    for i in range(0, 6, 2):
        results.update(eng.run(reqs[i:i + 2]))
    assert eng.prefix.n_evictions > 0 and eng.prefix.n_evict_calls > 0
    assert eng.prefix.frontier_pops["victim"] == eng.prefix.n_evictions
    _assert_exact(tr, reqs, results)
    monkeypatch.undo()
    eng.prefix.check_invariants()
    _pool_reclaimed(eng)


def test_a_model_with_window_rings_serves_without_the_prefix_index():
    """A window layer's ring is not the whole context: the engine serves
    such a model with the prefix index off — a shared prefix is served
    correctly as independent requests — and turning it on raises by name,
    through the recurrent models' one function."""
    from paddle_tpu.serving.paged_kv import RING_REFUSALS
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=23,dim=16,layers=2,heads=2,batch_size=4,"
                       "window=6")
    wtr = Trainer(cfg, seed=7)
    eng = ServingEngine(wtr.executor, wtr.params, num_slots=2, page_size=4,
                        max_context=32)
    assert eng.prefix is None and eng.kv.ring_specs
    shared = np.arange(2, 14, dtype=np.int32)
    reqs = [Request(i, np.concatenate([shared, [3 + i]]).astype(np.int32),
                    max_new=5) for i in range(3)]
    _assert_exact(wtr, reqs, eng.run(reqs))
    assert eng.n_prefix_hits == 0
    with pytest.raises(ValueError) as e:
        eng.set_prefix_cache(True)
    assert "the prefix index" in str(e.value)
    assert RING_REFUSALS["prefix"] in str(e.value)
    eng.kv.check_reclaimed()


@pytest.mark.parametrize("window", [24, 64])
def test_a_window_that_drops_no_page_keeps_prefix_hits_and_speculation(
        window):
    """A ring is built only where it is smaller than a context: a window
    of a whole context or more (64 against 32), or one whose ring of window
    + a step's rows would hold every page (24 + 18 rows = 12 pages against
    8), stays under the logical table — the prefix index hits, speculation
    runs, nothing refuses, and the tokens are the windowed oracle's (29
    tokens against the window of 24)."""
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       f"vocab=23,dim=16,layers=2,heads=2,batch_size=4,"
                       f"window={window}")
    wtr = Trainer(cfg, seed=7)
    eng = ServingEngine(wtr.executor, wtr.params, num_slots=2, page_size=4,
                        max_context=32, spec_k=2)
    assert not eng.kv.ring_specs and eng.prefix is not None
    assert {a.shape[0] for p in eng.kv.pools.values() for a in p.values()} \
        == {eng.kv.num_pages}
    shared = (np.arange(20) % 19 + 2).astype(np.int32)
    reqs = [Request(i, np.concatenate([shared, [3 + i]]).astype(np.int32),
                    max_new=8) for i in range(3)]
    _assert_exact(wtr, reqs, eng.run(reqs))
    assert eng.n_prefix_hits > 0 and eng.n_spec_steps > 0
    for mechanism in ("prefix", "spill", "export", "import", "spec", "role"):
        eng.kv.refuse(mechanism)
    eng.kv.check()
