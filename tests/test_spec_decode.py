"""Speculative decoding with exact verification (serving/engine.py
`_run_spec_step` / `_spec_impl`, serving/drafter.py, sampler.py
`pick_next_chain`, paged_kv.py `uncommit_tail`).

The contract is absolute: speculation may change how many compiled steps
it takes to emit the tokens, NEVER the tokens — spec-on output is
bit-identical to spec-off (and therefore to the cold
`lm_generate(use_cache=True)` oracle) across every sampling knob, GQA,
prefix-cache hits + COW, chunked prefill coexistence, preempt/replay,
and tensor parallelism, while the compiled set stays bounded (the one
decode signature + ONE verify signature per (budget, spec_k); the mixed
signature never compiles while speculation is on).  Rejections must also
leave the allocator EXACTLY as a sequential engine would — the
uncommit_tail rollback accounting is checked with the kv.check oracle
under a drafter built to be always wrong."""

import numpy as np
import pytest

import jax

from paddle_tpu.config.parser import parse_config
from paddle_tpu.serving import NgramDrafter, Request, ServingEngine
from paddle_tpu.trainer.trainer import Trainer
from tests.conftest import lm_oracle


@pytest.fixture(scope="module")
def tr():
    # layers=1 keeps compiles cheap on the tier-1 CPU budget; the
    # multi-layer + GQA spec paths get their own configs below
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=23,dim=16,layers=1,heads=2,batch_size=4")
    return Trainer(cfg, seed=7)


# the geometry of nearly every test here; with `spec_k=3` it is the module's
# one speculative engine (tests/conftest.py `engines`), counters read as
# differences
GEOM = dict(num_slots=2, page_size=4, max_context=32, prefill_chunk=-1)
KNOBS = {"greedy": dict(), "top-k": dict(temperature=0.8, top_k=5),
         "nucleus": dict(temperature=0.7, top_p=0.9),
         "full": dict(temperature=1.1)}

def _cold(eng):
    """A shared engine with its allocator and prefix index cold, as a fresh
    one's are: a draft's tail takes FREE pages only (`try_grow(evict=
    False)`), so over a pool an earlier test left full of cached prefixes
    no chain would verify a draft."""
    eng.reset_prefix_cache()
    return eng


def _oracle(tr, req: Request):
    return lm_oracle(tr.executor, tr.params, req)


def _assert_exact(tr, reqs, results):
    for r in reqs:
        np.testing.assert_array_equal(
            _oracle(tr, r), results[r.req_id],
            err_msg=f"request {r.req_id!r} diverged from the cold "
                    f"lm_generate oracle under speculation")


def _rep_prompt(rng, vocab, n, motif=4):
    """Locally-repetitive prompt (tiled motif) so the n-gram drafter has
    something to find — the workload speculation targets."""
    m = rng.integers(2, vocab, motif).astype(np.int32)
    return np.tile(m, -(-n // motif))[:n]


def _assert_sigs(eng):
    """The tentpole's signature discipline under speculation: the one
    decode signature, ONE verify signature, and the mixed step never
    compiled (the verify step subsumes it while spec is on)."""
    assert eng._decode_step._cache_size() <= 1
    assert eng._spec_step._cache_size() == 1
    assert eng._mixed_step._cache_size() == 0, \
        "the mixed step compiled while speculation was on — the verify " \
        "signature should be carrying the chunk rows"


# ---------------------------------------------------------------------------
# the bit-exact oracle across sampling knobs / GQA / TP
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def on_and_off(tr, engines):
    """All four sampling modes (greedy / top-k / nucleus / full), mixed
    repetitive prompt lengths, served together once by the sequential
    engine and once by the speculative one: at least one draft genuinely
    accepted (the accept path ran, not just the reject path) and the
    signature set pinned."""
    def reqs():
        return {name: Request(f"r{i}", _rep_prompt(
                                  np.random.default_rng(100 + i), 23,
                                  11 + 2 * i),
                              max_new=8, rng=jax.random.PRNGKey(40 + i), **kw)
                for i, (name, kw) in enumerate(KNOBS.items())}

    base = engines(tr.executor, tr.params, **GEOM).run(
        list(reqs().values()))
    eng = _cold(engines(tr.executor, tr.params, spec_k=3, **GEOM))
    drafted0, accepted0 = eng.n_spec_drafted, eng.n_spec_accepted
    spec = eng.run(list(reqs().values()))
    assert set(base) == set(spec)
    drafted = eng.n_spec_drafted - drafted0
    accepted = eng.n_spec_accepted - accepted0
    assert drafted > 0 and accepted > 0, \
        "the workload never exercised the accept path"
    assert accepted <= drafted
    _assert_sigs(eng)
    eng.kv.check_reclaimed()
    return reqs(), base, spec


@pytest.mark.parametrize("knobs", sorted(KNOBS))
def test_spec_on_equals_spec_off_across_sampling_knobs(tr, on_and_off,
                                                       knobs):
    """The speculative engine's tokens are bit-identical to the sequential
    engine's AND to the lm_generate oracle, in each sampling mode."""
    reqs, base, spec = on_and_off
    r = reqs[knobs]
    np.testing.assert_array_equal(base[r.req_id], spec[r.req_id])
    _assert_exact(tr, [r], spec)


def test_spec_gqa_grouped_heads_stay_exact():
    """Grouped-query attention under speculation: the verify step's
    ragged multi-row dispatch with h_kv < heads reproduces the
    sequential tokens exactly."""
    cfg = parse_config(
        "demo/model_zoo/transformer_lm.py",
        "vocab=97,dim=32,layers=2,heads=4,batch_size=4,kv_heads=2")
    tr2 = Trainer(cfg, seed=5)
    rng = np.random.default_rng(2)
    prompts = [_rep_prompt(rng, 97, n, motif=5) for n in (7, 12, 9)]
    kw = dict(num_slots=2, page_size=8, max_context=64)
    reqs = lambda: [Request(i, p.copy(), max_new=6)
                    for i, p in enumerate(prompts)]
    base = ServingEngine(tr2.executor, tr2.params, **kw).run(reqs())
    eng = ServingEngine(tr2.executor, tr2.params, spec_k=3, **kw)
    spec = eng.run(reqs())
    for k in base:
        np.testing.assert_array_equal(base[k], spec[k], err_msg=str(k))
    assert eng.n_spec_drafted > 0


def test_spec_tp_model2_host_mesh_stays_exact():
    """Speculation composes with tensor parallelism: a model=2 host-mesh
    engine with spec on is token-for-token the single-device spec-off
    engine (the verify step runs through the same sharded ragged core
    and the sharded MLP/vocab projections)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (conftest provides 8)")
    from paddle_tpu.parallel.mesh import model_mesh

    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=61,dim=32,layers=2,heads=4,batch_size=4")
    tr2 = Trainer(cfg, seed=3)
    rng = np.random.default_rng(4)
    prompts = [_rep_prompt(rng, 61, n) for n in (8, 13, 6)]
    knobs = [dict(), dict(temperature=0.8, top_k=5), dict(temperature=1.1)]
    reqs = lambda: [Request(i, p.copy(), max_new=6,
                            rng=jax.random.PRNGKey(70 + i), **kw)
                    for i, (p, kw) in enumerate(zip(prompts, knobs))]
    kw = dict(num_slots=2, page_size=8, max_context=64)
    tr2.executor.mesh = None
    base = ServingEngine(tr2.executor, tr2.params, **kw).run(reqs())
    tr2.executor.mesh = None
    eng = ServingEngine(tr2.executor, tr2.params, spec_k=3,
                        mesh=model_mesh(2), **kw)
    spec = eng.run(reqs())
    for k in base:
        np.testing.assert_array_equal(
            base[k], spec[k],
            err_msg=f"request {k!r} diverged between single-device "
                    f"sequential and model=2 speculative decode")
    assert eng.tp == 2 and eng.n_spec_drafted > 0
    _assert_sigs(eng)
    tr2.executor.mesh = None


# ---------------------------------------------------------------------------
# the distributional claim: fixed-key acceptance IS lm_generate's law
# ---------------------------------------------------------------------------

def test_rejection_sampled_acceptance_matches_lm_generate_law(tr, engines):
    """The rejection-sampling equivalence at fixed keys: across many rng
    keys, full-distribution sampling through the speculative engine
    emits EXACTLY what lm_generate samples with the same key schedule —
    i.e. acceptance never warps the sampling law, it only decides how
    many tokens a step emits.  (With deterministic per-slot keys the
    classic accept-with-p(target)/p(draft) test degenerates to this
    stronger per-key exactness — the distribution matches because every
    single stream matches.)"""
    rng = np.random.default_rng(6)
    prompt = _rep_prompt(rng, 23, 10)
    eng = _cold(engines(tr.executor, tr.params, spec_k=3, **GEOM))
    accepted_any = 0
    for seed in range(10):
        # odd keys sample the FULL distribution (the law at maximum
        # entropy — acceptance is rare there and that is fine); even
        # keys sample peaked (temperature 0.05 — the untrained model's
        # logits are nearly flat, so only a very low temperature makes
        # the drafted continuation likely and genuinely runs the
        # sampled-acceptance path)
        temp = 1.0 if seed % 2 else 0.05
        r = Request(f"k{seed}", prompt.copy(), max_new=7,
                    temperature=temp, rng=jax.random.PRNGKey(seed))
        a0 = eng.n_spec_accepted
        got = eng.run([r])[r.req_id]
        accepted_any += eng.n_spec_accepted - a0
        np.testing.assert_array_equal(
            _oracle(tr, r), got,
            err_msg=f"key {seed} (temp {temp}): speculative sampling "
                    f"diverged from lm_generate's sampling law")
    assert accepted_any > 0, \
        "no key ever accepted a draft — the law test never exercised " \
        "the acceptance path"


# ---------------------------------------------------------------------------
# composition: prefix cache, chunked prefill, preempt/replay
# ---------------------------------------------------------------------------

def test_spec_with_prefix_hits_and_cow_stays_exact(tr, engines):
    """Prefix-cache hits + mid-page COW divergence under speculation:
    followers map the donor's pages, diverge inside the boundary page,
    and speculate over their own committed tokens — all bit-exact, with
    the donor page surviving for an exact repeat."""
    rng = np.random.default_rng(7)
    base_p = _rep_prompt(rng, 23, 13)
    eng = _cold(engines(tr.executor, tr.params, spec_k=3, **GEOM))
    hits0, cow0, drafted0 = (eng.n_prefix_hits, eng.kv.n_cow,
                             eng.n_spec_drafted)
    a = Request("a", base_p.copy(), max_new=6)
    results = eng.run([a])
    b = Request("b", np.concatenate(
        [base_p[:11], (base_p[11:13] + 1) % 23 + 2]).astype(np.int32),
        max_new=6)
    results.update(eng.run([b]))
    assert eng.n_prefix_hits - hits0 >= 1 and eng.kv.n_cow - cow0 >= 1
    again = Request("again", base_p.copy(), max_new=6)
    results.update(eng.run([again]))
    _assert_exact(tr, [a, b, again], results)
    assert eng.n_spec_drafted > drafted0
    eng.kv.check_reclaimed()


def test_spec_chains_coexist_with_prefill_chunks_under_budget(tr):
    """Mode-aware packing: a long prompt commits in chunk rows on the
    SAME verify dispatches that carry another slot's draft chains — the
    decoder keeps advancing (no stall), the budget histogram never
    exceeds max_step_tokens, and both requests stay exact."""
    rng = np.random.default_rng(8)
    short = Request("short", _rep_prompt(rng, 23, 4), max_new=12)
    long_ = Request("long", _rep_prompt(rng, 23, 25), max_new=4)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, prefill_chunk=4,
                        max_step_tokens=8, spec_k=2)
    eng.add_request(short)
    eng.step()                        # short: final chunk + token 0
    eng.step()                        # short decoding (specs when drafts)
    eng.add_request(long_)
    overlapped = 0
    while any(sl is not None and sl.req is long_ and sl.gen == 0
              for sl in eng.slots) or long_ in eng.queue:
        chunks0, chains0 = eng.n_prefill_chunks, eng.n_spec_chains
        before = eng.tokens_generated
        eng.step()
        if eng.n_prefill_chunks > chunks0 and eng.n_spec_chains > chains0:
            overlapped += 1
        assert eng.tokens_generated > before, \
            "a chunk-carrying step advanced no decode token"
    assert overlapped > 0, \
        "no step carried chunk rows and a spec chain together"
    results = dict(eng.results)       # short may have finished already
    results.update(eng.run())
    _assert_exact(tr, [short, long_], results)
    # the hard budget bound holds for verify steps too
    h = eng.step_tokens_hist
    counts, _total, n = h._vals[()]
    over = counts[-1] - counts[h.buckets.index(8.0)]
    assert n == eng.n_decode_steps and over == 0, \
        "a verify step scheduled more rows than max_step_tokens"
    _assert_sigs(eng)


def test_verify_step_reserves_the_shares_and_chunks_take_no_draft_row(
        tr, monkeypatch):
    """The verify step's budget, in order: the decode base rows, the chunk
    RESERVE — each filling slot's share of `prefill_chunk`, what it was
    before a chunk could take free rows —, then the drafts, and the chunks
    get their reserve plus what the drafts LEFT.  A drafter that always
    proposes k = 2 beside a prompt of 25 under 8 step rows: every verify
    step reserves 4, verifies both drafts (a chunk that took the step's
    free rows first would have left them 0), and the chunk run is 4 + the
    one row no draft wanted."""
    class Two:
        def propose(self, ctx, k):
            return np.full(k, 3, np.int32)

    rng = np.random.default_rng(12)
    short = Request("short", rng.integers(2, 23, 4).astype(np.int32),
                    max_new=24)
    long_ = Request("long", rng.integers(2, 23, 25).astype(np.int32),
                    max_new=4)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, prefill_chunk=4, max_step_tokens=8,
                        spec_k=2, drafter=Two(), prefix_cache=False)
    eng.add_request(short)
    eng.step()                        # short: final chunk + token 0
    eng.step()                        # short decodes (a verify step)
    reserves, packs = [], []
    inner_shares, inner_pack = eng._chunk_shares, eng._pack_chunk_rows

    def shares(filling, budget):
        out = inner_shares(filling, budget)
        reserves.append((budget, [n for _, n in out]))
        return out

    def pack(filling, *a):
        advanced, r1 = inner_pack(filling, *a)
        packs.append((a[-1], [n for _, n, _ in advanced]))
        return advanced, r1

    monkeypatch.setattr(eng, "_chunk_shares", shares)
    monkeypatch.setattr(eng, "_pack_chunk_rows", pack)
    eng.add_request(long_)
    done = 0
    while done < 25:
        drafted0 = eng.n_spec_drafted
        reserves.clear()
        packs.clear()
        eng.step()
        rest = 25 - done
        # the reserve is computed first, from the rows the decoders left:
        # one share, whatever the step has free
        assert reserves[0] == (7, [min(rest, 4)])
        assert eng.n_spec_drafted - drafted0 == 2, \
            "a draft row was taken by the chunk's share-out"
        # the chunks then get T - r = 8 - (1 base + 2 drafts) rows
        assert packs == [(5, [min(rest, 5)])]
        done += packs[0][1][0]
    results = dict(eng.results)
    results.update(eng.run())
    _assert_exact(tr, [short, long_], results)
    _assert_sigs(eng)


def test_spec_preempt_replay_with_drafts_in_flight_stays_exact(tr):
    """Preempt/replay under an overcommitted pool with speculation on:
    victims roll back (their chain tails uncommitted), replay through
    verify steps, and every request still bit-matches the sequential
    engine AND the oracle; the allocator balances to zero refs."""
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=11,dim=16,layers=1,heads=2,batch_size=3")
    tr2 = Trainer(cfg, seed=7)
    rng = np.random.default_rng(9)
    prompts = [_rep_prompt(rng, 11, n, motif=3) for n in (6, 4, 5, 3, 6)]
    reqs = lambda: [Request(i, p.copy(), max_new=8)
                    for i, p in enumerate(prompts)]
    kw = dict(num_slots=2, page_size=4, max_context=16, num_pages=6)
    base_eng = ServingEngine(tr2.executor, tr2.params, **kw)
    base = base_eng.run(reqs())
    assert base_eng.n_preemptions > 0, "pool was never overcommitted"
    eng = ServingEngine(tr2.executor, tr2.params, spec_k=3, **kw)
    spec = eng.run(reqs())
    assert eng.n_preemptions > 0 and eng.n_spec_drafted > 0
    for k in base:
        np.testing.assert_array_equal(base[k], spec[k], err_msg=str(k))
    assert (eng.kv._ref == 0).all()
    eng.kv.check()


# ---------------------------------------------------------------------------
# rollback accounting + the drafter interface
# ---------------------------------------------------------------------------

class _WrongDrafter:
    """Pluggable-drafter interface exercised adversarially: proposes
    tokens chosen to NEVER match what greedy sampling emits (the oracle
    tokens shifted by one in vocab), forcing full rejection of every
    chain — the maximal-rollback path."""

    def __init__(self, tr, vocab, k_always):
        self.tr, self.vocab, self.k = tr, vocab, k_always

    def propose(self, ctx, k):
        return np.full(min(k, self.k), -1 % self.vocab, np.int32)


def test_forced_full_rejection_rolls_back_pages_exactly(tr):
    """A drafter that is ALWAYS wrong: every chain rejects completely,
    every step pays the maximal uncommit_tail rollback — and the engine
    still emits the exact oracle tokens one per step (a chain with zero
    accepts degenerates to sequential decode), with the allocator
    invariants (kv.check) holding mid-flight and the pool fully
    reclaimed at the end."""
    rng = np.random.default_rng(10)

    class Wrong:
        def propose(self, ctx, k):
            # token 0 is never generated (prompts/vocab draw from 2..),
            # and greedy argmax over a softmax head never emits it for
            # this seed — verified by the exactness assert below
            return np.zeros(k, np.int32)

    reqs = [Request(i, _rep_prompt(rng, 23, 6 + i), max_new=6)
            for i in range(3)]
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, spec_k=3, drafter=Wrong())
    for r in reqs:
        eng.add_request(r)
    rolled = 0
    while eng.step():
        # mid-flight allocator oracle after every step: a bad rollback
        # (leaked tail page, freed shared page) trips here, not at the
        # end-of-workload accounting
        eng.kv.check()
    results = {k: eng.results.pop(k) for k in list(eng.results)}
    assert eng.n_spec_drafted > 0 and eng.n_spec_accepted == 0
    _assert_exact(tr, reqs, results)
    eng.kv.check_reclaimed()


def test_oracle_drafter_multiplies_steps_down(tr):
    """The throughput claim at its ceiling: a drafter that knows the
    continuation (replays a recorded greedy run) gets accept rate 1.0
    and emits max_new tokens in ~max_new/(k+1) verify steps — the
    dispatch-rate multiplication the tentpole exists for."""
    rng = np.random.default_rng(11)
    prompt = _rep_prompt(rng, 23, 9)
    probe = Request("probe", prompt.copy(), max_new=12)
    full = _oracle(tr, probe)

    class Replay:
        def propose(self, ctx, k):
            n = ctx.size
            if n < full.size and np.array_equal(full[:n], ctx):
                return full[n:n + k].astype(np.int32)
            return np.zeros(0, np.int32)

    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, spec_k=4, drafter=Replay())
    got = eng.run([Request("o", prompt.copy(), max_new=12)])["o"]
    np.testing.assert_array_equal(full, got)
    assert eng.spec_accept_rate == 1.0
    # 12 tokens: token 0 at prefill + 11 decode tokens in chains of up
    # to 5 — at most ceil(11/5)+1 = 4 steps vs 12 sequentially
    assert eng.n_decode_steps <= 5, \
        f"{eng.n_decode_steps} steps for 12 tokens at accept rate 1.0"
    # counters reconcile exactly: chain tokens = accepted + chains
    assert eng.n_spec_tokens == eng.n_spec_accepted + eng.n_spec_chains


def test_ngram_drafter_proposes_recent_continuations():
    """The default prompt-lookup drafter: longest trailing n-gram wins,
    the MOST RECENT occurrence is used, proposals never exceed k, and
    degenerate contexts propose nothing."""
    d = NgramDrafter(max_ngram=3, min_ngram=1)
    ctx = np.asarray([5, 6, 7, 9, 5, 6, 7, 8, 5, 6, 7], np.int32)
    # trailing 3-gram [5,6,7] last occurred at index 4 -> continues [8, 5]
    np.testing.assert_array_equal(d.propose(ctx, 2), [8, 5])
    # k caps the proposal
    np.testing.assert_array_equal(d.propose(ctx, 1), [8])
    # no repeat anywhere: nothing proposed
    assert d.propose(np.asarray([1, 2, 3, 4], np.int32), 3).size == 0
    # sub-2-token context: nothing proposed
    assert d.propose(np.asarray([3], np.int32), 3).size == 0
    # min_ngram respected: unigram fallback finds the last occurrence
    ctx2 = np.asarray([4, 9, 4, 2, 4], np.int32)
    np.testing.assert_array_equal(
        NgramDrafter(max_ngram=3, min_ngram=1).propose(ctx2, 1), [2])


def test_set_speculation_validates_and_toggles(tr):
    """set_speculation is the idle A/B knob: negative k rejects, the
    toggle is idle-only, and flipping spec on/off round-trips to
    identical tokens (the A/B bench's precondition)."""
    rng = np.random.default_rng(12)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32)
    with pytest.raises(ValueError, match="spec_k"):
        eng.set_speculation(-1)
    prompt = _rep_prompt(rng, 23, 10)
    off = eng.run([Request("r", prompt.copy(), max_new=6)])["r"]
    eng.set_speculation(3)
    on = eng.run([Request("r", prompt.copy(), max_new=6)])["r"]
    eng.set_speculation(0)
    off2 = eng.run([Request("r", prompt.copy(), max_new=6)])["r"]
    np.testing.assert_array_equal(off, on)
    np.testing.assert_array_equal(off, off2)
    assert eng.spec_k == 0


def test_draft_growth_never_evicts_cached_prefix_pages(tr):
    """try_grow(evict=False) — the draft-tail growth mode — takes FREE
    pages only: when the free list cannot cover the chain, the grow
    fails (the chain verifies fewer drafts) instead of invoking the
    prefix index's LRU eviction.  Optimistic pages a rejection returns
    the same step must never cost a committed cached prefix its
    retention."""
    rng = np.random.default_rng(13)
    # pool of 9 real pages, ps=4: request a commits 3 pages and donates
    # 2 whole ones to the prefix index at retire
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, num_pages=10)
    eng.run([Request("a", _rep_prompt(rng, 23, 11), max_new=2)])
    kv = eng.kv
    cached0 = kv.cached_page_count
    assert cached0 > 0, "retire donated nothing to the prefix index"
    # occupy the whole free list on slot 0
    assert kv.try_grow(0, len(kv._free) * 4)
    assert kv.free_page_count == 0
    # draft-mode growth on slot 1 must FAIL dry, not evict the cache
    assert not kv.try_grow(1, 8, evict=False)
    assert kv.cached_page_count == cached0, \
        "evict=False growth reclaimed cached prefix pages"
    # the default admission-mode growth MAY evict (the existing policy)
    assert kv.try_grow(1, 4)
    assert kv.cached_page_count < cached0
    kv.release(0)
    kv.release(1)
    kv.check()


def test_uncommit_tail_releases_only_private_tail_pages(tr):
    """paged_kv.uncommit_tail unit contract: trailing pages above the
    committed token count return to the free list, pages the committed
    span still needs stay, and the allocator oracle holds."""
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, prefix_cache=False)
    kv = eng.kv
    assert kv.try_grow(0, 14)               # 4 pages for 14 tokens
    assert int(kv._n_pages[0]) == 4
    freed = kv.uncommit_tail(0, 6)          # keep 2 pages
    assert freed == 2 and int(kv._n_pages[0]) == 2
    kv.check()
    assert kv.uncommit_tail(0, 6) == 0      # idempotent at the boundary
    kv.release(0)
    kv.check_reclaimed()


# ---------------------------------------------------------------------------
# adaptive speculation (PR 18): model drafter, dynamic k, the clamp contract
# ---------------------------------------------------------------------------

from paddle_tpu.obs.compile_watch import get_compile_watch
from paddle_tpu.serving.drafter import ModelDrafter, clamp_proposal


def test_clamp_proposal_contract():
    """The drafter-side clamp unit: at most k tokens, truncated just
    AFTER the first eos (a drafted eos may retire the slot; tokens past
    it could never be banked), eos_id=-1 disables the eos cut, and
    degenerate inputs stay empty."""
    d = np.asarray([4, 5, 6, 7, 8], np.int32)
    np.testing.assert_array_equal(clamp_proposal(d, 3), [4, 5, 6])
    # eos mid-proposal: keep the eos, drop everything after
    np.testing.assert_array_equal(clamp_proposal(d, 5, eos_id=6), [4, 5, 6])
    # eos beyond the k cut: the k clamp applies first
    np.testing.assert_array_equal(clamp_proposal(d, 2, eos_id=6), [4, 5])
    # no eos sentinel: untouched besides the k cap
    np.testing.assert_array_equal(clamp_proposal(d, 9, eos_id=-1), d)
    assert clamp_proposal(d, 0).size == 0
    assert clamp_proposal(np.zeros(0, np.int32), 4, eos_id=2).size == 0


def test_ngram_drafter_never_proposes_past_eos():
    """The eos clamp reaches the default drafter: a looked-up
    continuation containing eos truncates just after it — the bug class
    the engine's tripwire exists for (proposals past eos / past k used
    to be silently truncated, skewing accept-rate stats)."""
    d = NgramDrafter(max_ngram=2, min_ngram=1)
    # trailing [5, 6] last occurred early; its continuation is [9, 3, 8]
    ctx = np.asarray([5, 6, 9, 3, 8, 2, 5, 6], np.int32)
    np.testing.assert_array_equal(d.propose(ctx, 3), [9, 3, 8])
    # same lookup with eos=3: the proposal cuts just AFTER the eos
    np.testing.assert_array_equal(d.propose(ctx, 3, eos_id=3), [9, 3])
    # eos as the first continuation token: a one-token proposal
    np.testing.assert_array_equal(d.propose(ctx, 3, eos_id=9), [9])


def test_engine_asserts_on_drafter_clamp_violation(tr):
    """A drafter that violates the clamp contract (returns more than k
    tokens) trips the engine's assert instead of being silently
    truncated — a drafter bug must fail loudly, not masquerade as a low
    accept rate."""
    class Overlong:
        def propose(self, ctx, k):
            return np.zeros(k + 2, np.int32)

    rng = np.random.default_rng(14)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, spec_k=2, drafter=Overlong())
    with pytest.raises(AssertionError, match="clamp contract"):
        eng.run([Request("r", _rep_prompt(rng, 23, 8), max_new=6)])


@pytest.fixture(scope="module")
def self_spec_engine(tr, engines):
    """`self_spec_engine()`: the module's one self-speculating engine,
    ModelDrafter.from_target drafting for ALL slots in one batched
    dispatch, with dynamic k on."""
    drafter = ModelDrafter.from_target(tr.executor, tr.params, window=16)
    return lambda: _cold(engines(tr.executor, tr.params, spec_k=3,
                                 spec_dynamic=True, drafter=drafter, **GEOM))


@pytest.fixture(scope="module")
def self_spec(tr, engines, self_spec_engine):
    """The four sampling modes served together once by the spec-off engine
    and once by the self-speculating one: the accept path genuinely
    exercised (greedy self-drafts agree with the greedy target), and
    EXACTLY ONE serving.draft_step signature for the whole workload
    (dynamic k rides as data)."""
    def reqs():
        return {name: Request(f"m{i}", _rep_prompt(
                                  np.random.default_rng(200 + i), 23,
                                  9 + 2 * i),
                              max_new=8, rng=jax.random.PRNGKey(60 + i), **kw)
                for i, (name, kw) in enumerate(KNOBS.items())}

    base = engines(tr.executor, tr.params, **GEOM).run(
        list(reqs().values()))
    cw = get_compile_watch()
    sigs0 = cw.signature_count("serving.draft_step")
    verify0 = cw.signature_count("serving.spec_step")
    eng = self_spec_engine()
    drafts0, accepted0 = eng.n_draft_steps, eng.n_spec_accepted
    spec = eng.run(list(reqs().values()))
    assert set(base) == set(spec)
    assert eng.drafter_kind == "model"
    assert eng.n_draft_steps > drafts0 and eng.n_spec_accepted > accepted0, \
        "self-speculation never accepted a draft — greedy agreement " \
        "with the target should be near-certain"
    assert cw.signature_count("serving.draft_step") == sigs0 + 1, \
        "the batched draft dispatch must be ONE signature per (S, k)"
    assert cw.signature_count("serving.spec_step") - verify0 <= 1, \
        "dynamic k minted extra verify signatures — variable k must " \
        "ride as data"
    _assert_sigs(eng)
    eng.kv.check_reclaimed()
    return reqs(), base, spec


@pytest.mark.parametrize("knobs", sorted(KNOBS))
def test_model_drafter_self_spec_exact_and_one_signature(tr, self_spec,
                                                         knobs):
    """Self-speculation end to end: tokens bit-identical to the spec-off
    engine and the lm_generate oracle, in each sampling mode."""
    reqs, base, spec = self_spec
    r = reqs[knobs]
    np.testing.assert_array_equal(base[r.req_id], spec[r.req_id])
    _assert_exact(tr, [r], spec)


def test_model_drafter_law_across_ten_keys(tr, self_spec_engine):
    """The distributional-law matrix with the MODEL drafter: across 10
    rng keys (full-distribution and peaked alternating), the adaptive
    engine (model drafts + dynamic k) emits EXACTLY what lm_generate
    samples with the same key schedule — adaptivity never warps the
    sampling law."""
    rng = np.random.default_rng(15)
    prompt = _rep_prompt(rng, 23, 10)
    eng = self_spec_engine()
    accepted_any = 0
    for seed in range(10):
        temp = 1.0 if seed % 2 else 0.05
        r = Request(f"k{seed}", prompt.copy(), max_new=7,
                    temperature=temp, rng=jax.random.PRNGKey(seed))
        a0 = eng.n_spec_accepted
        got = eng.run([r])[r.req_id]
        accepted_any += eng.n_spec_accepted - a0
        np.testing.assert_array_equal(
            _oracle(tr, r), got,
            err_msg=f"key {seed} (temp {temp}): adaptive speculation "
                    f"diverged from lm_generate's sampling law")
    assert accepted_any > 0


def test_dynamic_k_rises_to_full_depth_under_oracle_drafter(tr):
    """Dynamic-k convergence, favorable direction: an oracle drafter
    (accept rate 1.0) starts at the cold one-row probe and the EWMA
    drives k_s to the full spec_k — and the tokens stay exact."""
    rng = np.random.default_rng(16)
    prompt = _rep_prompt(rng, 23, 6)
    probe = Request("probe", prompt.copy(), max_new=16)
    full = _oracle(tr, probe)

    class Replay:
        def propose(self, ctx, k):
            n = ctx.size
            if n < full.size and np.array_equal(full[:n], ctx):
                return full[n:n + k].astype(np.int32)
            return np.zeros(0, np.int32)

    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, spec_k=4, spec_dynamic=True,
                        drafter=Replay())
    eng.add_request(Request("o", prompt.copy(), max_new=16))
    ks = []
    while eng.step():
        for sl in eng.slots:
            if sl is not None and sl.accept_ewma is not None:
                ks.append(eng._dyn_k(sl))
    got = eng.results["o"]
    np.testing.assert_array_equal(full, got)
    assert eng.spec_accept_rate == 1.0
    assert ks and max(ks) == eng.spec_k, \
        f"EWMA never drove k to full depth (saw {sorted(set(ks))})"
    assert ks[-1] == eng.spec_k, "k did not STAY at full depth"


def test_dynamic_k_decays_to_plain_decode_under_adversarial_drafter(tr):
    """Dynamic-k convergence, hostile direction: an always-wrong drafter
    decays the slot to k=0 (plain decode — zero wasted verify rows)
    after the cold probe rejects, leaving only the paced re-probe; the
    engine must spend almost nothing on drafts while staying exact."""
    rng = np.random.default_rng(17)

    class Wrong:
        def propose(self, ctx, k):
            return np.zeros(k, np.int32)     # token 0 is never emitted

    r = Request("w", _rep_prompt(rng, 23, 5), max_new=20)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, spec_k=3, spec_dynamic=True,
                        drafter=Wrong())
    eng.add_request(r)
    saw_zero = False
    while eng.step():
        for sl in eng.slots:
            if sl is not None and sl.accept_ewma is not None:
                saw_zero |= (int(round(sl.accept_ewma * eng.spec_k)) == 0)
    _assert_exact(tr, [r], dict(eng.results))
    assert saw_zero, "the EWMA never decayed the slot to k=0"
    assert eng.n_spec_accepted == 0
    # cold probe (1 token) + at most one paced re-probe over 19 windows
    # (_PROBE_EVERY = 16) + slack: nowhere near 19 * k = 57 static waste
    assert eng.n_spec_drafted <= 4, \
        f"dynamic k kept drafting against a 0.0 accept rate " \
        f"({eng.n_spec_drafted} drafted)"
    assert eng.n_spec_steps <= 4, "most windows should be PLAIN decode"


def test_model_drafter_tp_model2_stays_exact():
    """Adaptive speculation composes with tensor parallelism: a model=2
    engine with the batched model drafter + dynamic k is
    token-for-token the single-device spec-off engine.  The drafter's
    replication contract holds regardless of construction order (it
    snapshots a mesh-free executor), and the draft program stays ONE
    signature."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (conftest provides 8)")
    from paddle_tpu.parallel.mesh import model_mesh

    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=61,dim=32,layers=2,heads=4,batch_size=4")
    tr2 = Trainer(cfg, seed=3)
    rng = np.random.default_rng(18)
    prompts = [_rep_prompt(rng, 61, n) for n in (8, 13, 6)]
    knobs = [dict(), dict(temperature=0.8, top_k=5), dict(temperature=1.1)]
    reqs = lambda: [Request(i, p.copy(), max_new=6,
                            rng=jax.random.PRNGKey(80 + i), **kw)
                    for i, (p, kw) in enumerate(zip(prompts, knobs))]
    kw = dict(num_slots=2, page_size=8, max_context=64)
    tr2.executor.mesh = None
    base = ServingEngine(tr2.executor, tr2.params, **kw).run(reqs())
    tr2.executor.mesh = None
    # drafter built BEFORE the engine stamps the mesh — the ordering
    # serve.py uses; the mesh-free snapshot must hold anyway
    drafter = ModelDrafter.from_target(tr2.executor, tr2.params, window=16)
    cw = get_compile_watch()
    sigs0 = cw.signature_count("serving.draft_step")
    eng = ServingEngine(tr2.executor, tr2.params, spec_k=3,
                        spec_dynamic=True, drafter=drafter,
                        mesh=model_mesh(2), **kw)
    spec = eng.run(reqs())
    for k in base:
        np.testing.assert_array_equal(
            base[k], spec[k],
            err_msg=f"request {k!r} diverged between single-device "
                    f"sequential and model=2 adaptive speculation")
    assert eng.tp == 2 and eng.n_draft_steps > 0
    assert cw.signature_count("serving.draft_step") == sigs0 + 1
    _assert_sigs(eng)
    tr2.executor.mesh = None


def test_set_speculation_dynamic_toggle_and_state_roundtrip(tr):
    """set_speculation(k, drafter, dynamic) is the idle A/B surface for
    the whole adaptive matrix, and the per-slot EWMA state rides
    checkpoint/restore (a restored engine resumes the learned depths
    instead of re-probing cold)."""
    rng = np.random.default_rng(19)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32)
    assert not eng.spec_dynamic
    eng.set_speculation(3, dynamic=True)
    assert eng.spec_k == 3 and eng.spec_dynamic
    eng.set_speculation(3, dynamic=False)
    assert not eng.spec_dynamic
    eng.set_speculation(
        2, drafter=ModelDrafter.from_target(tr.executor, tr.params,
                                            window=16), dynamic=True)
    assert eng.drafter_kind == "model" and eng.spec_dynamic
    # roundtrip: a mid-flight snapshot carries accept_ewma/probe_tick
    eng.add_request(Request("r", _rep_prompt(rng, 23, 8), max_new=8))
    for _ in range(3):
        eng.step()
    sl = next(s for s in eng.slots if s is not None)
    sl.probe_tick = 5                      # make the value distinctive
    snap = eng.checkpoint_state()
    eng2 = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, spec_k=2, spec_dynamic=True,
                        drafter=ModelDrafter.from_target(
                            tr.executor, tr.params, window=16))
    eng2.restore_state(snap)
    sl2 = next(s for s in eng2.slots if s is not None)
    assert sl2.accept_ewma == sl.accept_ewma
    assert sl2.probe_tick == 5
    assert eng2.n_draft_steps == eng.n_draft_steps
    got = eng2.run()["r"]
    _assert_exact(tr, [Request("r", _rep_prompt(
        np.random.default_rng(19), 23, 8), max_new=8)], {"r": got})
