"""Continuous-batching serving engine oracles.

The exactness contract: for a mixed-length request set, per-request tokens
from the engine (paged KV + slot scheduler + per-slot sampler) EXACTLY
match `lm_generate(use_cache=True)` run on each request alone — same rng
stream, same sampler semantics, same eos early-stop — while the compiled
decode step stays at ONE jit signature for the whole workload and prompt
prefill compiles once per feeder bucket, not per length."""

import functools

import numpy as np
import pytest

import jax

from paddle_tpu.config.parser import parse_config
from paddle_tpu.graph.lm_decode import lm_generate
from paddle_tpu.serving import PagedKVCache, Request, ServingEngine
from paddle_tpu.trainer.trainer import Trainer
from tests.conftest import lm_oracle


@functools.lru_cache(maxsize=None)
def _make(args: str):
    """One Trainer a set of --config-args a module: tests of one model
    share its executor, so its engines (tests/conftest.py `engines`) and
    lm_generate's compiled programs."""
    cfg = parse_config("demo/model_zoo/transformer_lm.py", args)
    return Trainer(cfg, seed=7)


TINY = "vocab=11,dim=16,layers=1,heads=2,batch_size=3"
# two slots and pages of 4 in a context of 16, wherever the geometry is not
# the test's subject
GEOM = dict(num_slots=2, page_size=4, max_context=16, prefill_chunk=-1)


def _prompts(lens, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, n).astype(np.int32) for n in lens]


def _oracle(tr, req: Request):
    return lm_oracle(tr.executor, tr.params, req)


def _assert_pool_reclaimed(eng):
    """End-of-workload pool accounting under prefix caching — the
    allocator's own check_reclaimed oracle (free or prefix-cached-only =
    whole pool; no slot-mapped pages left)."""
    eng.kv.check_reclaimed()


def _assert_all_match(tr, reqs, results):
    for r in reqs:
        np.testing.assert_array_equal(
            _oracle(tr, r), results[r.req_id],
            err_msg=f"request {r.req_id!r} diverged from the "
                    f"lm_generate(use_cache=True) oracle")


# the 2-layer model's engine: three slots refill from six requests
WIDE = dict(num_slots=3, page_size=8, max_context=64, prefill_chunk=-1)


def test_engine_matches_per_request_oracle_greedy(engines):
    """Mixed prompt lengths and max_new across more requests than slots:
    freed slots refill mid-flight, tokens stay per-request exact, and the
    whole workload runs through ONE compiled decode signature."""
    tr = _make("vocab=61,dim=32,layers=2,heads=4,batch_size=4")
    prompts = _prompts((3, 9, 5, 12, 7, 4), 61)
    reqs = [Request(i, p, max_new=m)
            for i, (p, m) in enumerate(zip(prompts, (5, 7, 3, 6, 8, 2)))]
    eng = engines(tr.executor, tr.params, **WIDE)
    steps0 = eng.n_decode_steps
    results = eng.run(reqs)
    _assert_all_match(tr, reqs, results)
    # jit cache inspection (the test_fused_dispatch discipline): the decode
    # step compiled exactly once for the whole mixed workload
    assert eng._decode_step._cache_size() == 1
    assert eng.n_decode_steps > steps0


@pytest.mark.parametrize("extra", ["kv_heads=2", "window=5"])
def test_engine_oracle_gqa_and_window(extra):
    """Grouped-query heads and sliding-window attention flow through the
    paged read path (kv-head groups in the gather, window in the mask)
    without breaking per-request exactness."""
    tr = _make(f"vocab=97,dim=32,layers=2,heads=4,batch_size=4,{extra}")
    prompts = _prompts((3, 9, 6), 97)
    reqs = [Request(i, p, max_new=6) for i, p in enumerate(prompts)]
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                        max_context=64)
    _assert_all_match(tr, reqs, eng.run(reqs))
    assert eng._decode_step._cache_size() == 1


def test_engine_matches_per_request_oracle_sampled(engines):
    """Per-request sampling knobs (greedy / top-k / nucleus / full) and
    per-request rng keys, all inside the one compiled step."""
    tr = _make("vocab=61,dim=32,layers=2,heads=4,batch_size=4")
    prompts = _prompts((4, 9, 6, 11), 61, seed=1)
    knobs = [dict(),                                     # greedy
             dict(temperature=0.8, top_k=5),
             dict(temperature=0.7, top_p=0.9),
             dict(temperature=1.1)]                      # full sampling
    reqs = [Request(i, p, max_new=6, rng=jax.random.PRNGKey(100 + i), **kw)
            for i, (p, kw) in enumerate(zip(prompts, knobs))]
    eng = engines(tr.executor, tr.params, **WIDE)
    results = eng.run(reqs)
    _assert_all_match(tr, reqs, results)
    assert eng._decode_step._cache_size() == 1


def test_engine_eos_early_stop_refills_slots(engines):
    """eos-stopped requests retire their slot early; the freed slot admits
    the next request mid-flight and every output stays oracle-exact."""
    tr = _make(TINY)
    prompts = _prompts((6, 4, 5, 3, 6, 4), 11, seed=3)
    # eos = the first token request 0 greedily emits, so at least one
    # request is guaranteed to stop early
    t0, _ = lm_generate(tr.executor, tr.params, prompts[0][None, :],
                        max_new=1, use_cache=True)
    eos = int(np.asarray(t0)[0, prompts[0].size])
    reqs = [Request(i, p, max_new=8, eos_id=eos)
            for i, p in enumerate(prompts)]
    eng = engines(tr.executor, tr.params, **dict(GEOM, max_context=32))
    results = eng.run(reqs)
    _assert_all_match(tr, reqs, results)
    assert eng._decode_step._cache_size() == 1
    # at least one row must actually have hit eos for this test to bite
    assert any(results[r.req_id].size < r.prompt_ids.size + r.max_new
               for r in reqs)


# 2 slots x 4 pages would want 8; 5 real pages force preemption
TIGHT = dict(GEOM, num_pages=6)


def test_overcommitted_pool_preempts_and_stays_exact(engines):
    """A pool smaller than the worst case forces pauses/preemptions; the
    deterministic per-request key schedule makes them invisible in the
    output — tokens still match the oracle exactly, and every page returns
    to the free list."""
    tr = _make(TINY)
    prompts = _prompts((6, 4, 5, 3, 6), 11, seed=3)
    reqs = [Request(i, p, max_new=8) for i, p in enumerate(prompts)]
    eng = engines(tr.executor, tr.params, **TIGHT)
    preempted0 = eng.n_preemptions
    results = eng.run(reqs)
    _assert_all_match(tr, reqs, results)
    assert eng.n_preemptions > preempted0, \
        "pool was never actually overcommitted"
    _assert_pool_reclaimed(eng)
    assert eng._decode_step._cache_size() == 1


def test_request_validation(engines):
    tr = _make(TINY)
    eng = engines(tr.executor, tr.params, **GEOM)
    with pytest.raises(ValueError, match="temperature"):
        Request(0, [3, 4], max_new=4, top_k=5)
    with pytest.raises(ValueError, match="slot capacity"):
        eng.add_request(Request(0, list(range(2, 10)), max_new=12))
    # network-reachable garbage must raise, not crash the pump later
    with pytest.raises(ValueError, match="negative"):
        eng.add_request(Request(0, [3, 4], max_new=-1))
    with pytest.raises(ValueError, match="empty prompt"):
        Request(0, [], max_new=4)
    # max_new=0 resolves immediately to the prompt (lm_generate semantics)
    # — even when the prompt alone would flunk capacity/page validation,
    # since it never touches a slot or a page
    eng.add_request(Request("p", [3, 4, 5], max_new=0))
    eng.add_request(Request("big0", list(range(2, 40)), max_new=0))
    assert not eng.step()
    np.testing.assert_array_equal(eng.results["p"], [3, 4, 5])
    assert eng.results["big0"].size == 38


def test_pool_too_small_to_complete_is_rejected():
    """A request whose worst-case footprint (prompt + max_new - 1 tokens)
    exceeds the whole pool can never finish — preemption would just replay
    it forever once it is alone.  add_request must reject it up front."""
    tr = _make(TINY)
    eng = ServingEngine(tr.executor, tr.params, num_slots=1, page_size=4,
                        max_context=32, num_pages=4)   # 3 real pages
    with pytest.raises(ValueError, match="pages to complete"):
        # 4 + 16 - 1 = 19 tokens -> 5 pages > 3
        eng.add_request(Request(0, [3, 4, 5, 6], max_new=16))
    # the same footprint fits exactly -> admitted and completes
    ok = Request(1, [3, 4, 5, 6], max_new=9)           # 12 tokens -> 3 pages
    res = eng.run([ok])
    np.testing.assert_array_equal(_oracle(tr, ok), res[1])


def test_failed_admission_releases_partial_page_grab():
    """An admission attempt that grabs some pages and then starves must
    return them: a later retry can land on a DIFFERENT free slot, and
    pages stranded on the first one would leak the pool and strand the
    queued request forever."""
    tr = _make(TINY)
    # 5 real pages, ps=4: A (prompt 14 -> 4 pages, max_new=3) fills slot 0;
    # B (prompt 17 -> 5 pages, max_new=2) must wait for A, then take the
    # whole pool — regardless of which slot the retry lands on
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=20, num_pages=6)
    rng = np.random.default_rng(0)
    a = Request("a", rng.integers(2, 11, 14).astype(np.int32), max_new=3)
    b = Request("b", rng.integers(2, 11, 17).astype(np.int32), max_new=2)
    results = eng.run([a, b])
    assert set(results) == {"a", "b"}, "queued request was dropped"
    _assert_all_match(tr, [a, b], results)
    _assert_pool_reclaimed(eng)


ROOMY = dict(num_slots=2, page_size=8, max_context=32, prefill_chunk=-1)


def test_run_returns_only_its_own_completions_and_pools_stay_live(engines):
    """A long-lived engine: each run() pops exactly the requests that
    completed on its watch (no bleed from earlier workloads, no unbounded
    result archive), and kv.pools always points at live buffers (the
    donating jits must rebind it, not leave deleted aliases)."""
    tr = _make("vocab=31,dim=16,layers=1,heads=2,batch_size=4")
    prompts = _prompts((4, 7), 31, seed=6)
    eng = engines(tr.executor, tr.params, **ROOMY)
    first = eng.run([Request("a", prompts[0], max_new=3)])
    assert set(first) == {"a"}
    # the donated-and-rebound pool must still be readable
    for pool in eng.kv.pools.values():
        np.asarray(pool["k"][0, 0, 0, 0])
    second = eng.run([Request("b", prompts[1], max_new=3)])
    assert set(second) == {"b"}
    assert not eng.results, "completed results were retained after run()"


def test_cancel_inflight_frees_slot_and_pages_and_survivors_stay_exact(
        engines):
    """Client-initiated cancellation mid-flight: the victim's slot and
    pages return to the pool immediately (accounting back to baseline at
    the end), its partial tokens are an exact PREFIX of its oracle run,
    and every surviving request still matches the oracle token-for-token
    through ONE compiled decode signature."""
    tr = _make("vocab=31,dim=16,layers=1,heads=2,batch_size=4")
    prompts = _prompts((5, 9, 4, 7), 31, seed=4)
    reqs = [Request(i, p, max_new=8) for i, p in enumerate(prompts)]
    eng = engines(tr.executor, tr.params, **ROOMY)
    cancelled0 = eng.n_cancelled
    for r in reqs:
        eng.add_request(r)
    for _ in range(3):                     # get the first wave mid-flight
        eng.step()
    victim = next(sl.req.req_id for sl in eng.slots if sl is not None)
    # cancel must return the victim's pages to the pool THIS call — free
    # outright, or donated to the prefix index (cached refcount-zero =
    # reclaimable by eviction on the very next allocation)
    reclaimable_before = eng.kv.free_page_count + eng.kv.cached_page_count
    mapped_before = eng.kv.private_pages_in_use + eng.kv.shared_pages_in_use
    assert eng.cancel(victim)
    assert eng.kv.free_page_count + eng.kv.cached_page_count \
        > reclaimable_before, "cancel freed no pages"
    assert eng.kv.private_pages_in_use + eng.kv.shared_pages_in_use \
        < mapped_before, "cancel left the victim's pages slot-mapped"
    assert not eng.cancel(victim), "double-cancel must report unknown"
    assert eng.finish_reasons[victim] == "cancelled"
    partial = eng.results[victim]
    full = _oracle(tr, reqs[victim])
    np.testing.assert_array_equal(partial, full[:partial.size],
                                  err_msg="cancelled tokens are not a "
                                          "prefix of the oracle run")
    assert partial.size > reqs[victim].prompt_ids.size, \
        "victim was not actually mid-flight"
    results = eng.run()
    survivors = [r for r in reqs if r.req_id != victim]
    _assert_all_match(tr, survivors, results)
    _assert_pool_reclaimed(eng)
    assert eng._decode_step._cache_size() == 1
    assert eng.n_cancelled - cancelled0 == 1


def test_deadline_expiry_frees_pages_for_waiting_requests():
    """Deadline sweep on a deterministic step-count clock over an
    overcommitted pool: the expired request's pages are what let the
    WAITING request admit at all — after expiry it runs to completion
    oracle-exact, and the sweep reports reason 'deadline'."""
    tr = _make("vocab=31,dim=16,layers=1,heads=2,batch_size=4")
    rng = np.random.default_rng(5)
    # ps=4, 4 pages/slot, pool = 8 real pages: a and b (4 pages each once
    # decoding) fill it; c can only ever admit from freed pages
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=16, num_pages=9)
    eng.clock = lambda: float(eng.n_decode_steps)   # deterministic clock
    a = Request("a", rng.integers(2, 31, 9).astype(np.int32), max_new=7,
                deadline=3.0)                       # expires at step 3
    b = Request("b", rng.integers(2, 31, 10).astype(np.int32), max_new=6)
    c = Request("c", rng.integers(2, 31, 11).astype(np.int32), max_new=5)
    results = eng.run([a, b, c])
    assert eng.n_expired == 1
    assert set(results) == {"a", "b", "c"}
    partial = results["a"]
    np.testing.assert_array_equal(partial, _oracle(tr, a)[:partial.size])
    assert partial.size < _oracle(tr, a).size, \
        "deadline request ran to completion — never actually expired"
    _assert_all_match(tr, [b, c], results)
    _assert_pool_reclaimed(eng)
    assert eng._decode_step._cache_size() == 1


def test_cancel_and_deadline_on_queued_requests(engines, monkeypatch):
    """A queued (never-admitted) request cancels/expires cleanly: result
    is the bare prompt, no slot or page was ever touched."""
    tr = _make(TINY)
    eng = engines(tr.executor, tr.params, **dict(GEOM, num_slots=1))
    monkeypatch.setattr(eng, "clock", lambda: float(eng.n_decode_steps))
    expired0, cancelled0 = eng.n_expired, eng.n_cancelled
    run = Request("run", [3, 4, 5], max_new=4)
    q_cancel = Request("qc", [4, 5], max_new=4)
    q_expire = Request("qe", [5, 6], max_new=4,
                       deadline=eng.clock())                   # born dead
    eng.add_request(run)
    eng.add_request(q_cancel)
    eng.add_request(q_expire)
    assert eng.cancel("qc")
    np.testing.assert_array_equal(eng.results["qc"], [4, 5])
    assert eng.finish_reasons["qc"] == "cancelled"
    results = eng.run()
    np.testing.assert_array_equal(results["qe"], [5, 6])
    assert eng.n_expired - expired0 == 1
    assert eng.n_cancelled - cancelled0 == 1
    np.testing.assert_array_equal(_oracle(tr, run), results["run"])
    assert not eng.cancel("nonexistent")


def test_cancel_of_preempted_queued_request_keeps_streamed_tokens(
        engines, monkeypatch):
    """A preempted request waits in the queue with its generated-so-far
    rolled back; cancelling it THERE must still report the tokens that
    were already emitted (a front end streamed them to the client — the
    done frame has to agree with the stream) and restore the
    tokens_generated accounting the preempt rollback subtracted."""
    tr = _make(TINY)
    prompts = _prompts((6, 4, 5), 11, seed=3)
    reqs = [Request(i, p, max_new=8) for i, p in enumerate(prompts)]
    eng = engines(tr.executor, tr.params, **TIGHT)
    eng.reset_prefix_cache()        # the other test's prompts are these
    preempted0 = eng.n_preemptions
    streamed: dict = {}
    monkeypatch.setattr(eng, "on_token", lambda rid, tok, idx:
                        streamed.setdefault(rid, {}).update({idx: tok}))
    for r in reqs:
        eng.add_request(r)
    while eng.n_preemptions == preempted0 and eng.step():
        pass
    assert eng.n_preemptions > preempted0, "pool was never overcommitted"
    victim = eng.queue[0]              # preemption requeues at the front
    stash = list(victim._preempted_gen)
    assert stash, "preempted request carried no generated-token stash"
    tg_before = eng.tokens_generated
    assert eng.cancel(victim.req_id)
    toks = eng.results[victim.req_id]
    # prompt + exactly what was emitted (== what a server streamed), and
    # still a prefix of the uninterrupted oracle run
    np.testing.assert_array_equal(toks[victim.prompt_ids.size:], stash)
    seen = streamed[victim.req_id]
    np.testing.assert_array_equal(
        stash, [seen[i] for i in range(len(stash))])
    np.testing.assert_array_equal(toks, _oracle(tr, victim)[:toks.size])
    assert eng.tokens_generated == tg_before + len(stash)
    # survivors finished either during the pressure loop (still sitting in
    # eng.results) or under run() — merge both phases
    results = dict(eng.results)
    results.update(eng.run())
    survivors = [r for r in reqs if r.req_id != victim.req_id]
    _assert_all_match(tr, survivors, results)
    _assert_pool_reclaimed(eng)


def test_cancel_mid_replay_reports_all_previously_streamed_tokens(engines):
    """Preempt a request that already emitted k tokens, re-admit it, and
    cancel while the deterministic replay is still short of k: the result
    must carry all k originally-delivered tokens (replay and original are
    identical prefixes of one stream) and re-bank the not-yet-replayed
    remainder in tokens_generated."""
    tr = _make(TINY)
    eng = engines(tr.executor, tr.params, **dict(GEOM, num_slots=1))
    r = Request("r", [3, 4, 5], max_new=8)
    eng.add_request(r)
    for _ in range(3):       # mixed(chunk+token 0) + 2 decode: gen = 3
        assert eng.step()
    s = next(i for i, sl in enumerate(eng.slots) if sl is not None)
    stash = list(eng.slots[s].generated)
    assert len(stash) == 3
    eng._preempt(s)
    assert r._preempted_gen == stash
    assert eng.step()                      # re-admit; replay at gen = 2
    sl = next(sl for sl in eng.slots if sl is not None)
    assert sl.req is r and sl.gen < len(stash), "replay already caught up"
    tg = eng.tokens_generated
    behind = len(stash) - sl.gen
    assert eng.cancel("r")
    toks = eng.results["r"]
    np.testing.assert_array_equal(
        toks, np.concatenate([r.prompt_ids, np.asarray(stash, np.int32)]),
        err_msg="mid-replay cancel dropped already-delivered tokens")
    np.testing.assert_array_equal(toks, _oracle(tr, r)[:toks.size])
    assert eng.tokens_generated == tg + behind
    _assert_pool_reclaimed(eng)


def test_finish_hooks_fire_once_per_token_and_request(engines, monkeypatch):
    """on_token sees every emitted token exactly once (index = position in
    the generated stream), on_finish exactly once per request with the
    final array — the contract serving/server.py streams through."""
    tr = _make(TINY)
    eng = engines(tr.executor, tr.params, **GEOM)
    seen_toks: dict = {}
    finishes: dict = {}
    monkeypatch.setattr(eng, "on_token", lambda rid, tok, idx:
                        seen_toks.setdefault(rid, []).append((idx, tok)))
    monkeypatch.setattr(eng, "on_finish", lambda rid, toks, reason:
                        finishes.setdefault(rid, (toks, reason)))
    reqs = [Request(i, p, max_new=m) for i, (p, m) in
            enumerate(zip(_prompts((3, 5, 4), 11, seed=7), (4, 6, 1)))]
    results = eng.run(reqs)
    for r in reqs:
        toks, reason = finishes[r.req_id]
        np.testing.assert_array_equal(toks, results[r.req_id])
        assert reason in ("stop", "length")
        gen = [t for _, t in sorted(seen_toks[r.req_id])]
        idxs = [i for i, _ in sorted(seen_toks[r.req_id])]
        assert idxs == list(range(len(gen))), "token indices not dense"
        np.testing.assert_array_equal(
            gen, results[r.req_id][r.prompt_ids.size:],
            err_msg="streamed tokens disagree with the final result")


def test_paged_kv_allocator():
    """Page accounting: grow on demand, pause on exhaustion, release on
    retire; page 0 stays reserved as the trash page."""
    tr = _make(TINY)
    kv = PagedKVCache(tr.executor, num_slots=2, page_size=4,
                      pages_per_slot=3, num_pages=5)   # 4 real pages
    assert kv.free_page_count == 4
    assert kv.try_grow(0, 9)                  # 3 pages
    assert kv.pages_in_use == 3
    assert (kv.table[0, :3] > 0).all()        # never the trash page
    assert kv.try_grow(1, 4)                  # 1 page
    assert not kv.try_grow(1, 5)              # exhausted -> pause
    kv.release(0)
    assert kv.free_page_count == 3
    assert kv.try_grow(1, 8)                  # resumes after the release
    assert (kv.table[0] == 0).all()


def test_paged_attention_step_matches_cached_dense():
    """Ops-level oracle: the paged read/write path reproduces
    cached_attention_step's math on a slot whose pages are mapped
    arbitrarily (non-contiguous, interleaved across slots)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import (cached_attention_step,
                                          paged_attention_step)

    rng = np.random.default_rng(1)
    S, H, Hkv, D, ps, maxp, P = 3, 4, 2, 8, 4, 4, 12
    pos = np.asarray([5, 9, 2], np.int32)
    table = np.asarray([[4, 7, 0, 0], [2, 9, 5, 0], [11, 0, 0, 0]], np.int32)

    def mk(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q, kn, vn = mk(S, 1, H, D), mk(S, 1, Hkv, D), mk(S, 1, Hkv, D)
    kp, vp = jnp.zeros((P, ps, Hkv, D)), jnp.zeros((P, ps, Hkv, D))
    # seed each slot's mapped pages with its own history
    hist_k = [mk(int(p), Hkv, D) for p in pos]
    hist_v = [mk(int(p), Hkv, D) for p in pos]
    for s in range(S):
        for t in range(int(pos[s])):
            kp = kp.at[table[s, t // ps], t % ps].set(hist_k[s][t])
            vp = vp.at[table[s, t // ps], t % ps].set(hist_v[s][t])

    out, _, _ = paged_attention_step(q, kn, vn, kp, vp,
                                     jnp.asarray(table), jnp.asarray(pos),
                                     use_kernel=False)
    for s in range(S):
        n = int(pos[s])
        Tmax = n + 1
        ck = jnp.zeros((1, Tmax, Hkv, D)).at[0, :n].set(hist_k[s])
        cv = jnp.zeros((1, Tmax, Hkv, D)).at[0, :n].set(hist_v[s])
        want, _, _, _ = cached_attention_step(
            q[s:s + 1], kn[s:s + 1], vn[s:s + 1], ck, cv,
            jnp.asarray([n], jnp.int32), jnp.ones((1,), jnp.int32))
        np.testing.assert_allclose(np.asarray(out[s]), np.asarray(want[0]),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_pallas_paged_kernel_matches_fallback():
    """Interpret-mode parity of the ragged-paged Pallas kernel against the
    jnp gather fallback, incl. grouped-query heads and ragged lengths."""
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import paged_attention_step
    from paddle_tpu.ops.pallas_paged import paged_attention

    rng = np.random.default_rng(0)
    for (S, H, Hkv, D, ps, maxp) in [(3, 4, 2, 8, 4, 4),
                                     (2, 8, 8, 16, 8, 3),
                                     (4, 6, 3, 32, 16, 2)]:
        P = 1 + S * maxp
        kp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
        pos = rng.integers(0, maxp * ps - 1, S).astype(np.int32)
        table = np.zeros((S, maxp), np.int32)
        free = list(range(1, P))
        for s in range(S):
            for j in range(-(-int(pos[s] + 1) // ps)):
                table[s, j] = free.pop()
        q = jnp.asarray(rng.normal(size=(S, 1, H, D)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(S, 1, Hkv, D)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(S, 1, Hkv, D)), jnp.float32)
        want, ck, cv = paged_attention_step(
            q, kn, vn, kp, vp, jnp.asarray(table), jnp.asarray(pos),
            use_kernel=False)
        got = paged_attention(q[:, 0], ck, cv, jnp.asarray(table),
                              jnp.asarray(pos) + 1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=str((S, H, Hkv, D, ps, maxp)))


# ---------------------------------------------------------------------------
# the paged kernel's block loop (interpret mode) against the jnp fallback
# ---------------------------------------------------------------------------

def _paged_case(lengths, H, Hkv, D, ps, maxp, dtype, seed=0, row=None):
    """Pools, a table and queries for one kernel call: one slot a length
    (0 = a dead slot: an all-zero table row read at length 1), each live
    slot's pages scattered over the pool, a token's row stored [Hkv, D] or
    as `row` (`kv_row_shape`: the same values in the same order).  Returns
    the kernel's output and the fallback's (the decode step with
    use_kernel=False)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import paged_attention_step
    from paddle_tpu.ops.pallas_paged import paged_attention

    rng = np.random.default_rng(seed)
    S = len(lengths)
    P = 1 + S * maxp
    row = (Hkv, D) if row is None else row
    kp = jnp.asarray(rng.normal(size=(P, ps) + row), dtype)
    vp = jnp.asarray(rng.normal(size=(P, ps) + row), dtype)
    table = np.zeros((S, maxp), np.int32)
    free = rng.permutation(np.arange(1, P)).tolist()
    for s, n in enumerate(lengths):
        for j in range(-(-n // ps)):
            table[s, j] = free.pop()
    pos = np.maximum(np.asarray(lengths, np.int32) - 1, 0)
    q = jnp.asarray(rng.normal(size=(S, 1, H, D)), dtype)
    kn = jnp.asarray(rng.normal(size=(S, 1, Hkv, D)), dtype)
    vn = jnp.asarray(rng.normal(size=(S, 1, Hkv, D)), dtype)
    want, ck, cv = paged_attention_step(
        q, kn, vn, kp, vp, jnp.asarray(table), jnp.asarray(pos),
        use_kernel=False)
    got = jax.jit(paged_attention)(q[:, 0], ck, cv, jnp.asarray(table),
                                   jnp.asarray(pos) + 1)
    return np.asarray(got, np.float32), np.asarray(want[:, 0], np.float32)


# at H_kv 2, D 128, float32 a block is 128 tokens (8 pages of 16); the
# table maps 320, so the last block of a full row runs past its end
_BLOCK = 128
_LENGTH_CASES = {
    "one-token": 1,
    "block-minus-1": _BLOCK - 1,
    "one-block": _BLOCK,
    "block-plus-1": _BLOCK + 1,
    "boundary-inside-a-page-run": _BLOCK + 24,
    "two-blocks": 2 * _BLOCK,
    "full-context": 320,
}


@pytest.mark.parametrize("case", list(_LENGTH_CASES))
def test_paged_kernel_block_loop_matches_fallback(case):
    """Every trip count of the in-kernel block loop, beside a dead slot
    (one block of the trash page) and a second live row of another length:
    no token left out, none past the row's length let in."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_paged import block_tokens

    assert block_tokens(16, 2, 128, 4, 20) == _BLOCK
    got, want = _paged_case([_LENGTH_CASES[case], 0, 77], H=4, Hkv=2,
                            D=128, ps=16, maxp=20, dtype=jnp.float32)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


_SHAPE_CASES = {
    # name: (H, H_kv, D, page_size, max_pages, dtype, tolerance)
    "rep1-d64-page8": (4, 4, 64, 8, 40, "float32", 2e-5),
    "rep12-d128-page16": (24, 2, 128, 16, 20, "float32", 2e-5),
    "rep12-d128-page16-bf16": (24, 2, 128, 16, 20, "bfloat16", 2e-2),
    "rep3-d64-page16": (6, 2, 64, 16, 20, "float32", 2e-5),
    "rep2-d128-page8": (4, 2, 128, 8, 40, "float32", 2e-5),
    "one-kv-head": (4, 1, 32, 16, 20, "float32", 2e-5),
}
# a block is [pages x page_size x rows-a-token, lanes] in the kernel's VMEM
# buffer, each page's copy landing in its own row range: rows that end in
# the LAST page's range of a block, and a table of one page (a block of one
# page, `max_pages` under a block), for a pool row as the heads are, a
# packed one ([.., 4, 128], two heads of 64 a lane tile) and one KV head
_EDGE_ROWS = {"unpacked": (4, 2, 128), "packed-4x128": (16, 8, 64),
              "one-kv-head-d128": (4, 1, 128)}
_EDGE_CASES = {f"{name}-{edge}": heads + (16, maxp, "float32", 2e-5)
               for name, heads in _EDGE_ROWS.items()
               for edge, maxp in (("last-page-of-a-block", 40),
                                  ("block-of-one-page", 1))}
_SHAPE_CASES.update(_EDGE_CASES)


@pytest.mark.parametrize("case", list(_SHAPE_CASES))
def test_paged_kernel_shapes_match_fallback(case):
    """One algorithm adapted by shape: grouped-query ratios 1-12, head
    sizes under and at a lane tile, pages of 8 and 16, float32 and
    bfloat16 pools (bf16 operands, float32 accumulation — the fallback's
    precision, so only the output's rounding separates them)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_paged import block_tokens, kv_row_shape

    H, Hkv, D, ps, maxp, dtype, tol = _SHAPE_CASES[case]
    lengths, row = [1, 130, 0, 300, 256], None
    if case in _EDGE_CASES:
        row = kv_row_shape(Hkv, D)
        bt = block_tokens(ps, *row, jnp.dtype(dtype).itemsize, maxp)
        # the last page's first and last token, of the first block and of
        # the second; a row short of it; a dead row
        lengths = [bt - ps + 1, bt, 0, 2 * bt - 3, bt - ps] if maxp > 1 \
            else [1, ps, 0, ps // 2 + 1]
    got, want = _paged_case(lengths, H, Hkv, D, ps, maxp, jnp.dtype(dtype),
                            seed=1, row=row)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_paged_kernel_bounds_its_loop_by_the_table():
    """The trip count is a run-time value, so a length no table can hold
    (a corrupted position) must not become minutes of device time: the
    loop stops where the table ends and reads what a full row reads."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_paged import paged_attention

    rng = np.random.default_rng(2)
    ps, maxp, Hkv, D = 16, 20, 2, 128
    kp = jnp.asarray(rng.normal(size=(1 + maxp, ps, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(1 + maxp, ps, Hkv, D)), jnp.float32)
    table = jnp.asarray(np.arange(1, maxp + 1, dtype=np.int32)[None, :])
    q = jnp.asarray(rng.normal(size=(1, 4, D)), jnp.float32)
    outs = [np.asarray(paged_attention(q, kp, vp, table,
                                       jnp.asarray([n], jnp.int32)))
            for n in (maxp * ps, 2 ** 31 - 1)]
    np.testing.assert_array_equal(outs[0], outs[1])


# -- two kinds of page: window layers hold rings --------------------------------

def test_window_layers_hold_rings_and_wrap_them_exactly():
    """A model of window layers alone (window 5, pages of 4, 4 + 2 = 6 rows
    a step): every layer's pool is 1 + slots x ring_pages pages — the ring
    ceil((5 + 6) / 4) + 1 = 4 pages — whatever `num_pages` the allocator
    has; contexts of 30 tokens lap the ring of 16 twice and the tokens are
    lm_generate's; release, preemption's release and `uncommit_tail` have
    nothing of a ring to undo and leave `check()` clean."""
    tr = _make("vocab=97,dim=32,layers=2,heads=4,batch_size=4,window=5")
    prompts = _prompts((22, 9, 17), 97)
    reqs = [Request(i, p, max_new=8) for i, p in enumerate(prompts)]
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, prefill_chunk=4)
    kv = eng.kv
    assert eng.max_step_tokens == kv.step_tokens == 6
    assert set(kv.ring_specs.values()) == {4} and len(kv.ring_specs) == 2
    for name in kv.ring_specs:
        assert {a.shape[0] for a in kv.pools[name].values()} == {1 + 2 * 4}
    assert kv.pool_bytes_by_kind["full"] == 0
    assert kv.pool_bytes_by_kind["window"] == kv.pool_bytes
    assert eng.prefix is None
    _assert_all_match(tr, reqs, eng.run(reqs))
    assert eng.n_window_pages_recycled > 0
    kv.check_reclaimed()
    # the allocator's calls by hand: the logical table moves, a ring never
    assert kv.try_grow(0, 30) and kv.try_grow(1, 9)
    assert kv.uncommit_tail(0, 17) == 3
    kv.check()
    kv.release(0)
    kv.release(1)
    kv.check_reclaimed()
    # a cache built by hand, without a step size: whole contexts as before
    whole = PagedKVCache(tr.executor, 2, 4, 8)
    assert not whole.ring_specs
    assert {a.shape[0] for p in whole.pools.values() for a in p.values()} \
        == {whole.num_pages}
