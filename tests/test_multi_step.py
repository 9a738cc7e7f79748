"""Device-resident multi-step decode (ISSUE 16 tentpole): the scanned
step's exactness and signature discipline.

The contract (docs/serving.md "Multi-step decode"): with
`decode_steps=k`, whenever every live slot is pure-decode the engine runs
ONE jitted lax.scan of k identical decode bodies — pos/gen/tokens/KV
advance on device k tokens per dispatch, eos/max_new retirement applied
by an on-device run mask INSIDE the scan — and the emitted tokens are
BIT-IDENTICAL to decode_steps=1 and to the per-request
`lm_generate(use_cache=True)` oracle, across every sampling knob, eos
mid-window, prefix hits + COW, preempt/replay, chunked prefill
coexistence (mixed steps fall back to k=1 scheduling), and model-axis
sharding.  Dispatch accounting is exact (ceil((max_new-1)/k) scanned
flushes for an undisturbed request), the steady-state scan window stages
NOTHING from the host, and each (slot count, k) is exactly ONE compiled
scan signature at the `serving.scan_step` site.
"""

import math

import numpy as np
import pytest

import jax

import paddle_tpu.serving.engine as engine_mod
from paddle_tpu.config.parser import parse_config
from paddle_tpu.graph.lm_decode import lm_generate
from paddle_tpu.obs.compile_watch import get_compile_watch
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.trainer.trainer import Trainer


def _make(args: str):
    cfg = parse_config("demo/model_zoo/transformer_lm.py", args)
    return Trainer(cfg, seed=7)


@pytest.fixture(scope="module")
def tr():
    return _make("vocab=61,dim=32,layers=2,heads=4,batch_size=4")


def _prompts(lens, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, n).astype(np.int32) for n in lens]


def _oracle(tr, req: Request):
    toks, lens = lm_generate(
        tr.executor, tr.params, req.prompt_ids[None, :],
        max_new=req.max_new, temperature=req.temperature, top_k=req.top_k,
        top_p=req.top_p, eos_id=req.eos_id, rng=req.rng, use_cache=True)
    return np.asarray(toks)[0, :int(np.asarray(lens)[0])]


def _sampled_reqs(vocab, seed=1, max_new=6):
    """The four sampling modes over mixed prompt lengths — the standard
    exactness matrix from test_serving, rebuilt fresh per run so rng keys
    never alias between the A and B engines."""
    prompts = _prompts((4, 9, 6, 11), vocab, seed=seed)
    knobs = [dict(),                                     # greedy
             dict(temperature=0.8, top_k=5),
             dict(temperature=0.7, top_p=0.9),
             dict(temperature=1.1)]                      # full sampling
    return [Request(i, p, max_new=max_new,
                    rng=jax.random.PRNGKey(100 + i), **kw)
            for i, (p, kw) in enumerate(zip(prompts, knobs))]


def _assert_equal_results(a: dict, b: dict, label: str):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(
            np.asarray(a[k]), np.asarray(b[k]),
            err_msg=f"request {k!r} diverged: {label}")


# ---------------------------------------------------------------------------
# the bit-exactness matrix: scan == k=1 == oracle
# ---------------------------------------------------------------------------


def test_scan_matches_k1_and_oracle_across_sampling_knobs(tr):
    """All four sampling modes, more requests than slots: decode_steps=4
    emits exactly the decode_steps=1 tokens, which are exactly the
    lm_generate oracle — and the whole k=4 workload compiled ONE scan
    signature while actually running scanned flushes."""
    base = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                         max_context=64)
    res_1 = base.run(_sampled_reqs(61))

    cw = get_compile_watch()
    sigs0 = cw.signature_count("serving.scan_step")
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                        max_context=64, decode_steps=4)
    reqs = _sampled_reqs(61)
    res_4 = eng.run(reqs)
    _assert_equal_results(res_1, res_4, "decode_steps=4 vs decode_steps=1")
    for r in reqs:
        np.testing.assert_array_equal(
            _oracle(tr, r), np.asarray(res_4[r.req_id]),
            err_msg=f"request {r.req_id!r} diverged from the "
                    f"lm_generate(use_cache=True) oracle under scan")
    assert eng.n_scan_flushes > 0, "multi-step never actually engaged"
    assert eng.n_scan_steps == eng.decode_steps * eng.n_scan_flushes
    assert cw.signature_count("serving.scan_step") == sigs0 + 1, \
        "one (slot count, k) must be exactly ONE scanned program"
    assert eng._scan_step._cache_size() == 1     # the jit cache agrees
    assert eng._decode_step._cache_size() <= 1   # fallback: at most one
    eng.kv.check_reclaimed()


def test_eos_mid_window_retires_on_device(tr2=None):
    """eos landing MID-window: the on-device run mask freezes the slot at
    the same token the host banking rule cuts at, later scan iterations
    write only garbage that is never read, and the freed slot refills —
    outputs stay exact and at least one request genuinely stops early."""
    tr = _make("vocab=11,dim=16,layers=1,heads=2,batch_size=3")
    prompts = _prompts((6, 4, 5, 3, 6, 4), 11, seed=3)
    t0, _ = lm_generate(tr.executor, tr.params, prompts[0][None, :],
                        max_new=1, use_cache=True)
    eos = int(np.asarray(t0)[0, prompts[0].size])
    mk = lambda: [Request(i, p, max_new=8, eos_id=eos)   # noqa: E731
                  for i, p in enumerate(prompts)]
    base = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                         max_context=32)
    res_1 = base.run(mk())
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                        max_context=32, decode_steps=3)
    reqs = mk()
    res_3 = eng.run(reqs)
    _assert_equal_results(res_1, res_3, "eos mid-window")
    assert eng.n_scan_flushes > 0
    assert any(np.asarray(res_3[r.req_id]).size
               < r.prompt_ids.size + r.max_new for r in reqs), \
        "no request hit eos early — the mid-window case never ran"
    eng.kv.check_reclaimed()


def test_ceil_dispatch_count_single_request(tr):
    """The perf claim, assertable: one undisturbed greedy request that
    emits n tokens runs exactly ceil((n-1)/k) scanned flushes (token 0
    comes from the prefill boundary), each a full k-body scan."""
    k, max_new = 4, 10
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=32,
                        max_context=64, decode_steps=k)
    req = Request("solo", _prompts((5,), 61, seed=4)[0], max_new=max_new)
    out = eng.run([req])
    np.testing.assert_array_equal(_oracle(tr, req),
                                  np.asarray(out["solo"]))
    assert eng.n_scan_flushes == math.ceil((max_new - 1) / k)
    assert eng.n_scan_steps == k * eng.n_scan_flushes
    # every scanned flush counts ONCE as a decode-advancing dispatch;
    # the +1 is the final-chunk prefill step that emitted token 0
    assert eng.n_decode_steps == eng.n_scan_flushes + 1


# ---------------------------------------------------------------------------
# staging discipline: the scan window is device-resident
# ---------------------------------------------------------------------------


class _CountingJnp:
    """Proxy for the engine module's `jnp` binding (the
    test_engine_state idiom): counts asarray calls — the host->device
    staging primitive — while delegating everything else."""

    def __init__(self, real):
        self._real = real
        self.asarray_calls = 0

    def asarray(self, *a, **kw):
        self.asarray_calls += 1
        return self._real.asarray(*a, **kw)

    def __getattr__(self, name):
        return getattr(self._real, name)


def test_steady_scan_flushes_restage_nothing(monkeypatch):
    """Across a window of scanned flushes with no admission/retire/page
    boundary, the engine performs ZERO host->device transfers — both by
    its own `n_host_stages` counter and by the jnp.asarray proxy.  The
    [k, S] token block readback is device->host and free of staging."""
    tr = _make("vocab=31,dim=16,layers=1,heads=2,batch_size=3")
    eng = ServingEngine(tr.executor, tr.params, num_slots=3, page_size=32,
                        max_context=64, decode_steps=3)
    for i, p in enumerate(_prompts((4, 4, 4), 31, seed=1)):
        eng.add_request(Request(i, p, max_new=20))
    # admit + commit every prompt, then one settling scanned flush so the
    # run mask, eos/max_new operands and slot arrays are staged + cached
    while not all(sl is not None and sl.gen >= 1 for sl in eng.slots):
        assert eng.step()
    assert eng.step()
    assert eng.n_scan_flushes >= 1, "settling step was not a scan flush"

    proxy = _CountingJnp(engine_mod.jnp)
    monkeypatch.setattr(engine_mod, "jnp", proxy)
    stages0, flushes0 = eng.n_host_stages, eng.n_scan_flushes
    for _ in range(3):
        assert eng.step()
    assert eng.n_scan_flushes == flushes0 + 3
    assert eng.n_host_stages == stages0, \
        "steady scanned flushes re-staged host arrays (pos/keys/knobs/" \
        "eos/max_new/table must live on device between boundaries)"
    assert proxy.asarray_calls == 0, \
        "a staging path bypassed the engine's _stage chokepoint"
    monkeypatch.undo()
    results = eng.run()
    assert len(results) == 3
    eng.kv.check_reclaimed()


def test_one_scan_signature_per_k(tr):
    """Each distinct k is ONE scanned program: a k=3 workload then a k=2
    workload on the same engine adds exactly two signatures at the
    serving.scan_step site, and re-running k=3 adds none."""
    cw = get_compile_watch()
    sigs0 = cw.signature_count("serving.scan_step")
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                        max_context=64, decode_steps=3)
    eng.run(_sampled_reqs(61, seed=5))
    assert cw.signature_count("serving.scan_step") == sigs0 + 1
    eng.set_decode_steps(2)              # idle: boundary by construction
    eng.run(_sampled_reqs(61, seed=6))
    assert cw.signature_count("serving.scan_step") == sigs0 + 2
    eng.set_decode_steps(3)              # back: cached, no new program
    eng.run(_sampled_reqs(61, seed=7))
    assert cw.signature_count("serving.scan_step") == sigs0 + 2
    assert eng._scan_step._cache_size() == 2     # k=3 and k=2, nothing else
    assert eng._decode_step._cache_size() <= 1   # fallback: at most one


def test_set_decode_steps_guards(tr):
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                        max_context=32)
    with pytest.raises(ValueError, match="decode_steps"):
        eng.set_decode_steps(0)
    eng.add_request(Request("x", np.asarray([3, 4, 5], np.int32),
                            max_new=4))
    with pytest.raises(AssertionError, match="idle"):
        eng.set_decode_steps(4)


# ---------------------------------------------------------------------------
# the hard scheduling boundaries: sharing, preemption, chunked prefill
# ---------------------------------------------------------------------------


def test_prefix_hits_and_cow_stay_exact_under_scan():
    """Prefix-cache hits map committed pages read-only into a scanning
    slot; the window tripwire + COW keep every scanned write on private
    pages — outputs bit-match the k=1 engine and the cold oracle."""
    tr = _make("vocab=23,dim=16,layers=2,heads=2,batch_size=4")
    rng = np.random.default_rng(0)
    system = rng.integers(2, 23, 19).astype(np.int32)   # spans 2+ pages

    def mk_reqs():
        knobs = [dict(), dict(temperature=0.8, top_k=5),
                 dict(temperature=0.7, top_p=0.9), dict(temperature=1.1)]
        r2 = np.random.default_rng(1)
        return [Request(f"r{i}",
                        np.concatenate([system,
                                        r2.integers(2, 23, 3 + i)
                                        .astype(np.int32)]),
                        max_new=5, rng=jax.random.PRNGKey(40 + i), **kw)
                for i, kw in enumerate(knobs)]

    def run(decode_steps):
        eng = ServingEngine(tr.executor, tr.params, num_slots=2,
                            page_size=8, max_context=64,
                            decode_steps=decode_steps)
        results = {}
        for r in mk_reqs():               # sequential: later requests
            results.update(eng.run([r]))  # prefix-hit earlier donations
        return eng, results

    eng1, res_1 = run(1)
    eng3, res_3 = run(3)
    _assert_equal_results(res_1, res_3, "prefix hits under scan")
    for r in mk_reqs():
        np.testing.assert_array_equal(_oracle(tr, r),
                                      np.asarray(res_3[r.req_id]))
    assert eng3.n_prefix_hits >= 3 and eng3.n_scan_flushes > 0
    eng3.kv.check_reclaimed()


def test_preempt_replay_at_boundaries_stays_exact():
    """An overcommitted pool preempts between flushes (scheduling only
    ever happens at scan boundaries); the deterministic keys[s, gen]
    schedule makes the replay invisible — k=3 output equals k=1 equals
    the oracle, and every page returns to the free list."""
    tr = _make("vocab=11,dim=16,layers=1,heads=2,batch_size=3")
    prompts = _prompts((6, 4, 5, 3, 6), 11, seed=3)
    mk = lambda: [Request(i, p, max_new=8)               # noqa: E731
                  for i, p in enumerate(prompts)]
    # 2 slots x 4 pages would want 8; give 6 (incl. trash page 0)
    base = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                         max_context=16, num_pages=6)
    res_1 = base.run(mk())
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=16, num_pages=6, decode_steps=3)
    reqs = mk()
    res_3 = eng.run(reqs)
    _assert_equal_results(res_1, res_3, "preempt/replay under scan")
    for r in reqs:
        np.testing.assert_array_equal(_oracle(tr, r),
                                      np.asarray(res_3[r.req_id]))
    assert eng.n_preemptions > 0, "pool was never actually overcommitted"
    eng.kv.check_reclaimed()


def test_chunked_prefill_coexists_mixed_steps_fall_back(tr):
    """A long prompt chunk-prefilling beside decoders: those dispatches
    are MIXED steps (never scanned); once every live slot is pure-decode
    the scan re-engages — both counters advance and outputs stay exact
    against the k=1 engine and the oracle."""
    def mk_reqs():
        prompts = _prompts((30, 5, 9), 61, seed=8)
        return [Request(i, p, max_new=6,
                        rng=jax.random.PRNGKey(200 + i),
                        **({"temperature": 0.8, "top_k": 5} if i == 1
                           else {}))
                for i, p in enumerate(prompts)]

    base = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                         max_context=64, prefill_chunk=8)
    res_1 = base.run(mk_reqs())
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                        max_context=64, prefill_chunk=8, decode_steps=4)
    reqs = mk_reqs()
    res_4 = eng.run(reqs)
    _assert_equal_results(res_1, res_4, "chunked prefill + scan")
    for r in reqs:
        np.testing.assert_array_equal(_oracle(tr, r),
                                      np.asarray(res_4[r.req_id]))
    assert eng.n_mixed_steps > 0, "the chunked prompt never mixed-stepped"
    assert eng.n_scan_flushes > 0, "scan never re-engaged after prefill"
    eng.kv.check_reclaimed()


# ---------------------------------------------------------------------------
# checkpoint/restore: flush boundaries are checkpoint boundaries
# ---------------------------------------------------------------------------


def test_save_restore_at_scan_boundary_cross_k(tmp_path, tr):
    """A snapshot taken mid-flight under k=3 restores onto a fresh k=1
    engine AND a fresh k=5 engine (decode_steps is an A/B knob, not
    engine shape — deliberately excluded from the config match) and both
    finish bit-exactly what the uninterrupted k=3 engine produces."""
    def mk_engine(k):
        return ServingEngine(tr.executor, tr.params, num_slots=2,
                             page_size=8, max_context=64,
                             decode_steps=k)

    eng_a = mk_engine(3)
    for r in _sampled_reqs(61, seed=9, max_new=8):
        eng_a.add_request(r)
    # drive to a mid-flight point where scanning has actually happened
    for _ in range(200):
        if eng_a.n_scan_flushes >= 2 and any(
                sl is not None and sl.gen >= 1 for sl in eng_a.slots):
            break
        assert eng_a.step()
    assert eng_a.n_scan_flushes >= 2, "never reached a scanned state"
    path = str(tmp_path / "scan_state.pkl")
    eng_a.save_state(path)
    while eng_a.step():
        pass
    res_a = {k: np.asarray(v) for k, v in eng_a.results.items()}

    for k_restore in (1, 5):
        eng_b = mk_engine(k_restore)
        eng_b.load_state(path)
        while eng_b.step():
            pass
        res_b = {k: np.asarray(v) for k, v in eng_b.results.items()}
        _assert_equal_results(res_a, res_b,
                              f"restore onto decode_steps={k_restore}")
        eng_b.kv.check_reclaimed()


# ---------------------------------------------------------------------------
# model-axis sharding
# ---------------------------------------------------------------------------


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 devices (conftest provides 8)")
def test_scan_matches_under_model_parallel():
    """`--mesh model=2` + decode_steps=4: the scanned step runs under the
    same shard_map as the k=1 step (the scan body appears once in the
    program, collectives and all) and the token streams are identical to
    the single-device k=1 engine."""
    from paddle_tpu.parallel.mesh import model_mesh
    tr = _make("vocab=64,dim=32,layers=2,heads=4,batch_size=4")
    tr.executor.mesh = None
    base = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                         max_context=64)
    res_1 = base.run(_sampled_reqs(64, seed=11))
    tr.executor.mesh = None
    eng = ServingEngine(tr.executor, tr.params, mesh=model_mesh(2),
                        num_slots=2, page_size=8, max_context=64,
                        decode_steps=4)
    res_tp = eng.run(_sampled_reqs(64, seed=11))
    _assert_equal_results(res_1, res_tp, "model=2 scanned decode")
    assert eng.n_scan_flushes > 0
    eng.kv.check_reclaimed()
    tr.executor.mesh = None


# ---------------------------------------------------------------------------
# speculation and the scan COMPOSE per window (PR 18)
# ---------------------------------------------------------------------------


def test_auto_mode_composes_spec_and_scan(tr):
    """With spec_k > 0 AND decode_steps > 1, the
    per-window policy routes drafted windows through the verify step and
    draft-free pure-decode windows through the scan — BOTH counters
    advance in one run, tokens stay bit-exact against the plain engine
    and the oracle, and the composition mints no extra scan or verify
    signatures (one of each)."""
    prompt = _prompts((10,), 61, seed=11)[0]

    def mk_req():
        return Request("c", prompt.copy(), max_new=20)

    full = _oracle(tr, mk_req())

    class ParityReplay:
        """Deterministic in ctx: replays the greedy continuation when the
        context length is even, proposes nothing when odd — so the engine
        alternates between verified chains and draft-free scan windows."""

        def propose(self, ctx, k):
            n = ctx.size
            if n % 2 == 0 and n < full.size and \
                    np.array_equal(full[:n], ctx):
                return full[n:n + k].astype(np.int32)
            return np.zeros(0, np.int32)

    base = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                         max_context=64)
    res_plain = base.run([mk_req()])
    cw = get_compile_watch()
    scan0 = cw.signature_count("serving.scan_step")
    spec0 = cw.signature_count("serving.spec_step")
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                        max_context=64, spec_k=2, decode_steps=3,
                        drafter=ParityReplay())
    res = eng.run([mk_req()])
    _assert_equal_results(res_plain, res, "auto spec x scan vs plain")
    np.testing.assert_array_equal(full, np.asarray(res["c"]))
    assert eng.n_spec_steps > 0, "no window ever took the verify step"
    assert eng.n_scan_flushes > 0, \
        "no draft-free window ever scanned — spec_k > 0 must not " \
        "disable multi-step"
    assert eng.n_spec_accepted > 0, "the replay chains never accepted"
    # per-engine: ONE scan program and ONE verify program carried the
    # whole composed run.  (The compile-watch site counts are global
    # and dedup identical signatures across tests, so they bound the
    # delta at <= 1 rather than == 1.)
    assert eng._scan_step._cache_size() == 1
    assert eng._spec_step._cache_size() == 1
    assert cw.signature_count("serving.scan_step") <= scan0 + 1
    assert cw.signature_count("serving.spec_step") <= spec0 + 1, \
        "composition minted extra verify signatures"
    eng.kv.check_reclaimed()


def test_admission_never_stalls_behind_scan(tr):
    """The adaptive fallback regression (PR 18 satellite): a request
    admitted MID-FLIGHT while the engine is in scanned steady state must
    start chunk-prefilling on the very next dispatch — the window falls
    back to mixed/verify scheduling instead of making the prompt wait
    out k-step scan windows.  Checked with speculation on
    AND off: no scan flush may occur while a prompt is mid-prefill."""
    for spec_k in (0, 2):
        eng = ServingEngine(tr.executor, tr.params, num_slots=2,
                            page_size=8, max_context=64, prefill_chunk=8,
                            decode_steps=4, spec_k=spec_k)
        short, long_ = _prompts((5, 30), 61, seed=13)
        eng.add_request(Request("short", short, max_new=24))
        # reach scanned steady state before the mid-flight admission
        while eng.n_scan_flushes == 0:
            assert eng.step(), "never reached the scan steady state"
        eng.add_request(Request("long", long_, max_new=4))
        chunks0, flushes0 = eng.n_prefill_chunks, eng.n_scan_flushes
        eng.step()
        assert eng.n_prefill_chunks > chunks0, \
            f"spec_k={spec_k}: the admitted prompt's first chunk did " \
            f"not ride the NEXT dispatch after admission"
        while any(sl is not None and sl.gen == 0
                  for sl in eng.slots if sl is not None):
            assert eng.n_scan_flushes == flushes0, \
                f"spec_k={spec_k}: a k-step scan ran while a prompt " \
                f"was mid-prefill (admission stalled behind the scan)"
            eng.step()
        res = eng.run()
        for r in (Request("short", short.copy(), max_new=24),
                  Request("long", long_.copy(), max_new=4)):
            np.testing.assert_array_equal(
                _oracle(tr, r), np.asarray(res[r.req_id]),
                err_msg=f"spec_k={spec_k}: {r.req_id} diverged")
        eng.kv.check_reclaimed()
