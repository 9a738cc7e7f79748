"""EngineState pytree regressions: the zero-restaging hot path and the
mid-flight checkpoint/restore (fleet-migration) primitive.

Two contracts from the EngineState refactor:

  * a STEADY pure-decode run re-stages NOTHING from the host — pos/gen/
    last-token advance on device, keys are indexed by the device gen
    counter, and the page table re-uploads only when a host-side table
    write (admission/COW/preempt/retire/page-boundary growth) bumps
    `PagedKVCache.version`.  The engine's `_stage` chokepoint counts every
    host->device transfer, and a module-level jnp proxy double-checks no
    staging path bypasses it;
  * `checkpoint_state()` / `restore_state()` freeze an engine MID-FLIGHT
    (queued + decoding + mid-chunk-prefill slots) and a fresh engine of
    the same configuration resumes and finishes BIT-EXACTLY what the
    uninterrupted engine produces — key schedules, admit_seq preemption
    order, allocator free-list order and the prefix index all survive.
"""

import numpy as np
import pytest

import jax

import paddle_tpu.serving.engine as engine_mod
from paddle_tpu.config.parser import parse_config
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.trainer.trainer import Trainer


def _make(args: str):
    cfg = parse_config("demo/model_zoo/transformer_lm.py", args)
    return Trainer(cfg, seed=7)


def _prompts(lens, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, n).astype(np.int32) for n in lens]


class _CountingJnp:
    """Proxy for the engine module's `jnp` binding: counts asarray calls
    (the host->device staging primitive) while delegating everything
    else — compiled steps never re-trace in the steady state, so any
    count during the window is a genuine per-step transfer."""

    def __init__(self, real):
        self._real = real
        self.asarray_calls = 0

    def asarray(self, *a, **kw):
        self.asarray_calls += 1
        return self._real.asarray(*a, **kw)

    def __getattr__(self, name):
        return getattr(self._real, name)


def test_pure_decode_steps_restage_nothing(monkeypatch):
    """The satellite regression: across a window of pure-decode steps
    with no admission/retire/pause and no page-boundary crossing, the
    engine performs ZERO host->device transfers — both by its own
    `n_host_stages` counter and by the jnp.asarray proxy."""
    tr = _make("vocab=31,dim=16,layers=1,heads=2,batch_size=3")
    # page_size 32: after the 4-token prompts commit, decode positions
    # 4..31 stay inside the first page — no try_grow allocation (and so
    # no table-version bump) for the whole window
    eng = ServingEngine(tr.executor, tr.params, num_slots=3, page_size=32,
                        max_context=64)
    for i, p in enumerate(_prompts((4, 4, 4), 31, seed=1)):
        eng.add_request(Request(i, p, max_new=20))
    # admit + commit every prompt (mixed steps), then one settling PURE
    # decode step so the run mask and slot arrays are staged and cached
    while not all(sl is not None and sl.gen >= 1 for sl in eng.slots):
        assert eng.step()
    assert eng.step()

    proxy = _CountingJnp(engine_mod.jnp)
    monkeypatch.setattr(engine_mod, "jnp", proxy)
    stages0 = eng.n_host_stages
    steps0 = eng.n_decode_steps
    for _ in range(8):
        assert eng.step()
    assert eng.n_decode_steps == steps0 + 8
    assert eng.n_host_stages == stages0, \
        "pure-decode steps re-staged host arrays (pos/keys/knobs/table " \
        "must live on device between scheduling boundaries)"
    assert proxy.asarray_calls == 0, \
        "a staging path bypassed the engine's _stage chokepoint"
    monkeypatch.undo()
    # the window changed nothing semantically: drain and check exactness
    results = eng.run()
    assert len(results) == 3
    eng.kv.check_reclaimed()


def test_boundary_events_do_restage_and_stay_exact():
    """The inverse guard: an admission mid-flight (a genuine scheduling
    boundary) DOES re-stage the slot arrays — the dirty-flag system must
    not under-sync — and the workload stays exact end to end."""
    tr = _make("vocab=31,dim=16,layers=1,heads=2,batch_size=3")
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=32,
                        max_context=64)
    prompts = _prompts((4, 4, 4), 31, seed=2)
    for i in (0, 1):
        eng.add_request(Request(i, prompts[i], max_new=12))
    while not all(sl is not None and sl.gen >= 1 for sl in eng.slots):
        assert eng.step()
    assert eng.step()
    stages0 = eng.n_host_stages
    eng.add_request(Request(2, prompts[2], max_new=4))   # no free slot:
    eng.step()                                           # stays queued
    queued_stages = eng.n_host_stages
    while eng.step():
        pass
    assert eng.n_host_stages > stages0, \
        "the mid-flight admission/retire boundary never re-synced"
    assert queued_stages >= stages0, "queued-only admission is host-side"
    assert len(eng.results) == 3


def _drive_until(eng, pred, cap=200):
    for _ in range(cap):
        if pred():
            return
        assert eng.step(), "engine went idle before reaching the staged " \
                           "scenario"
    raise AssertionError("scenario never reached")


def _mk_engine(tr):
    return ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                         max_context=64, prefill_chunk=8)


def _mk_requests():
    rng = np.random.default_rng(9)
    mk = lambda n: rng.integers(2, 61, n).astype(np.int32)  # noqa: E731
    return [
        Request("dec", mk(5), max_new=10,
                temperature=0.8, top_k=5, rng=jax.random.PRNGKey(3)),
        Request("chunky", mk(30), max_new=8,
                temperature=0.7, top_p=0.9, rng=jax.random.PRNGKey(4)),
        Request("q1", mk(9), max_new=6),
        Request("q2", mk(12), max_new=5, temperature=1.1,
                rng=jax.random.PRNGKey(5)),
    ]


def test_checkpoint_restore_midflight_is_bit_exact(tmp_path):
    """The fleet-migration smoke: freeze an engine holding a DECODING
    slot, a MID-CHUNK-PREFILL slot and two QUEUED requests; a fresh
    engine restored from the (file-roundtripped) snapshot finishes every
    request with exactly the tokens the uninterrupted engine produces."""
    tr = _make("vocab=61,dim=32,layers=2,heads=4,batch_size=4")

    # --- uninterrupted reference run, snapshotting mid-flight ----------
    eng_a = _mk_engine(tr)
    for r in _mk_requests():
        eng_a.add_request(r)

    def staged():
        # slot holding a decoder + a slot still chunking + queue nonempty
        modes = [sl.gen if sl is not None else None for sl in eng_a.slots]
        return (any(g is not None and g >= 1 for g in modes)
                and any(g == 0 for g in modes) and len(eng_a.queue) > 0)

    _drive_until(eng_a, staged)
    chunking = [sl.req.req_id for sl in eng_a.slots
                if sl is not None and sl.gen == 0]
    assert chunking, "no mid-chunk prefill at snapshot time"
    assert any(0 < sl.pos < sl.req.prompt_ids.size for sl in eng_a.slots
               if sl is not None and sl.gen == 0), \
        "the chunking slot had not committed a partial prompt yet"
    path = str(tmp_path / "engine_state.pkl")
    eng_a.save_state(path)
    while eng_a.step():
        pass
    results_a = {k: np.asarray(v) for k, v in eng_a.results.items()}
    assert set(results_a) == {"dec", "chunky", "q1", "q2"}

    # --- fresh engine, restored, resumed --------------------------------
    eng_b = _mk_engine(tr)
    eng_b.load_state(path)
    while eng_b.step():
        pass
    results_b = {k: np.asarray(v) for k, v in eng_b.results.items()}
    assert set(results_b) == set(results_a)
    for k in results_a:
        np.testing.assert_array_equal(
            results_a[k], results_b[k],
            err_msg=f"request {k!r} diverged after mid-flight restore")
    assert eng_b.finish_reasons == eng_a.finish_reasons
    eng_b.kv.check_reclaimed()


def test_restore_guards_config_and_idleness():
    """A snapshot must only land on an idle engine of the SAME shape —
    page accounting silently corrupts otherwise, so both misuses raise
    actionable ValueErrors."""
    tr = _make("vocab=31,dim=16,layers=1,heads=2,batch_size=3")
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                        max_context=32)
    snap = eng.checkpoint_state()
    other = ServingEngine(tr.executor, tr.params, num_slots=3, page_size=8,
                          max_context=32)
    with pytest.raises(ValueError, match="configuration mismatch"):
        other.restore_state(snap)
    busy = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                         max_context=32)
    busy.add_request(Request("x", np.asarray([3, 4, 5], np.int32),
                             max_new=4))
    with pytest.raises(ValueError, match="idle"):
        busy.restore_state(busy.checkpoint_state())


def test_a_snapshot_with_the_old_scan_counters_restores_exactly():
    """A snapshot written before the scanned step went carries
    `n_scan_steps` / `n_scan_flushes` among its counters: it still restores
    — mid-flight, to the tokens the uninterrupted engine serves — and the
    two keys become no attribute of the engine (`restore_state` sets the
    counters `checkpoint_state` writes and no other)."""
    tr = _make("vocab=31,dim=16,layers=1,heads=2,batch_size=3")
    kw = dict(num_slots=2, page_size=8, max_context=32)
    reqs = lambda: [Request("a", np.asarray([3, 4, 5, 6, 7], np.int32),
                            max_new=7),
                    Request("b", np.asarray([9, 8, 7], np.int32), max_new=5)]
    donor = ServingEngine(tr.executor, tr.params, **kw)
    for r in reqs():
        donor.add_request(r)
    for _ in range(3):
        donor.step()
    snap = donor.checkpoint_state()
    assert not {"n_scan_steps", "n_scan_flushes"} & snap["counters"].keys()
    assert snap["counters"].keys() == set(ServingEngine._SNAPSHOT_COUNTERS)
    snap["counters"].update(n_scan_steps=8, n_scan_flushes=2)
    while donor.step():
        pass
    eng = ServingEngine(tr.executor, tr.params, **kw)
    eng.restore_state(snap)
    assert not hasattr(eng, "n_scan_steps")
    assert not hasattr(eng, "n_scan_flushes")
    assert eng.n_decode_steps == snap["counters"]["n_decode_steps"] == 3
    while eng.step():
        pass
    assert set(eng.results) == {"a", "b"}
    for rid, toks in donor.results.items():
        np.testing.assert_array_equal(toks, eng.results[rid])
    assert eng.tokens_generated == donor.tokens_generated == 12
    eng.kv.check_reclaimed()
