"""Chunked prefill with mixed prefill/decode steps (serving/engine.py
`_launch_mixed` + ops/attention.py `ragged_paged_attention_step`).

The exactness contract is unchanged and non-negotiable: whatever the
chunk size, token budget, prefix-cache state, or preemption schedule, a
request's tokens are identical to a cold `lm_generate(use_cache=True)`
run — while the compiled-step signature set stays small and FIXED (the
one `[S, 1]` decode signature plus ONE mixed-step signature per
max_step_tokens value, and zero per-bucket prefill programs)."""

import numpy as np
import pytest

import jax

from paddle_tpu.config.parser import parse_config
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.trainer.trainer import Trainer
from tests.conftest import lm_oracle


@pytest.fixture(scope="module")
def tr():
    # layers=1 keeps every compile in this file cheap (the 2-CPU tier-1
    # budget is tight); multi-layer state threading through the chunked
    # path is covered by test_serving/test_prefix_cache, which run the
    # chunked default on layers=2 models
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=23,dim=16,layers=1,heads=2,batch_size=4")
    return Trainer(cfg, seed=7)


# the geometry of every test whose point is not a budget, a pool or a page
# size: they meet in one engine (tests/conftest.py `engines`) and read its
# counters as differences
GEOM = dict(num_slots=2, page_size=4, max_context=32, prefill_chunk=4,
            max_step_tokens=6)

def _oracle(tr, req: Request):
    return lm_oracle(tr.executor, tr.params, req)


def _assert_exact(tr, reqs, results):
    for r in reqs:
        np.testing.assert_array_equal(
            _oracle(tr, r), results[r.req_id],
            err_msg=f"request {r.req_id!r} diverged from the cold "
                    f"lm_generate oracle")


def _assert_sigs(eng):
    """The tentpole's signature discipline: one decode signature, at most
    one mixed signature — prompt length never mints a program."""
    assert eng._decode_step._cache_size() == 1
    assert eng._mixed_step._cache_size() <= 1


# ---------------------------------------------------------------------------
# the token-exactness oracle under multi-chunk prefill
# ---------------------------------------------------------------------------

KNOBS = {"greedy": dict(), "top-k": dict(temperature=0.8, top_k=5),
         "nucleus": dict(temperature=0.7, top_p=0.9),
         "full": dict(temperature=1.1)}


@pytest.fixture(scope="module")
def multi_chunk(tr, engines):
    """Prompts spanning 1..5 chunks with mixed sampling knobs, tiny chunk
    (= page size) and a tight token budget, served together once: at least
    one request decoded WHILE another was still chunking (the mixed step
    actually mixed), and the signature set is the fixed pair."""
    rng = np.random.default_rng(0)
    lens = (3, 19, 9, 17)
    reqs = {name: Request(f"r{i}", rng.integers(2, 23, n).astype(np.int32),
                          max_new=5, rng=jax.random.PRNGKey(40 + i), **kw)
            for i, (n, (name, kw)) in enumerate(zip(lens, KNOBS.items()))}
    eng = engines(tr.executor, tr.params, **GEOM)
    mixed0, chunks0 = eng.n_mixed_steps, eng.n_prefill_chunks
    results = eng.run(list(reqs.values()))
    assert eng.n_mixed_steps > mixed0 and eng.n_prefill_chunks - chunks0 >= 4
    _assert_sigs(eng)
    eng.kv.check_reclaimed()
    return reqs, results


@pytest.mark.parametrize("knobs", sorted(KNOBS))
def test_multi_chunk_prompts_stay_oracle_exact_across_knobs(tr, multi_chunk,
                                                            knobs):
    """Each of them bit-matches its cold run."""
    reqs, results = multi_chunk
    _assert_exact(tr, [reqs[knobs]], results)


def test_decode_advances_while_long_prompt_chunks(tr, engines):
    """The HOL-blocking kill shot: a short request is mid-decode when a
    long prompt admits — the short request's tokens keep advancing on
    the very steps that carry the long prompt's chunks (no stall), and
    both stay exact."""
    rng = np.random.default_rng(1)
    short = Request("short", rng.integers(2, 23, 3).astype(np.int32),
                    max_new=12)
    long_ = Request("long", rng.integers(2, 23, 25).astype(np.int32),
                    max_new=4)
    eng = engines(tr.executor, tr.params, **GEOM)
    eng.add_request(short)
    eng.step()                       # short: chunk+token0 (mixed step)
    eng.step()                       # short decoding alone
    gen_before = next(sl for sl in eng.slots if sl is not None).gen
    eng.add_request(long_)
    # 25 prompt tokens / (budget 6 - 1 decode row) = 5 chunk steps
    stalled = 0
    while any(sl is not None and sl.req is long_ and sl.gen == 0
              for sl in eng.slots) or long_ in eng.queue:
        before = eng.tokens_generated
        eng.step()
        if eng.tokens_generated == before:
            stalled += 1
    short_sl = next((sl for sl in eng.slots
                     if sl is not None and sl.req is short), None)
    assert short_sl is not None and short_sl.gen > gen_before, \
        "the decoding request stalled behind the long prompt's prefill"
    assert stalled == 0, \
        f"{stalled} steps advanced no decode token while chunking"
    results = eng.run()
    _assert_exact(tr, [short, long_], results)
    _assert_sigs(eng)


def test_step_token_budget_is_never_exceeded(tr):
    """max_step_tokens is a hard per-step bound: across a workload
    saturating every slot with multi-chunk prompts, no recorded step
    scheduled more rows than the budget (the serving_step_tokens
    histogram's +Inf bucket equals its <=budget bucket)."""
    rng = np.random.default_rng(2)
    reqs = [Request(f"r{i}", rng.integers(2, 23, 14 + i).astype(np.int32),
                    max_new=4) for i in range(6)]
    eng = ServingEngine(tr.executor, tr.params, num_slots=3, page_size=4,
                        max_context=24, prefill_chunk=8,
                        max_step_tokens=16)   # == a histogram bucket edge
    results = eng.run(reqs)
    _assert_exact(tr, reqs, results)
    h = eng.step_tokens_hist
    counts, _total, n = h._vals[()]
    over_budget = counts[-1] - counts[h.buckets.index(16.0)]
    assert n == eng.n_decode_steps and n > 0
    assert over_budget == 0, \
        "a step scheduled more rows than max_step_tokens"
    # and the budget actually bit: some step packed more than one row
    # per live slot (chunk rows rode along with decodes)
    assert eng.n_mixed_steps > 0


# ---------------------------------------------------------------------------
# chunked prefill x prefix cache (the PR-7 machinery at chunk granularity)
# ---------------------------------------------------------------------------

def test_prefix_hit_ending_mid_chunk_stays_exact(tr):
    """A cached prefix that ends MID-chunk (and mid-page): the follower's
    chunk cursor starts at the matched token count inside the COW'd
    boundary page, only the uncached remainder takes chunk rows, and the
    output bit-matches the cold run."""
    rng = np.random.default_rng(3)
    base = rng.integers(2, 23, 13).astype(np.int32)      # 3.25 pages of 4
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, prefill_chunk=8,
                        max_step_tokens=10)
    a = Request("a", base.copy(), max_new=6)
    results = eng.run([a])
    chunks_a = eng.n_prefill_chunks
    # b shares 11 of a's 13 tokens (2 full pages + 3 into the boundary
    # page — the match ends inside b's FIRST chunk), then diverges
    b_prompt = np.concatenate([base[:11], (base[11:13] + 1) % 23 + 2,
                               rng.integers(2, 23, 4)]).astype(np.int32)
    b = Request("b", b_prompt, max_new=6)
    results.update(eng.run([b]))
    assert eng.n_prefix_hits >= 1 and eng.kv.n_cow >= 1
    assert eng.prefill_tokens_saved >= 11
    # the suffix (17 - 11 = 6 tokens) fits one budget window after the
    # hit, so b paid fewer chunks than a cold 17-token prompt would
    assert eng.n_prefill_chunks - chunks_a <= 2
    # c repeats a exactly: the shared original page was never written
    c = Request("c", base.copy(), max_new=6)
    results.update(eng.run([c]))
    _assert_exact(tr, [a, b, c], results)
    _assert_sigs(eng)
    eng.kv.check_reclaimed()


def test_cow_divergence_inside_chunk_boundary_stays_exact(tr, engines):
    """COW divergence landing inside a chunk's page span: two concurrent
    followers of the same prefix, one diverging mid-page — each writes
    only its private boundary copy, both bit-match cold runs, and the
    donor page survives for a later exact repeat."""
    rng = np.random.default_rng(4)
    base = rng.integers(2, 23, 10).astype(np.int32)
    eng = engines(tr.executor, tr.params, **GEOM)
    hits0, cow0 = eng.n_prefix_hits, eng.kv.n_cow
    warm = Request("warm", base.copy(), max_new=5)
    results = eng.run([warm])
    x = Request("x", np.concatenate([base[:9], [3, 4, 5]])
                .astype(np.int32), max_new=5)
    y = Request("y", np.concatenate([base[:9], [7, 8]])
                .astype(np.int32), max_new=5)
    eng.add_request(x)
    eng.add_request(y)
    eng.step()                       # both admitted: both hit, both COW
    assert eng.n_prefix_hits - hits0 >= 2
    assert eng.kv.n_cow - cow0 >= 2, \
        "mid-page divergence never copied-on-write"
    assert eng.kv.shared_pages_in_use >= 2
    results.update(eng.run())
    again = Request("again", base.copy(), max_new=5)
    results.update(eng.run([again]))
    _assert_exact(tr, [warm, x, y, again], results)
    _assert_sigs(eng)
    eng.kv.check_reclaimed()


def test_preempt_of_half_chunked_prefill_replays_exact(tr):
    """Preempt -> replay of a request whose prefill was HALF-CHUNKED: a
    decoding slot starves for its next page while `big` is still
    chunking, so the scheduler preempts `big` MID-PREFILL (gen == 0,
    chunk cursor inside the prompt — never letting the decoder stall
    behind the remaining chunks), donates its committed whole pages, and
    its re-admission prefix-hits its own chunks — both requests finish
    bit-exact."""
    rng = np.random.default_rng(5)
    # 8 real pages, ps=4: a takes 2 (prompt 8) then grows to 4 while
    # decoding; big reserves 5 (prompt 20) at admission — a's growth at
    # pos 12 finds the pool dry while big, chunking 4 tokens per
    # 5-token-budget step, is still mid-prefill.  The preempt donates
    # big's 4 committed pages; its re-admission retries fail WITHOUT
    # evicting them (the try_grow feasibility gate) until a finishes,
    # then prefix-hit.
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=24, num_pages=9,
                        prefill_chunk=4, max_step_tokens=5)
    a = Request("a", rng.integers(2, 23, 8).astype(np.int32), max_new=8)
    big = Request("big", rng.integers(2, 23, 20).astype(np.int32),
                  max_new=3)
    eng.add_request(a)
    eng.step()                        # a: first chunk
    eng.add_request(big)
    preempted_mid_prefill = False
    for _ in range(80):
        n_pre = eng.n_preemptions
        busy = eng.step()
        if eng.n_preemptions > n_pre and big in eng.queue \
                and (big._preempted_gen or []) == []:
            preempted_mid_prefill = True
        if not busy:
            break
    results = dict(eng.results)
    results.update(eng.run())
    assert eng.n_preemptions > 0, "pool was never overcommitted"
    assert preempted_mid_prefill, \
        "big was never preempted mid-prefill — the decoder must not " \
        "stall behind a filler's remaining chunks"
    _assert_exact(tr, [a, big], results)
    # big's replay prefix-hit its own donated chunk pages
    assert eng.n_prefix_hits > 0
    assert (eng.kv._ref == 0).all()
    _assert_sigs(eng)


def test_preempt_of_decoding_slot_replays_exact_with_chunks_inflight(tr):
    """The classic decode-preempt replay, but with the mixed step in the
    loop: pressure comes from a chunking admission, the decode victim's
    stash replays through mixed steps, everything stays exact."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(2, 23, n).astype(np.int32) for n in (6, 4, 7)]
    reqs = [Request(i, p, max_new=8) for i, p in enumerate(prompts)]
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=16, num_pages=6,
                        prefill_chunk=4, max_step_tokens=6)
    results = eng.run(reqs)
    assert eng.n_preemptions > 0, "pool was never overcommitted"
    _assert_exact(tr, reqs, results)
    assert (eng.kv._ref == 0).all()
    _assert_sigs(eng)


# ---------------------------------------------------------------------------
# admission beyond the feeder-bucket grid (the bucket-ceiling fix)
# ---------------------------------------------------------------------------

def test_prompts_beyond_the_largest_feeder_bucket_admit_and_serve(tr):
    """Chunk count derives from prompt length, not a bucket ceiling: a
    prompt longer than the largest feeder bucket (512) admits, serves
    oracle-exact through ~bucketless chunk steps, and the signature set
    does NOT grow with prompt length.  Only pool capacity rejects, with
    an actionable error."""
    rng = np.random.default_rng(7)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=16,
                        max_context=576, prefill_chunk=64,
                        max_step_tokens=66)
    long_req = Request("long", rng.integers(2, 23, 520).astype(np.int32),
                       max_new=3)
    short = Request("short", rng.integers(2, 23, 5).astype(np.int32),
                    max_new=3)
    results = eng.run([long_req, short])
    _assert_exact(tr, [long_req, short], results)
    _assert_sigs(eng)
    # capacity (not bucket) is the only rejection, and it says what to do
    with pytest.raises(ValueError, match="raise max_context"):
        eng.add_request(Request("huge",
                                rng.integers(2, 23, 640).astype(np.int32),
                                max_new=3))


def test_set_chunking_validates_and_toggles(tr):
    """set_chunking is the A/B knob: budget must exceed num_slots, the
    chunk must be positive, None (the whole-prompt prefill that no longer
    exists) is refused by name, and two chunk sizes produce identical
    tokens for the same request."""
    rng = np.random.default_rng(8)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, prefix_cache=False)
    assert eng.prefill_chunk == 16 and eng.max_step_tokens == 18
    with pytest.raises(ValueError, match="must exceed num_slots"):
        eng.set_chunking(4, max_step_tokens=2)
    with pytest.raises(ValueError, match="must be positive"):
        eng.set_chunking(0)
    with pytest.raises(ValueError, match="chunked prefill is the only"):
        eng.set_chunking(None)
    assert eng.prefill_chunk == 16 and eng.max_step_tokens == 18, \
        "a refused set_chunking must leave the engine as it was"
    prompt = rng.integers(2, 23, 9).astype(np.int32)
    wide = eng.run([Request("r", prompt.copy(), max_new=5)])["r"]
    assert eng.n_prefill_chunks == 1                # 9 rows of a step's 18
    eng.set_chunking(4)
    assert eng.prefill_chunk == 4 and eng.max_step_tokens == 6
    narrow = eng.run([Request("r", prompt.copy(), max_new=5)])["r"]
    # a lone prompt takes the step's free rows, its share of 4 and the 2
    # beside it: 6 + 3 rows, where a chunk capped at 4 made ceil(9 / 4)
    assert eng.n_prefill_chunks == 1 + 2
    assert eng.n_chunk_rows == 9 + 9 and eng.n_chunk_extra_rows == 2
    np.testing.assert_array_equal(wide, narrow)


def test_unchunked_prefill_is_refused_at_construction(tr):
    """prefill_chunk=None selected the whole-prompt prefill programs; they
    are gone, and asking for them says so instead of defaulting."""
    with pytest.raises(ValueError, match="chunked prefill is the only"):
        ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                      max_context=32, prefill_chunk=None)


def test_restore_refuses_a_snapshot_taken_unchunked(tr):
    """A checkpoint whose engine ran prefill_chunk=None must not resume
    into a mode that no longer exists: the refusal names the cause, not
    a bare configuration diff."""
    kw = dict(num_slots=2, page_size=4, max_context=32)
    donor = ServingEngine(tr.executor, tr.params, **kw)
    donor.add_request(Request("r", np.arange(2, 11, dtype=np.int32),
                              max_new=4))
    donor.step()
    snap = donor.checkpoint_state()
    snap["config"]["prefill_chunk"] = None
    with pytest.raises(ValueError, match="chunked prefill is the only"):
        ServingEngine(tr.executor, tr.params, **kw).restore_state(snap)


def test_decode_mode_is_no_longer_an_argument(tr):
    """The dispatch policy is not an option: the retired argument is a
    TypeError rather than a silently ignored keyword."""
    with pytest.raises(TypeError, match="decode_mode"):
        ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                      max_context=32, decode_mode="auto")
    assert not hasattr(ServingEngine, "set_decode_mode")


def test_decode_steps_is_no_longer_an_argument(tr, engines):
    """There is one decode body a dispatch (docs/serving.md "The step
    loop"): the scanned step's argument is a TypeError, and its setter,
    counters and burst size are gone from the engine."""
    with pytest.raises(TypeError, match="decode_steps"):
        ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                      max_context=32, decode_steps=2)
    eng = engines(tr.executor, tr.params, **GEOM)
    for gone in ("set_decode_steps", "decode_steps", "n_scan_steps",
                 "n_scan_flushes", "cur_burst", "_scan_step"):
        assert not hasattr(eng, gone), gone


@pytest.mark.parametrize("length", ["chunk-1", "chunk", "chunk+1",
                                    "2*chunk+3"])
def test_chunk_boundary_lengths_exact_cold_and_on_a_prefix_hit(tr, engines,
                                                               length):
    """The lengths the whole-prompt prefill tests used, on the one path
    that remains: a prompt just under, at, just over one chunk and over
    two chunks bit-matches the cold lm_generate oracle — and so does the
    SAME prompt admitted a second time, when its leading pages are mapped
    from the prefix index and only the suffix takes chunk rows (the case
    the suffix-prefill program served)."""
    chunk, ps = 8, 4
    p = {"chunk-1": chunk - 1, "chunk": chunk, "chunk+1": chunk + 1,
         "2*chunk+3": 2 * chunk + 3}[length]
    rng = np.random.default_rng(p)
    prompt = rng.integers(2, 23, p).astype(np.int32)
    # one engine the four lengths: its counters are read as differences
    eng = engines(tr.executor, tr.params, num_slots=2, page_size=ps,
                  max_context=48, prefill_chunk=chunk)
    hits0, saved0, cow0 = (eng.n_prefix_hits, eng.prefill_tokens_saved,
                           eng.kv.n_cow)
    chunks0, rows0, extra0 = (eng.n_prefill_chunks, eng.n_chunk_rows,
                              eng.n_chunk_extra_rows)
    cold = Request("cold", prompt.copy(), max_new=6, temperature=0.8,
                   top_k=5, rng=jax.random.PRNGKey(p))
    res = eng.run([cold])
    _assert_exact(tr, [cold], res)
    assert eng.n_prefix_hits == hits0
    # nothing else runs, so the step's 10 rows (chunk + 2 slots) are all
    # free: ceil(p / 10) runs, the first longer than `prefill_chunk`
    assert eng.n_prefill_chunks - chunks0 == -(-p // eng.max_step_tokens)
    assert eng.n_chunk_rows - rows0 == p
    assert eng.n_chunk_extra_rows - extra0 == sum(
        max(0, min(10, p - at) - chunk) for at in range(0, p, 10))
    chunks0 = eng.n_prefill_chunks
    warm = Request("warm", prompt.copy(), max_new=6, temperature=0.8,
                   top_k=5, rng=jax.random.PRNGKey(p))
    res = eng.run([warm])
    _assert_exact(tr, [warm], res)
    # the retired run donated the prompt's pages and its output's, so
    # the walk matches all but the last prompt token (one always
    # prefills): the suffix is one row starting mid-page, on the copy of
    # the boundary page that reservation made
    assert eng.n_prefix_hits - hits0 == 1
    assert eng.prefill_tokens_saved - saved0 == p - 1
    assert eng.n_prefill_chunks - chunks0 == 1
    assert eng.kv.n_cow - cow0 == (1 if (p - 1) % ps else 0)
    _assert_sigs(eng)
    eng.kv.check_reclaimed()


# ---------------------------------------------------------------------------
# the share-out: prefill_chunk is a filling slot's share, the step's free
# rows go to the oldest prompt
# ---------------------------------------------------------------------------

def _spy_packing(eng, monkeypatch):
    """Record every `_pack_chunk_rows` call: (first row, budget, the runs
    as (slot, start, rows, prompt length), the packed rows' slots)."""
    calls = []
    inner = eng._pack_chunk_rows

    def spy(filling, row_ids, row_slot, row_pos, sample_row, adv, emit, r,
            budget):
        advanced, r1 = inner(filling, row_ids, row_slot, row_pos,
                             sample_row, adv, emit, r, budget)
        runs, at = [], r
        for s, n, _ in advanced:
            runs.append((s, int(row_pos[at]), n,
                         int(eng.slots[s].req.prompt_ids.size)))
            at += n
        calls.append((r, budget, runs, row_slot[r:r1].copy()))
        return advanced, r1

    monkeypatch.setattr(eng, "_pack_chunk_rows", spy)
    return calls


def _packing_kw(chunk, budget, slots) -> dict:
    return dict(num_slots=slots, page_size=4, max_context=40,
                prefill_chunk=chunk, max_step_tokens=budget,
                prefix_cache=False)


@pytest.mark.parametrize("decoding", [False, True],
                         ids=["alone", "beside-a-decode-row"])
def test_a_lone_prompt_takes_the_steps_free_rows(tr, engines, decoding,
                                                 monkeypatch):
    """One filling slot gets min(rest of its prompt, the step's free
    rows) a step — its share of 4 and every row no one else wants: 25
    prompt tokens go in 12 + 12 + 1 (11 + 11 + 3 beside one decode row)
    where a chunk capped at 4 took 7 steps; the counters say how many rows
    were given past the share and how many went empty."""
    rng = np.random.default_rng(21)
    eng = engines(tr.executor, tr.params, num_slots=3, page_size=4,
                  max_context=48, prefill_chunk=4, max_step_tokens=12,
                  prefix_cache=False)
    reqs = []
    if decoding:
        reqs.append(Request("short", rng.integers(2, 23, 3).astype(np.int32),
                            max_new=16))
        eng.add_request(reqs[0])
        eng.step()                   # short: its one chunk, token 0
        eng.step()                   # short decodes alone
    rows0, extra0, pad0, mixed0 = (eng.n_chunk_rows, eng.n_chunk_extra_rows,
                                   eng.n_step_pad_rows, eng.n_mixed_steps)
    calls = _spy_packing(eng, monkeypatch)
    long_ = Request("long", rng.integers(2, 23, 25).astype(np.int32),
                    max_new=3)
    reqs.append(long_)
    eng.add_request(long_)
    for _ in range(3):
        eng.step()
    free = 11 if decoding else 12
    want = [free, free, 25 - 2 * free]
    assert [[n for _, _, n, _ in runs] for _, _, runs, _ in calls] == \
        [[n] for n in want]
    assert [b for _, b, _, _ in calls] == [free] * 3
    assert eng.n_mixed_steps - mixed0 == 3
    assert eng.n_chunk_rows - rows0 == 25
    assert eng.n_chunk_extra_rows - extra0 == 2 * (free - 4)
    # the last step alone had rows to spare
    assert eng.n_step_pad_rows - pad0 == free - want[-1]
    results = dict(eng.results)
    results.update(eng.run())
    assert len(calls) == 3
    _assert_exact(tr, reqs, results)
    _assert_sigs(eng)


def test_two_filling_slots_get_their_share_and_the_older_the_rest(
        tr, engines, monkeypatch):
    """Two prompts of 20 admitted together under a step of 16 rows and a
    share of 4: both get 4 first, the 8 rows left go to the OLDER (12 + 4);
    then the older's last 8 and the younger's 4 + 4; then the younger's
    last 8 beside the older's decode row.  Each slot's rows are one
    contiguous run, the older's first."""
    rng = np.random.default_rng(22)
    eng = engines(tr.executor, tr.params, **_packing_kw(4, 16, 3))
    chunks0, rows0, extra0 = (eng.n_prefill_chunks, eng.n_chunk_rows,
                              eng.n_chunk_extra_rows)
    calls = _spy_packing(eng, monkeypatch)
    a = Request("a", rng.integers(2, 23, 20).astype(np.int32), max_new=4)
    b = Request("b", rng.integers(2, 23, 20).astype(np.int32), max_new=4)
    results = eng.run([a, b])
    sa, sb = 0, 1                    # admit order = slot order here
    assert [[run[:3] for run in runs] for _, _, runs, _ in calls] == [
        [(sa, 0, 12), (sb, 0, 4)],
        [(sa, 12, 8), (sb, 4, 8)],
        [(sb, 12, 8)]]
    assert calls[2][:2] == (1, 15)   # behind a's decode row
    for _, _, runs, slots in calls:
        assert slots.tolist() == [s for s, _, n, _ in runs for _ in range(n)]
    assert eng.n_prefill_chunks - chunks0 == 5
    assert eng.n_chunk_rows - rows0 == 40
    assert eng.n_chunk_extra_rows - extra0 == 8 + (4 + 4) + 4
    _assert_exact(tr, [a, b], results)
    _assert_sigs(eng)


@pytest.mark.parametrize("chunk,budget,slots", [(4, 7, 3), (4, 16, 3),
                                                (8, 24, 4), (3, 5, 4)])
def test_no_step_packs_more_than_its_budget_and_a_slot_is_one_run(
        tr, engines, chunk, budget, slots, monkeypatch):
    """Whatever the share and the step: the chunk rows end inside the step
    and inside the budget they were given, every slot appears as ONE
    contiguous run a step (the recurrent layers' packing contract), no
    slot gets less than its share while it has prompt left, a row goes
    empty only when no prompt is left to fill it — and the tokens are the
    oracle's."""
    rng = np.random.default_rng(chunk * 100 + budget)
    reqs = [Request(f"r{i}", rng.integers(2, 23, n).astype(np.int32),
                    max_new=5, rng=jax.random.PRNGKey(60 + i))
            for i, n in enumerate((13, 3, 22, 9, 17, 30, 6))]
    eng = engines(tr.executor, tr.params, **_packing_kw(chunk, budget, slots))
    rows0 = eng.n_chunk_rows
    calls = _spy_packing(eng, monkeypatch)
    results = eng.run(reqs)
    assert calls and any(n > chunk for _, _, runs, _ in calls
                         for _, _, n, _ in runs)
    for r0, b, runs, row_slots in calls:
        n_rows = sum(n for _, _, n, _ in runs)
        assert n_rows <= b and r0 + n_rows <= budget
        assert row_slots.size == n_rows
        changes = 1 + int(np.count_nonzero(np.diff(row_slots)))
        assert changes == len(runs) == len({s for s, _, _, _ in runs})
        left = b
        for s, start, n, p in runs:
            assert n >= min(chunk, left, p - start)
            left -= n
        if n_rows < b:
            assert all(start + n == p for _, start, n, p in runs)
    assert sum(n for _, _, runs, _ in calls for _, _, n, _ in runs) == \
        sum(r.prompt_ids.size for r in reqs) == eng.n_chunk_rows - rows0
    _assert_exact(tr, reqs, results)
    _assert_sigs(eng)


def test_a_run_longer_than_the_share_is_exact_on_a_prefix_hit(tr):
    """A prefix hit whose uncached suffix is LONGER than `prefill_chunk`:
    the suffix starts mid-page on the copy reservation made and goes in
    one run of 14 rows (share 4, 16 rows free) — the tokens are the cold
    oracle's, and so is an exact repeat of the donor afterwards."""
    rng = np.random.default_rng(23)
    base = rng.integers(2, 23, 13).astype(np.int32)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=40, prefill_chunk=4, max_step_tokens=16)
    a = Request("a", base.copy(), max_new=5)
    results = eng.run([a])
    assert eng.n_prefill_chunks == 1 and eng.n_chunk_extra_rows == 9
    chunks0, rows0 = eng.n_prefill_chunks, eng.n_chunk_rows
    b = Request("b", np.concatenate(
        [base[:10], rng.integers(2, 23, 14)]).astype(np.int32), max_new=5,
        temperature=0.8, top_k=5, rng=jax.random.PRNGKey(9))
    results.update(eng.run([b]))
    assert eng.n_prefix_hits == 1 and eng.prefill_tokens_saved == 10
    assert eng.kv.n_cow == 1                    # 10 % 4: mid-page
    assert eng.n_prefill_chunks - chunks0 == 1
    assert eng.n_chunk_rows - rows0 == 14
    c = Request("c", base.copy(), max_new=5)
    results.update(eng.run([c]))
    _assert_exact(tr, [a, b, c], results)
    _assert_sigs(eng)
    eng.kv.check_reclaimed()


# ---------------------------------------------------------------------------
# ops-level oracle: the ragged row path vs the per-slot decode path
# ---------------------------------------------------------------------------

def test_ragged_paged_attention_matches_per_slot_step(tr):
    """A packed row list holding one decode row per slot reproduces
    paged_attention_step exactly (same math, row-indirected), and chunk
    rows of one slot see each other's K/V under the causal mask."""
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)

    rng = np.random.default_rng(1)
    S, H, Hkv, D, ps, maxp, P = 3, 4, 2, 8, 4, 4, 12
    pos = np.asarray([5, 9, 2], np.int32)
    table = np.asarray([[4, 7, 0, 0], [2, 9, 5, 0], [11, 0, 0, 0]],
                       np.int32)

    def mk(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q, kn, vn = mk(S, 1, H, D), mk(S, 1, Hkv, D), mk(S, 1, Hkv, D)
    kp, vp = jnp.zeros((P, ps, Hkv, D)), jnp.zeros((P, ps, Hkv, D))
    for s in range(S):
        for t in range(int(pos[s])):
            kp = kp.at[table[s, t // ps], t % ps].set(mk(Hkv, D))
            vp = vp.at[table[s, t // ps], t % ps].set(mk(Hkv, D))

    want, wck, wcv = paged_attention_step(
        q, kn, vn, kp, vp, jnp.asarray(table), jnp.asarray(pos),
        use_kernel=False)
    got, gck, gcv = ragged_paged_attention_step(
        q[:, 0], kn[:, 0], vn[:, 0], kp, vp, jnp.asarray(table),
        jnp.arange(S, dtype=jnp.int32), jnp.asarray(pos),
        use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(gck), np.asarray(wck))
    np.testing.assert_array_equal(np.asarray(gcv), np.asarray(wcv))

    # intra-chunk causality: two consecutive rows of slot 2 — row 1 must
    # attend row 0's K/V written THIS call.  Oracle: run the rows one at
    # a time through the per-slot step.
    q2 = mk(2, H, D)
    kn2, vn2 = mk(2, Hkv, D), mk(2, Hkv, D)
    chunk_out, _, _ = ragged_paged_attention_step(
        q2, kn2, vn2, kp, vp, jnp.asarray(table),
        jnp.asarray([2, 2], jnp.int32),
        jnp.asarray([pos[2], pos[2] + 1], jnp.int32), use_kernel=False)
    o1, ck1, cv1 = paged_attention_step(
        q2[0][None, None], kn2[0][None, None], vn2[0][None, None],
        kp, vp, jnp.asarray(table[2:3]), jnp.asarray(pos[2:3]),
        use_kernel=False)
    o2, _, _ = paged_attention_step(
        q2[1][None, None], kn2[1][None, None], vn2[1][None, None],
        ck1, cv1, jnp.asarray(table[2:3]), jnp.asarray(pos[2:3] + 1),
        use_kernel=False)
    np.testing.assert_allclose(np.asarray(chunk_out[0]),
                               np.asarray(o1[0, 0]), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(chunk_out[1]),
                               np.asarray(o2[0, 0]), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_pallas_ragged_kernel_matches_fallback(tr):
    """Interpret-mode parity of the row-indirected Pallas kernel against
    the jnp ragged gather fallback over a mixed decode/chunk row list."""
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import ragged_paged_attention_step
    from paddle_tpu.ops.pallas_paged import paged_attention

    rng = np.random.default_rng(0)
    S, H, Hkv, D, ps, maxp = 3, 4, 2, 8, 4, 4
    P = 1 + S * maxp
    kp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
    table = np.zeros((S + 1, maxp), np.int32)   # + virtual trash row
    free = list(range(1, P))
    pos = np.asarray([6, 3, 10], np.int32)
    for s in range(S):
        for j in range(-(-int(pos[s] + 4) // ps)):
            table[s, j] = free.pop()
    # rows: slot 0 decode, slot 1 a 3-token chunk, slot 2 decode, one pad
    row_slot = np.asarray([0, 1, 1, 1, 2, S], np.int32)
    row_pos = np.asarray([pos[0], pos[1], pos[1] + 1, pos[1] + 2,
                          pos[2], 0], np.int32)
    T = row_slot.size
    q = jnp.asarray(rng.normal(size=(T, H, D)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(T, Hkv, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(T, Hkv, D)), jnp.float32)
    want, ck, cv = ragged_paged_attention_step(
        q, kn, vn, kp, vp, jnp.asarray(table), jnp.asarray(row_slot),
        jnp.asarray(row_pos), use_kernel=False)
    got = paged_attention(q, ck, cv, jnp.asarray(table),
                          jnp.asarray(row_pos) + 1,
                          row_slot=jnp.asarray(row_slot))
    real = row_slot < S
    np.testing.assert_allclose(np.asarray(got)[real],
                               np.asarray(want)[real],
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the paged kernel's row indirection over its block loop (interpret mode)
# ---------------------------------------------------------------------------

def _ragged_pools(slot_tokens, Hkv=2, D=128, ps=16, maxp=20, seed=0):
    """Pools and a table (+ the virtual all-zero trash row) whose slots
    hold pages for `slot_tokens` tokens each."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    S = len(slot_tokens)
    P = 1 + S * maxp
    kp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
    table = np.zeros((S + 1, maxp), np.int32)
    free = rng.permutation(np.arange(1, P)).tolist()
    for s, n in enumerate(slot_tokens):
        for j in range(-(-n // ps)):
            table[s, j] = free.pop()
    return rng, kp, vp, jnp.asarray(table)


# rows as (slot, position); slot 3 is the virtual trash row.  A block is
# 128 tokens at these shapes (tests/test_serving.py), so positions
# 126..130 walk a chunk or a chain across a block boundary
_ROW_CASES = {
    "chunk-rows-across-a-block": [(0, 200)] + [(1, p) for p in
                                               range(125, 131)] + [(2, 40)],
    "spec-chain-of-one-slot": [(0, 126), (0, 127), (0, 128), (0, 129),
                               (1, 9), (2, 260)],
    "padding-rows-beside-live": [(3, 0), (0, 255), (3, 0), (1, 0), (3, 0)],
    "final-row-is-padding": [(0, 128), (2, 319), (3, 0)],
    # a chunk that fills a block's LAST page (the last row range of the
    # kernel's buffer) and steps into the next block's first
    "chunk-fills-a-blocks-last-page": [(1, p) for p in range(112, 130)]
    + [(0, 255), (2, 127)],
}


@pytest.mark.parametrize("case", list(_ROW_CASES))
def test_paged_kernel_ragged_rows_match_fallback(case):
    """Chunk rows, a speculative chain and padding rows through the whole
    step (scatter, then the kernel's read) against use_kernel=False."""
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import ragged_paged_attention_step

    rng, kp, vp, table = _ragged_pools([264, 136, 320])
    rows = _ROW_CASES[case]
    row_slot = jnp.asarray([s for s, _ in rows], jnp.int32)
    row_pos = jnp.asarray([p for _, p in rows], jnp.int32)
    T = len(rows)
    q = jnp.asarray(rng.normal(size=(T, 4, 128)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(T, 2, 128)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(T, 2, 128)), jnp.float32)
    outs = [ragged_paged_attention_step(q, kn, vn, kp, vp, table, row_slot,
                                        row_pos, use_kernel=use)[0]
            for use in (True, False)]
    real = np.asarray(row_slot) < 3
    np.testing.assert_allclose(np.asarray(outs[0])[real],
                               np.asarray(outs[1])[real],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("form", ["decode", "mixed"])
def test_paged_kernel_inside_scan_matches_fallback(form):
    """The kernels inside a `lax.scan`: positions are a scan carry, so each
    body's trip counts come from run-time values — three bodies walk one
    slot from the last token of a block into the next."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)

    rng, kp, vp, table = _ragged_pools([264, 136, 320], seed=1)
    S = 3
    q = jnp.asarray(rng.normal(size=(3, S, 4, 128)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(3, S, 2, 128)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(3, S, 2, 128)), jnp.float32)
    pos0 = jnp.asarray([126, 0, 255], jnp.int32)

    def run(use_kernel):
        def body(carry, x):
            kp, vp, pos = carry
            q, kn, vn = x
            if form == "decode":
                out, kp, vp = paged_attention_step(
                    q[:, None], kn[:, None], vn[:, None], kp, vp,
                    table[:S], pos, use_kernel=use_kernel)
                out = out[:, 0]
            else:
                out, kp, vp = ragged_paged_attention_step(
                    q, kn, vn, kp, vp, table, jnp.arange(S), pos,
                    use_kernel=use_kernel)
            return (kp, vp, pos + 1), out
        return jax.lax.scan(body, (kp, vp, pos0), (q, kn, vn))[1]

    np.testing.assert_allclose(np.asarray(run(True)), np.asarray(run(False)),
                               rtol=2e-5, atol=2e-5)
