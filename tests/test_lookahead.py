"""One step in flight (docs/serving.md "The step loop").

A decode or mixed step is two halves, LAUNCH and LAND.  At `lookahead` 1
(what `ServingServer` sets) `step()` launches step N+1 before it lands step
N; at 0 (the engine's default) it lands each step where it launched it.  The
contract here: the two depths bank the SAME tokens, finish reasons, counts
and prefix-cache donations — the host merely learns them one call later —
and both match `lm_generate` run on each request alone."""

import os
import time

import numpy as np
import pytest

import jax

from paddle_tpu.config.parser import parse_config
from paddle_tpu.obs import Tracer
from paddle_tpu.obs.flight import FlightRecorder
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.serving.client import ServerError, ServingClient
from paddle_tpu.serving.server import ServingServer
from paddle_tpu.trainer.trainer import Trainer
from tests.conftest import lm_oracle

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tr():
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=61,dim=32,layers=2,heads=4,batch_size=4")
    return Trainer(cfg, seed=7)


def _engine(tr, depth, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_step_tokens", 12)
    eng = ServingEngine(tr.executor, tr.params, **kw)
    eng.lookahead = depth
    eng.clock = lambda: float(eng.n_decode_steps)
    return eng


def _shared(engines, tr, depth, **kw):
    """The module's ONE engine of these arguments (tests/conftest.py
    `engines`), handed over as a fresh one is — allocator and prefix index
    cold, no hooks — at `depth`: both depths run the same compiled steps,
    and a test reads its counters as differences (`_counts`, `_grew`)."""
    kw = {"num_slots": 3, "page_size": 8, "max_context": 64,
          "prefill_chunk": 8, "max_step_tokens": 12, **kw}
    eng = engines(tr.executor, tr.params, **kw)
    return _handed_over(eng, depth)


def _handed_over(eng, depth):
    eng.reset_prefix_cache()
    eng.on_token = eng.on_finish = None
    eng.lookahead = depth
    eng.clock = lambda: float(eng.n_decode_steps)
    return eng


COUNTS = ("n_lookahead_steps", "n_lookahead_dropped_rows", "n_mixed_steps",
          "n_decode_steps", "tokens_generated", "n_preemptions",
          "n_cancelled", "n_expired", "moe_steps", "moe_pairs_total",
          "moe_pairs_max_sum", "recurrent_steps", "recurrent_slot_updates")


def _counts(eng) -> dict:
    return {k: getattr(eng, k) for k in COUNTS}


def _grew(eng, before: dict) -> dict:
    return {k: getattr(eng, k) - v for k, v in before.items()}


def _requests(eos=-1, temperature=0.0, lens=(3, 9, 5, 12, 7, 4, 30, 2),
              max_new=(5, 7, 3, 6, 8, 2, 9, 1), **kw):
    rng = np.random.default_rng(0)
    return [Request(f"r{i}", rng.integers(2, 61, n).astype(np.int32),
                    max_new=m, eos_id=eos, temperature=temperature,
                    top_k=5 if temperature else 0,
                    rng=jax.random.PRNGKey(40 + i), **kw)
            for i, (n, m) in enumerate(zip(lens, max_new))]


def _record(eng):
    """Everything a front end sees: (req, token, index) per emitted token
    and (req, tokens, reason) per finish, in order."""
    seen = {"tokens": [], "done": []}
    eng.on_token = lambda rid, tok, i: seen["tokens"].append((rid, tok, i))
    eng.on_finish = lambda rid, toks, why: seen["done"].append(
        (rid, np.asarray(toks).tolist(), why))
    return seen


def _per_request(seen):
    out = {}
    for rid, tok, i in seen["tokens"]:
        out.setdefault(rid, []).append((i, tok))
    return out, {rid: (toks, why) for rid, toks, why in seen["done"]}


# -- (a) decode + mixed steps, staggered admissions --------------------------

@pytest.fixture(scope="module")
def banked(tr, engines):
    """`banked(temperature)`: the default requests through both depths, run
    once a temperature — per depth the results, what a front end saw, and
    what the counters grew by."""
    done = {}

    def run(temperature):
        if temperature not in done:
            got = {}
            for depth in (0, 1):
                eng = _shared(engines, tr, depth)
                seen, before = _record(eng), _counts(eng)
                reqs = _requests(temperature=temperature)
                got[depth] = (eng.run(reqs), _per_request(seen),
                              _grew(eng, before))
                assert eng._pending is None         # run() ends landed
                eng.kv.check_reclaimed()
            done[temperature] = (reqs, got)
        return done[temperature]
    return run


TEMPERATURES = pytest.mark.parametrize("temperature", [0.0, 0.9],
                                       ids=["greedy", "sampled"])


@TEMPERATURES
@pytest.mark.parametrize("i", range(8), ids=lambda i: f"r{i}")
def test_depths_bank_the_same_tokens_as_lm_generate(tr, banked, temperature,
                                                    i):
    """More requests than slots, prompts longer than a chunk: slots refill
    mid-flight, decode rows and chunk rows share steps — and each request's
    tokens, frames and done frame are `lm_generate`'s at either depth."""
    reqs, got = banked(temperature)
    r = reqs[i]
    want = lm_oracle(tr.executor, tr.params, r)
    for depth in (0, 1):
        np.testing.assert_array_equal(want, got[depth][0][r.req_id])
    for what in (0, 1):                 # its token frames, its done frame
        assert got[0][1][what][r.req_id] == got[1][1][what][r.req_id]


@TEMPERATURES
def test_depth_1_launches_nearly_every_step_beside_the_one_before(
        banked, temperature):
    _, got = banked(temperature)
    assert got[0][1] == got[1][1]       # per request: frames and done alike
    n0, n1 = got[0][2], got[1][2]
    assert n0["n_lookahead_steps"] == n0["n_lookahead_dropped_rows"] == 0
    assert n1["n_mixed_steps"] > 2
    assert n1["n_decode_steps"] > n1["n_mixed_steps"]
    # every launch but the first of a burst found a step in flight
    assert n1["n_lookahead_steps"] >= n1["n_decode_steps"] - 2
    assert n1["n_lookahead_dropped_rows"] == 0  # max_new is known at launch
    assert n1["tokens_generated"] == n0["tokens_generated"]


def test_a_direct_caller_of_step_sees_what_it_banked(tr, engines):
    """The engine's default keeps the contract `run`, the tools and a dozen
    test files rely on: after step() returns, its tokens are banked."""
    assert ServingEngine(tr.executor, tr.params).lookahead == 0
    eng = _shared(engines, tr, depth=0)
    eng.add_request(Request("a", [3, 4, 5], max_new=4))
    t0 = before = eng.tokens_generated
    while eng.step():
        assert eng._pending is None
        assert eng.tokens_generated == before + 1
        before += 1
    assert before - t0 == 4


# -- (b) eos while the next row is in flight ---------------------------------

def test_eos_with_the_next_row_in_flight_drops_that_row(tr, engines,
                                                        monkeypatch):
    """A request that ends on eos at land N has a row in step N+1: that row
    is dropped — not banked, not emitted, not counted — and the frames, the
    done frame, tokens_generated and the prefix-cache donation are depth
    0's."""
    plain = _shared(engines, tr, 0).run(
        _requests(lens=(9, 12, 5), max_new=(9, 9, 9)))
    eos = int(plain["r0"][9 + 3])       # r0's 4th generated token
    got = {}
    for depth in (0, 1):
        eng = _shared(engines, tr, depth)
        seen, before = _record(eng), _counts(eng)
        reqs = _requests(eos=eos, lens=(9, 12, 5), max_new=(9, 9, 9))
        flight = FlightRecorder()
        flight.enabled = True
        monkeypatch.setattr(eng, "flight", flight)
        res = eng.run(reqs)
        got[depth] = (res, _per_request(seen), _grew(eng, before),
                      (eng.prefix.n_nodes, eng.kv.cached_page_count),
                      [e["kind"] for e in flight.snapshot()])
        for r in reqs:
            np.testing.assert_array_equal(
                lm_oracle(tr.executor, tr.params, r), res[r.req_id])
        eng.kv.check_reclaimed()
    assert got[0][1] == got[1][1]
    assert got[1][1][1]["r0"][1] == "stop"
    assert len(got[1][0]["r0"]) <= 9 + 4
    n0, n1 = got[0][2], got[1][2]
    stops = sum(1 for _, why in got[1][1][1].values() if why == "stop")
    assert n1["n_lookahead_dropped_rows"] == stops >= 1
    assert n0["n_lookahead_dropped_rows"] == 0
    assert n1["tokens_generated"] == n0["tokens_generated"]
    # what retirement donated to the prefix index is the same pages' worth
    assert got[1][3] == got[0][3]
    # one flight event a drop, none a step
    kinds = got[1][4]
    assert kinds.count("lookahead_drop") == stops
    assert sorted(k for k in kinds if k != "lookahead_drop") == \
        sorted(got[0][4])


# -- (c) cancel and deadline with a step pending -----------------------------

@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_abort_with_a_step_pending_lands_it_first(tr, engines, how):
    """After k calls depth 1 has launched k steps and landed k-1; an abort
    lands the k-th first, so it reports the tokens depth 0 reports."""
    got = {}
    for depth in (0, 1):
        eng = _shared(engines, tr, depth, num_slots=2)
        seen, before = _record(eng), _counts(eng)
        # the clock counts the engine's steps: 5 of them from here
        eng.add_request(Request(
            "work", [3, 4, 5, 6], max_new=30,
            deadline=eng.clock() + 5.0 if how == "deadline" else None))
        eng.add_request(Request("other", [7, 8, 9], max_new=12))
        for _ in range(5):
            eng.step()
        assert (eng._pending is not None) == (depth == 1)
        if how == "cancel":
            assert eng.cancel("work") is True
            assert eng._pending is None
        else:
            eng.step()                  # the sweep expires it
        assert eng.finish_reasons["work"] == \
            ("cancelled" if how == "cancel" else "deadline")
        res = eng.run()
        n = _grew(eng, before)
        got[depth] = (res, _per_request(seen), n["tokens_generated"],
                      n["n_cancelled"], n["n_expired"])
        np.testing.assert_array_equal(
            res["other"], lm_oracle(tr.executor, tr.params,
                                  Request("other", [7, 8, 9], max_new=12)))
        eng.kv.check_reclaimed()
    assert got[0][1:] == got[1][1:]
    toks, why = got[1][1][1]["work"]            # its done frame
    assert len(toks) == 4 + 5                   # prompt + 5 steps' tokens


# -- (d) a wedged pool preempts ----------------------------------------------

def test_a_wedged_pool_lands_before_it_preempts(tr, engines):
    """Two requests that cannot both finish in 5 pages: the wedge lands
    the step in flight, preempts the youngest, and the replay is exact."""
    got = {}
    for depth in (0, 1):
        eng = _shared(engines, tr, depth, num_slots=2, page_size=4,
                      max_context=16, num_pages=6, prefill_chunk=4,
                      max_step_tokens=8)
        seen, before = _record(eng), _counts(eng)
        reqs = _requests(lens=(8, 8), max_new=(8, 8))
        results = eng.run(reqs)
        got[depth] = (results, _per_request(seen)[1], _grew(eng, before))
        assert got[depth][2]["n_preemptions"] > 0, \
            "pool was never overcommitted"
        for r in reqs:
            np.testing.assert_array_equal(
                lm_oracle(tr.executor, tr.params, r), got[depth][0][r.req_id])
        eng.kv.check_reclaimed()
    assert got[0][1] == got[1][1]
    assert got[1][2]["n_lookahead_steps"] > 0
    assert got[1][2]["tokens_generated"] == \
        got[0][2]["tokens_generated"] == 16


# -- (e) slot state and the counts behind the tokens -------------------------

@pytest.mark.parametrize("family", ["kimi_linear", "lfm2_moe", "gigachat3"])
def test_recurrent_and_moe_models_ride_the_same_tokens(family, engines):
    """KDA state, short-conv tails (slot state in the cache manager) and
    the MoE pair counts behind the tokens: an eos-dropped row moves the
    state of a slot whose next admission starts at position 0, which
    resets it; the counts come back with every landed step."""
    from benchmark.lib.spec import Benchmark
    from tests import model_parity as parity
    case = parity.CASES[family]
    cfg = parity.cfg(case)
    ex = parity.build(case, cfg)
    w = Benchmark(ROOT).reference(family).make_weights(cfg, 7)

    def reqs(eos=-1):
        rng = np.random.default_rng(3)
        return [Request(f"r{i}", rng.integers(2, 64, n).astype(np.int32),
                        max_new=7, eos_id=eos,
                        rng=jax.random.PRNGKey(40 + i))
                for i, n in enumerate((3, 19, 9, 17, 6))]

    with jax.default_matmul_precision("highest"):
        kw = dict(num_slots=2, page_size=4, max_context=48, prefill_chunk=5)
        plain = _handed_over(engines(ex, w, **kw), 0).run(reqs())
        eos = int(plain["r1"][19 + 2])
        got = {}
        for depth in (0, 1):
            eng = _handed_over(engines(ex, w, **kw), depth)
            before = _counts(eng)
            got[depth] = (eng.run(reqs(eos)), _grew(eng, before))
            eng.kv.check_reclaimed()
        for r in reqs(eos):
            # a recurrent layer has no dense cache: the whole-sequence form
            want = lm_oracle(ex, w, r, use_cache=family == "gigachat3")
            for depth in (0, 1):
                np.testing.assert_array_equal(want, got[depth][0][r.req_id])
    n0, n1 = got[0][1], got[1][1]
    assert n1["n_lookahead_steps"] > 0
    assert n1["n_lookahead_dropped_rows"] >= 1
    assert n1["tokens_generated"] == n0["tokens_generated"]
    assert n1["moe_steps"] == n1["n_decode_steps"] > 0
    assert 0 < n1["moe_pairs_max_sum"] <= n1["moe_pairs_total"]
    if eng._recurrent:
        assert n1["recurrent_steps"] == n1["n_decode_steps"]
        assert n1["recurrent_slot_updates"] > 0


# -- (f), (g): through the server, which runs the engine one step ahead ------

def _serve(eng, **kw):
    srv = ServingServer(eng, max_queue=32, **kw)
    assert eng.lookahead == 1           # the pump's engine runs ahead
    return srv, srv.start_background()


def test_a_spec_engine_keeps_nothing_in_flight(tr):
    """The drafter reads banked tokens: under the server such an engine
    lands every step where it launched it, and serves today's tokens."""
    eng = _engine(tr, 0, max_step_tokens=None, spec_k=2)
    srv, (host, port) = _serve(eng)
    prompts = [np.tile(np.random.default_rng(i).integers(2, 61, 4), 4)
               for i in range(4)]
    try:
        with ServingClient(host, port) as c:
            ids = [c.submit(p.tolist(), max_new=9) for p in prompts]
            out = c.collect(ids)
    finally:
        srv.stop_background(drain=True)
    for i, p in zip(ids, prompts):
        np.testing.assert_array_equal(
            out[i]["tokens"],
            lm_oracle(tr.executor, tr.params, Request("o", p, max_new=9)))
    assert eng.n_lookahead_steps == 0 and eng._pending is None
    assert eng.n_spec_steps > 0


def test_frames_arrive_in_index_order_and_done_comes_last(tr):
    """Streamed through the wire with a step always in flight: each
    request's token frames carry indexes 0, 1, 2, ... and its `done` frame
    follows its last token; the stats RPC prints both counters."""
    eng = _engine(tr, 0)
    srv, (host, port) = _serve(eng)
    reqs = _requests()
    try:
        with ServingClient(host, port) as c:
            ids = {c.submit(r.prompt_ids.tolist(), max_new=r.max_new,
                            stream=True): r for r in reqs}
            frames = {i: [] for i in ids}
            left = set(ids)
            while left:
                msg = c.recv()
                if msg.get("id") in frames:
                    frames[msg["id"]].append(msg)
                    if msg["type"] == "done":
                        left.discard(msg["id"])
            stats = c.stats()
    finally:
        srv.stop_background(drain=True)
    for i, r in ids.items():
        kinds = [m["type"] for m in frames[i]]
        assert kinds == ["token"] * r.max_new + ["done"]
        assert [m["index"] for m in frames[i][:-1]] == list(range(r.max_new))
        want = lm_oracle(tr.executor, tr.params,
                       Request("o", r.prompt_ids, max_new=r.max_new))
        assert [m["token"] for m in frames[i][:-1]] == \
            want[r.prompt_ids.size:].tolist()
        assert frames[i][-1]["tokens"] == want.tolist()
    assert stats["lookahead"] == 1
    assert stats["lookahead_steps"] == eng.n_lookahead_steps > 0
    assert stats["lookahead_dropped_rows"] == 0
    assert stats["tokens_generated"] == sum(r.max_new for r in reqs)


@pytest.mark.parametrize("drain", [True, False], ids=["drain", "stop"])
def test_the_pump_lands_the_step_in_flight_before_it_stops(tr, engines,
                                                            drain):
    """An eos-ended request leaves its next row in flight with every slot
    empty; drain() and stop() land it before the pump is gone."""
    plain = _shared(engines, tr, 0).run(
        [Request("a", [3, 4, 5, 6], max_new=9)])
    eos = int(plain["a"][4 + 2])
    eng = _engine(tr, 0)
    srv, (host, port) = _serve(eng)
    with ServingClient(host, port) as c:
        out = c.collect([c.submit([3, 4, 5, 6], max_new=9, eos_id=eos)])
        assert list(out.values())[0]["reason"] == "stop"
        if not drain:
            c.submit([5, 6, 7], max_new=40, stream=True)
            c.recv()                    # mid-stream when the stop comes
    srv.stop_background(drain=drain)
    assert eng._pending is None
    assert all(sl is None for sl in eng.slots)
    assert eng.n_lookahead_dropped_rows == 1
    eng.kv.check_reclaimed()


def test_a_dying_pump_waits_for_the_step_in_flight_and_forgets_it(tr):
    """The pump dies with a step in flight: it is waited for and dropped
    (the mirrors it would bank into are as the failure left them), every
    route gets its error, nothing is left on the device."""
    eng = _engine(tr, 0)
    inner, died = eng.step, []

    def step():
        if eng._pending is not None and eng.tokens_generated >= 2:
            died.append(eng.tokens_generated)
            raise RuntimeError("induced device fault")
        return inner()

    eng.step = step
    srv, (host, port) = _serve(eng)
    with ServingClient(host, port) as c:
        rid = c.submit([3, 4, 5], max_new=20)
        with pytest.raises(ServerError, match="pump died"):
            c.collect([rid])
    assert len(died) == 1               # it died with a step pending
    assert eng._pending is None and eng.tokens_generated == died[0]
    with pytest.raises(RuntimeError, match="engine pump died"):
        srv.stop_background(drain=True)


# -- the span model ----------------------------------------------------------

def test_ring_spans_of_a_depth_1_run_nest_on_the_pump_thread(tr):
    """What benchmark/lib/phases.py needs of a traced slice: the pump
    thread's spans nest properly (innermost() loses no piece of them) and
    every name its serve groups require is there, with both step kinds."""
    from benchmark.lib import phases

    t = Tracer()
    t.enabled = True
    eng = _engine(tr, 0, tracer=t)
    srv, (host, port) = _serve(eng)
    try:
        with ServingClient(host, port) as c:
            c.collect([c.submit(r.prompt_ids.tolist(), max_new=r.max_new,
                                stream=True) for r in _requests()])
            time.sleep(0.05)
            c.stats()
    finally:
        srv.stop_background(drain=True)
    assert eng.n_lookahead_steps > 0
    spans = [(int(s["ts"] * 1e9), int((s["ts"] + s["dur"]) * 1e9), s["name"])
             for s in t.snapshot()
             if s["track"] in ("pump", "engine") and not s.get("instant")
             and s["dur"] > 0 and s["name"].startswith(
                 phases.FAMILIES["serve"])]
    spans.sort(key=lambda e: (e[0], -e[1]))
    names = {n for _, _, n in spans}
    for must, _ in phases.GROUPS["serve"].values():
        assert set(must) <= names, (must, sorted(names))
    assert {"pt.step.decode", "pt.step.mixed", "pt.engine.step"} <= names
    # properly nested: any two spans are disjoint or one holds the other
    stack = []
    for s, e, n in spans:
        while stack and stack[-1][0] <= s:
            stack.pop()
        assert not stack or e <= stack[-1][0], \
            f"{n} [{s}, {e}) straddles the end of {stack[-1][1]}"
        stack.append((e, n))
    # ... so the innermost pieces tile the outermost spans exactly
    pieces = phases.innermost(spans)
    assert all(a[1] <= b[0] for a, b in zip(pieces, pieces[1:]))
    top, end = 0, 0
    for s, e, _ in spans:
        if s >= end:
            top += e - s
            end = e
    assert sum(e - s for s, e, _ in pieces) == top
    # a compiled step's span holds its dispatch and no readback: the
    # readback under pt.engine.step is the PREVIOUS step's
    kinds = [(s, e) for s, e, n in spans
             if n in ("pt.step.decode", "pt.step.mixed")]
    assert len(kinds) == eng.n_decode_steps
    for name, want in (("pt.step.dispatch", True),
                       ("pt.step.readback", False)):
        for s, e, n in spans:
            if n == name:
                assert any(k0 <= s and e <= k1 for k0, k1 in kinds) is want
