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
from paddle_tpu.graph.lm_decode import lm_generate
from paddle_tpu.obs import Tracer
from paddle_tpu.obs.flight import FlightRecorder
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.serving.client import ServerError, ServingClient
from paddle_tpu.serving.server import ServingServer
from paddle_tpu.trainer.trainer import Trainer

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


def _requests(eos=-1, temperature=0.0, lens=(3, 9, 5, 12, 7, 4, 30, 2),
              max_new=(5, 7, 3, 6, 8, 2, 9, 1), **kw):
    rng = np.random.default_rng(0)
    return [Request(f"r{i}", rng.integers(2, 61, n).astype(np.int32),
                    max_new=m, eos_id=eos, temperature=temperature,
                    top_k=5 if temperature else 0,
                    rng=jax.random.PRNGKey(40 + i), **kw)
            for i, (n, m) in enumerate(zip(lens, max_new))]


def _oracle(ex, w, r: Request, use_cache=True):
    toks, lens = lm_generate(ex, w, r.prompt_ids[None, :], max_new=r.max_new,
                             temperature=r.temperature, top_k=r.top_k,
                             top_p=r.top_p, eos_id=r.eos_id, rng=r.rng,
                             use_cache=use_cache)
    return np.asarray(toks)[0, :int(np.asarray(lens)[0])]


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

@pytest.mark.parametrize("temperature", [0.0, 0.9], ids=["greedy", "sampled"])
def test_depths_bank_the_same_tokens_as_lm_generate(tr, temperature):
    """More requests than slots, prompts longer than a chunk: slots refill
    mid-flight, decode rows and chunk rows share steps, and at depth 1
    nearly every step is launched beside the one before it."""
    got = {}
    for depth in (0, 1):
        eng = _engine(tr, depth)
        seen = _record(eng)
        reqs = _requests(temperature=temperature)
        got[depth] = (eng.run(reqs), _per_request(seen), eng)
        eng.kv.check_reclaimed()
    for r in reqs:
        want = _oracle(tr.executor, tr.params, r)
        for depth in (0, 1):
            np.testing.assert_array_equal(want, got[depth][0][r.req_id])
    assert got[0][1] == got[1][1]       # per request: frames and done alike
    e0, e1 = got[0][2], got[1][2]
    assert e0.n_lookahead_steps == 0 and e0.n_lookahead_dropped_rows == 0
    assert e1.n_mixed_steps > 2 and e1.n_decode_steps > e1.n_mixed_steps
    # every launch but the first of a burst found a step in flight
    assert e1.n_lookahead_steps >= e1.n_decode_steps - 2
    assert e1.n_lookahead_dropped_rows == 0     # max_new is known at launch
    assert e1.tokens_generated == e0.tokens_generated
    assert e1._pending is None                  # run() ends landed


def test_a_direct_caller_of_step_sees_what_it_banked(tr):
    """The engine's default keeps the contract `run`, the tools and a dozen
    test files rely on: after step() returns, its tokens are banked."""
    eng = _engine(tr, depth=0)
    assert ServingEngine(tr.executor, tr.params).lookahead == 0
    eng.add_request(Request("a", [3, 4, 5], max_new=4))
    before = 0
    while eng.step():
        assert eng._pending is None
        assert eng.tokens_generated == before + 1
        before += 1
    assert before == 4


# -- (b) eos while the next row is in flight ---------------------------------

def test_eos_with_the_next_row_in_flight_drops_that_row(tr):
    """A request that ends on eos at land N has a row in step N+1: that row
    is dropped — not banked, not emitted, not counted — and the frames, the
    done frame, tokens_generated and the prefix-cache donation are depth
    0's."""
    plain = _engine(tr, 0).run(_requests(lens=(9, 12, 5), max_new=(9, 9, 9)))
    eos = int(plain["r0"][9 + 3])       # r0's 4th generated token
    got = {}
    for depth in (0, 1):
        eng = _engine(tr, depth)
        seen = _record(eng)
        reqs = _requests(eos=eos, lens=(9, 12, 5), max_new=(9, 9, 9))
        eng.flight = FlightRecorder()
        eng.flight.enabled = True
        res = eng.run(reqs)
        got[depth] = (res, _per_request(seen), eng)
        for r in reqs:
            np.testing.assert_array_equal(
                _oracle(tr.executor, tr.params, r), res[r.req_id])
        eng.kv.check_reclaimed()
    assert got[0][1] == got[1][1]
    assert got[1][1][1]["r0"][1] == "stop"
    assert len(got[1][0]["r0"]) <= 9 + 4
    e0, e1 = got[0][2], got[1][2]
    stops = sum(1 for _, why in got[1][1][1].values() if why == "stop")
    assert e1.n_lookahead_dropped_rows == stops >= 1
    assert e0.n_lookahead_dropped_rows == 0
    assert e1.tokens_generated == e0.tokens_generated
    # what retirement donated to the prefix index is the same pages' worth
    assert e1.prefix.n_nodes == e0.prefix.n_nodes
    assert e1.kv.cached_page_count == e0.kv.cached_page_count
    # one flight event a drop, none a step
    kinds = [e["kind"] for e in e1.flight.snapshot()]
    assert kinds.count("lookahead_drop") == stops
    assert sorted(k for k in kinds if k != "lookahead_drop") == \
        sorted(e["kind"] for e in e0.flight.snapshot())


# -- (c) cancel and deadline with a step pending -----------------------------

@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_abort_with_a_step_pending_lands_it_first(tr, how):
    """After k calls depth 1 has launched k steps and landed k-1; an abort
    lands the k-th first, so it reports the tokens depth 0 reports."""
    got = {}
    for depth in (0, 1):
        eng = _engine(tr, depth, num_slots=2)
        seen = _record(eng)
        eng.add_request(Request("work", [3, 4, 5, 6], max_new=30,
                                deadline=5.0 if how == "deadline" else None))
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
        got[depth] = (res, _per_request(seen), eng.tokens_generated,
                      eng.n_cancelled, eng.n_expired)
        np.testing.assert_array_equal(
            res["other"], _oracle(tr.executor, tr.params,
                                  Request("other", [7, 8, 9], max_new=12)))
        eng.kv.check_reclaimed()
    assert got[0][1:] == got[1][1:]
    toks, why = got[1][1][1]["work"]            # its done frame
    assert len(toks) == 4 + 5                   # prompt + 5 steps' tokens


# -- (d) a wedged pool preempts ----------------------------------------------

def test_a_wedged_pool_lands_before_it_preempts(tr):
    """Two requests that cannot both finish in 5 pages: the wedge lands
    the step in flight, preempts the youngest, and the replay is exact."""
    got = {}
    for depth in (0, 1):
        eng = _engine(tr, depth, num_slots=2, page_size=4, max_context=16,
                      num_pages=6, prefill_chunk=4, max_step_tokens=8)
        seen = _record(eng)
        reqs = _requests(lens=(8, 8), max_new=(8, 8))
        got[depth] = (eng.run(reqs), _per_request(seen)[1], eng)
        assert eng.n_preemptions > 0, "pool was never overcommitted"
        for r in reqs:
            np.testing.assert_array_equal(
                _oracle(tr.executor, tr.params, r), got[depth][0][r.req_id])
        eng.kv.check_reclaimed()
    assert got[0][1] == got[1][1]
    assert got[1][2].n_lookahead_steps > 0
    assert got[1][2].tokens_generated == got[0][2].tokens_generated == 16


# -- (e) slot state and the counts behind the tokens -------------------------

@pytest.mark.parametrize("family", ["kimi_linear", "lfm2_moe", "gigachat3"])
def test_recurrent_and_moe_models_ride_the_same_tokens(family):
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
        plain = ServingEngine(ex, w, **kw).run(reqs())
        eos = int(plain["r1"][19 + 2])
        got = {}
        for depth in (0, 1):
            eng = ServingEngine(ex, w, **kw)
            eng.lookahead = depth
            got[depth] = (eng.run(reqs(eos)), eng)
            eng.kv.check_reclaimed()
        for r in reqs(eos):
            # a recurrent layer has no dense cache: the whole-sequence form
            want = _oracle(ex, w, r, use_cache=family == "gigachat3")
            for depth in (0, 1):
                np.testing.assert_array_equal(want, got[depth][0][r.req_id])
    e0, e1 = got[0][1], got[1][1]
    assert e1.n_lookahead_steps > 0 and e1.n_lookahead_dropped_rows >= 1
    assert e1.tokens_generated == e0.tokens_generated
    assert e1.moe_steps == e1.n_decode_steps > 0
    assert 0 < e1.moe_pairs_max_sum <= e1.moe_pairs_total
    if e1._recurrent:
        assert e1.recurrent_steps == e1.n_decode_steps
        assert e1.recurrent_slot_updates > 0


# -- (f), (g): through the server, which runs the engine one step ahead ------

def _serve(eng, **kw):
    srv = ServingServer(eng, max_queue=32, **kw)
    assert eng.lookahead == 1           # the pump's engine runs ahead
    return srv, srv.start_background()


@pytest.mark.parametrize("kind,kw", [
    ("spec", {"spec_k": 2}), ("scan", {"decode_steps": 4})])
def test_spec_and_scan_engines_keep_nothing_in_flight(tr, kind, kw):
    """The drafter reads banked tokens and the scan starts at the banked
    cursor: under the server such an engine lands every step where it
    launched it, and serves today's tokens."""
    eng = _engine(tr, 0, max_step_tokens=None, **kw)
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
            _oracle(tr.executor, tr.params, Request("o", p, max_new=9)))
    assert eng.n_lookahead_steps == 0 and eng._pending is None
    assert (eng.n_spec_steps if kind == "spec" else eng.n_scan_flushes) > 0


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
        want = _oracle(tr.executor, tr.params,
                       Request("o", r.prompt_ids, max_new=r.max_new))
        assert [m["token"] for m in frames[i][:-1]] == \
            want[r.prompt_ids.size:].tolist()
        assert frames[i][-1]["tokens"] == want.tolist()
    assert stats["lookahead"] == 1
    assert stats["lookahead_steps"] == eng.n_lookahead_steps > 0
    assert stats["lookahead_dropped_rows"] == 0
    assert stats["tokens_generated"] == sum(r.max_new for r in reqs)


@pytest.mark.parametrize("drain", [True, False], ids=["drain", "stop"])
def test_the_pump_lands_the_step_in_flight_before_it_stops(tr, drain):
    """An eos-ended request leaves its next row in flight with every slot
    empty; drain() and stop() land it before the pump is gone."""
    plain = _engine(tr, 0).run([Request("a", [3, 4, 5, 6], max_new=9)])
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
