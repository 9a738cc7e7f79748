"""Fleet-router loopback tests (paddle_tpu/fleet/ over serving/server.py).

The acceptance contract (ISSUE 10): token streams through the router are
BIT-IDENTICAL to a direct single-replica connection (itself oracle-checked
against lm_generate) — including requests transparently retried after a
replica death; prefix-affinity placement steers shared-prefix traffic to
one replica; a rolling restart of a 2-replica fleet under load completes
with zero failed requests; and a saturated fleet answers an explicit
overload frame instead of queueing.  Replicas here are in-process
ServingServer instances — the same wire protocol `tools/serve.py` serves
from its own process (the slow churn soak exercises 3 of them).
"""

import threading
import time

import numpy as np
import pytest

from paddle_tpu.config.parser import parse_config
from paddle_tpu.fleet import FleetCtl, FleetRouter
from paddle_tpu.fleet.policy import AffinityIndex, PlacementPolicy
from paddle_tpu.fleet.replica import Replica
from paddle_tpu.graph.lm_decode import lm_generate
from paddle_tpu.obs.flight import get_flight_recorder, load_bundle
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.client import (OverloadError, ServerError,
                                       ServingClient)
from paddle_tpu.serving.server import ServingServer
from paddle_tpu.trainer.trainer import Trainer

PAGE = 8


@pytest.fixture(scope="module")
def tiny_tr():
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=31,dim=16,layers=1,heads=2,batch_size=4")
    return Trainer(cfg, seed=7)


def _replica(tr, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_context", 64)
    max_queue = kw.pop("max_queue", 16)
    role = kw.pop("role", "both")
    eng = ServingEngine(tr.executor, tr.params, **kw)
    srv = ServingServer(eng, max_queue=max_queue, role=role)
    host, port = srv.start_background()
    return srv, host, port


def _fleet(tr, n, router_kw=None, **replica_kw):
    """n in-process replicas + a router joined to all of them."""
    reps = [_replica(tr, **replica_kw) for _ in range(n)]
    rkw = dict(poll_interval_s=0.1, heartbeat_misses=100)  # no accidental
    rkw.update(router_kw or {})                            # expiry on a
    rt = FleetRouter(port=0,                               # loaded CI box
                     replicas=[(h, p) for _, h, p in reps], **rkw)
    host, port = rt.start_background()
    return rt, host, port, [srv for srv, _, _ in reps]


def _stop_all(rt, srvs, drain=True):
    rt.stop_background(drain=drain)
    for srv in srvs:
        try:
            srv.stop_background(drain=drain)
        except RuntimeError:
            pass                       # a deliberately-killed replica


def _oracle(tr, prompt, max_new, **kw):
    import jax

    rng = jax.random.PRNGKey(kw.pop("seed")) if "seed" in kw else None
    toks, lens = lm_generate(tr.executor, tr.params,
                             np.asarray(prompt, np.int32)[None, :],
                             max_new=max_new, use_cache=True, rng=rng, **kw)
    return np.asarray(toks)[0, :int(np.asarray(lens)[0])].tolist()


def _loop_call(rt, fn):
    """Run fn on the router's loop thread (transport ops are not
    thread-safe from the test thread)."""
    done = threading.Event()
    rt._loop.call_soon_threadsafe(lambda: (fn(), done.set()))
    assert done.wait(10)


# ---------------------------------------------------------------------------
# policy unit coverage (no sockets)
# ---------------------------------------------------------------------------

def test_affinity_index_bounds_and_replica_drop():
    idx = AffinityIndex(window=4, capacity=3)
    assert idx.key_of([1, 2, 3]) is None          # shorter than one page
    k1, k2 = idx.key_of([1, 2, 3, 4, 9]), idx.key_of([5, 6, 7, 8])
    idx.put(k1, "r0")
    idx.put(k2, "r1")
    assert idx.get(k1) == "r0" and idx.get(k2) == "r1"
    idx.put(idx.key_of([9] * 4), "r0")
    idx.put(idx.key_of([8] * 4), "r0")            # capacity 3: k1 evicted
    assert len(idx) == 3 and idx.get(k1) is None
    assert idx.drop_replica("r0") == 2            # both r0 keys forgotten
    assert idx.get(k2) == "r1"


def test_policy_places_by_affinity_then_least_loaded():
    pol = PlacementPolicy("affinity", window=2)
    a, b = Replica("r0", "h", 1), Replica("r1", "h", 2)
    a.hello = {"max_inflight": 10}
    b.hello = {"max_inflight": 10}
    a.pending.add("g0")                           # a is busier
    first, why = pol.place([7, 7, 1], [a, b])
    assert first is b and why == "least_loaded"
    again, why = pol.place([7, 7, 2], [a, b])     # same first-page run
    assert again is b and why == "affinity"
    # the remembered replica gone -> fall back AND re-point the key
    moved, why = pol.place([7, 7, 3], [a])
    assert moved is a and why == "least_loaded"
    back, why = pol.place([7, 7, 4], [a, b])
    assert back is a and why == "affinity"


# ---------------------------------------------------------------------------
# the router over real TCP loopback
# ---------------------------------------------------------------------------

def test_fleet_token_exactness_through_router_vs_direct(tiny_tr):
    """ISSUE 10 acceptance: streamed tokens through the router are
    bit-identical to a direct single-replica connection, which itself
    matches lm_generate — greedy AND seeded-sampled requests."""
    rng = np.random.default_rng(0)
    rt, host, port, srvs = _fleet(tiny_tr, 2)
    try:
        prompts = [rng.integers(2, 31, int(rng.integers(3, 14))).tolist()
                   for _ in range(6)]
        jobs = [(p, 4 + i % 3) for i, p in enumerate(prompts)]
        with ServingClient(host, port) as c:
            ids = [c.submit(p, max_new=mn) for p, mn in jobs]
            sampled = c.submit(prompts[0], max_new=5, temperature=0.9,
                               top_k=4, seed=13)
            out = c.collect(ids + [sampled])
        # direct connection to ONE replica, same requests
        dsrv, dh, dp = _replica(tiny_tr)
        try:
            with ServingClient(dh, dp) as d:
                for rid, (p, mn) in zip(ids, jobs):
                    toks, reason = d.generate(p, max_new=mn)
                    assert out[rid]["tokens"] == toks == _oracle(
                        tiny_tr, p, mn)
                    assert out[rid]["reason"] == reason == "length"
                    # the per-token stream agrees with the final frame
                    assert out[rid]["stream"] == \
                        out[rid]["tokens"][len(p):]
                stoks, _ = d.generate(prompts[0], max_new=5,
                                      temperature=0.9, top_k=4, seed=13)
                assert out[sampled]["tokens"] == stoks == _oracle(
                    tiny_tr, prompts[0], 5, temperature=0.9, top_k=4,
                    seed=13)
        finally:
            dsrv.stop_background(drain=True)
        # every request went through the router exactly once
        with ServingClient(host, port) as c:
            rows = c.stats()["replicas"]
        assert sum(r["routed_total"] for r in rows) == 7
    finally:
        _stop_all(rt, srvs)


def test_prefix_affinity_steers_shared_prefixes_to_one_replica(tiny_tr):
    """Requests sharing a first-page token run land on the SAME replica
    (so PR 7's per-replica prefix cache can hit under fan-out), and the
    router's flight `route` events record the affinity decisions."""
    flight = get_flight_recorder()
    rng = np.random.default_rng(1)
    rt, host, port, srvs = _fleet(tiny_tr, 2)
    mark = flight.recorded
    try:
        prefixes = [rng.integers(2, 31, PAGE).tolist() for _ in range(2)]
        assert prefixes[0][:PAGE] != prefixes[1][:PAGE]
        with ServingClient(host, port) as c:
            ids = []
            for i in range(8):                    # interleave the groups
                p = prefixes[i % 2] + rng.integers(2, 31, 3).tolist()
                ids.append((c.submit(p, max_new=3), i % 2, p))
            out = c.collect([rid for rid, _, _ in ids])
        for rid, g, p in ids:
            assert out[rid]["tokens"] == _oracle(tiny_tr, p, 3)
        routes = [e for e in flight.snapshot()
                  if e["seq"] >= mark and e["kind"] == "route"]
        assert len(routes) == 8
        by_key: dict = {}
        for e in routes:
            by_key.setdefault(e["data"]["akey"], []).append(e["data"])
        assert len(by_key) == 2, "two prefix groups, two affinity keys"
        for key, evs in by_key.items():
            homes = {e["replica"] for e in evs}
            assert len(homes) == 1, \
                f"prefix group {key} split across {homes}"
            # first placement picks a home; every follower is an
            # affinity decision
            assert [e["policy"] for e in evs[1:]] == ["affinity"] * 3
        # the two groups went to DIFFERENT replicas (least-loaded spread)
        assert {evs[0]["replica"] for evs in by_key.values()} == \
            {"r0", "r1"}
        # and the steering paid: the replicas' prefix caches hit (each
        # replica has 2 slots, so per 4-request group at least the two
        # admissions after the first retirement map donated pages)
        hits = sum(srv.engine.n_prefix_hits for srv in srvs)
        assert hits >= 4, f"affinity routing should produce prefix hits " \
                          f"(got {hits})"
    finally:
        _stop_all(rt, srvs)


def test_replica_kill_midstream_retries_unstreamed_on_survivor(tiny_tr):
    """A replica dying mid-stream: requests whose client saw ZERO tokens
    retry transparently on the survivor (bit-exact); a partially-streamed
    request gets an honest error, never a spliced stream."""
    flight = get_flight_recorder()
    rng = np.random.default_rng(2)
    rt, host, port, srvs = _fleet(tiny_tr, 2)
    mark = flight.recorded
    try:
        prefix = rng.integers(2, 31, PAGE).tolist()
        p_a = prefix + [3, 4]
        p_b = prefix + [5, 6]
        p_c = prefix + [7, 8]
        with ServingClient(host, port) as c:
            ra = c.submit(p_a, max_new=30)        # will stream first
            msg = c.recv()
            while msg.get("type") != "token":     # ra provably streamed
                msg = c.recv()
            c._pending.append(msg)
            # two more requests whose client sees NOTHING before the kill:
            # rb decodes in the second slot, rc queues behind (2 slots)
            rb = c.submit(p_b, max_new=25, stream=False)
            rc = c.submit(p_c, max_new=4, stream=False)
            # all three co-located by affinity (shared first-page run)
            deadline = time.time() + 30
            victim = None
            while victim is None and time.time() < deadline:
                victim = next((r for r in rt.table
                               if len(r.pending) >= 3), None)
                time.sleep(0.005)
            assert victim is not None, \
                "affinity should have co-located all three requests"
            survivor = next(r for r in rt.table if r is not victim)
            _loop_call(rt, victim.backend.abort)  # the replica "dies"
            out = c.collect([rb, rc])
            assert out[rb]["tokens"] == _oracle(tiny_tr, p_b, 25), \
                "retried request must stay bit-exact"
            assert out[rc]["tokens"] == _oracle(tiny_tr, p_c, 4)
            with pytest.raises(ServerError, match="already streamed"):
                c.collect([ra])
            s = c.stats()
            assert s["replicas_registered"] == 1
            assert s["replicas"][0]["replica"] == survivor.rid
            assert s["retries"] >= 2.0
        kinds = [e["kind"] for e in flight.snapshot() if e["seq"] >= mark]
        assert "replica_leave" in kinds and "retry" in kinds
    finally:
        _stop_all(rt, srvs)


def test_nonstreaming_request_retries_even_after_replica_made_tokens(
        tiny_tr):
    """A stream=False client has seen ZERO tokens no matter how far its
    replica got — the retry predicate is tokens DELIVERED, not tokens
    produced, so a replica death mid-decode must still retry the request
    transparently (bit-exact: the verbatim resend replays the same
    deterministic decode)."""
    rng = np.random.default_rng(7)
    rt, host, port, srvs = _fleet(tiny_tr, 2)
    try:
        p = rng.integers(2, 31, PAGE + 2).tolist()
        with ServingClient(host, port) as c:
            rid = c.submit(p, max_new=25, stream=False)
            # wait until the VICTIM's engine has provably decoded tokens
            deadline = time.time() + 30
            victim = None
            while victim is None and time.time() < deadline:
                victim = next(
                    (r for r in rt.table if r.pending
                     and next(s for s in srvs if s.port == r.port)
                     .engine.tokens_generated >= 3), None)
                time.sleep(0.005)
            assert victim is not None, "request never started decoding"
            _loop_call(rt, victim.backend.abort)
            out = c.collect([rid])
            assert out[rid]["tokens"] == _oracle(tiny_tr, p, 25), \
                "non-streaming request must retry bit-exact"
            assert c.stats()["retries"] >= 1.0
    finally:
        _stop_all(rt, srvs)


def test_malformed_prompt_answers_error_without_leaking_a_route(tiny_tr):
    """Garbage prompts (non-list, or non-numeric tokens) must answer an
    error frame BEFORE touching routing state — in least_loaded/random
    modes placement never reads the prompt, so a late failure used to
    strand a phantom in-flight request that inflated load and wedged
    drain forever."""
    rt, host, port, srvs = _fleet(tiny_tr, 2,
                                  router_kw=dict(policy="least_loaded"))
    try:
        with ServingClient(host, port) as c:
            for bad in ("zzz", 5, [3, "x", 4], [True, 3]):
                c.send({"type": "generate", "id": f"b{bad!r}"[:12],
                        "prompt": bad, "max_new": 3})
                msg = c.recv()
                assert msg["type"] == "error" and "prompt" in msg["error"]
            s = c.stats()
            assert s["inflight"] == 0, "a malformed prompt leaked a route"
            assert all(r["pending"] == 0 for r in s["replicas"])
            # the connection and the fleet still serve real work
            toks, reason = c.generate([3, 4, 5], max_new=3)
            assert reason == "length" and len(toks) == 6
    finally:
        _stop_all(rt, srvs)           # drain: wedges if a route leaked


def test_rolling_restart_under_load_zero_failed_requests(tiny_tr):
    """ISSUE 10 acceptance: drain-aware rolling restart of a 2-replica
    fleet while clients keep submitting — every request completes with
    reason=length and oracle-exact tokens; nothing fails, nothing drops."""
    rng = np.random.default_rng(3)
    rt, host, port, srvs = _fleet(tiny_tr, 2)
    live = {s: True for s in srvs}
    results: list = []
    errors: list = []
    stop_load = threading.Event()

    def load_worker(wid):
        try:
            with ServingClient(host, port) as c:
                w_rng = np.random.default_rng(100 + wid)
                for i in range(10):
                    p = w_rng.integers(2, 31, int(w_rng.integers(3, 10))
                                       ).tolist()
                    toks, reason = c.generate(p, max_new=4)
                    results.append((p, toks, reason))
                    if stop_load.is_set():
                        break
        except Exception as e:                     # noqa: BLE001
            errors.append(e)

    workers = [threading.Thread(target=load_worker, args=(w,))
               for w in range(2)]
    try:
        for t in workers:
            t.start()
        time.sleep(0.2)                           # load provably flowing

        def restart(row):
            host_r, port_r = row["addr"].rsplit(":", 1)
            old = next(s for s in srvs
                       if live[s] and s.port == int(port_r))
            old.stop_background(drain=True)       # the SIGTERM-drain path
            live[old] = False
            new_srv, nh, np_ = _replica(tiny_tr)
            srvs.append(new_srv)
            live[new_srv] = True
            return nh, np_

        with FleetCtl(host, port) as ctl:
            new_ids = ctl.rolling_restart(restart, drain_timeout_s=120,
                                          log=lambda s: None)
        assert len(new_ids) == 2
        for t in workers:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in workers), "load wedged"
        assert errors == [], f"rolling restart failed requests: {errors}"
        assert len(results) == 20
        for p, toks, reason in results:
            assert reason == "length"
            assert toks == _oracle(tiny_tr, p, 4)
        with ServingClient(host, port) as c:
            s = c.stats()
        assert s["replicas_healthy"] == 2
        assert {r["replica"] for r in s["replicas"]} == set(new_ids)
    finally:
        stop_load.set()
        _stop_all(rt, [s for s in srvs if live.get(s)])


def test_fleet_overload_sheds_when_every_replica_saturated(tiny_tr):
    """The fleet-level backpressure contract: every healthy replica at
    its admission cap -> an explicit overload frame (reason
    fleet_saturated), never unbounded queueing."""
    flight = get_flight_recorder()
    rt, host, port, srvs = _fleet(tiny_tr, 2, num_slots=1, max_queue=0)
    mark = flight.recorded
    try:
        with ServingClient(host, port) as c:
            # each replica's cap is 1 (one slot, no queue): two long
            # requests saturate the fleet; frames on one connection are
            # processed in order, so placement is deterministic
            r0 = c.submit([3, 4, 5], max_new=25)
            r1 = c.submit([4, 5, 6], max_new=25)
            over = c.submit([5, 6, 7], max_new=4)
            with pytest.raises(OverloadError) as ei:
                c.collect([over])
            assert ei.value.info["reason"] == "fleet_saturated"
            assert ei.value.info["max_inflight"] == 2
            # shedding cost nothing admitted: the two placed requests
            # finish exactly
            out = c.collect([r0, r1])
            assert out[r0]["tokens"] == _oracle(tiny_tr, [3, 4, 5], 25)
            assert out[r1]["tokens"] == _oracle(tiny_tr, [4, 5, 6], 25)
            text = c.metrics()
            vals = {ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
                    for ln in text.splitlines() if not ln.startswith("#")}
            assert vals["fleet_sheds_total"] >= 1.0
            assert vals["fleet_requests_accepted_total"] == 2.0
        kinds = [e["kind"] for e in flight.snapshot() if e["seq"] >= mark]
        assert "shed" in kinds
    finally:
        _stop_all(rt, srvs)


def test_replica_overload_race_answers_overload_not_error(tiny_tr):
    """A replica refusing admission (filled by a DIRECT client between
    the router's poll and the frame's arrival) with no alternative
    capacity must surface as the retryable `overload` contract — a
    terminal error frame would turn transient saturation into a hard
    failure (and skip the shed accounting)."""
    rt, host, port, srvs = _fleet(
        tiny_tr, 1, router_kw=dict(poll_interval_s=60.0),  # stale view
        num_slots=1, max_queue=0)                          # replica cap 1
    try:
        rep_srv = srvs[0]
        with ServingClient(rep_srv.host, rep_srv.port) as direct:
            rid = direct.submit([3, 4, 5], max_new=25)     # fills the cap
            # same-connection barrier: admission provably happened
            assert direct.stats(stale_ok=True)["inflight"] == 1
            with ServingClient(host, port) as c:
                over = c.submit([4, 5, 6], max_new=3)
                with pytest.raises(OverloadError) as ei:
                    c.collect([over])
                assert ei.value.info["reason"] == "fleet_saturated"
                assert c.stats()["sheds"] >= 1.0
            direct.cancel(rid)
            direct.collect([rid])
    finally:
        _stop_all(rt, srvs)


def test_fleet_stats_metrics_dump_frames_and_unhealthy_bundle(
        tiny_tr, tmp_path):
    """The ops surface: fleet-shaped stats, CATALOG-lockstep metrics, an
    on-demand postmortem bundle — and the automatic bundle frozen the
    moment the LAST healthy replica is gone."""
    rt, host, port, srvs = _fleet(
        tiny_tr, 2, router_kw=dict(postmortem_dir=str(tmp_path)))
    try:
        with ServingClient(host, port) as c:
            h = c.hello()
            assert h["role"] == "router" and h["proto"] == 1
            assert "fleet" in h["capabilities"]
            toks, reason = c.generate([3, 4, 5, 6], max_new=3)
            assert reason == "length" and len(toks) == 7
            s = c.stats()
            assert s["fleet"] is True and s["replicas_healthy"] == 2
            assert s["affinity_window"] == PAGE
            assert len(s["replicas"]) == 2
            text = c.metrics()
            vals = {}
            for line in text.splitlines():
                if line and not line.startswith("#"):
                    key, v = line.rsplit(" ", 1)
                    vals[key] = float(v)
            assert vals["fleet_replicas_healthy"] == 2.0
            assert vals["fleet_requests_accepted_total"] == 1.0
            from paddle_tpu.obs import CATALOG
            from paddle_tpu.obs.metrics import MetricsRegistry
            for key in vals:
                base = key.split("{", 1)[0]
                fam = MetricsRegistry._family_of(base, "histogram")
                assert base in CATALOG or fam in CATALOG, \
                    f"{base} rendered but not in CATALOG"
            d = c.dump()
            b = load_bundle(d["path"])
            assert b["meta"]["reason"] == "rpc"
            assert b["engine"]["router"] is True
            assert len(b["engine"]["replicas"]) == 2
            assert b["config"]["policy"] == "affinity"
            # now the whole fleet dies: ONE fleet_unhealthy bundle
            for r in list(rt.table):
                _loop_call(rt, r.backend.abort)
            deadline = time.time() + 20
            while time.time() < deadline:
                if any(load_bundle(str(p)).get("meta", {}).get("reason")
                       == "fleet_unhealthy"
                       for p in tmp_path.iterdir()
                       if p.is_dir() and not str(p).endswith(".tmp")):
                    break
                time.sleep(0.05)
            bundles = [load_bundle(str(p)) for p in tmp_path.iterdir()
                       if p.is_dir() and not str(p).endswith(".tmp")]
            unhealthy = [b for b in bundles
                         if b["meta"]["reason"] == "fleet_unhealthy"]
            assert len(unhealthy) == 1, \
                "total-fleet-unhealthy must freeze exactly one bundle"
            assert "no healthy replicas" in unhealthy[0]["meta"]["error"]
            # with nothing registered, generate sheds with no_replicas
            with pytest.raises(OverloadError) as ei:
                c.generate([3, 4], max_new=2)
            assert ei.value.info["reason"] == "no_replicas"
    finally:
        _stop_all(rt, srvs)


def test_router_relay_itl_counts_every_relayed_token(tiny_tr):
    """Relay ITL is the gap between a request's relayed token frames: one
    sample a token past the first, streams bit-exact, and the percentiles
    surface in the stats frame + CATALOG metrics."""
    rng = np.random.default_rng(3)
    rt, host, port, srvs = _fleet(tiny_tr, 2)
    try:
        prompts = [rng.integers(2, 31, int(rng.integers(3, 10))).tolist()
                   for _ in range(4)]
        with ServingClient(host, port) as c:
            ids = [c.submit(p, max_new=7) for p in prompts]
            out = c.collect(ids)
            for rid, p in zip(ids, prompts):
                assert out[rid]["tokens"] == _oracle(tiny_tr, p, 7)
                assert out[rid]["stream"] == out[rid]["tokens"][len(p):]
            s = c.stats()
            itl = s["relay_itl_ms"]
            assert set(itl) == {"p50", "p90", "p99"}
            assert 0.0 <= itl["p50"] <= itl["p99"]
            text = c.metrics()
            vals = {}
            for line in text.splitlines():
                if line and not line.startswith("#"):
                    key, v = line.rsplit(" ", 1)
                    vals[key] = float(v)
        # every relayed token past each request's first charged exactly
        # one relay_token_latency sample: 4 requests x (7 - 1) tokens
        assert vals['fleet_relay_latency_count'
                    '{stat="relay_token_latency"}'] == 24.0
        assert vals['fleet_relay_latency_seconds'
                    '{quantile="p99",stat="relay_token_latency"}'] >= 0.0
    finally:
        _stop_all(rt, srvs)


def test_router_rejects_non_replica_peer_on_join(tiny_tr):
    """Joining an address that is not a serving replica (here: the
    router ITSELF — role 'router') must fail the hello classification,
    not route traffic into a loop."""
    rt, host, port, srvs = _fleet(tiny_tr, 1)
    try:
        with FleetCtl(host, port) as ctl:
            with pytest.raises(ServerError,
                               match="not a serving replica"):
                ctl.join(host, port)              # the router's own addr
            assert len(ctl.list()) == 1           # table unchanged
    finally:
        _stop_all(rt, srvs)


# ---------------------------------------------------------------------------
# ISSUE 19: disaggregated prefill/decode through the router
# ---------------------------------------------------------------------------

def _disagg_fleet(tr, router_kw=None, prefill_kw=None, decode_kw=None):
    """1 prefill-role + 1 decode-role replica behind a router — the
    minimal disaggregated fleet.  Long prompts place on the prefill
    replica, kv_push their committed pages to the decode replica, and
    the generate frame follows the pages."""
    sp, hp, pp = _replica(tr, role="prefill", **(prefill_kw or {}))
    sd, hd, pd = _replica(tr, role="decode", **(decode_kw or {}))
    rkw = dict(poll_interval_s=0.1, heartbeat_misses=100)
    rkw.update(router_kw or {})
    rt = FleetRouter(port=0, replicas=[(hp, pp), (hd, pd)], **rkw)
    host, port = rt.start_background()
    return rt, host, port, [sp, sd]


def test_disagg_cross_replica_exactness_and_role_surfaces(tiny_tr):
    """ISSUE 19 acceptance: a request prefilled on replica A and decoded
    on replica B streams token-for-token what a single replica (itself
    oracle-checked) produces — greedy AND seeded-sampled — while the
    router's kv_xfer counters, the placement ledger, and ctl's role
    column all tell the disaggregation story.  Short prompts bypass the
    split and stay exact."""
    rng = np.random.default_rng(5)
    rt, host, port, srvs = _disagg_fleet(tiny_tr)
    sp, sd = srvs
    try:
        prompts = [rng.integers(2, 31, int(rng.integers(2 * PAGE + 1,
                                                        3 * PAGE))).tolist()
                   for _ in range(3)]
        with ServingClient(host, port) as c:
            ids = [c.submit(p, max_new=5) for p in prompts]
            sampled = c.submit(prompts[0], max_new=5, temperature=0.9,
                               top_k=4, seed=13)
            out = c.collect(ids + [sampled])
        dsrv, dh, dp = _replica(tiny_tr)          # single-replica control
        try:
            with ServingClient(dh, dp) as d:
                for rid, p in zip(ids, prompts):
                    toks, reason = d.generate(p, max_new=5)
                    assert out[rid]["tokens"] == toks == _oracle(
                        tiny_tr, p, 5), "disagg decode diverged"
                    assert out[rid]["reason"] == reason == "length"
                    assert out[rid]["stream"] == \
                        out[rid]["tokens"][len(p):]
                stoks, _ = d.generate(prompts[0], max_new=5,
                                      temperature=0.9, top_k=4, seed=13)
                assert out[sampled]["tokens"] == stoks == _oracle(
                    tiny_tr, prompts[0], 5, temperature=0.9, top_k=4,
                    seed=13), "seeded sampling must survive the split"
        finally:
            dsrv.stop_background(drain=True)
        # every long prompt actually split: prefill leg + decode leg
        with ServingClient(host, port) as c:
            s = c.stats()
            assert s["kv_pushes"] == 4 and s["kv_push_failures"] == 0
            assert s["kv_fallbacks"] == 0
            assert s["kv_pages_shipped"] == 8     # 4 x two committed pages
            assert s["placements"]["disagg"] == 8.0
            roles = {r["replica"]: r["role"] for r in s["replicas"]}
            assert sorted(roles.values()) == ["decode", "prefill"]
            # the pages really moved: shipped == received, and the decode
            # side's admissions were prefix hits on mounted runs
            by_role = {r["role"]: r for r in s["replicas"]}
            assert by_role["prefill"]["kv_pushes"] == 4
            assert by_role["prefill"]["kv_pages_shipped"] == 8
            assert by_role["decode"]["kv_pages_received"] == 8
            # the ctl's fleet view carries the same columns
            with FleetCtl(host, port) as ctl:
                rows = ctl.list()
            assert sorted(r["role"] for r in rows) == ["decode", "prefill"]
        assert sd.engine.n_kv_mounts >= 3 and sd.engine.n_prefix_hits >= 4
        # a prompt under the floor (one KV page) never splits
        short = [3, 4, 5, 6, 7]
        with ServingClient(host, port) as c:
            toks, reason = c.generate(short, max_new=4)
            assert reason == "length"
            assert toks == _oracle(tiny_tr, short, 4)
            assert c.stats()["kv_pushes"] == 4    # unchanged
    finally:
        _stop_all(rt, srvs)


def test_disagg_cow_divergence_on_shipped_pages_stays_exact(tiny_tr):
    """Two requests sharing the shipped two-page run then DIVERGING
    afterward: both reference the same mounted pages on the decode
    replica concurrently, each appends into its own pages past the
    shared run, and both stay bit-exact (a write-through into a shared
    mounted page would corrupt the sibling)."""
    rng = np.random.default_rng(6)
    rt, host, port, srvs = _disagg_fleet(tiny_tr)
    sp, sd = srvs
    try:
        shared = rng.integers(2, 31, 2 * PAGE).tolist()
        p_a = shared + [9, 3, 11]
        p_b = shared + [4, 17]
        with ServingClient(host, port) as c:
            ra = c.submit(p_a, max_new=6)
            rb = c.submit(p_b, max_new=6)
            out = c.collect([ra, rb])
        assert out[ra]["tokens"] == _oracle(tiny_tr, p_a, 6)
        assert out[rb]["tokens"] == _oracle(tiny_tr, p_b, 6), \
            "divergent sibling corrupted by a shared shipped page?"
        assert sd.engine.n_kv_mounts >= 1
        assert sd.engine.n_prefix_hits >= 2      # both legs hit the run
        for srv in srvs:
            srv.engine.kv.check_reclaimed()
    finally:
        _stop_all(rt, srvs)


def test_disagg_decode_preemption_replay_stays_exact(tiny_tr):
    """An OVERCOMMITTED decode-side pool under disaggregated load:
    mounted pages are shared by concurrent slots, growth wedges the
    pool, victims are preempted and replayed — and every completed
    request still matches its oracle exactly."""
    rng = np.random.default_rng(8)
    rt, host, port, srvs = _disagg_fleet(tiny_tr,
                                         decode_kw=dict(num_pages=5))
    sp, sd = srvs
    try:
        shared = rng.integers(2, 31, 2 * PAGE).tolist()
        jobs = []
        with ServingClient(host, port) as c:
            for i in range(4):
                # 2 shared pages + 1 distinct token, then 14 new tokens:
                # two concurrent slots want 6 of the 5 real pages
                p = shared + [2 + i]
                jobs.append((c.submit(p, max_new=14, stream=False), p))
            out = c.collect([rid for rid, _ in jobs])
        for rid, p in jobs:
            assert out[rid]["tokens"] == _oracle(tiny_tr, p, 14), \
                "preemption/replay changed a disagg request's tokens"
            assert out[rid]["reason"] == "length"
        assert sd.engine.n_preemptions > 0, \
            "decode pool was never overcommitted"
        sd.engine.kv.check_reclaimed()
    finally:
        _stop_all(rt, srvs)


def test_disagg_prefill_tier_death_degrades_to_both_mode(tiny_tr):
    """Killing the prefill tier mid-workload: requests in their prefill
    phase (never streamed, by construction) retry transparently, the
    router stops planning splits the moment the tier is gone, and the
    workload completes with ZERO failed requests — all oracle-exact on
    the surviving decode replica."""
    rt, host, port, srvs = _disagg_fleet(tiny_tr)
    sp, sd = srvs
    results: list = []
    errors: list = []

    def load_worker(wid):
        try:
            with ServingClient(host, port) as c:
                w_rng = np.random.default_rng(300 + wid)
                for _ in range(8):
                    p = w_rng.integers(
                        2, 31, 2 * PAGE + int(w_rng.integers(1, 6))
                    ).tolist()
                    rid = c.submit(p, max_new=4, stream=False)
                    res = c.collect([rid])[rid]
                    results.append((p, res["tokens"], res["reason"]))
        except Exception as e:                     # noqa: BLE001
            errors.append(e)

    workers = [threading.Thread(target=load_worker, args=(w,))
               for w in range(2)]
    try:
        for t in workers:
            t.start()
        time.sleep(0.3)                           # splits provably flowing
        victim = next(r for r in rt.table if r.role == "prefill")
        _loop_call(rt, victim.backend.abort)      # the tier "dies"
        sp.stop_background(drain=False)
        for t in workers:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in workers), "load wedged"
        assert errors == [], \
            f"prefill-tier death failed requests: {errors}"
        assert len(results) == 16
        for p, toks, reason in results:
            assert reason == "length"
            assert toks == _oracle(tiny_tr, p, 4)
        with ServingClient(host, port) as c:
            s = c.stats()
        assert s["replicas_registered"] == 1
        assert s["replicas"][0]["role"] == "decode"
        assert s["kv_pushes"] >= 1, "no split ever ran before the kill"
    finally:
        _stop_all(rt, [sd])


@pytest.mark.slow
def test_soak_3replica_churn_stays_exact(tiny_tr):
    """3-replica churn soak: continuous mixed-prefix load while one
    replica is abruptly killed and another is drain-restarted through
    ctl; every completed request stays oracle-exact, the only tolerated
    failures are mid-stream deaths, and the fleet ends healthy at 3."""
    rng = np.random.default_rng(4)
    rt, host, port, srvs = _fleet(tiny_tr, 3)
    live = {s: True for s in srvs}
    prefixes = [rng.integers(2, 31, PAGE).tolist() for _ in range(3)]
    results: list = []
    failures: list = []
    done_load = threading.Event()

    def load_worker(wid):
        w_rng = np.random.default_rng(200 + wid)
        with ServingClient(host, port) as c:
            for i in range(12):
                p = prefixes[int(w_rng.integers(0, 3))] + \
                    w_rng.integers(2, 31, int(w_rng.integers(2, 6))
                                   ).tolist()
                try:
                    toks, reason = c.generate(p, max_new=4)
                    results.append((p, toks, reason))
                except (ServerError, OverloadError) as e:
                    failures.append(str(e))
                except ConnectionError as e:
                    failures.append(f"conn: {e}")
                    return

    workers = [threading.Thread(target=load_worker, args=(w,))
               for w in range(3)]
    try:
        for t in workers:
            t.start()
        time.sleep(0.3)
        # churn 1: abrupt kill of whichever replica is busiest
        victim = max(rt.table, key=lambda r: len(r.pending))
        _loop_call(rt, victim.backend.abort)
        vic_srv = next(s for s in srvs if s.port == victim.port)
        vic_srv.stop_background(drain=False)
        live[vic_srv] = False
        with FleetCtl(host, port) as ctl:
            # churn 2: drain-restart one survivor through the runbook
            rid = ctl.list()[0]["replica"]
            ctl.drain(rid)
            ctl.wait_drained(rid, timeout_s=120)
            row = ctl.status(rid)
            ctl.leave(rid)
            old_port = int(row["addr"].rsplit(":", 1)[1])
            old = next(s for s in srvs if live[s] and s.port == old_port)
            old.stop_background(drain=True)
            live[old] = False
            for _ in range(2):                     # restore to 3 replicas
                new_srv, nh, np_ = _replica(tiny_tr)
                srvs.append(new_srv)
                live[new_srv] = True
                ctl.join(nh, np_)
            for t in workers:
                t.join(timeout=300)
            assert not any(t.is_alive() for t in workers), "load wedged"
            rows = ctl.list()
        for p, toks, reason in results:
            assert reason == "length" and toks == _oracle(tiny_tr, p, 4), \
                "a churn survivor diverged from its oracle"
        # only mid-stream deaths may fail; everything else completed
        assert len(results) + len(failures) == 36
        for f in failures:
            assert "already streamed" in f or "no healthy replica" in f \
                or "retry limit" in f or "overloaded" in f, \
                f"unexpected failure: {f}"
        assert len(results) >= 30, f"too much lost to churn: {failures}"
        assert sum(1 for r in rows if r["state"] == "healthy") == 3
    finally:
        done_load.set()
        _stop_all(rt, [s for s in srvs if live.get(s)])
