"""Serving front-end loopback tests (serving/server.py + client.py).

The acceptance contract: mixed-length STREAMING requests over real TCP —
with one client-initiated cancellation and one deadline expiry mid-flight
— produce per-request token streams exactly matching
`lm_generate(use_cache=True)` run per surviving request, while the engine
pump keeps ONE compiled decode signature; overload yields an explicit
backpressure response instead of unbounded queueing; drain finishes
in-flight work and refuses new; SIGTERM on tools/serve.py drains and
exits 0 (slow)."""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.config.parser import parse_config
from paddle_tpu.graph.lm_decode import lm_generate
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.serving.client import OverloadError, ServingClient
from paddle_tpu.serving.server import ServingServer
from paddle_tpu.trainer.trainer import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_tr():
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=31,dim=16,layers=1,heads=2,batch_size=4")
    return Trainer(cfg, seed=7)


def _engine(tr, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_context", 64)
    eng = ServingEngine(tr.executor, tr.params, **kw)
    # deterministic deadline clock: "seconds" = decode steps taken
    eng.clock = lambda: float(eng.n_decode_steps)
    return eng


def _oracle(tr, prompt, max_new, **kw):
    import jax

    rng = jax.random.PRNGKey(kw.pop("seed")) if "seed" in kw else None
    toks, lens = lm_generate(tr.executor, tr.params,
                             np.asarray(prompt, np.int32)[None, :],
                             max_new=max_new, use_cache=True, rng=rng, **kw)
    return np.asarray(toks)[0, :int(np.asarray(lens)[0])].tolist()


def _paired_client():
    """A ServingClient wired to one end of a socketpair — lets the frame
    routing be tested without a server (or jax) in the loop."""
    import socket

    a, b = socket.socketpair()
    a.settimeout(5.0)
    c = ServingClient.__new__(ServingClient)
    c.sock = a
    c._next_id = 0
    c._pending = []
    return c, b


def test_client_routing_drains_socket_past_buffered_foreign_frames():
    """Regression: collect()/stats() with _pending holding ONLY other
    requests' frames must fall through to the socket instead of recycling
    the buffer forever (the pre-fix behavior busy-looped here)."""
    from paddle_tpu.serving import wire

    c, peer = _paired_client()
    try:
        # buffer frames that belong to a different in-flight request
        c._pending = [{"type": "token", "id": "r1", "token": 5, "index": 0},
                      {"type": "token", "id": "r1", "token": 6, "index": 1}]
        peer.sendall(wire.encode({"type": "done", "id": "r0",
                                  "tokens": [1, 2], "reason": "length"}))
        res = c.collect(["r0"])
        assert res["r0"]["tokens"] == [1, 2]
        # r1's frames survived, untouched and in order
        assert [m["token"] for m in c._pending] == [5, 6]

        # stats() mid-stream: socket frames for r1 get stashed, stats returns
        peer.sendall(wire.encode({"type": "token", "id": "r1",
                                  "token": 7, "index": 2}))
        peer.sendall(wire.encode({"type": "stats", "queue_depth": 0}))
        assert c.stats()["queue_depth"] == 0
        assert [m["token"] for m in c._pending] == [5, 6, 7]

        # the buffered stream then collects exactly, buffer first
        peer.sendall(wire.encode({"type": "done", "id": "r1",
                                  "tokens": [5, 6, 7], "reason": "length"}))
        res = c.collect(["r1"])
        assert res["r1"]["stream"] == [5, 6, 7]
        assert c._pending == []
    finally:
        c.close()
        peer.close()


def test_streaming_cancel_deadline_oracle_exact_over_tcp(tiny_tr):
    """The end-to-end acceptance test (ISSUE 4)."""
    rng = np.random.default_rng(0)
    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=32)
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            assert c.ping()
            # mixed lengths spanning prefill buckets; r3 sampled (seeded)
            p0 = rng.integers(2, 31, 3).tolist()
            p1 = rng.integers(2, 31, 9).tolist()
            p2 = rng.integers(2, 31, 5).tolist()
            p3 = rng.integers(2, 31, 12).tolist()
            p_dead = rng.integers(2, 31, 4).tolist()
            p_cancel = rng.integers(2, 31, 6).tolist()
            # the deadline request goes FIRST: the idle pump admits it to a
            # slot at step ~0, and a 3-step budget (engine.clock counts
            # decode steps) against 30 tokens guarantees an IN-SLOT expiry
            r_dead = c.submit(p_dead, max_new=30, timeout_s=3.0)
            r0 = c.submit(p0, max_new=6)
            r1 = c.submit(p1, max_new=8)
            r2 = c.submit(p2, max_new=4)
            r3 = c.submit(p3, max_new=5, temperature=0.8, top_k=5, seed=11)
            r_cancel = c.submit(p_cancel, max_new=30)

            cancelled = []

            def on_token(rid, tok, idx):
                # cancel mid-flight: after its first streamed token the
                # request provably occupies a slot
                if rid == r_cancel and idx >= 1 and not cancelled:
                    cancelled.append(True)
                    c.cancel(r_cancel)

            out = c.collect([r0, r1, r2, r3, r_dead, r_cancel],
                            on_token=on_token)
        # surviving requests: token-for-token against the per-request oracle
        assert out[r0]["tokens"] == _oracle(tiny_tr, p0, 6)
        assert out[r1]["tokens"] == _oracle(tiny_tr, p1, 8)
        assert out[r2]["tokens"] == _oracle(tiny_tr, p2, 4)
        assert out[r3]["tokens"] == _oracle(tiny_tr, p3, 5, temperature=0.8,
                                            top_k=5, seed=11)
        # every stream (survivors AND aborted) is exactly its final result:
        # token frames arrive in order and the done frame agrees
        for rid, prompt in ((r0, p0), (r1, p1), (r2, p2), (r3, p3),
                            (r_dead, p_dead), (r_cancel, p_cancel)):
            assert out[rid]["tokens"][:len(prompt)] == prompt
            assert out[rid]["stream"] == out[rid]["tokens"][len(prompt):]
        for rid in (r0, r1, r2, r3):
            assert out[rid]["reason"] == "length"
        # the aborted pair: right reasons, genuinely stopped mid-flight
        assert out[r_dead]["reason"] == "deadline"
        assert len(p_dead) < len(out[r_dead]["tokens"]) < len(p_dead) + 30, \
            "deadline request should die in a slot with partial output"
        assert out[r_cancel]["reason"] == "cancelled"
        assert cancelled, "cancel hook never fired"
        assert len(out[r_cancel]["tokens"]) < len(p_cancel) + 30
        assert eng.n_expired == 1 and eng.n_cancelled >= 1
        # ONE compiled decode signature for the whole mixed workload
        assert eng._decode_step._cache_size() == 1
        # every page reclaimable once all requests resolved: free outright
        # or retained only by the prefix index (evictable on demand)
        eng.kv.check_reclaimed()
    finally:
        srv.stop_background(drain=True)


def test_stats_rpc_reports_occupancy_and_latency(tiny_tr):
    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4)
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            c.generate([3, 4, 5], max_new=4)
            s = c.stats()
        assert s["num_slots"] == 2
        assert s["max_inflight"] == 6
        assert s["queue_depth"] == 0 and s["inflight"] == 0
        assert s["tokens_generated"] >= 4
        assert s["free_pages"] == s["num_pages"] - 1
        # the paged kernel's reads: whole blocks fetched for the tokens
        # its rows attended (one 64-token block a row at this context)
        assert 0 < s["kv_tokens_attended"] <= s["kv_tokens_fetched"]
        assert s["kv_tokens_fetched"] % 64 == 0
        assert s["draining"] is False
        lat = s["latency_ms"]
        assert lat["request_latency"]["p50"] > 0.0
        assert lat["first_token_latency"]["p99"] >= \
            lat["first_token_latency"]["p50"]
    finally:
        srv.stop_background(drain=True)


def test_metrics_frame_and_consistent_stats_over_tcp(tiny_tr):
    """ISSUE 5: the Prometheus-style `metrics` frame over TCP loopback,
    plus the reworked stats snapshot — the default path builds the engine
    half on the PUMP thread (consistent), `stale_ok` answers from the
    loop thread immediately, and both carry the watchdog fields."""
    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4)
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            c.generate([3, 4, 5], max_new=4)
            text = c.metrics()
            # exposition-format spot checks against documented names
            assert "# TYPE serving_queue_depth gauge" in text
            assert "# TYPE serving_tokens_generated_total counter" in text
            assert "pump_alive 1" in text
            vals = {}
            for line in text.splitlines():
                if line and not line.startswith("#"):
                    key, v = line.rsplit(" ", 1)
                    vals[key] = float(v)
            assert vals["serving_tokens_generated_total"] >= 4.0
            assert vals["serving_requests_accepted_total"] == 1.0
            assert vals["serving_num_slots"] == 2.0
            assert 0.0 <= vals["pump_last_step_age_s"] < 60.0
            assert vals['serving_latency_seconds'
                        '{quantile="p50",stat="request_latency"}'] > 0.0
            assert vals['serving_latency_count'
                        '{stat="first_token_latency"}'] == 1.0
            # the kept eviction frontier's families (ISSUE 44): a pool
            # that never came under pressure made no call and popped
            # nothing, and the request's one donated leaf holds the
            # frontier's one entry
            assert vals["serving_prefix_evict_calls_total"] == 0.0
            for outcome in ("victim", "stale", "ineligible"):
                assert vals['serving_prefix_frontier_pops_total'
                            f'{{outcome="{outcome}"}}'] == 0.0
            assert 0.0 <= vals["serving_prefix_frontier_size"] <= \
                vals["serving_prefix_nodes"]
            # consistent (pump round-trip) vs stale_ok (loop fast path)
            s = c.stats()
            assert s["consistent"] is True and s["pump_alive"] is True
            assert s["queue_depth"] == 0 and s["slots_in_use"] == 0
            s2 = c.stats(stale_ok=True)
            assert s2["consistent"] is False
            assert s2["tokens_generated"] == s["tokens_generated"]
            assert s2["pump_last_step_age_s"] >= 0.0
        # docs lint lockstep: every name the frame rendered is catalogued
        # (histogram samples render as <family>_bucket/_sum/_count — the
        # family name is the catalogued one, same mapping the strict
        # registry applies)
        from paddle_tpu.obs import CATALOG
        from paddle_tpu.obs.metrics import MetricsRegistry
        for key in vals:
            base = key.split("{", 1)[0]
            fam = MetricsRegistry._family_of(base, "histogram")
            assert base in CATALOG or fam in CATALOG, \
                f"{base} rendered but not in CATALOG"
    finally:
        srv.stop_background(drain=True)


def test_token_frame_has_no_burst_and_token_latency_is_the_gap(tiny_tr):
    """A token frame is its id, token and index — no `burst` field, no
    scan counters in `stats` or the metrics text — and the server's
    token_latency of a request is the gap between its tokens' arrivals
    from the engine, one sample a fresh token past the first."""
    from paddle_tpu.serving import wire

    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=8)
    arrivals, inner = [], srv._on_token

    def on_token(rid, tok, idx):
        inner(rid, tok, idx)
        arrivals.append(srv._routes[rid].t_last)

    eng.on_token = on_token
    host, port = srv.start_background()
    try:
        import socket

        prompt = [3, 9, 4, 7, 2]
        sock = socket.create_connection((host, port), timeout=30)
        try:
            wire.write_frame_sync(sock, wire.hello_msg("client"))
            assert wire.read_frame_sync(sock)["role"] == "replica"
            _generate(sock, "r0", prompt, 9)
            frames = [m for m, _ in _read_until_terminal(sock, ["r0"])]
        finally:
            sock.close()
        toks, done = frames[:-1], frames[-1]
        assert done["reason"] == "length"
        assert done["tokens"] == _oracle(tiny_tr, prompt, 9)
        assert [f["token"] for f in toks] == done["tokens"][len(prompt):]
        assert all(f.keys() == {"type", "id", "token", "index"}
                   for f in toks)

        with ServingClient(host, port) as c:
            s = c.stats()
            assert not {"decode_steps_k", "scan_steps", "scan_flushes"} \
                & s.keys()
            assert s["decode_steps"] == eng.n_decode_steps
            text = c.metrics()
        assert "serving_scan_" not in text
        lat = srv.stats.get("token_latency")
        assert lat.count == 8 and len(arrivals) == 9
        assert lat.samples == [b - a for a, b in zip(arrivals, arrivals[1:])]
        assert srv.stats.get("first_token_latency").count == 1
        eng.kv.check_reclaimed()
    finally:
        srv.stop_background(drain=True)


def test_stats_stale_ok_works_with_pump_off(tiny_tr):
    """The watchdog path must answer when the pump never started — and
    the DEFAULT path must fall back rather than hang forever."""
    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4)
    host, port = srv.start_background(start_pump=False)
    try:
        with ServingClient(host, port) as c:
            s = c.stats(stale_ok=True)
            assert s["consistent"] is False and s["pump_alive"] is False
            assert s["pump_last_step_age_s"] == -1.0
            s = c.stats()                      # no pump -> stale fallback
            assert s["consistent"] is False
    finally:
        srv.stop_background(drain=True)


def test_stats_queued_behind_stop_is_still_answered(tiny_tr):
    """A consistent-stats command already sitting in the command queue
    when the pump pops "stop" must be answered, not orphaned — the
    pump's stop-drain replies (consistently: it runs between steps on
    the pump thread) instead of leaving the client blocked until its
    socket times out."""
    import socket

    from paddle_tpu.serving import wire

    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4)
    host, port = srv.start_background()
    sock = socket.create_connection((host, port))
    sock.settimeout(30)
    try:
        deadline = time.time() + 10
        while not srv._conns and time.time() < deadline:
            time.sleep(0.01)
        conn = next(iter(srv._conns))
        # deterministic ordering: the stats round trip lands BEHIND stop
        srv._cmds.put(("stop",))
        srv._cmds.put(("stats", conn))
        srv._wake.set()
        msg = wire.read_frame_sync(sock)
        assert msg["type"] == "stats" and msg["consistent"] is True
    finally:
        sock.close()
        srv.stop_background(drain=True)


def test_overload_returns_backpressure_not_unbounded_queue(tiny_tr):
    """Admission cap = num_slots + max_queue accepted-but-unfinished
    requests; one more gets an explicit overload frame.  The pump is held
    off so the staging is deterministic."""
    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=1)          # cap = 2 slots + 1 = 3
    host, port = srv.start_background(start_pump=False)
    try:
        with ServingClient(host, port) as c:
            prompt = [3, 4, 5]
            ids = [c.submit(prompt, max_new=3) for _ in range(3)]
            over = c.submit(prompt, max_new=3)
            with pytest.raises(OverloadError) as ei:
                c.collect([over])
            assert ei.value.info["reason"] == "queue_full"
            assert ei.value.info["max_inflight"] == 3
            # the three accepted ones complete once the pump starts —
            # backpressure never cost admitted work
            srv.start_pump()
            out = c.collect(ids)
            want = _oracle(tiny_tr, prompt, 3)
            for rid in ids:
                assert out[rid]["tokens"] == want
    finally:
        srv.stop_background(drain=True)


def test_drain_finishes_inflight_and_refuses_new(tiny_tr):
    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=8)
    host, port = srv.start_background(start_pump=False)
    stopper = threading.Thread(target=lambda: srv.stop_background(drain=True))
    try:
        with ServingClient(host, port) as c:
            prompt = [4, 5, 6, 7]
            rid = c.submit(prompt, max_new=5)      # accepted, pump off
            # same-connection barrier: the stats reply proves the generate
            # frame was ADMITTED before drain flips the refusal flag —
            # otherwise drain could see inflight=0 and shut down first
            assert c.stats()["inflight"] == 1
            stopper.start()
            for _ in range(200):                   # wait for draining state
                if srv._draining:
                    break
                time.sleep(0.01)
            assert srv._draining
            late = c.submit(prompt, max_new=5)
            with pytest.raises(OverloadError) as ei:
                c.collect([late])
            assert ei.value.info["reason"] == "draining"
            # draining still FINISHES accepted work — drain itself starts
            # the pump that was never running (no explicit start_pump)
            out = c.collect([rid])
            assert out[rid]["tokens"] == _oracle(tiny_tr, prompt, 5)
            assert out[rid]["reason"] == "length"
    finally:
        stopper.join(timeout=120)
    assert not stopper.is_alive(), "drain never completed"
    # listener is down: fresh connections are refused
    with pytest.raises(OSError):
        ServingClient(host, port, timeout=5)


def test_disconnect_cancels_inflight_requests(tiny_tr):
    """A client that vanishes mid-stream must not pin its slot and pages
    forever — the server cancels its requests on connection loss."""
    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=8)
    host, port = srv.start_background()
    try:
        c = ServingClient(host, port)
        rid = c.submit([3, 4, 5, 6], max_new=50)
        # wait for the first token frame, then vanish
        msg = c.recv()
        while msg.get("type") != "token":
            msg = c.recv()
        c.close()
        # cancelled pages are reclaimable — free, or donated to the prefix
        # index as cached refcount-zero (evictable on the next allocation)
        def _reclaimable():
            return (eng.kv.free_page_count + eng.kv.cached_page_count
                    == eng.kv.num_pages - 1)

        deadline = time.time() + 60
        while time.time() < deadline:
            if _reclaimable() and srv._inflight == 0:
                break
            time.sleep(0.02)
        assert srv._inflight == 0, "dead client's request never cancelled"
        eng.kv.check_reclaimed()
    finally:
        srv.stop_background(drain=True)


def test_malformed_frames_get_error_frames_not_disconnect(tiny_tr):
    """Protocol garbage — unhashable ids, negative max_new, empty prompts,
    unknown types — must each answer an `error` frame and leave the
    connection (and every other request multiplexed on it) alive."""
    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4)
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            c.send({"type": "generate", "id": [1], "prompt": [3, 4]})
            assert c.recv()["type"] == "error"          # unhashable id
            c.send({"type": "generate", "id": "neg", "prompt": [3, 4],
                    "max_new": -1})
            msg = c.recv()
            assert msg["type"] == "error" and msg["id"] == "neg"
            assert "negative" in msg["error"]
            c.send({"type": "generate", "id": "empty", "prompt": []})
            msg = c.recv()
            assert msg["type"] == "error" and "prompt" in msg["error"]
            c.send({"type": "generate", "id": "bad", "prompt": "zzz"})
            assert c.recv()["type"] == "error"          # non-id prompt
            c.send({"type": "cancel", "id": {}})        # silently ignored
            c.send({"type": "wat"})
            assert "unknown" in c.recv()["error"]
            # the connection survived all of it — real work still flows
            toks, reason = c.generate([3, 4, 5], max_new=3)
            assert reason == "length" and len(toks) == 6
    finally:
        srv.stop_background(drain=True)


def test_int_and_str_client_ids_do_not_collide(tiny_tr):
    """JSON id 1 and id \"1\" are distinct requests: the engine req_id
    namespace must keep them apart or one route is overwritten and
    _inflight leaks (wedging drain forever)."""
    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4)
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            c.send({"type": "generate", "id": 1, "prompt": [3, 4],
                    "max_new": 2})
            c.send({"type": "generate", "id": "1", "prompt": [3, 4, 5],
                    "max_new": 2})
            out = c.collect([1, "1"])
        assert len(out[1]["tokens"]) == 4
        assert len(out["1"]["tokens"]) == 5
        assert srv._inflight == 0, "a route was overwritten and leaked"
    finally:
        srv.stop_background(drain=True)


def test_pump_death_fails_pending_and_refuses_new(tiny_tr):
    """If the engine pump dies (device fault mid-step), every accepted
    request gets an error frame and later generates are refused
    immediately — no client may hang on frames that will never come."""
    from paddle_tpu.serving.client import ServerError

    eng = _engine(tiny_tr)
    orig_step = eng.step

    def bad_step():
        if eng.queue or any(s is not None for s in eng.slots):
            raise RuntimeError("boom")
        return orig_step()

    eng.step = bad_step
    srv = ServingServer(eng, max_queue=8)
    host, port = srv.start_background()
    with ServingClient(host, port) as c:
        rid = c.submit([3, 4, 5], max_new=4)
        with pytest.raises(ServerError, match="pump died.*boom"):
            c.collect([rid])           # pending work failed, not stranded
        rid2 = c.submit([3, 4], max_new=4)
        with pytest.raises(ServerError, match="pump died"):
            c.collect([rid2])          # new work refused up front
        s = c.stats()                  # dead pump: stale fallback, no hang
        assert s["consistent"] is False and s["pump_alive"] is False
    with pytest.raises(RuntimeError, match="engine pump died"):
        srv.stop_background(drain=True)


@pytest.mark.slow
def test_serve_cli_sigterm_drains_and_exits_zero():
    """tools/serve.py end to end in a subprocess: bind ephemeral port,
    stream one completion, SIGTERM mid-flight on a second, the drain
    finishes it, process exits 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve.py"),
         "--config-args", "vocab=31,dim=16,layers=1,heads=2,batch_size=2",
         "--slots", "2", "--page-size", "8", "--max-context", "32",
         "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=REPO, env=env)
    try:
        line = ""
        t0 = time.time()
        while time.time() - t0 < 300:
            line = proc.stdout.readline()
            if line.startswith("SERVE_JSON:"):
                break
        assert line.startswith("SERVE_JSON:"), "server never bound"
        import json as _json

        addr = _json.loads(line[len("SERVE_JSON:"):])
        with ServingClient(addr["host"], addr["port"]) as c:
            toks, reason = c.generate([3, 4, 5], max_new=4)
            assert reason == "length" and len(toks) == 7
            rid = c.submit([4, 5, 6], max_new=12)
            # first token seen -> mid-flight; now ask for shutdown
            msg = c.recv()
            while msg.get("type") != "token":
                msg = c.recv()
            proc.send_signal(15)                   # SIGTERM
            c._pending.append(msg)
            out = c.collect([rid])
            assert out[rid]["reason"] == "length"
            assert len(out[rid]["tokens"]) == 3 + 12, \
                "drain did not finish the in-flight request"
        assert proc.wait(timeout=120) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


@pytest.mark.slow
def test_soak_overcommitted_pool_over_tcp_stays_exact(tiny_tr):
    """Longer mixed workload through TCP against an OVERCOMMITTED pool:
    preemptions fire under the server pump and every completed request
    still matches its oracle exactly."""
    rng = np.random.default_rng(3)
    eng = _engine(tiny_tr, num_slots=2, page_size=4, max_context=16,
                  num_pages=6)
    srv = ServingServer(eng, max_queue=64)
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            jobs = []
            for i in range(10):
                # every request's full footprint is 16 tokens = 4 pages,
                # so any two concurrently-decoding slots want 8 of the 5
                # real pages — the pool MUST wedge and preempt
                plen = int(rng.integers(7, 11))
                p = rng.integers(2, 31, plen).tolist()
                mn = 16 - plen
                jobs.append((c.submit(p, max_new=mn), p, mn))
            out = c.collect([rid for rid, _, _ in jobs])
        for rid, p, mn in jobs:
            assert out[rid]["tokens"] == _oracle(tiny_tr, p, mn), \
                f"request {rid} diverged (preemption changed its tokens?)"
        assert eng.n_preemptions > 0, "pool was never overcommitted"
        assert eng._decode_step._cache_size() == 1
    finally:
        srv.stop_background(drain=True)


# ---------------------------------------------------------------------------
# ISSUE 10 satellites: hello negotiation, protocol-naming errors, and the
# client's reconnect-with-backoff
# ---------------------------------------------------------------------------

def test_hello_frame_reports_proto_and_capabilities(tiny_tr):
    """The version/capabilities frame answered on connect — the fleet
    router classifies peers with it, so role/proto/page_size must hold."""
    from paddle_tpu.serving import wire

    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4)
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            h = c.hello()
            assert h["proto"] == wire.PROTO
            assert h["role"] == "replica"
            assert "generate" in h["capabilities"]
            assert "dump" in h["capabilities"]
            assert h["page_size"] == 8 and h["num_slots"] == 2
            assert h["max_inflight"] == 6 and h["draining"] is False
            # the KV transfer plane (ISSUE 19): the capability the router
            # keys disaggregated placement on, plus the replica's role tier
            assert "kv_xfer" in h["capabilities"]
            assert h["role_mode"] == "both"
            # negotiation is just another frame: real work still flows
            toks, reason = c.generate([3, 4, 5], max_new=3)
            assert reason == "length" and len(toks) == 6
    finally:
        srv.stop_background(drain=True)


def test_trace_rpc_live_flip_and_context_adoption(tiny_tr):
    """ISSUE 13: the `trace` RPC snapshots the span ring with process
    identity + a clock sample, flips tracing LIVE via `enable` (no
    restart — the operator move and the bench probe's A/B switch), and
    a generate frame's trace context is adopted into the engine's
    lifecycle spans."""
    from paddle_tpu.obs import Tracer

    tracer = Tracer()
    eng = _engine(tiny_tr, tracer=tracer)
    srv = ServingServer(eng, max_queue=4)
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            assert "trace" in c.hello()["capabilities"]
            t0 = c.trace()
            assert t0["enabled"] is False and t0["spans"] == []
            assert t0["process"]["role"] == "replica"
            assert t0["process"]["addr"].endswith(f":{port}")
            assert abs(t0["offset_s"]) < 1.0     # same-process clocks
            # flip on live, run one traced request with a CLIENT context
            assert c.trace(enable=True)["enabled"] is True
            toks, reason = c.generate(
                [2, 7, 9], max_new=4,
                trace={"trace_id": "cafe01", "parent": "p9"})
            assert reason == "length"
            # flip off + collect what it froze
            t1 = c.trace(enable=False)
            assert t1["enabled"] is False and tracer.enabled is False
            req = [s for s in t1["spans"]
                   if (s.get("attrs") or {}).get("trace_id") == "cafe01"]
            assert [s["name"] for s in req] == \
                ["queued", "prefill", "decode", "done"]
            assert all(s["attrs"]["parent"] == "p9" for s in req)
            # the done frame carried the timing breakdown too
            rid = c.submit([2, 3, 4], max_new=3)
            timing = c.collect([rid])[rid]["timing"]
            assert timing["total_ms"] <= timing["request_ms"] + 1.0
    finally:
        srv.stop_background(drain=True)


def test_malformed_first_frame_names_expected_protocol(tiny_tr):
    """A peer speaking the wrong protocol (here: HTTP) gets an `error`
    frame NAMING the expected protocol, not a silent close — the router
    depends on this to classify peers."""
    import socket

    from paddle_tpu.serving import wire

    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4)
    host, port = srv.start_background()
    try:
        s = socket.create_connection((host, port), timeout=10)
        s.settimeout(10)
        try:
            s.sendall(b"GET / HTTP/1.1\r\nHost: x\r\n\r\n")
            msg = wire.read_frame_sync(s)
            assert msg["type"] == "error"
            assert "4-byte big-endian length" in msg["error"]
            assert "hello" in msg["error"]
            assert f"wire protocol v{wire.PROTO}" in msg["error"]
            # after the error frame the server closes the connection
            assert wire.read_frame_sync(s) is None
        finally:
            s.close()
    finally:
        srv.stop_background(drain=True)


def test_client_connect_backoff_survives_restart_window():
    """ECONNREFUSED during a rolling restart's rebind window is a WAIT,
    not an instant failure: the client retries with bounded jittered
    backoff until the listener binds."""
    import socket

    from paddle_tpu.serving import wire

    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()                      # port free -> connects are refused

    accepted = []

    def late_bind():
        time.sleep(0.6)                # the restart window
        srv = socket.socket()
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", port))
        srv.listen(1)
        conn, _ = srv.accept()
        accepted.append(True)
        # answer a pong so the client can prove the connection works
        f = wire.read_frame_sync(conn)
        assert f == {"type": "ping"}
        conn.sendall(wire.encode({"type": "pong"}))
        time.sleep(0.2)
        conn.close()
        srv.close()

    t = threading.Thread(target=late_bind)
    t.start()
    try:
        c = ServingClient("127.0.0.1", port, timeout=10,
                          connect_attempts=10)
        try:
            assert c.ping()
        finally:
            c.close()
        assert accepted, "client never reached the late-bound listener"
    finally:
        t.join(timeout=30)


def test_client_connect_backoff_exhaustion_is_actionable():
    """Capped attempts against a dead address fail with an error that
    says what was tried and what to do — still an OSError subclass, so
    existing callers' except clauses keep working."""
    import socket

    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    t0 = time.monotonic()
    with pytest.raises(ConnectionRefusedError,
                       match="after 3 attempts") as ei:
        ServingClient("127.0.0.1", port, timeout=5, connect_attempts=3,
                      connect_backoff_s=0.02)
    assert "restart" in str(ei.value)
    assert time.monotonic() - t0 < 5.0, "backoff must stay bounded"


# ---------------------------------------------------------------------------
# ISSUE 6: flight recorder + postmortem bundle trigger paths
# ---------------------------------------------------------------------------

def _bundles(d):
    import glob

    return sorted(p for p in glob.glob(os.path.join(str(d), "postmortem-*"))
                  if not p.endswith(".tmp"))


def test_pump_crash_writes_loadable_postmortem_bundle(tiny_tr, tmp_path):
    """An induced pump crash freezes one atomic bundle — written on the
    DYING pump thread with engine state exactly as the failure left it —
    and tools/postmortem.py round-trips it."""
    from paddle_tpu.obs.flight import load_bundle
    from paddle_tpu.serving.client import ServerError
    from tools.postmortem import main as postmortem_main

    eng = _engine(tiny_tr)
    orig_step = eng.step

    def bad_step():
        if eng.queue or any(s is not None for s in eng.slots):
            raise RuntimeError("induced device fault")
        return orig_step()

    eng.step = bad_step
    srv = ServingServer(eng, max_queue=8, postmortem_dir=str(tmp_path))
    host, port = srv.start_background()
    with ServingClient(host, port) as c:
        rid = c.submit([3, 4, 5], max_new=4)
        with pytest.raises(ServerError, match="pump died"):
            c.collect([rid])

    found = _bundles(tmp_path)
    assert len(found) == 1, "pump death must freeze exactly one bundle"
    b = load_bundle(found[0])
    assert b["meta"]["reason"] == "pump_death"
    assert "induced device fault" in b["meta"]["error"]
    assert "Traceback" in b["meta"]["error"]
    kinds = [e["kind"] for e in b["events"]]
    assert "pump_death" in kinds and "accept" in kinds
    # the engine snapshot froze the crash state: the victim request is
    # still visible (queued or in its slot), pools are accounted
    occupied = [s for s in b["engine"]["slots"] if s]
    assert b["engine"]["queued"] or occupied
    assert b["engine"]["num_pages"] == eng.kv.num_pages
    assert "compile_watch" in b["engine"] and "hbm" in b["engine"]
    assert b["config"]["num_slots"] == 2
    assert postmortem_main([found[0]]) == 0     # pretty-printer round-trip
    with pytest.raises(RuntimeError, match="engine pump died"):
        srv.stop_background(drain=True)


def test_wedge_watchdog_dumps_once_and_metrics_stay_readable(tiny_tr,
                                                             tmp_path):
    """ISSUE 6 acceptance: deliberately wedge the pump — the watchdog
    sees `pump_last_step_age_s` grow, the metrics frame stays readable
    the whole time (loop-thread path), and the flight recorder emits
    EXACTLY ONE bundle at the threshold (one per wedge episode)."""
    from paddle_tpu.obs.flight import load_bundle

    eng = _engine(tiny_tr)
    orig_step = eng.step
    wedged, release = threading.Event(), threading.Event()

    def wedge_step():
        if not release.is_set() and \
                (eng.queue or any(s is not None for s in eng.slots)):
            wedged.set()
            release.wait(60)                  # the deliberate wedge
        return orig_step()

    eng.step = wedge_step
    # threshold must clear the 0.5 s idle-wait bound or an idle pump
    # reads as wedged (docs/observability.md watchdog semantics)
    srv = ServingServer(eng, max_queue=4, postmortem_dir=str(tmp_path),
                        wedge_threshold_s=1.0)
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            rid = c.submit([3, 4, 5], max_new=3)
            assert wedged.wait(30), "pump never picked up the request"
            # the age gauge grows while wedged — stale-ok reads answer
            # from the loop thread against the stuck pump
            a1 = c.stats(stale_ok=True)["pump_last_step_age_s"]
            time.sleep(0.3)
            a2 = c.stats(stale_ok=True)["pump_last_step_age_s"]
            # a1 can round to 0.0 when the read lands within 0.5ms of
            # the frozen beat — the growth is the signal, not the start
            assert a2 > a1 >= 0.0 and a2 >= 0.25
            # the metrics frame stays readable against the wedged engine
            text = c.metrics()
            assert "pump_alive 1" in text
            assert "pump_last_step_age_s" in text
            # the watchdog crosses the 1.0s threshold and dumps ONCE
            deadline = time.time() + 20
            while not _bundles(tmp_path) and time.time() < deadline:
                time.sleep(0.05)
            found = _bundles(tmp_path)
            assert len(found) == 1, "no bundle at the wedge threshold"
            time.sleep(0.6)                   # > watchdog poll period
            assert len(_bundles(tmp_path)) == 1, \
                "a sustained wedge must be one bundle, not one per poll"
            b = load_bundle(found[0])
            assert b["meta"]["reason"] == "wedge"
            assert "pump wedged" in b["meta"]["error"]
            assert "wedge" in [e["kind"] for e in b["events"]]
            # the wedged request is frozen in the snapshot
            assert b["engine"]["queued"] or \
                [s for s in b["engine"]["slots"] if s]
            # release: the pump recovers and the request completes exactly
            release.set()
            out = c.collect([rid])
            assert out[rid]["tokens"] == _oracle(tiny_tr, [3, 4, 5], 3)
    finally:
        release.set()
        srv.stop_background(drain=True)


def test_dump_rpc_freezes_bundle_on_demand(tiny_tr, tmp_path):
    """The operator path: `dump` over the wire freezes a bundle NOW and
    answers its path; without a configured directory it is a clean error
    frame, not a dead connection."""
    from paddle_tpu.obs.flight import load_bundle
    from paddle_tpu.serving.client import ServerError

    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4, postmortem_dir=str(tmp_path))
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            toks, reason = c.generate([3, 4, 5], max_new=4)
            assert reason == "length"
            d = c.dump()
            assert os.path.isdir(d["path"])
            assert d["events"] > 0
            b = load_bundle(d["path"])
            assert b["meta"]["reason"] == "rpc"
            kinds = [e["kind"] for e in b["events"]]
            assert "dump_rpc" in kinds and "finish" in kinds
            assert b["metrics"]["serving_requests_accepted_total"] >= 1.0
            # the engine is healthy and idle in the snapshot
            assert b["engine"]["queued"] == []
            assert all(s is None for s in b["engine"]["slots"])
            # connection survives; the server keeps serving after a dump
            toks2, _ = c.generate([4, 5], max_new=3)
            assert len(toks2) == 5
    finally:
        srv.stop_background(drain=True)

    eng2 = _engine(tiny_tr)
    srv2 = ServingServer(eng2, max_queue=4)    # no postmortem dir
    host, port = srv2.start_background()
    try:
        with ServingClient(host, port) as c:
            with pytest.raises(ServerError, match="no postmortem dir"):
                c.dump()
    finally:
        srv2.stop_background(drain=True)


# ---------------------------------------------------------------------------
# ISSUE 19: binary-frame robustness + the kv_push page-transfer plane
# ---------------------------------------------------------------------------

def test_bin_frame_over_cap_answers_error_then_severs(tiny_tr):
    """A peer declaring a binary frame bigger than the endpoint's 8 MiB
    cap gets an error frame NAMING the cap, then a clean close — the
    declared length is refused from the 4-byte prefix alone, before a
    single payload byte is buffered."""
    import socket
    import struct

    from paddle_tpu.serving import wire

    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4)
    host, port = srv.start_background()
    try:
        s = socket.create_connection((host, port), timeout=10)
        s.settimeout(10)
        try:
            wire.write_frame_sync(s, {"type": "ping"})
            assert wire.read_frame_sync(s)["type"] == "pong"
            s.sendall(struct.pack(
                ">I", wire.BIN_BIT | (wire.MAX_BIN_PAYLOAD + 1)))
            msg = wire.read_frame_sync(s)
            assert msg["type"] == "error"
            assert "binary-frame cap" in msg["error"]
            assert str(wire.MAX_BIN_PAYLOAD) in msg["error"]
            assert wire.read_frame_sync(s) is None     # severed cleanly
        finally:
            s.close()
        # the listener survived the hostile peer: real work still flows
        with ServingClient(host, port) as c:
            toks, reason = c.generate([3, 4, 5], max_new=3)
            assert reason == "length" and len(toks) == 6
    finally:
        srv.stop_background(drain=True)


def test_bin_frame_truncated_mid_payload_severs_cleanly(tiny_tr):
    """A binary frame whose sender dies mid-payload must not wedge the
    reader or leak half-buffered kv_push state — the connection dies,
    the buffered parts die with it, the server keeps serving."""
    import socket
    import struct

    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4)
    host, port = srv.start_background()
    try:
        from paddle_tpu.serving import wire

        s = socket.create_connection((host, port), timeout=10)
        try:
            # declare a 4096-byte binary body, deliver 10 bytes, vanish
            s.sendall(struct.pack(">I", wire.BIN_BIT | 4096) + b"x" * 10)
        finally:
            s.close()
        deadline = time.time() + 20
        while srv._conns and time.time() < deadline:
            time.sleep(0.01)
        assert not srv._conns, "truncated peer's connection never reaped"
        assert srv._kv_parts == {}
        with ServingClient(host, port) as c:
            toks, reason = c.generate([3, 4, 5], max_new=3)
            assert reason == "length" and len(toks) == 6
    finally:
        srv.stop_background(drain=True)


def test_kv_push_malformed_frames_refused_not_fatal(tiny_tr):
    """Hostile/buggy kv_push senders — no part 0, page counts outside
    the pool, payload overrunning the declared blob, garbage meta — each
    answer a `kv_push ok:false` (or error) frame and leave the
    connection serving; nothing is buffered past the refusal."""
    import socket

    from paddle_tpu.serving import wire

    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4)
    host, port = srv.start_background()
    try:
        s = socket.create_connection((host, port), timeout=30)
        s.settimeout(30)
        try:
            # unusable id: error frame, not a dead socket
            s.sendall(wire.encode_bin({"type": "kv_push", "id": [1],
                                       "seq": 0, "last": True}, b""))
            msg = wire.read_frame_sync(s, bin_cap=wire.MAX_BIN_PAYLOAD)
            assert msg["type"] == "error" and "id" in msg["error"]
            # part 1 with no part 0 before it
            s.sendall(wire.encode_bin({"type": "kv_push", "id": "a",
                                       "seq": 1, "last": True}, b"zz"))
            msg = wire.read_frame_sync(s, bin_cap=wire.MAX_BIN_PAYLOAD)
            assert msg["type"] == "kv_push" and msg["ok"] is False
            assert "no part 0" in msg["error"]
            # page counts the pool cannot hold (zero / the whole pool)
            for n in (0, eng.kv.num_pages):
                s.sendall(wire.encode_bin(
                    {"type": "kv_push", "id": "b", "seq": 0, "last": True,
                     "tokens": [3] * 8, "meta": {"n_pages": n}}, b""))
                msg = wire.read_frame_sync(s, bin_cap=wire.MAX_BIN_PAYLOAD)
                assert msg["ok"] is False and "pool" in msg["error"]
            # payload overruns the declared 1-page blob
            s.sendall(wire.encode_bin(
                {"type": "kv_push", "id": "c", "seq": 0, "last": True,
                 "tokens": [3] * 8, "meta": {"n_pages": 1}},
                b"\0" * (eng.kv.page_nbytes + 1)))
            msg = wire.read_frame_sync(s, bin_cap=wire.MAX_BIN_PAYLOAD)
            assert msg["ok"] is False and "declared blob" in msg["error"]
            # structurally valid framing, garbage meta: the import itself
            # refuses on the pump thread and answers ok:false
            s.sendall(wire.encode_bin(
                {"type": "kv_push", "id": "d", "seq": 0, "last": True,
                 "tokens": [3] * 8,
                 "meta": {"n_pages": 1, "page_size": 8, "layers": []}},
                b"\0" * eng.kv.page_nbytes))
            msg = wire.read_frame_sync(s, bin_cap=wire.MAX_BIN_PAYLOAD)
            assert msg["type"] == "kv_push" and msg["ok"] is False
            assert srv._kv_parts == {}, "a refusal left buffered parts"
            # a repeated part 0 while the id's blob is still accumulating
            # is refused (the half-built blob dropped), never a silent
            # restart of the accumulation
            for _ in range(2):
                s.sendall(wire.encode_bin(
                    {"type": "kv_push", "id": "e", "seq": 0, "last": False,
                     "tokens": [3] * 8, "meta": {"n_pages": 1}}, b""))
            msg = wire.read_frame_sync(s, bin_cap=wire.MAX_BIN_PAYLOAD)
            assert msg["ok"] is False and "repeated" in msg["error"]
            # server-wide buffer budget: two blobs that together declare
            # more than one pool's worth of bytes — the second is refused
            # up front instead of buffering multiples of the pool
            s.sendall(wire.encode_bin(
                {"type": "kv_push", "id": "f", "seq": 0, "last": False,
                 "tokens": [3] * 8,
                 "meta": {"n_pages": eng.kv.num_pages - 1}}, b""))
            s.sendall(wire.encode_bin(
                {"type": "kv_push", "id": "g", "seq": 0, "last": True,
                 "tokens": [3] * 8,
                 "meta": {"n_pages": eng.kv.num_pages - 1}}, b""))
            msg = wire.read_frame_sync(s, bin_cap=wire.MAX_BIN_PAYLOAD)
            assert msg["ok"] is False and "budget" in msg["error"]
            # finish the live blob: the pump's import refuses the
            # token/page mismatch cleanly and nothing stays buffered
            s.sendall(wire.encode_bin(
                {"type": "kv_push", "id": "f", "seq": 1, "last": True},
                b""))
            msg = wire.read_frame_sync(s, bin_cap=wire.MAX_BIN_PAYLOAD)
            assert msg["type"] == "kv_push" and msg["ok"] is False
            assert srv._kv_parts == {}, "a refusal left buffered parts"
            # the connection survived every refusal — real work flows
            wire.write_frame_sync(s, {"type": "generate", "id": "ok",
                                      "prompt": [3, 4, 5], "max_new": 2,
                                      "stream": False})
            while True:
                msg = wire.read_frame_sync(s, bin_cap=wire.MAX_BIN_PAYLOAD)
                if msg["type"] == "done":
                    break
            assert msg["reason"] == "length" and len(msg["tokens"]) == 5
        finally:
            s.close()
        eng.kv.check_reclaimed()
    finally:
        srv.stop_background(drain=True)


def test_kv_push_ships_pages_and_decode_side_admission_hits(tiny_tr):
    """The transfer plane end to end between two servers: a prefill_only
    request on replica A pushes its committed prompt pages to replica B;
    B mounts them through its prefix tree, so the SAME prompt admitted
    at B is a prefix hit and decodes token-for-token with the oracle.
    A push aimed at a dead port degrades to push_ok:false on the done
    frame (counted), never an error."""
    rng = np.random.default_rng(9)
    eng_a = _engine(tiny_tr)
    srv_a = ServingServer(eng_a, max_queue=8, role="prefill")
    ha, pa = srv_a.start_background()
    eng_b = _engine(tiny_tr)
    srv_b = ServingServer(eng_b, max_queue=8, role="decode")
    hb, pb = srv_b.start_background()
    try:
        prompt = rng.integers(2, 31, 19).tolist()   # 2 committed pages
        with ServingClient(ha, pa) as ca:
            rid = ca.submit(prompt, max_new=8, prefill_only=True,
                            push_to={"host": hb, "port": pb})
            out = ca.collect([rid])
            assert out[rid]["push_ok"] is True
            assert out[rid]["pushed_pages"] == 2
            # prefill_only clamps generation to the 1-token boundary
            assert len(out[rid]["tokens"]) == len(prompt) + 1
            sa = ca.stats()
            assert sa["role"] == "prefill"
            assert sa["kv_pushes"] == 1 and sa["kv_push_failures"] == 0
            assert sa["kv_pages_shipped"] == 2
        with ServingClient(hb, pb) as cb:
            sb = cb.stats()
            assert sb["role"] == "decode"
            assert sb["kv_pages_received"] == 2 and sb["kv_mounts"] == 1
            toks, reason = cb.generate(prompt, max_new=6)
            assert reason == "length"
            assert toks == _oracle(tiny_tr, prompt, 6)
            assert cb.stats()["prefix_hits"] == 1, \
                "shipped pages must make the decode-side admission a hit"
        # same request single-replica: identical tokens (the exactness bar)
        eng_c = _engine(tiny_tr)
        srv_c = ServingServer(eng_c, max_queue=8)
        hc, pc = srv_c.start_background()
        try:
            with ServingClient(hc, pc) as cc:
                ctoks, _ = cc.generate(prompt, max_new=6)
            assert toks == ctoks
        finally:
            srv_c.stop_background(drain=True)
        # a push to a dead port: honest push_ok:false, request still done
        import socket

        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        dead_port = probe.getsockname()[1]
        probe.close()
        with ServingClient(ha, pa) as ca:
            rid = ca.submit(rng.integers(2, 31, 10).tolist(), max_new=4,
                            prefill_only=True,
                            push_to={"host": "127.0.0.1",
                                     "port": dead_port})
            out = ca.collect([rid])
            assert out[rid]["push_ok"] is False
            assert "kv_push" in out[rid]["push_error"]
            assert ca.stats()["kv_push_failures"] == 1
        eng_a.kv.check_reclaimed()
        eng_b.kv.check_reclaimed()
    finally:
        srv_a.stop_background(drain=True)
        srv_b.stop_background(drain=True)


def test_kv_push_part0_chunk_sized_from_encoded_header():
    """Long-prompt regression: part 0's JSON header carries the FULL
    token list, so a fixed 64 KiB headroom busts the 8 MiB bin cap past
    ~9k tokens — exactly the prompts --disagg-min-prompt selects for.
    Every frame must stay under the receiver's bin_cap with the part-0
    chunk sized from the encoded header, and the parts must reassemble
    the exact payload.  Pure framing — no engine in the loop."""
    from paddle_tpu.serving import wire
    from paddle_tpu.serving.server import _kv_push_frames

    toks = list(range(20_000))               # header alone ~ 130 KiB
    meta = {"n_pages": 4, "page_size": 8, "layers": [
        {"name": "l0.attn", "h_kv": 2, "dh": 8, "dtype": "float32"}]}
    payload = bytes(range(256)) * 66_000     # ~16 MiB -> several parts
    frames = _kv_push_frames("rid", toks, meta, payload)
    assert len(frames) >= 3
    got = b""
    for i, fr in enumerate(frames):
        # the receiver's first act: bound the DECLARED body by bin_cap —
        # an over-cap part 0 would be refused and the connection severed
        n, binary = wire.split_length(fr[:4], bin_cap=wire.MAX_BIN_PAYLOAD)
        assert binary and n == len(fr) - 4
        msg = wire._decode_bin_body(fr[4:])
        assert msg["seq"] == i and msg["last"] == (i == len(frames) - 1)
        if i == 0:
            assert msg["tokens"] == toks and msg["meta"] == meta
        got += msg[wire.PAYLOAD_KEY]
    assert got == payload
    # a token list that cannot fit even an empty-chunk part 0 raises
    # FrameError — the sender degrades to push_ok:false, never ships a
    # frame the peer is guaranteed to refuse
    with pytest.raises(wire.FrameError, match="binary-frame cap"):
        _kv_push_frames("rid", list(range(1_500_000)), meta, b"")


def test_kv_push_malformed_reply_degrades_to_push_ok_false(tiny_tr):
    """A decode peer that answers the push with a MALFORMED frame raises
    wire.FrameError (a ValueError, not an OSError) in the sender's
    reply read — the fire-and-forget push task must still resolve the
    prefill leg: done arrives with push_ok:false, the route does not
    leak, and the inflight slot is released.  (An uncaught exception
    here hangs the router's prefill leg forever — the replica stays
    healthy so no retry fires — and pins an inflight slot per hit.)"""
    import socket
    import struct

    lst = socket.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    port = lst.getsockname()[1]
    peers = []

    def peer():
        # accept the push, then answer a valid-length non-JSON body —
        # FrameError on the sender, with the socket held OPEN so no
        # OSError path can mask the bug
        c, _ = lst.accept()
        peers.append(c)
        c.recv(1 << 20)
        c.sendall(struct.pack(">I", 5) + b"notjs")

    threading.Thread(target=peer, daemon=True).start()
    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=4, role="prefill")
    host, sport = srv.start_background()
    try:
        with ServingClient(host, sport) as c:
            rid = c.submit([3, 4, 5, 6, 7, 8, 9, 10], max_new=4,
                           prefill_only=True,
                           push_to={"host": "127.0.0.1", "port": port})
            out = c.collect([rid])
            assert out[rid]["push_ok"] is False
            assert "kv_push failed" in out[rid]["push_error"]
            assert c.stats()["kv_push_failures"] == 1
        assert srv._routes == {} and srv._inflight == 0, \
            "a failed push leaked its route/inflight slot"
        eng.kv.check_reclaimed()
    finally:
        srv.stop_background(drain=True)
        lst.close()
        for p in peers:
            p.close()


# ---------------------------------------------------------------------------
# phase spans (docs/observability.md "The span model"): the pump thread's
# `pt.` vocabulary on the profiler's timeline and in the ring
# ---------------------------------------------------------------------------

def test_pump_and_engine_phase_spans_nest_on_the_profilers_clock(
        tiny_tr, tmp_path):
    """A few mixed and decode steps through the server under a CPU
    profiler session: every span name of the pump's family is on ONE
    thread's line, properly nested and in order; the loop thread's
    `pt.loop.send` is on another."""
    import jax

    from tests.test_obs import _nested_ok, _profiler_events

    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=32)
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            c.collect([c.submit([3, 4, 5], max_new=2)])       # warm
            jax.profiler.start_trace(str(tmp_path))
            try:
                rng = np.random.default_rng(0)
                ids = [c.submit(rng.integers(2, 31, n).tolist(), max_new=6,
                                stream=True) for n in (20, 11)]
                c.collect(ids)
                time.sleep(0.1)        # the pump goes idle: pt.pump.wait
                c.stats()              # ... and a command ends the wait
            finally:
                jax.profiler.stop_trace()
    finally:
        srv.stop_background(drain=True)
    assert eng.n_mixed_steps >= 2 and eng.n_decode_steps > eng.n_mixed_steps
    by_name, lines = _profiler_events(tmp_path)
    pump_names = {"pt.pump.commands", "pt.pump.wait", "pt.engine.step",
                  "pt.step.admit", "pt.step.plan", "pt.step.decode",
                  "pt.step.mixed", "pt.step.dispatch", "pt.step.readback",
                  "pt.step.emit"}
    assert pump_names | {"pt.loop.send"} <= set(by_name), sorted(by_name)
    pump = [evs for evs in lines if any(n == "pt.engine.step"
                                        for n, *_ in evs)]
    assert len(pump) == 1, "the pump's spans are on one thread's line"
    pump = pump[0]
    assert {n for n, *_ in pump} == pump_names
    assert _nested_ok(pump)
    loop = [evs for evs in lines if any(n == "pt.loop.send" for n, *_ in evs)]
    assert len(loop) == 1 and loop[0] is not pump
    # the step's kind and counts ride as attributes
    assert all({"live", "step"} <= set(st) for st in by_name["pt.step.decode"])
    assert all({"rows", "decode_rows"} <= set(st)
               for st in by_name["pt.step.mixed"])

    # inside every busy pt.engine.step: admit, plan, ONE compiled step
    # holding its dispatch (the launch), then the readback and the emit of
    # the step launched one call earlier (the land) — in that order.  The
    # server runs the engine one step ahead, so a burst's first call
    # launches and lands nothing, and its last lands and launches nothing.
    def inside(outer, name):
        return sorted((s, e) for n, s, e, _ in pump
                      if n == name and outer[0] <= s and e <= outer[1])

    busy = lands = 0
    for n, s, e, _ in pump:
        if n != "pt.engine.step":
            continue
        kinds = inside((s, e), "pt.step.decode") + \
            inside((s, e), "pt.step.mixed")
        read, emit = (inside((s, e), "pt.step." + p)
                      for p in ("readback", "emit"))
        assert len(read) == len(emit) <= 1
        lands += len(read)
        if not kinds:
            continue                   # an idle poll, or a land alone
        busy += 1
        assert len(kinds) == 1
        (admit,), (plan,) = (inside((s, e), "pt.step." + p)
                             for p in ("admit", "plan"))
        (disp,) = inside(kinds[0], "pt.step.dispatch")
        assert not inside(kinds[0], "pt.step.readback")
        order = [admit, plan, disp, kinds[0]] + read + emit
        assert all(a[1] <= b[1] + 1000 for a, b in zip(order, order[1:]))
        assert all(a[1] <= b[0] + 1000
                   for a, b in zip(order[3:], order[4:]))
    assert busy >= eng.n_decode_steps - 2      # the warm request's are out
    assert lands >= busy                       # every launch was landed
    assert eng.n_lookahead_steps >= busy - 4   # ... one call later


@pytest.mark.parametrize("kind", ["decode", "mixed", "spec"])
def test_engine_ring_spans_name_the_step_kind(tiny_tr, kind):
    """The ring (the operator's sink) gets the same phases: one compiled-
    step span a step, named by the kind the scheduler chose, between
    plan and emit.  No perf_counter pair around a compiled step is left."""
    from paddle_tpu.obs import Tracer

    t = Tracer()
    t.enabled = True
    from paddle_tpu.ops.pallas_paged import tile_rows
    kw = {"spec": {"spec_k": 2}}.get(kind, {})
    eng = ServingEngine(tiny_tr.executor, tiny_tr.params, num_slots=2,
                        page_size=8, max_context=64, tracer=t, **kw)
    rng = np.random.default_rng(1)
    prompt = np.tile(rng.integers(2, 31, 4), 5)     # repetitive: drafts hit
    eng.add_request(Request("a", prompt, max_new=9))
    eng.run()
    lane = [s for s in t.snapshot() if s["track"] == "engine"]
    names = [s["name"] for s in lane]
    assert "pt.step." + kind in names, names
    steps = [s for s in lane if s["name"] in (
        "pt.step.decode", "pt.step.mixed", "pt.step.spec")]
    assert len(steps) == eng.n_decode_steps
    assert [s["attrs"]["step"] for s in steps] == \
        list(range(1, len(steps) + 1))
    if kind == "spec":
        assert "pt.step.draft" in names
        assert names.count("pt.step.draft") >= eng.n_draft_steps > 0
    # per step, in the order the spans close (a span is recorded when it
    # ends): admit, plan, dispatch, then a decode or mixed step's own span
    # (its launch) BEFORE the readback and the emit of its land; a verify
    # step's span still holds its readback
    per_step = [n for n in names if n != "pt.step.draft"]
    i = per_step.index("pt.step.plan") - 1
    first = steps[0]["name"]
    land = ["pt.step.readback", first] if kind == "spec" else \
        [first, "pt.step.readback"]
    assert per_step[i:i + 6] == [
        "pt.step.admit", "pt.step.plan", "pt.step.dispatch", *land,
        "pt.step.emit"]
    import inspect
    import re
    src = inspect.getsource(ServingEngine)
    assert not re.search(r"\bt_step\b|tracer\.add\(\"\w+_step\"", src)


@pytest.mark.parametrize("kind", ["decode", "mixed", "spec"])
def test_kv_read_counters_follow_each_step_kind(tiny_tr, kind):
    """`kv_tokens_attended` / `kv_tokens_fetched` grow by what the step's
    rows read: pos + 1 a decode row (1 for an empty slot), every packed
    row of a mixed or verify step (a padding row reads 1), and a whole
    block a row — here the 64 tokens the table maps — or once a run of one
    slot's rows in a tile (`kv_shared_rows` of `kv_rows`)."""
    from paddle_tpu.ops.pallas_paged import tile_rows
    kw = {"spec": {"spec_k": 2}}.get(kind, {})
    eng = ServingEngine(tiny_tr.executor, tiny_tr.params, num_slots=2,
                        page_size=8, max_context=64, **kw)
    assert eng._kv_block == 64
    rng = np.random.default_rng(1)
    eng.add_request(Request("a", np.tile(rng.integers(2, 31, 4), 5),
                            max_new=9))
    S, T, seen = 2, eng.max_step_tokens, set()
    counted, count_kv = [], eng._count_kv
    eng._count_kv = lambda lengths, row_slot=None, *a, **k: (
        counted.append((lengths, row_slot)), count_kv(
            lengths, row_slot, *a, **k))[1]
    while eng.queue or any(sl is not None for sl in eng.slots):
        pos = [None if sl is None else sl.pos for sl in eng.slots]
        before = (eng.kv_tokens_attended, eng.kv_tokens_fetched,
                  eng.n_decode_steps, eng.n_mixed_steps, eng.n_spec_steps,
                  eng.n_kv_rows, eng.n_kv_shared_rows)
        eng.step()
        att = eng.kv_tokens_attended - before[0]
        fetched = eng.kv_tokens_fetched - before[1]
        rows = eng.n_kv_rows - before[5]
        shared = eng.n_kv_shared_rows - before[6]
        if eng.n_decode_steps == before[2]:
            assert att == fetched == 0          # no compiled step ran
        elif eng.n_mixed_steps > before[3] or eng.n_spec_steps > before[4]:
            seen.add("spec" if eng.n_spec_steps > before[4] else "mixed")
            # a block a row alone, and ONE a run of one slot's rows in a
            # tile (the prompt's, the padding's behind it): a plain count
            # over the rows the step really packed
            bq = tile_rows(T, *eng._kv_tile)
            lengths, slots = counted[-1]
            assert rows == T == lengths.size and T <= att <= T * 64
            walks = in_runs = 0
            for t in range(0, T, bq):
                tile = list(slots[t:t + bq])
                tile += tile[-1:] * (bq - len(tile))    # the call's padding
                starts = [i for i in range(bq)
                          if i == 0 or tile[i] != tile[i - 1]]
                walks += len(starts)
                in_runs += sum(min(j, T - t) - i for i, j in zip(
                    starts, starts[1:] + [bq]) if j - i > 1)
            assert (fetched, shared) == (walks * 64, in_runs)
            assert 0 < shared <= T and walks < T
        else:
            seen.add("decode")
            want = sum(1 if p is None else p + 1 for p in pos)
            assert (att, fetched) == (want, S * 64)
            assert (rows, shared) == (S, 0)
    assert kind in seen, seen
    snap = eng.checkpoint_state()["counters"]
    assert snap["kv_tokens_attended"] == eng.kv_tokens_attended
    assert snap["kv_tokens_fetched"] == eng.kv_tokens_fetched
    assert snap["n_kv_shared_rows"] == eng.n_kv_shared_rows


def test_prefix_eviction_has_its_own_span(tiny_tr):
    """`pt.kv.evict` times PrefixTree.evict_for, the call a full pool
    makes before nearly every launch (a walk of the whole tree until the
    tree kept its frontier: PERF.md section 6)."""
    from paddle_tpu.obs import Tracer

    t = Tracer()
    t.enabled = True
    rng = np.random.default_rng(3)
    eng = ServingEngine(tiny_tr.executor, tiny_tr.params, num_slots=1,
                        page_size=4, max_context=16, num_pages=5, tracer=t)
    for i in range(2):
        eng.run([Request(f"f{i}", rng.integers(2, 23, 7).astype(np.int32),
                         max_new=5)])
    eng.run([Request("big", rng.integers(2, 23, 9).astype(np.int32),
                     max_new=7)])
    assert eng.prefix.n_evictions > 0
    evicts = [s for s in t.snapshot() if s["name"] == "pt.kv.evict"]
    assert evicts and all(s["attrs"]["pages"] >= 1 for s in evicts)
    assert {s["track"] for s in evicts} == {"engine"}
    # one span a call of the hook, and every eviction a victim pop
    assert len(evicts) == eng.prefix.n_evict_calls
    assert eng.prefix.frontier_pops["victim"] == eng.prefix.n_evictions


# -- one hand-off a step, one write a connection (ISSUE 30) ------------------
class _StubWriter:
    """An asyncio StreamWriter as far as wire.FrameConn uses one."""

    def __init__(self, buffered=0):
        self.writes, self.closed, self.buffered = [], False, buffered
        self.transport = self

    def is_closing(self):
        return self.closed

    def get_write_buffer_size(self):
        return self.buffered

    def write(self, data):
        self.writes.append(bytes(data))

    def close(self):
        self.closed = True


class _StubLoop:
    """The loop as far as the pump uses it: counts the hand-offs and runs
    each at once, on the calling thread."""

    def __init__(self):
        self.handoffs = []

    def call_soon_threadsafe(self, fn, *args):
        self.handoffs.append((fn, args))
        fn(*args)

    def call_exception_handler(self, ctx):
        raise ctx["exception"]


def _split_frames(data: bytes) -> list:
    """Wire bytes -> [(message, its raw bytes)]."""
    import json
    import struct

    out, i = [], 0
    while i < len(data):
        (n,) = struct.unpack(">I", data[i:i + 4])
        out.append((json.loads(data[i + 4:i + 4 + n]), data[i:i + 4 + n]))
        i += 4 + n
    assert i == len(data)
    return out


def _read_until_terminal(sock, ids) -> list:
    """(message, raw bytes) of every frame off a raw socket until each of
    `ids` has had its done / error frame."""
    from paddle_tpu.serving import wire

    left, out = set(ids), []
    while left:
        head = wire._recv_exact(sock, 4)
        assert head is not None and len(head) == 4, "server closed early"
        body = wire._recv_exact(sock, wire.check_length(head))
        msg = wire._decode_body(body)
        out.append((msg, head + body))
        if msg["type"] in ("done", "error"):
            left.discard(msg["id"])
    return out


def _record_per_token_path(srv) -> dict:
    """{client id: [wire bytes]} of the frame the per-token path sent for
    each fresh streamed token — encoded AT BANKING TIME, from the state
    `_on_token` had just left, the way it handed each to the loop."""
    from paddle_tpu.serving import wire

    rec, inner = {}, srv._on_token

    def on_token(rid, tok, idx):
        st = srv._routes.get(rid)
        fresh = st is not None and idx >= st.next_idx
        inner(rid, tok, idx)
        if fresh and st.stream:
            rec.setdefault(st.cid, []).append(wire.encode(
                {"type": "token", "id": st.cid, "token": int(tok),
                 "index": int(idx)}))

    srv.engine.on_token = on_token
    return rec


def _generate(sock, cid, prompt, max_new, **kw):
    from paddle_tpu.serving import wire

    wire.write_frame_sync(sock, {"type": "generate", "id": cid,
                                 "prompt": [int(t) for t in prompt],
                                 "max_new": max_new, "stream": True, **kw})


_KIND_KW = {"decode": {}, "mixed": {"num_slots": 4}, "spec": {"spec_k": 2}}


@pytest.mark.parametrize("kind", ["decode", "mixed", "spec"])
def test_batched_delivery_is_byte_identical_per_request(tiny_tr, kind):
    """N concurrent streams over ONE connection: the bytes the socket
    carries are, per request, exactly `wire.encode` of the frames the
    per-token path produced — indexes 0..n-1 in order, `done` last —
    whatever kind of step banked them."""
    import socket

    eng = _engine(tiny_tr, **_KIND_KW[kind])
    srv = ServingServer(eng, max_queue=32)
    want = _record_per_token_path(srv)
    host, port = srv.start_background()
    rng = np.random.default_rng(5)
    if kind == "mixed":          # later admissions chunk beside decode rows
        lens, news = (20, 11, 5, 17, 9, 13), (9, 6, 8, 7, 9, 6)
    else:
        lens, news = (5, 9, 20), (9, 7, 8)
    prompts = [np.tile(rng.integers(2, 31, 4), 5)[:n] for n in lens]
    try:
        sock = socket.create_connection((host, port), timeout=60)
        try:
            for i, (p, n) in enumerate(zip(prompts, news)):
                _generate(sock, f"r{i}", p, n)
            got = _read_until_terminal(sock, [f"r{i}" for i in
                                              range(len(lens))])
        finally:
            sock.close()
    finally:
        srv.stop_background(drain=True)
    assert {"mixed": eng.n_mixed_steps >= 4,
            "decode": eng.n_decode_steps > eng.n_mixed_steps,
            "spec": eng.n_spec_steps > 0}[kind]
    for i, (p, n) in enumerate(zip(prompts, news)):
        mine = [(m, raw) for m, raw in got if m["id"] == f"r{i}"]
        toks, done = mine[:-1], mine[-1][0]
        assert done["type"] == "done" and done["reason"] == "length"
        assert b"".join(raw for _, raw in toks) == b"".join(want[f"r{i}"])
        assert [m["index"] for m, _ in toks] == list(range(n))
        assert [m["token"] for m, _ in toks] == done["tokens"][len(p):]
        assert done["tokens"] == _oracle(tiny_tr, p, n)
    assert srv.n_token_frames == sum(news)
    assert srv._outbox == [] and srv._tok_lat == []


@pytest.mark.parametrize("end", ["length", "cancelled", "deadline",
                                 "failed"])
def test_terminal_frame_never_overtakes_the_last_token(tiny_tr, end):
    """A request that finishes, is cancelled, expires or fails IN the
    step that banked its last token: every token banked for it is on the
    wire, in order, before its done / error frame."""
    import socket

    eng = _engine(tiny_tr)
    inner = eng.step

    def step():
        busy = inner()
        sl = next((s for s in eng.slots if s is not None
                   and s.req.req_id.endswith(":s:r")), None)
        if sl is not None and sl.gen >= 3:
            if end == "cancelled":    # a finish in the banking step itself
                eng.cancel(sl.req.req_id)
            elif end == "failed":
                raise RuntimeError("boom")
        return busy

    eng.step = step
    srv = ServingServer(eng, max_queue=8)
    want = _record_per_token_path(srv)
    host, port = srv.start_background()
    try:
        sock = socket.create_connection((host, port), timeout=60)
        try:
            kw = {"timeout_s": 3.0} if end == "deadline" else {}
            _generate(sock, "other", [4, 9, 2], 12 if end != "failed" else 30)
            _generate(sock, "r", [3, 7, 5, 11], 6 if end == "length" else 30,
                      **kw)
            got = _read_until_terminal(sock, ["r", "other"])
        finally:
            sock.close()
    finally:
        if end == "failed":
            with pytest.raises(RuntimeError, match="engine pump died"):
                srv.stop_background(drain=True)
        else:
            srv.stop_background(drain=True)
    for cid in ("r", "other"):
        mine = [(m, raw) for m, raw in got if m["id"] == cid]
        toks, last = mine[:-1], mine[-1][0]
        assert all(m["type"] == "token" for m, _ in toks)
        # nothing banked was dropped, nothing reordered
        assert [raw for _, raw in toks] == want[cid]
        assert [m["index"] for m, _ in toks] == list(range(len(toks)))
        if cid == "r" or end == "failed":
            assert last["type"] == ("error" if end == "failed" else "done")
        if last["type"] == "done":
            assert [m["token"] for m, _ in toks] == \
                last["tokens"][-len(toks):]
            if cid == "r":
                assert last["reason"] == end
    if end != "length":
        assert 2 <= len(want["r"]) < 30
    assert srv._outbox == [] and srv._inflight == 0


@pytest.mark.parametrize("topology", ["one_connection", "connection_each"])
def test_one_handoff_a_step_and_one_write_a_connection(tiny_tr, topology):
    """With a stub loop that counts `call_soon_threadsafe`: a step that
    banks S tokens makes ONE hand-off; S streams on one connection make
    one transport write, S connections make S."""
    from paddle_tpu.serving.server import _Conn, _ReqState

    S = 4
    eng = _engine(tiny_tr, num_slots=S)
    srv = ServingServer(eng, max_queue=8)
    loop = srv._loop = _StubLoop()
    writers = [_StubWriter() for _ in range(S)]
    conns = [_Conn(w) for w in writers]
    if topology == "one_connection":
        conns = conns[:1] * S
    per_step = []                      # (tokens banked, hand-offs, writes)
    inner = eng.step

    def step():
        before = (eng.tokens_generated, len(loop.handoffs),
                  sum(len(w.writes) for w in writers))
        busy = inner()
        srv._flush_outbox()            # what the pump does after the step
        per_step.append((eng.tokens_generated - before[0],
                         len(loop.handoffs) - before[1],
                         sum(len(w.writes) for w in writers) - before[2]))
        return busy

    eng.step = step
    rng = np.random.default_rng(2)
    for i in range(S):
        req = Request(f"q{i}", rng.integers(2, 31, 5), max_new=8)
        srv._routes[req.req_id] = _ReqState(conns[i], f"q{i}", True)
        srv._inflight += 1
        srv._cmds.put(("add", req))
    srv.start_pump()
    deadline = time.time() + 60
    while srv._routes and time.time() < deadline:
        time.sleep(0.01)
    srv._cmds.put(("stop",))
    srv._wake.set()
    srv._pump_thread.join(timeout=30)
    assert not srv._routes and srv._pump_error is None
    assert all(fn == srv._deliver_on_loop for fn, _ in loop.handoffs)
    full = [p for p in per_step if p[0] == S]
    assert len(full) >= 5, per_step    # the pure-decode steps, all S live
    n_conns = 1 if topology == "one_connection" else S
    for banked, handoffs, writes in per_step:
        assert handoffs == (1 if banked else 0), per_step
        # the retiring step adds each finished stream's `done` write
        assert writes >= (min(banked, n_conns) if banked else 0)
    assert (S, 1, n_conns) in full, per_step
    # every write of token frames carried a connection's whole share
    sent = [m for w in writers for data in w.writes
            for m, _ in _split_frames(data)]
    assert sum(m["type"] == "token" for m in sent) == S * 8 \
        == srv.n_token_frames
    assert sum(m["type"] == "done" for m in sent) == S
    # mixed (prefill) steps bank a row or two each, decode steps S
    assert S * 8 / srv.n_frame_writes >= (2.0 if n_conns == 1 else 1.0)
    assert srv.n_frame_writes == sum(
        1 for w in writers for data in w.writes
        if _split_frames(data)[0][0]["type"] == "token")


@pytest.mark.parametrize("end", ["drain", "stop", "death"])
def test_outbox_is_empty_whenever_the_pump_rests(tiny_tr, end):
    """Nothing is left in the outbox when the pump enters `pt.pump.wait`,
    after stop_background, or after the pump died — and every client has
    its terminal frame, no token_latency sample lost."""
    import socket

    eng = _engine(tiny_tr)
    srv = ServingServer(eng, max_queue=8)
    at_wait = []

    class Wake(threading.Event):
        def wait(self, timeout=None):
            at_wait.append((len(srv._outbox), len(srv._tok_lat)))
            return super().wait(timeout)

    srv._wake = Wake()
    if end == "death":
        inner = eng.step

        def step():
            busy = inner()
            if eng.tokens_generated >= 6:
                raise RuntimeError("boom")
            return busy

        eng.step = step
    host, port = srv.start_background()
    sock = socket.create_connection((host, port), timeout=60)
    try:
        ids = ["a", "b", "c"]
        _generate(sock, "a", [3, 4, 5], 6)
        _generate(sock, "b", [7, 8], 30)
        _generate(sock, "c", [9, 2, 6, 4], 30)
        if end == "stop":              # hard stop: b and c are cancelled
            _read_until_terminal(sock, ["a"])
            stopper = threading.Thread(
                target=srv.stop_background, kwargs={"drain": False})
            stopper.start()
        got = _read_until_terminal(sock, ids if end != "stop" else ids[1:])
        last = {m["id"]: m for m, _ in got if m["type"] != "token"}
        if end == "death":             # every client has its terminal frame
            assert all("pump died" in last[i]["error"] for i in ids)
        elif end == "stop":
            assert {last[i]["reason"] for i in ids[1:]} == {"cancelled"}
        else:
            assert all(last[i]["reason"] == "length" for i in ids)
            time.sleep(0.1)            # the pump goes idle
    finally:
        sock.close()
    if end == "death":
        with pytest.raises(RuntimeError, match="engine pump died"):
            srv.stop_background(drain=True)
    elif end == "drain":
        srv.stop_background(drain=True)
    else:
        stopper.join(timeout=60)
        assert not stopper.is_alive()
    assert srv._outbox == [] and srv._tok_lat == []
    assert srv._inflight == 0 and not srv._routes
    if end != "death":
        assert at_wait, "the pump never waited"
    assert all(w == (0, 0) for w in at_wait), at_wait
    # every fresh post-first token charged token_latency exactly once
    assert srv.stats.get("token_latency").count == \
        srv.n_token_frames - srv.stats.get("first_token_latency").count


@pytest.mark.parametrize("path", ["send_many", "deliver"])
def test_slow_reader_is_still_severed_at_max_write_buffer(tiny_tr, path):
    """A reader whose transport buffer is past MAX_WRITE_BUFFER is closed
    instead of buffered into — by the batched write as by send()."""
    from paddle_tpu.serving import wire
    from paddle_tpu.serving.server import _Conn

    frames = [{"type": "token", "id": "r", "token": 5, "index": i}
              for i in range(3)]
    slow = _StubWriter(buffered=wire.FrameConn.MAX_WRITE_BUFFER + 1)
    ok = _StubWriter(buffered=wire.FrameConn.MAX_WRITE_BUFFER)
    if path == "send_many":
        conns = [wire.FrameConn(slow), wire.FrameConn(ok)]
        for conn in conns:
            conn.send_many(frames)
    else:
        srv = ServingServer(_engine(tiny_tr), max_queue=4)
        srv._loop = _StubLoop()
        conns = [_Conn(slow), _Conn(ok)]
        srv._deliver_on_loop([(conn, None, f) for f in frames
                              for conn in conns])
        assert (srv.n_token_frames, srv.n_frame_writes) == (6, 2)
    assert conns[0].dead and slow.closed and slow.writes == []
    assert not conns[1].dead and not ok.closed
    assert ok.writes == [b"".join(wire.encode(f) for f in frames)]
    conns[0].send_many(frames)         # a dead connection stays silent
    assert slow.writes == []


def test_token_frame_counters_reconcile_with_tokens_generated(tiny_tr):
    """`serving_token_frames_total` counts exactly the streamed requests'
    tokens, `serving_frame_writes_total` the writes that carried them, in
    the stats RPC, the metrics frame and the process's counters alike."""
    from paddle_tpu.obs.metrics import process_counters

    pc0 = process_counters().snapshot()
    eng = _engine(tiny_tr, num_slots=4)
    srv = ServingServer(eng, max_queue=8)
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            streamed = [c.submit([3, 4, 5 + i], max_new=7 + i, stream=True)
                        for i in range(3)]
            quiet = c.submit([9, 8, 7], max_new=5, stream=False)
            out = c.collect(streamed + [quiet])
            s = c.stats()
            text = c.metrics()
    finally:
        srv.stop_background(drain=True)
    n_streamed = sum(len(out[r]["stream"]) for r in streamed)
    assert n_streamed == 7 + 8 + 9 and out[quiet]["stream"] == []
    assert eng.tokens_generated == n_streamed + 5
    assert s["token_frames"] == n_streamed == srv.n_token_frames
    assert 9 <= s["frame_writes"] == srv.n_frame_writes < n_streamed
    vals = dict(line.rsplit(" ", 1) for line in text.splitlines()
                if line and not line.startswith("#"))
    assert float(vals["serving_token_frames_total"]) == n_streamed
    assert float(vals["serving_frame_writes_total"]) == srv.n_frame_writes
    pc = process_counters().snapshot()
    assert pc["serving_token_frames_total"] \
        - pc0.get("serving_token_frames_total", 0) == n_streamed
    assert pc["serving_frame_writes_total"] \
        - pc0.get("serving_frame_writes_total", 0) == srv.n_frame_writes


def test_stat_add_many_is_add_in_order():
    from paddle_tpu.utils import stat

    a, b = stat.Stat("a"), stat.Stat("b")
    dts = [0.001 * (i % 7) for i in range(stat.SAMPLE_WINDOW + 50)]
    for dt in dts:
        a.add(dt)
    b.add_many(dts[:10])
    b.add_many(dts[10:])
    b.add_many([])
    assert (a.count, a.total_s, a.max_s, a.samples) == \
        (b.count, b.total_s, b.max_s, b.samples)
