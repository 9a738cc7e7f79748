"""Parameter-server tier units + loopback integration (paddle_tpu/pserver/).

Covers the deterministic block map, the wire codec's bit-exactness, the
elastic membership state machine (join/drain/leave/expiry — ISSUE 14
satellite), the live server's elastic behavior over real sockets
(mid-window join, drain, abrupt death discarding the in-flight
contribution), the streaming snapshotter's no-stall contract, the
sharded-checkpoint reassembly, and the misconnected-peer refusals both
directions.  The full training exactness oracle lives in
tests/test_train_dist.py."""

import threading
import time

import numpy as np
import pytest

from paddle_tpu.config.schema import OptimizationConfig, ParameterConfig
from paddle_tpu.pserver import membership as mem
from paddle_tpu.pserver.blocks import BlockMap, decode_array, encode_array
from paddle_tpu.pserver.client import ParameterClient
from paddle_tpu.pserver.membership import Membership
from paddle_tpu.pserver.server import (ParameterServer, UpdateEngine,
                                       assemble_sharded_checkpoint)

# ---------------------------------------------------------------------------
# block map + codec units (no sockets, no jax)
# ---------------------------------------------------------------------------


def test_codec_bit_exact_roundtrip():
    rng = np.random.default_rng(0)
    arrs = [rng.standard_normal((5, 7)).astype(np.float32),
            np.array([np.nan, np.inf, -np.inf, 1e-45, -0.0], np.float32),
            rng.integers(0, 100, (3,)).astype(np.int32),
            np.float64(3.141592653589793) * np.ones((2, 2))]
    for a in arrs:
        b = decode_array(encode_array(a))
        assert b.dtype == a.dtype and b.shape == a.shape
        np.testing.assert_array_equal(
            a.view(np.uint8) if a.dtype.kind == "f" else a,
            b.view(np.uint8) if b.dtype.kind == "f" else b)


def test_block_map_deterministic_and_partitions():
    specs = {"b": ((7,), "float32"), "a": ((10, 3), "float32"),
             "c": ((4,), "float32")}
    bm1 = BlockMap(specs, n_shards=3, block_size=8)
    bm2 = BlockMap.from_config(bm1.config())
    assert bm1 == bm2
    # every element covered exactly once, shards disjoint
    seen = set()
    for s in range(3):
        for r in bm1.shard_blocks(s):
            key = (r.name, r.start, r.stop)
            assert key not in seen
            seen.add(key)
    for name, (shape, _dt) in specs.items():
        size = int(np.prod(shape))
        covered = sorted((r.start, r.stop) for r in bm1.blocks[name])
        assert covered[0][0] == 0 and covered[-1][1] == size
        for (s0, e0), (s1, e1) in zip(covered, covered[1:]):
            assert e0 == s1
    # a 10x3 param at block 8 must split into 4 blocks
    assert len(bm1.blocks["a"]) == 4


def test_block_split_assemble_roundtrip():
    rng = np.random.default_rng(1)
    params = {"w": rng.standard_normal((9, 5)).astype(np.float32),
              "b": rng.standard_normal((3,)).astype(np.float32)}
    bm = BlockMap.from_arrays(params, n_shards=2, block_size=7)
    blocks = {}
    for s in range(2):
        blocks.update(bm.split_all(params, shard=s))
    out = bm.assemble_all(blocks)
    for n in params:
        np.testing.assert_array_equal(out[n], params[n])
    with pytest.raises(KeyError, match="missing block"):
        one_shard = bm.split_all(params, shard=0)
        bm.assemble("w", one_shard)


def test_bin_blocks_codec_bit_exact_and_bounds_checked():
    from paddle_tpu.pserver.blocks import (decode_blocks_bin,
                                           encode_blocks_bin)
    rng = np.random.default_rng(7)
    blocks = {"w#1": rng.standard_normal((5, 3)).astype(np.float32),
              "w#0": np.array([np.nan, np.inf, -0.0, 1e-45], np.float32),
              "b#0": rng.integers(0, 9, (4,)).astype(np.int32)}
    meta, payload = encode_blocks_bin(blocks)
    # layout is sorted-bid and gap-free
    assert list(meta) == sorted(blocks)
    assert sum(d["n"] for d in meta.values()) == len(payload)
    out = decode_blocks_bin(meta, payload)
    assert set(out) == set(blocks)
    for bid, a in blocks.items():
        b = out[bid]
        assert b.dtype == a.dtype and b.shape == a.shape
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))
        assert b.flags.writeable          # same contract as decode_array
    # exactly what decode_array yields from the JSON codec — the two wire
    # formats are interchangeable representations of the same arrays
    for bid, a in blocks.items():
        np.testing.assert_array_equal(
            out[bid].view(np.uint8),
            decode_array(encode_array(a)).view(np.uint8))
    # a corrupt span must fail loudly, not read out of bounds
    bad = {k: dict(v) for k, v in meta.items()}
    bad["w#1"]["off"] = len(payload)
    with pytest.raises(ValueError, match="overruns"):
        decode_blocks_bin(bad, payload)


def test_bin_wire_frame_roundtrip_and_json_interleave():
    import socket as socket_mod

    from paddle_tpu.serving import wire

    a, b = socket_mod.socketpair()
    try:
        payload = bytes(range(256)) * 17
        wire.write_frame_bin_sync(a, {"type": "send_grad", "window": 3},
                                  payload)
        wire.write_frame_sync(a, {"type": "barrier", "window": 3})
        msg = wire.read_frame_sync(b)
        assert msg["type"] == "send_grad" and msg["window"] == 3
        assert msg[wire.PAYLOAD_KEY] == payload
        # a plain JSON frame on the same stream is untouched by the
        # binary variant (no payload key, same framing)
        nxt = wire.read_frame_sync(b)
        assert nxt == {"type": "barrier", "window": 3}
        assert wire.PAYLOAD_KEY not in nxt
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# membership state machine units (ISSUE 14 satellite: deterministic
# join/drain/leave — no sockets, injected clocks)
# ---------------------------------------------------------------------------


def test_membership_join_drain_leave():
    ms = Membership()
    a = ms.join(now=0.0)
    b = ms.join(now=0.0)
    assert (a.tid, a.rank) == ("t0", 0) and (b.tid, b.rank) == ("t1", 1)
    # both active: both required at a barrier nobody reached yet
    assert ms.required(set()) == {"t0", "t1"}
    assert ms.required({"t0"}) == {"t1"}
    # drain: b stops stalling the fleet but may still contribute
    assert ms.drain("t1")
    assert ms.required(set()) == {"t0"}
    assert ms.in_rank_order(["t1", "t0"]) == ["t0", "t1"]
    assert ms.counts() == {mem.ACTIVE: 1, mem.DRAINING: 1}
    assert ms.undrain("t1") and ms.required(set()) == {"t0", "t1"}
    ms.drain("t1")
    # clean leave removes entirely
    left = ms.leave("t1")
    assert left.state == mem.LEFT and len(ms) == 1
    # rank 1 is free again: a restarted trainer slides back in
    c = ms.join(now=1.0)
    assert c.rank == 1
    # duplicate explicit rank refused (double-counted data shard)
    with pytest.raises(ValueError, match="already held"):
        ms.join(rank=0)


def test_membership_expiry_and_rank_reuse():
    ms = Membership()
    a = ms.join(now=0.0)
    b = ms.join(now=0.0)
    ms.beat("t0", now=5.0)
    dead = ms.expire(timeout_s=3.0, now=6.0)
    assert [m.tid for m in dead] == ["t1"] and b.state == mem.DEAD
    assert ms.required(set()) == {"t0"}
    assert a.state == mem.ACTIVE
    # beat on a dropped member is a no-op, not a resurrection
    assert not ms.beat("t1", now=7.0)


# ---------------------------------------------------------------------------
# live-server helpers
# ---------------------------------------------------------------------------

OPT = OptimizationConfig(batch_size=4, learning_method="momentum",
                         momentum=0.9, learning_rate=0.1)
PCFGS = {"w": ParameterConfig(name="w", size=12, dims=[3, 4]),
         "b": ParameterConfig(name="b", size=4, dims=[4])}


def _init_params(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal((3, 4)).astype(np.float32),
            "b": rng.standard_normal((4,)).astype(np.float32)}


def _grads(seed):
    rng = np.random.default_rng(100 + seed)
    return {"w": rng.standard_normal((3, 4)).astype(np.float32),
            "b": rng.standard_normal((4,)).astype(np.float32)}


def _client(addrs, params=None, join=True, rank=None, **kw):
    c = ParameterClient(addrs, timeout=30.0, **kw)
    if join:
        c.join(rank=rank)
    c.init_or_fetch(params if params is not None else _init_params(),
                    OPT.to_dict(), {n: p.to_dict()
                                    for n, p in PCFGS.items()})
    return c


def _start(n_shards=1, block_size=5, **kw):
    srvs = [ParameterServer(port=0, shard_index=i, n_shards=n_shards,
                            block_size=block_size, **kw)
            for i in range(n_shards)]
    addrs = [s.start_background() for s in srvs]
    return srvs, addrs


# ---------------------------------------------------------------------------
# elastic behavior over real sockets (tier-1, deterministic)
# ---------------------------------------------------------------------------


def test_elastic_join_drain_leave_and_abrupt_death():
    srvs, addrs = _start(beat_timeout_s=60.0)
    try:
        a = _client(addrs, rank=0)
        # single member: a window commits immediately
        out = a.push_grads(_grads(0), samples=4)
        assert a.version == 1 and set(out) == {"w", "b"}

        # B joins: the next window requires BOTH
        b = _client(addrs, rank=1)
        got = {}

        def push_a():
            got["a"] = a.push_grads(_grads(1), samples=4)

        th = threading.Thread(target=push_a)
        th.start()
        time.sleep(0.2)                  # A is parked in the barrier
        assert not got
        got["b"] = b.push_grads(_grads(2), samples=4)
        th.join(timeout=30)
        assert "a" in got
        for n in ("w", "b"):
            np.testing.assert_array_equal(got["a"][n], got["b"][n])
        log = a.commit_log()
        assert [m[1] for m in log[-1]["members"]] == [0, 1]  # rank order

        # B drains: A alone commits the next window (B never stalls it)
        b.drain()
        a.push_grads(_grads(3), samples=4)
        assert a.version == 3
        b.leave()
        b.close()

        # C joins then dies ABRUPTLY with a contribution in flight: the
        # buffered grads are discarded and A's barrier re-sizes
        c = _client(addrs, rank=1)
        # send C's gradient WITHOUT barriering, then kill the sockets
        blocks = c.block_map.split_all(_grads(4), shard=0)
        from paddle_tpu.serving import wire as w_
        w_.write_frame_sync(c.socks[0], {
            "type": "send_grad", "tid": c.tid, "window": c.window,
            "samples": 4,
            "blocks": {bid: encode_array(arr)
                       for bid, arr in blocks.items()}})
        assert w_.read_frame_sync(c.socks[0])["type"] == "grad_ack"
        c.close()                        # abrupt: no drain, no leave
        out = a.push_grads(_grads(5), samples=4)   # must not deadlock
        assert a.version == 4
        log = a.commit_log()
        assert [m[1] for m in log[-1]["members"]] == [0]
        st = a.stats()
        assert st["trainers_active"] == 1
        mtext = a.metrics()
        assert "pserver_grads_discarded_total 1" in mtext
        a.leave()
        a.close()
    finally:
        for s in srvs:
            s.stop_background(drain=False)


def test_bin_blocks_negotiated_and_bit_identical_to_json():
    """ISSUE 16 satellite: the binary block framing changes BYTES ON THE
    WIRE only — a fleet driven through binary frames commits bit-identical
    parameters to one driven by a legacy JSON-only client, and a client
    that advertises nothing (old peer) keeps working against a new
    server because sending binary is hello-negotiated."""
    def run_windows(force_json):
        srvs, addrs = _start(n_shards=2)
        try:
            c = ParameterClient(addrs, timeout=30.0)
            # every new shard advertises the capability
            assert c._bin is True
            if force_json:
                c._bin = False       # what a pre-capability client sends
            c.join(rank=0)
            c.init_or_fetch(_init_params(), OPT.to_dict(),
                            {n: p.to_dict() for n, p in PCFGS.items()})
            out = None
            for w in range(3):
                out = c.push_grads(_grads(w), samples=4)
            c.leave()
            c.close()
            return out
        finally:
            for s in srvs:
                s.stop_background(drain=False)

    p_bin = run_windows(force_json=False)
    p_json = run_windows(force_json=True)
    assert set(p_bin) == set(p_json) == {"w", "b"}
    for n in p_bin:
        np.testing.assert_array_equal(p_bin[n].view(np.uint8),
                                      p_json[n].view(np.uint8))


def test_wrong_window_after_eviction_is_actionable():
    srvs, addrs = _start(beat_timeout_s=0.4)
    try:
        a = _client(addrs, rank=0, beat_interval_s=10.0)  # beats too slow
        a._beat_stop.set()               # stop beating entirely
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if a.stats()["trainers_active"] == 0:
                break
            time.sleep(0.1)
        assert a.stats()["trainers_active"] == 0, "expiry never fired"
        from paddle_tpu.pserver.client import StaleTrainerError
        with pytest.raises(StaleTrainerError, match="rejoin"):
            a.push_grads(_grads(0), samples=4)
        a.close()
    finally:
        for s in srvs:
            s.stop_background(drain=False)


def test_async_mode_staleness_guard():
    srvs, addrs = _start(mode="async", max_staleness=1)
    try:
        a = _client(addrs, rank=0)
        b = _client(addrs, rank=1)
        assert a.push_grads(_grads(0), samples=4) is None
        # b races ahead: after 3 more applies, a's base version (0) is
        # 4 behind — its next contribution must be REJECTED, not applied
        for i in range(3):
            b.push_grads(_grads(1 + i), samples=4)
            b.pull()
        v_before = b.version
        assert a.push_grads(_grads(9), samples=4) is None
        st = a.stats(0)
        assert st["version"] == v_before, "stale gradient was applied"
        m = a.metrics()
        assert "pserver_async_rejected_total 1" in m
        # after a re-pull the same trainer contributes fine
        a.pull()
        a.push_grads(_grads(10), samples=4)
        assert a.stats(0)["version"] == v_before + 1
        for cl in (a, b):
            cl.leave()
            cl.close()
    finally:
        for s in srvs:
            s.stop_background(drain=False)


# ---------------------------------------------------------------------------
# streaming checkpoints
# ---------------------------------------------------------------------------


def test_streaming_snapshot_does_not_stall_updates(tmp_path):
    """The ISSUE 14 regression pin: a snapshot in progress must not pause
    send_grad traffic.  The write is artificially slowed via the test
    seam; the client keeps committing windows THROUGH it, and the
    snapshot's own capture stays consistent (copy-on-write)."""
    srvs, addrs = _start(snapshot_dir=str(tmp_path / "ck"),
                         snapshot_every=3)
    srv = srvs[0]
    progressed = {"during": 0, "version_at_capture": None}
    release = threading.Event()

    def slow_hook(snap):
        if progressed["version_at_capture"] is None:
            progressed["version_at_capture"] = snap["version"]
        release.wait(timeout=30)

    srv._snap_hook = slow_hook
    try:
        a = _client(addrs, rank=0)
        for i in range(3):               # 3rd commit triggers the snapshot
            a.push_grads(_grads(i), samples=4)
        deadline = time.monotonic() + 10
        while not srv.snapshot_in_progress and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.snapshot_in_progress, "snapshot never started"
        # updates must keep committing while the writer is stuck
        for i in range(4):
            a.push_grads(_grads(10 + i), samples=4)
        progressed["during"] = srv.engine.version
        assert progressed["during"] >= 7, \
            "send_grad stalled during the snapshot"
        release.set()
        deadline = time.monotonic() + 30
        while srv.snapshots_written == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        # commits 3 and 6 both trigger; the event coalesces to >= 1 write
        assert srv.snapshots_written >= 1
        # the first capture froze the state AT CAPTURE TIME, version 3 —
        # immutable-array copy-on-write means later commits never leak in
        assert progressed["version_at_capture"] == 3
        from paddle_tpu.trainer.checkpoint import load_checkpoint
        out = load_checkpoint(srv.last_snapshot_path)
        assert set(out["params"]) == {"w", "b"}
        assert "momentum" in out["opt"]["slots"]["w"]
        a.leave()
        a.close()
    finally:
        for s in srvs:
            s.stop_background(drain=False)


def test_sharded_snapshot_reassembles_bit_exact(tmp_path):
    """2-shard fleet checkpoints reassemble to exactly the state a
    1-shard server reaches on the same contribution sequence — INCLUDING
    a pass boundary, which must relay to the non-coordinator shard (its
    pass_id and snapshot pass labels must not lag shard 0's)."""
    seq = [(_grads(i), 4) for i in range(5)]

    def run(n_shards, snap_dir):
        srvs, addrs = [], []
        for i in range(n_shards):
            s = ParameterServer(port=0, shard_index=i, n_shards=n_shards,
                                block_size=5, snapshot_dir=snap_dir)
            addrs.append(s.start_background())
            srvs.append(s)
        a = _client(addrs, rank=0)
        for g, n in seq[:3]:
            a.push_grads(g, n)
        assert a.pass_barrier() == 1     # relays to every shard
        for s in srvs:
            assert s.engine.pass_id == 1, \
                f"shard {s.shard_index} missed the pass boundary"
        for g, n in seq[3:]:
            a.push_grads(g, n)
        a.leave()
        a.close()
        for s in srvs:
            s.stop_background(drain=True)   # final snapshot
        return srvs

    run(1, str(tmp_path / "one"))
    run(2, str(tmp_path / "two"))
    from paddle_tpu.trainer.checkpoint import (latest_checkpoint,
                                               load_checkpoint)
    ref = load_checkpoint(latest_checkpoint(str(tmp_path / "one")))
    import os
    shard0 = os.path.join(str(tmp_path / "two"), "shard-00")
    label = os.path.basename(latest_checkpoint(shard0))
    params, opt = assemble_sharded_checkpoint(str(tmp_path / "two"), label)
    for n in ref["params"]:
        np.testing.assert_array_equal(params[n], ref["params"][n])
    for n in ref["opt"]["slots"]:
        for k in ref["opt"]["slots"][n]:
            np.testing.assert_array_equal(opt["slots"][n][k],
                                          ref["opt"]["slots"][n][k])
    assert int(opt["num_updates"]) == int(ref["opt"]["num_updates"])


# ---------------------------------------------------------------------------
# misconnected peers get actionable refusals (both directions)
# ---------------------------------------------------------------------------


def test_wrong_role_connect_names_both_roles():
    srvs, addrs = _start()
    try:
        # a SERVING client pointed at a pserver: the op is refused with
        # the role named, the connection survives
        from paddle_tpu.serving.client import ServerError, ServingClient
        sc = ServingClient(addrs[0][0], addrs[0][1])
        assert sc.hello()["role"] == "pserver"
        with pytest.raises(ServerError, match="parameter server"):
            sc.generate([1, 2, 3], max_new=4)
        sc.close()
        # a PSERVER client pointed at... itself is fine; the negative
        # (pserver client at a serving replica) rides connect_with_backoff
        # expect_role and is covered without booting a full engine by the
        # role-mismatch error below
        from paddle_tpu.serving.client import connect_with_backoff
        sock, hello = connect_with_backoff(addrs[0][0], addrs[0][1], 10.0,
                                           expect_role="pserver")
        assert hello["role"] == "pserver"
        sock.close()
        with pytest.raises(ConnectionError, match="pserver.*not the "
                                                  "expected.*replica|is a"):
            connect_with_backoff(addrs[0][0], addrs[0][1], 10.0,
                                 expect_role="replica")
    finally:
        for s in srvs:
            s.stop_background(drain=False)


def test_pserver_client_refuses_serving_replica():
    """The satellite's headline case: a trainer pointed at a serving
    replica port must fail NAMING both roles, not with a frame error."""
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.server import ServingServer
    from paddle_tpu.trainer.trainer import Trainer

    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=31,dim=16,layers=1,heads=2,batch_size=4")
    tr = Trainer(cfg, seed=7)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                        max_context=64)
    srv = ServingServer(eng)
    host, port = srv.start_background()
    try:
        with pytest.raises(ConnectionError) as ei:
            ParameterClient([(host, port)], timeout=10.0)
        msg = str(ei.value)
        assert "serving replica" in msg and "parameter server" in msg
    finally:
        srv.stop_background(drain=False)


def test_async_multi_shard_refused():
    """Per-shard async staleness decisions could silently half-apply a
    gradient — multi-shard async is refused loudly at construction."""
    with pytest.raises(ValueError, match="half-applied"):
        ParameterServer(mode="async", n_shards=2, shard_index=0)


def test_restarted_shard_mixed_init_is_loud(tmp_path):
    """A shard that lost its state mid-job must NOT let a joiner train
    on a silent mix of trained and fresh-init blocks."""
    s0 = ParameterServer(port=0, shard_index=0, n_shards=2, block_size=5)
    s1 = ParameterServer(port=0, shard_index=1, n_shards=2, block_size=5)
    a0 = s0.start_background()
    a1 = s1.start_background()
    try:
        a = _client([a0, a1], rank=0)
        a.push_grads(_grads(0), samples=4)
        a.leave()
        a.close()
        # shard 1 "restarts" empty
        s1.stop_background(drain=False)
        s1b = ParameterServer(port=0, shard_index=1, n_shards=2,
                              block_size=5)
        a1b = s1b.start_background()
        from paddle_tpu.pserver.client import PServerError
        with pytest.raises(PServerError, match="restarted mid-job"):
            _client([a0, a1b], rank=1)
        s1b.stop_background(drain=False)
    finally:
        s0.stop_background(drain=False)


def test_refused_joiner_holds_no_socket():
    """A joiner the coordinator refuses (its rank is held by a live
    trainer) raises and closes every socket it opened — a caller that
    catches the error is left with nothing to leak."""
    from paddle_tpu.pserver.client import PServerError
    srvs, addrs = _start(n_shards=1)
    try:
        a = _client(addrs, rank=0)
        b = ParameterClient(addrs, timeout=30.0)
        with pytest.raises(PServerError, match="already held"):
            b.join(rank=0)
        assert all(s.fileno() == -1 for s in b.socks + [b._ctl])
        a.leave()
        a.close()
    finally:
        for s in srvs:
            s.stop_background(drain=False)


def test_stop_does_not_wait_for_an_idle_peer():
    """A server asked to stop while a connected peer sits idle, its
    socket never closed, stops anyway: shutdown closes the connections
    it accepted instead of waiting for each peer to hang up."""
    import socket

    from paddle_tpu.serving import wire
    srv = ParameterServer(port=0, shard_index=0, n_shards=1)
    host, port = srv.start_background()
    peer = socket.create_connection((host, port), timeout=10.0)
    try:
        # a completed round trip: the server has accepted the connection
        # and its handler is parked on the next read
        wire.write_frame_sync(peer, {"type": "ping"})
        assert wire.read_frame_sync(peer)["type"] == "pong"
        t0 = time.monotonic()
        srv.stop_background(drain=False, timeout=30)
        assert time.monotonic() - t0 < 10.0
        assert wire.read_frame_sync(peer) is None, \
            "the idle peer must see the server hang up"
    finally:
        peer.close()


def test_joiner_pull_waits_for_commit_relay():
    """A joiner pulling between a coordinator commit and the commit-set
    relay must not assemble a mixed-version parameter state: the
    non-coordinator shard parks the version-gated read until the relay
    lands."""
    from paddle_tpu.serving import wire as w_

    s0 = ParameterServer(port=0, shard_index=0, n_shards=2, block_size=5)
    s1 = ParameterServer(port=0, shard_index=1, n_shards=2, block_size=5)
    a0 = s0.start_background()
    a1 = s1.start_background()
    try:
        a = _client([a0, a1], rank=0)
        # push window 0 by hand: grads to BOTH shards, barrier at shard
        # 0 (commits there) — but do NOT relay to shard 1 yet
        for s, sock in enumerate(a.socks):
            blocks = {}
            for name in a.block_map.names():
                blocks.update(a.block_map.split(name, _grads(0)[name],
                                                shard=s))
            w_.write_frame_sync(sock, {
                "type": "send_grad", "tid": a.tid, "window": 0,
                "samples": 4,
                "blocks": {bid: encode_array(arr)
                           for bid, arr in blocks.items()}})
            assert w_.read_frame_sync(sock)["type"] == "grad_ack"
        w_.write_frame_sync(a.socks[0], {"type": "barrier", "tid": a.tid,
                                         "window": 0})
        reply = w_.read_frame_sync(a.socks[0])
        assert reply["type"] == "barrier" and reply["version"] == 1
        assert s1.engine.version == 0       # relay withheld

        # joiner pulls NOW: must block until the relay, not mix v1+v0
        b = ParameterClient([a0, a1], timeout=30.0)
        b.join(rank=1)
        got = {}

        def join_pull():
            got["params"] = b.init_or_fetch(
                _init_params(), OPT.to_dict(),
                {n: p.to_dict() for n, p in PCFGS.items()})

        th = threading.Thread(target=join_pull)
        th.start()
        time.sleep(0.3)
        assert "params" not in got, "joiner read a mixed-version state"
        # now relay the commit set; the parked pull completes
        w_.write_frame_sync(a.socks[1], {
            "type": "get_params", "want": "params",
            "apply": {"window": 0, "members": reply["members"]}})
        assert w_.read_frame_sync(a.socks[1])["type"] == "params"
        th.join(timeout=30)
        assert "params" in got
        # both shards at version 1: the joiner's state is consistent
        ref = {}
        for s, sock in enumerate(a.socks):
            w_.write_frame_sync(sock, {"type": "get_params",
                                       "want": "params"})
            r = w_.read_frame_sync(sock)
            assert r["version"] == 1
            for bid, d in r["blocks"].items():
                ref[bid] = decode_array(d)
        ref = a.block_map.assemble_all(ref)
        for n in ref:
            np.testing.assert_array_equal(got["params"][n], ref[n])
        for cl in (a, b):
            cl.close()
    finally:
        s0.stop_background(drain=False)
        s1.stop_background(drain=False)


def test_engine_refuses_updater_hooks():
    bm = BlockMap.from_arrays(_init_params(), 1, block_size=5)
    bad = {"w": ParameterConfig(name="w", size=12, dims=[3, 4],
                                update_hooks=[{"type": "pruning",
                                               "sparsity_ratio": 0.5}]),
           "b": PCFGS["b"]}
    with pytest.raises(NotImplementedError, match="hooks"):
        UpdateEngine(bm, 0, OPT, bad,
                     bm.split_all(_init_params()))


# ---------------------------------------------------------------------------
# straggler detection + the wedged-update-thread path (ISSUE 15)
# ---------------------------------------------------------------------------


def test_window_skew_histogram_and_straggler_event():
    """The shard-0 coordinator measures per-window barrier-arrival skew;
    past straggler_ms, a `straggler` flight event NAMES the late rank."""
    from paddle_tpu.obs.flight import get_flight_recorder

    fr = get_flight_recorder()
    was_enabled = fr.enabled
    fr.enabled = True
    n0 = fr.recorded
    srvs, addrs = _start(beat_timeout_s=60.0, straggler_ms=50.0)
    try:
        a = _client(addrs, rank=0)
        b = _client(addrs, rank=1)
        got = {}

        def push_a():
            got["a"] = a.push_grads(_grads(0), samples=4)

        th = threading.Thread(target=push_a)
        th.start()
        time.sleep(0.3)                  # rank 1 is the straggler
        got["b"] = b.push_grads(_grads(1), samples=4)
        th.join(timeout=30)
        assert "a" in got
        events = [e for e in fr.snapshot()
                  if e["kind"] == "straggler" and e["seq"] >= n0]
        assert len(events) == 1
        assert events[0]["data"]["rank"] == 1        # the LATE rank
        assert events[0]["data"]["skew_ms"] >= 100.0
        m = a.metrics()
        assert "pserver_window_skew_ms_count 1" in m
        st = a.stats()
        assert st["last_skew_ms"] >= 100.0
        assert st["straggler_ms"] == 50.0
        # the barrier reply fed the skew into the client's attribution
        assert a.last_timing["skew_ms"] >= 100.0
        for cl in (a, b):
            cl.leave()
            cl.close()
    finally:
        fr.enabled = was_enabled
        for s in srvs:
            s.stop_background(drain=False)


def test_wedged_update_thread_stale_ok_one_bundle_per_episode(tmp_path):
    """ISSUE 15 satellite — the serving wedge e2e, ported to the
    pserver: a deliberately wedged optimizer apply leaves stats/metrics/
    trace RPCs answerable stale-ok on the loop thread, the watchdog's
    lag gauge grows, EXACTLY one postmortem bundle freezes per episode
    (role-aware in tools/postmortem.py), and releasing the wedge lets
    the barrier commit and re-arms the watchdog for the next episode."""
    import os

    from paddle_tpu.obs import Tracer
    from paddle_tpu.obs.flight import get_flight_recorder, load_bundle
    from paddle_tpu.serving.client import ServingClient
    from tools.postmortem import render

    fr = get_flight_recorder()
    was_enabled = fr.enabled
    fr.enabled = True
    tracer = Tracer()
    tracer.enabled = True
    srvs, addrs = _start(beat_timeout_s=60.0, wedge_threshold_s=0.5,
                         snapshot_dir=str(tmp_path), tracer=tracer)
    srv = srvs[0]

    def bundles():
        return sorted(p for p in os.listdir(tmp_path)
                      if p.startswith("postmortem-"))

    try:
        a = _client(addrs, rank=0)
        orig = srv.engine.commit
        gate = {"wedged": threading.Event(), "release": threading.Event()}

        def commit_wedged(entries, **kw):
            gate["wedged"].set()
            assert gate["release"].wait(60), "wedge never released"
            return orig(entries, **kw)

        srv.engine.commit = commit_wedged
        got = {}
        th = threading.Thread(
            target=lambda: got.update(out=a.push_grads(_grads(0),
                                                       samples=4)))
        th.start()
        assert gate["wedged"].wait(10), "update thread never picked up"
        # stale-ok frames answer on the LOOP thread while the update
        # thread is stuck, and the lag gauge grows between reads
        with ServingClient(addrs[0][0], addrs[0][1], timeout=10) as c:
            st1 = c.stats()
            assert st1["update_alive"] is True
            assert st1["update_lag_s"] >= 0.0
            time.sleep(0.3)
            st2 = c.stats()
            assert st2["update_lag_s"] > st1["update_lag_s"]
            mtext = c.metrics()
            assert "pserver_update_lag_s" in mtext
            assert "pserver_update_alive 1" in mtext
            pull = c.trace()             # answers against the wedge
            assert pull["process"]["role"] == "pserver"
        # exactly ONE bundle at the threshold, not one per poll
        deadline = time.monotonic() + 10
        while not bundles() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert len(bundles()) == 1, "no bundle at the wedge threshold"
        time.sleep(0.5)                  # > watchdog poll period
        assert len(bundles()) == 1, \
            "a sustained wedge must be one bundle, not one per poll"
        b = load_bundle(str(tmp_path / bundles()[0]))
        assert b["meta"]["reason"] == "update_wedge"
        assert "update thread wedged" in b["meta"]["error"]
        assert "ps_wedge" in [e["kind"] for e in b["events"]]
        # the bundle renders ROLE-AWARE: membership table + update-
        # thread state + window counters, not the serving slots layout
        txt = render(b)
        assert "pserver: shard 0/1" in txt
        assert "update thread: WEDGED" in txt
        assert "rank 0" in txt
        assert "slots" not in txt.split("events:")[0]
        # release: the parked barrier commits and the client advances
        gate["release"].set()
        th.join(timeout=30)
        assert got.get("out") is not None
        assert a.version == 1
        # recovery re-arms the episode latch: a SECOND wedge freezes a
        # second bundle
        deadline = time.monotonic() + 5
        while srv._wedge_dumped and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not srv._wedge_dumped, "watchdog never re-armed"
        gate["wedged"] = threading.Event()
        gate["release"] = threading.Event()
        th2 = threading.Thread(
            target=lambda: got.update(out2=a.push_grads(_grads(1),
                                                        samples=4)))
        th2.start()
        assert gate["wedged"].wait(10)
        deadline = time.monotonic() + 10
        while len(bundles()) < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert len(bundles()) == 2, "second episode must dump again"
        gate["release"].set()
        th2.join(timeout=30)
        assert got.get("out2") is not None
        a.leave()
        a.close()
    finally:
        fr.enabled = was_enabled
        for s in srvs:
            s.stop_background(drain=False)


def test_pass_relay_reads_the_pass_once_while_its_job_is_in_flight():
    """Two trainers' pass relays reach a non-coordinator shard a moment
    apart: the second lands while the first's finish_pass job runs on the
    update thread, which moves `pass_id` between the handler's statements
    (tests/test_train_dist_trace.py's K=2 x 2-shard run hit it once in a
    dozen runs on a loaded machine).  ONE read decides: a relay that saw
    the pass before the boundary waits for the job's `done`; it is never
    told that a boundary was skipped."""
    import types

    srv = ParameterServer(port=0, shard_index=1, n_shards=2)
    reads = iter([0, 1, 1, 1])
    srv.engine = type("Moving", (), {
        "pass_id": property(lambda self: next(reads))})()
    srv._pass_relaying = True           # the first relay's job is running
    sent = []
    conn = types.SimpleNamespace(send=sent.append)
    srv._handle_pass_relay(conn, {"type": "pass_relay", "pass_id": 1})
    assert sent == [] and srv._pass_relay_waiters == [conn]
    # and a relay that arrives after the job is told the pass it reached
    srv._handle_pass_relay(conn, {"type": "pass_relay", "pass_id": 1})
    assert [m["type"] for m in sent] == ["barrier"]
    assert sent[0]["pass_id"] == 1
