"""ParameterClient: one trainer's connection to the pserver fleet.

The TPU-native ParameterClient2 (ref: paddle/pserver/ParameterClient2.
{h,cpp}: sendAndReceiveParameter, per-server send threads): one blocking
socket per server shard (plus a dedicated CONTROL connection to shard 0
carrying membership — join, heartbeats, drain/leave), speaking the
serving wire framing through `connect_with_backoff(expect_role=
"pserver")`, so a trainer pointed at a serving replica or fleet router
port fails with an error naming both roles instead of a cryptic frame
error several RPCs later.

Deliberately jax-free (numpy + stdlib + serving/wire.py + the retry/
handshake helpers of serving/client.py): the gradient push/param pull
path must be liftable onto any box.  The sync-mode batch flow is:

    send_grad -> every shard (acked = buffered everywhere)
    barrier   -> shard 0 (blocks until the window commits; the reply
                 carries the rank-ordered commit set)
    get_params-> every shard (relaying the commit set, which is what
                 triggers the identical apply on shards 1..N-1)

so a trainer only ever advances on parameters every shard has committed
identically.  Heartbeats ride the control connection from a daemon
thread; an abrupt trainer death drops both sockets and the server
discards its in-flight contribution immediately.
"""

from __future__ import annotations

import socket
import threading
import time
from typing import Optional

import numpy as np

from paddle_tpu.obs.trace import get_tracer
from paddle_tpu.pserver.blocks import (BlockMap, decode_array,
                                       decode_blocks_bin,
                                       encode_array, encode_blocks_bin)
from paddle_tpu.serving import wire
from paddle_tpu.serving.client import connect_with_backoff


class PServerError(RuntimeError):
    """The parameter server answered an error frame."""


class StaleTrainerError(PServerError):
    """This trainer was evicted (heartbeat expiry / connection loss) and
    its window is gone — rejoin and pull fresh parameters."""


class ParameterClient:
    def __init__(self, addrs: list, timeout: float = 300.0,
                 connect_attempts: int = 5,
                 beat_interval_s: float = 1.0):
        """`addrs` = [(host, port), ...] in SHARD ORDER (shard 0 first —
        the membership coordinator)."""
        self.addrs = [(h, int(p)) for h, p in addrs]
        self.timeout = float(timeout)
        self.socks: list[socket.socket] = []
        self.hellos: list[dict] = []
        for i, (h, p) in enumerate(self.addrs):
            sock, hello = connect_with_backoff(
                h, p, timeout, attempts=connect_attempts,
                expect_role="pserver")
            if int(hello.get("shard", -1)) != i:
                sock.close()
                raise PServerError(
                    f"--pserver list order is wrong: {h}:{p} is shard "
                    f"{hello.get('shard')} of {hello.get('n_shards')}, "
                    f"but position {i} in the list — pass the shards in "
                    f"shard-index order")
            if int(hello.get("n_shards", 1)) != len(self.addrs):
                sock.close()
                raise PServerError(
                    f"{h}:{p} serves a {hello.get('n_shards')}-shard "
                    f"fleet but {len(self.addrs)} address(es) were "
                    f"given — every shard must be listed")
            self.socks.append(sock)
            self.hellos.append(hello)
        self.mode = self.hellos[0].get("mode", "sync")
        # hot-path framing: binary block frames only if EVERY shard
        # advertises the capability (an old shard keeps getting JSON)
        self._bin = all("bin_blocks" in (h.get("capabilities") or ())
                        for h in self.hellos)
        # trainer-side pre-accumulation (num_batches_per_send > 1): only
        # usable when EVERY shard knows the send_grad pre_accum flag —
        # an old shard would sample-weight the summed blocks a second
        # time and silently break the grad_accum equivalence
        self.pre_accum_capable = all(
            "pre_accum" in (h.get("capabilities") or ())
            for h in self.hellos)
        # dedicated control connection to the coordinator: membership +
        # heartbeats, so a beat never interleaves with a blocked barrier
        self._ctl, _ = connect_with_backoff(
            self.addrs[0][0], self.addrs[0][1], timeout,
            attempts=connect_attempts, expect_role="pserver")
        self._ctl_lock = threading.Lock()
        self.tid: Optional[str] = None
        self.rank: Optional[int] = None
        self.window = 0
        self.version = 0
        self.pass_id = 0
        self.block_map: Optional[BlockMap] = None
        self._beat_thread: Optional[threading.Thread] = None
        self._beat_stop = threading.Event()
        self._beat_interval = float(beat_interval_s)
        # per-window timing attribution (docs/distributed_training.md
        # "Observability"): push_grads/pull stamp contiguous phase walls
        # into `last_timing`; the RemoteParameterUpdater folds them into
        # the trainer's per-pass metrics.jsonl rows.  The tracer (obs is
        # stdlib-only, so the jax-free claim holds) records the same
        # phases as push[shard]/barrier_wait/pull spans on the `remote`
        # lane — all from the training thread, the single-writer rule.
        self.tracer = get_tracer()
        self.last_timing: dict = {}
        self.last_pull_timings: dict = {}   # shard -> relay-apply timing
        self.last_pull_ms = 0.0
        self.stale_rejects = 0         # async: grads refused as stale
        # wire accounting: every send_grad frame's full on-wire size
        # (length prefix + header + payload) summed here — the counter
        # the pre-accumulation N-fold reduction is proved against
        self.grad_bytes_sent = 0

    # -- plumbing ------------------------------------------------------------
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def close(self) -> None:
        self._beat_stop.set()
        for s in self.socks + [self._ctl]:
            try:
                s.close()
            except OSError:
                pass

    def _rpc(self, shard: int, msg: dict, reply_types: tuple,
             payload: Optional[bytes] = None) -> dict:
        sock = self.socks[shard]
        frame = (wire.encode(msg) if payload is None
                 else wire.encode_bin(msg, payload))
        if msg.get("type") == "send_grad":
            self.grad_bytes_sent += len(frame)
        sock.sendall(frame)
        while True:
            reply = wire.read_frame_sync(sock)
            if reply is None:
                raise ConnectionError(
                    f"pserver shard {shard} closed the connection")
            t = reply.get("type")
            if t == "error":
                err = reply.get("error", "unknown pserver error")
                if "rejoin" in err:
                    raise StaleTrainerError(err)
                raise PServerError(err)
            if t in reply_types:
                return reply
            # pserver connections are strictly request/reply per socket;
            # anything else is protocol drift worth failing loudly on
            raise PServerError(f"unexpected {t!r} frame awaiting "
                               f"{reply_types}")

    def _ctl_rpc(self, msg: dict, reply_types: tuple) -> dict:
        with self._ctl_lock:
            wire.write_frame_sync(self._ctl, msg)
            while True:
                reply = wire.read_frame_sync(self._ctl)
                if reply is None:
                    raise ConnectionError("pserver coordinator closed the "
                                          "control connection")
                t = reply.get("type")
                if t == "error":
                    raise PServerError(reply.get("error", "?"))
                if t in reply_types:
                    return reply

    # -- membership ----------------------------------------------------------
    def join(self, rank: Optional[int] = None) -> dict:
        msg = {"type": "ps_join"}
        if rank is not None:
            msg["rank"] = int(rank)
        try:
            reply = self._ctl_rpc(msg, ("ps_join",))
        except BaseException:
            self.close()               # refused: hold no shard's socket
            raise
        self.tid = reply["tid"]
        self.rank = int(reply["rank"])
        self.window = int(reply["window"])
        self.version = int(reply["version"])
        self.pass_id = int(reply["pass_id"])
        self._beat_stop.clear()
        self._beat_thread = threading.Thread(
            target=self._beat_loop, name="pserver-beat", daemon=True)
        self._beat_thread.start()
        return reply

    def _beat_loop(self) -> None:
        while not self._beat_stop.wait(self._beat_interval):
            try:
                with self._ctl_lock:
                    wire.write_frame_sync(
                        self._ctl, {"type": "ps_beat", "tid": self.tid})
            except OSError:
                return                 # server gone: the data path will
                #                        surface the real error loudly

    def drain(self) -> None:
        """Announce departure: the barrier stops waiting for this trainer
        while any already-sent contribution still counts."""
        self._ctl_rpc({"type": "ps_drain", "tid": self.tid},
                      ("ps_drain",))

    def leave(self) -> None:
        self._beat_stop.set()
        try:
            self._ctl_rpc({"type": "ps_leave", "tid": self.tid},
                          ("ps_leave",))
        except (OSError, ConnectionError):
            pass                       # best effort; EOF tells the server

    # -- init / pull ---------------------------------------------------------
    def init_or_fetch(self, params: dict[str, np.ndarray],
                      opt_config_dict: dict, param_cfg_dicts: dict,
                      config_json: Optional[str] = None
                      ) -> dict[str, np.ndarray]:
        """First trainer up seeds the server with its (deterministically
        seeded) initial values; every later trainer verifies the config
        hash and adopts the server's current parameters.  Returns the
        authoritative full parameter dict either way."""
        from paddle_tpu.pserver.blocks import DEFAULT_BLOCK_SIZE
        bm = BlockMap.from_arrays(
            params, n_shards=len(self.addrs),
            block_size=int(self.hellos[0].get("block_size")
                           or DEFAULT_BLOCK_SIZE))
        self.block_map = bm
        cfg = {"map": bm.config(), "opt": opt_config_dict,
               "params": param_cfg_dicts}
        flags = []
        for s in range(len(self.addrs)):
            blocks = bm.split_all(params, shard=s)
            reply = self._rpc(s, {
                "type": "ps_init", "config": cfg,
                "config_json": config_json,
                "blocks": {bid: encode_array(a)
                           for bid, a in blocks.items()}}, ("ps_init",))
            flags.append(bool(reply.get("initialized")))
        if all(flags):
            return dict(params)        # this trainer seeded the fleet
        if any(flags):
            # a single shard restarted mid-job: it just took our FRESH
            # init while the others hold trained state — training on
            # that mix would silently blend pass-N and pass-0 blocks
            fresh = [i for i, f in enumerate(flags) if f]
            self.close()               # refused: hold no shard's socket
            raise PServerError(
                f"shard(s) {fresh} had no state and took this trainer's "
                f"fresh init while the other shard(s) hold trained "
                f"parameters — a shard restarted mid-job; restore the "
                f"fleet from its streaming checkpoint (or restart every "
                f"shard together) before rejoining")
        return self.pull()

    def pull(self, want: str = "params",
             apply_members: Optional[list] = None,
             window: Optional[int] = None,
             trace: Optional[dict] = None) -> dict[str, np.ndarray]:
        """Fetch and assemble the full tree from every shard.  With
        `apply_members`, relays the coordinator's commit set so shards
        1..N-1 apply the window before answering.  A plain pull (the
        joiner path) reads shard 0 FIRST and version-gates the rest:
        a shard the commit-set relay has not reached yet answers only
        once it has caught up, so the assembled state always existed
        fleet-wide."""
        t0 = time.perf_counter()
        blocks: dict[str, np.ndarray] = {}
        self.last_pull_timings = {}    # shard -> its window-apply timing
        for s in range(len(self.addrs)):
            msg: dict = {"type": "get_params", "want": want}
            if self._bin:
                msg["bin"] = True
            if trace:
                msg["trace"] = trace
            if apply_members is not None and s != 0:
                msg["apply"] = {"window": window, "members": apply_members}
            elif s != 0:
                msg["min_version"] = self.version
            reply = self._rpc(s, msg, ("params",))
            if s == 0:
                self.version = int(reply["version"])
                self.pass_id = int(reply["pass_id"])
            if reply.get("timing"):
                # a commit-relay reply: this shard just applied the
                # window before answering — its breakdown nests inside
                # the caller's pull phase
                self.last_pull_timings[s] = reply["timing"]
            if wire.PAYLOAD_KEY in reply:
                blocks.update(decode_blocks_bin(reply["blocks"],
                                                reply[wire.PAYLOAD_KEY]))
            else:
                for bid, d in reply["blocks"].items():
                    blocks[bid] = decode_array(d)
        self.last_pull_ms = (time.perf_counter() - t0) * 1e3
        if self.tracer.enabled:
            self.tracer.add("pull", t0, time.perf_counter() - t0,
                            track="remote",
                            attrs={"want": want, **(trace or {})})
        return self.block_map.assemble_all(blocks)

    # -- the batch flow ------------------------------------------------------
    def push_grads(self, grads: dict[str, np.ndarray], samples: int,
                   tag: Optional[str] = None,
                   trace: Optional[dict] = None,
                   pre_accum: bool = False):
        """Sync: contribute one batch's gradients, barrier, return the
        post-window full parameters.  Async: contribute against the last
        pulled version; returns None (pair with pull() on the trainer's
        num_batches_per_get_parameter cadence) — a stale rejection also
        returns None after recording the fleet's version so the next
        pull re-bases.

        `pre_accum=True` marks the blocks as a trainer-side sample-
        weighted fp32 SUM over several batches (`samples` = the summed
        batch sizes): the server adds them to the window accumulator
        with weight 1 instead of re-weighting by `samples`.  Requires
        every shard to advertise the `pre_accum` capability
        (`pre_accum_capable`).

        `trace` ({"trace_id", "parent"}) stamps the window's wire trace
        context on every frame; `last_timing` afterwards holds the
        window's contiguous phase walls (push/barrier_wait/pull ms, plus
        the server-reported apply/skew) — the parts the updater's
        closure-checked per-window attribution is built from."""
        bm = self.block_map
        w = self.window
        tr = self.tracer
        async_t: dict = {}
        t_push0 = time.perf_counter()
        for s in range(len(self.addrs)):
            shard_blocks: dict = {}
            for name in bm.names():
                if name in grads:
                    shard_blocks.update(bm.split(name, grads[name],
                                                 shard=s))
            msg = {"type": "send_grad", "tid": self.tid, "window": w,
                   "samples": int(samples)}
            payload = None
            if self._bin:
                msg["blocks"], payload = encode_blocks_bin(shard_blocks)
            else:
                msg["blocks"] = {bid: encode_array(a)
                                 for bid, a in shard_blocks.items()}
            if tag is not None:
                msg["tag"] = tag
            if trace:
                msg["trace"] = trace
            if pre_accum:
                if not self.pre_accum_capable:
                    raise PServerError(
                        "pre_accum push but a shard lacks the pre_accum "
                        "capability — upgrade the fleet or run "
                        "num_batches_per_send_parameter=1")
                msg["pre_accum"] = True
            if self.mode == "async":
                msg["base_version"] = self.version
            t_s0 = time.perf_counter()
            ack = self._rpc(s, msg, ("grad_ack",), payload=payload)
            if tr.enabled:
                tr.add("push", t_s0, time.perf_counter() - t_s0,
                       track="remote",
                       attrs={"shard": s, "window": w, **(trace or {})})
            if self.mode == "async":
                self.version = int(ack["version"])
                if ack.get("rejected"):
                    self.stale_rejects += 1
                    self.last_timing = {
                        "window": w, "rejected": True,
                        "staleness": int(ack.get("staleness", 0)),
                        "push_ms": round(
                            (time.perf_counter() - t_push0) * 1e3, 3)}
                    return None
                async_t = {"staleness": int(ack.get("staleness", 0)),
                           **(ack.get("timing") or {})}
        t_push1 = time.perf_counter()
        if self.mode == "async":
            self.last_timing = {
                "window": w,
                "push_ms": round((t_push1 - t_push0) * 1e3, 3),
                "apply_ms": async_t.get("apply_ms", 0.0),
                "staleness": async_t.get("staleness", 0)}
            return None
        bmsg = {"type": "barrier", "tid": self.tid, "window": w}
        if trace:
            bmsg["trace"] = trace
        reply = self._rpc(0, bmsg, ("barrier",))
        t_bar1 = time.perf_counter()
        srv_t = reply.get("timing") or {}
        if tr.enabled:
            tr.add("barrier_wait", t_push1, t_bar1 - t_push1,
                   track="remote",
                   attrs={"window": w, "skew_ms": srv_t.get("skew_ms"),
                          **(trace or {})})
        self.window = int(reply["window"]) + 1
        members = reply["members"]
        out = self.pull(apply_members=members, window=w, trace=trace)
        t_end = time.perf_counter()
        # contiguous segments over [t_push0, t_end]: the three parts sum
        # to the client-side window wall EXACTLY (the updater adds the
        # grad_compute segment in front and asserts the closure)
        self.last_timing = {
            "window": w,
            "push_ms": round((t_push1 - t_push0) * 1e3, 3),
            "barrier_wait_ms": round((t_bar1 - t_push1) * 1e3, 3),
            "pull_ms": round((t_end - t_bar1) * 1e3, 3),
            "apply_ms": srv_t.get("apply_ms", 0.0),
            "accum_ms": srv_t.get("accum_ms", 0.0),
            "skew_ms": srv_t.get("skew_ms", 0.0),
            # shards 1..N-1 apply DURING the pull (the commit-set relay
            # triggers them) — the slowest relay apply nests inside
            # pull_ms the way shard 0's apply_ms nests in barrier_wait
            "relay_apply_ms": max(
                (t.get("apply_ms", 0.0)
                 for t in self.last_pull_timings.values()), default=0.0),
        }
        return out

    def pass_barrier(self, trace: Optional[dict] = None) -> int:
        """End-of-pass synchronization: the coordinator runs finish_pass
        once, then the boundary is RELAYED to every other shard (like
        window commit sets ride get_params) so pass-dependent LR
        schedules and snapshot pass labels never drift per shard.
        Returns the new pass_id."""
        t0 = time.perf_counter()
        msg = {"type": "barrier", "tid": self.tid, "kind": "pass"}
        if trace:
            msg["trace"] = trace
        reply = self._rpc(0, msg, ("barrier",))
        self.pass_id = int(reply["pass_id"])
        self.window = int(reply["window"])
        for s in range(1, len(self.addrs)):
            relay = {"type": "barrier", "kind": "pass",
                     "pass_id": self.pass_id}
            if trace:
                relay["trace"] = trace
            self._rpc(s, relay, ("barrier",))
        if self.tracer.enabled:
            # this span OWNS the boundary context's parent id: shard-side
            # pass-commit spans list the trace_id in their trace_ids
            self.tracer.add("pass_barrier", t0,
                            time.perf_counter() - t0, track="remote",
                            attrs={"pass": self.pass_id,
                                   **({"trace_id": trace["trace_id"],
                                       "span_id": trace["parent"]}
                                      if trace else {})})
        return self.pass_id

    # -- ops -----------------------------------------------------------------
    def stats(self, shard: int = 0) -> dict:
        return self._rpc(shard, {"type": "stats"}, ("stats",))

    def metrics(self, shard: int = 0) -> str:
        return self._rpc(shard, {"type": "metrics"}, ("metrics",))["text"]

    def commit_log(self, last: int = 0) -> list[dict]:
        return self._rpc(0, {"type": "ps_log", "last": int(last)},
                         ("ps_log",))["commits"]
