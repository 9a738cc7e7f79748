"""ParameterServer: the authoritative parameter + optimizer-state tier.

The TPU-native rebuild of the reference's ParameterServer2 (ref:
paddle/pserver/ParameterServer2.{h,cpp}: addGradient :501,
sendBackParameter, per-server parameter blocks :120-145; ProtoServer RPC)
over the serving wire protocol (`serving/wire.py` length-prefixed JSON
frames, hello role "pserver").  One process per shard; a shard holds the
blocks `pserver/blocks.py`'s deterministic map assigns it, plus their
optimizer slots, and applies updates with the REPO'S OWN
`optim/updater.py` math at block granularity — separately jitted but
bit-identical to the fused local train step (the optimizer family is
elementwise; tests/test_train_dist.py pins the oracle).

Architecture — three threads, mirroring the serving server's discipline:

  * the ASYNCIO LOOP owns frames, membership and window bookkeeping
    (single-writer, no cross-thread mutation);
  * an UPDATE THREAD owns the jax math (accumulate + apply), fed by a
    job queue so a slow optimizer apply never blocks heartbeats, and so
    commits are strictly ordered;
  * a SNAPSHOT THREAD streams checkpoints: it captures `(params, state,
    version)` by REFERENCE under a brief lock (updates replace arrays
    wholesale — jax arrays are immutable, so the capture is copy-on-write
    for free) and serializes into the atomic `trainer/checkpoint.py`
    pass-dir format while `send_grad` traffic keeps committing.

Sync mode: a window commits when every ACTIVE member has barrier'd; the
commit set is reduced in RANK order, so K trainers on disjoint stride
shards reproduce a single-process `grad_accum=K` run bit-for-bit (incl.
the LR schedule, weight decay and model averaging — all state lives
here).  A trainer that dies mid-window is dropped (conn EOF or heartbeat
expiry), its buffered in-flight contribution is DISCARDED, and the
barrier re-evaluates — the pass continues with the survivors.

Multi-shard sync: trainers join/barrier at SHARD 0 (the membership
coordinator); its barrier reply carries the window's commit set, which
trainers relay to the other shards inside `get_params` — every shard then
applies the identical rank-ordered reduction.  A trainer only barriers
after every shard acked its `send_grad`, so a commit-set member's
contribution is guaranteed buffered everywhere.

Async mode: no barrier — each contribution applies on arrival, guarded by
a per-trainer version check (`max_staleness` versions behind rejects the
gradient and tells the trainer to re-pull), with the applied staleness
distribution exported honestly as `pserver_async_staleness`.

Observability rides the existing machinery: pserver_* rows in
obs.metrics.CATALOG behind a strict registry (`metrics` frame), flight
events (trainer_join/trainer_leave/trainer_drain/ps_commit/ps_snapshot/
straggler/ps_wedge) on the process-global recorder, and a `dump` frame
freezing a postmortem bundle.  Training-fleet tracing (docs/
distributed_training.md "Observability"): `send_grad`/`barrier`/
`get_params` frames carry the trainer-minted wire trace context, this
shard records `recv_grad` (loop), `accumulate`/`apply`/`commit` (update
thread) and `snapshot` spans adopting it, and a `trace` RPC (loop
thread, stale-ok against a wedged update thread, live `enable` flip)
feeds `tools/trace_dump.py --pull` — so a K-trainer × N-shard run
stitches into ONE Perfetto trace.  The barrier reply carries the
window's `timing` (accum/apply ms + arrival skew) for the trainer's
per-window attribution; the shard-0 coordinator observes per-window
barrier-arrival skew (`pserver_window_skew_ms`, `straggler` events
naming the late rank), and a loop-side watchdog over the update
thread's job lag freezes one postmortem bundle per wedge episode.
Design doc: docs/distributed_training.md.
"""

from __future__ import annotations

import asyncio
import dataclasses
import hashlib
import json
import os
import queue
import sys
import threading
import time
from collections import deque
from types import SimpleNamespace
from typing import Optional

import numpy as np

from paddle_tpu.obs import MetricsRegistry, tracer_collector
from paddle_tpu.obs.flight import flight_collector, get_flight_recorder
from paddle_tpu.obs.slo import SloEvaluator, default_pserver_slos
from paddle_tpu.obs.timeseries import (HistorySampler, MetricHistory,
                                       history_collector, history_reply)
from paddle_tpu.obs.trace import get_tracer, trace_reply
from paddle_tpu.pserver import membership as mem
from paddle_tpu.pserver.blocks import (BlockMap, decode_array,
                                       decode_blocks_bin, encode_array,
                                       encode_blocks_bin)
from paddle_tpu.pserver.membership import Membership
from paddle_tpu.serving import wire
from paddle_tpu.serving.wire import FrameConn

#: staleness histogram buckets: versions behind at apply (async mode)
_STALENESS_BUCKETS = (0, 1, 2, 4, 8, 16, 32)


class UpdateEngine:
    """The jax half: block store + optimizer state + exact update math.

    Owned by the server's update thread (construction aside); `lock`
    guards only the params/state POINTER swap so the snapshot thread can
    capture a consistent reference set mid-training.  Usable standalone —
    the churn soak's replay oracle drives one directly.
    """

    def __init__(self, block_map: BlockMap, shard_index: int,
                 opt_config, param_cfgs: dict,
                 init_blocks: dict[str, np.ndarray]):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.optim.updater import ParameterUpdater

        self._jax = jax
        self._jnp = jnp
        # spans (accumulate/apply/commit on the "update" lane) land on the
        # owning server's ring; standalone engines (the replay oracle)
        # default to the process-global tracer, off unless a test flips it
        self.tracer = get_tracer()
        self.block_map = block_map
        self.shard_index = int(shard_index)
        self.refs = block_map.shard_blocks(self.shard_index)
        for name, cfg in param_cfgs.items():
            if cfg.update_hooks:
                raise NotImplementedError(
                    f"parameter {name!r} declares updater hooks (pruning "
                    f"masks) — masks are built from FULL-parameter "
                    f"magnitudes, which a block-sharded server cannot "
                    f"reproduce; train this config with the local "
                    f"ParameterUpdater")
        # block-level parameter configs: each block inherits its parent's
        # update knobs (per-param LR/momentum/decay/clipping are all
        # elementwise, so block granularity changes nothing)
        block_cfgs = []
        for r in self.refs:
            cfg = param_cfgs[r.name]
            block_cfgs.append(dataclasses.replace(
                cfg, name=r.bid, size=r.size, dims=[r.size],
                partition_spec=None))
        # windows are the SERVER'S construct here (their size is the live
        # trainer count, decided per commit) — the block updater itself
        # must never open a second accumulation window
        opt = dataclasses.replace(opt_config,
                                  num_batches_per_send_parameter=1)
        self.updater = ParameterUpdater(
            SimpleNamespace(parameters=block_cfgs), opt)
        self.params = {r.bid: jnp.asarray(init_blocks[r.bid])
                       for r in self.refs}
        self.state = self.updater.init_state(self.params)
        self.lock = threading.Lock()
        self.version = 0              # commits applied
        self._updatable = [r.bid for r in self.refs
                           if not param_cfgs[r.name].is_static]

        def _acc_zeros(p):
            dt = jnp.promote_types(p.dtype, jnp.float32) if \
                jnp.issubdtype(p.dtype, jnp.floating) else p.dtype
            return jnp.zeros(p.shape, dt)

        self._acc_zeros = _acc_zeros
        # EXACTNESS: these two mirror optim/updater.py step()'s
        # accumulate branch and apply_branch line for line — the sample-
        # weighted fp32 accumulation (static bsz, like the local step's
        # Python-int batch_size) and the traced-denominator mean + _apply
        from functools import partial

        @partial(jax.jit, static_argnums=(2,))
        def _acc_add(acc, g, bsz):
            return acc + bsz * g.astype(acc.dtype)

        def _apply_window(params, acc, core, n_samples):
            denom = n_samples.astype(jnp.float32)
            mean = {n: (a / denom).astype(a.dtype) for n, a in acc.items()}
            return self.updater._apply(params, mean, core, n_samples)

        self._acc_add = _acc_add
        self._apply_window = jax.jit(_apply_window)

    # -- properties ---------------------------------------------------------
    @property
    def pass_id(self) -> int:
        return int(self.state["pass_id"])

    @property
    def use_average(self) -> bool:
        return self.updater.use_average

    def block_bytes(self) -> int:
        return sum(int(np.dtype(v.dtype).itemsize) * int(np.size(v))
                   for v in self.params.values())

    # -- the commit (update thread) -----------------------------------------
    def commit(self, entries: list[tuple], window=None,
               trace=None) -> dict:
        """Apply one window: `entries` = [(rank, tid, samples,
        {bid: flat grad}, pre_accum)] ALREADY in rank order.  Accumulates
        sample-weighted in fp32 then applies the optimizer once on the
        mean — identical math to the local updater's grad_accum window.
        A `pre_accum` entry's blocks are ALREADY a trainer-side sample-
        weighted fp32 sum over `samples` batches' worth of gradients
        (the client ran the same `_acc_add` ladder locally), so they
        join the accumulator with weight 1 — the mean's denominator
        still counts every underlying sample.

        `window`/`trace` (the committed window id and its contributors'
        trace_ids) only label the accumulate/apply spans and the timing
        breakdown the barrier reply carries — the math never sees them.
        The apply is device-synced before the pointer swap so `apply_ms`
        is honest wall time (the trainers' pull would have paid the sync
        anyway) and a snapshot capture sees concrete arrays."""
        jnp = self._jnp
        assert entries, "commit with no contributions"
        t0 = time.perf_counter()
        acc = {bid: self._acc_zeros(self.params[bid])
               for bid in self._updatable}
        total = 0
        for _rank, _tid, samples, blocks, pre in entries:
            bsz = int(samples)
            total += bsz
            for bid, g in blocks.items():
                if bid in acc:
                    acc[bid] = self._acc_add(acc[bid], jnp.asarray(g),
                                             1 if pre else bsz)
        self._jax.block_until_ready(acc)
        t1 = time.perf_counter()
        new_params, new_state = self._apply_window(
            self.params, acc, self.state,
            jnp.asarray(total, jnp.int32))
        self._jax.block_until_ready(new_params)
        with self.lock:
            self.params = dict(new_params)
            self.state = new_state
            self.version += 1
        t2 = time.perf_counter()
        if self.tracer.enabled:
            attrs = {"version": self.version, "n": len(entries)}
            if window is not None:
                attrs["window"] = window
            if trace:
                attrs["trace_ids"] = trace
            self.tracer.add("accumulate", t0, t1 - t0, track="update",
                            attrs=attrs)
            self.tracer.add("apply", t1, t2 - t1, track="update",
                            attrs=attrs)
            self.tracer.add("commit", t0, t2 - t0, track="update",
                            attrs=attrs)
        return {"version": self.version, "samples": total,
                "n": len(entries),
                "timing": {"accum_ms": round((t1 - t0) * 1e3, 3),
                           "apply_ms": round((t2 - t1) * 1e3, 3),
                           "total_ms": round((t2 - t0) * 1e3, 3)}}

    def async_apply(self, tid: str, samples: int,
                    blocks: dict[str, np.ndarray],
                    trace=None) -> dict:
        """One async contribution = its own window of one."""
        return self.commit([(0, tid, int(samples), blocks, False)],
                           trace=trace)

    def finish_pass(self, trace_ids=None) -> int:
        """`trace_ids` = the pass-boundary frames' contributor contexts
        (attribution only, like commit's `trace`)."""
        t0 = time.perf_counter()
        with self.lock:
            self.state = self.updater.finish_pass(self.state)
        if self.tracer.enabled:
            attrs = {"kind": "pass", "pass": self.pass_id}
            if trace_ids:
                attrs["trace_ids"] = trace_ids
            self.tracer.add("commit", t0, time.perf_counter() - t0,
                            track="update", attrs=attrs)
        return self.pass_id

    # -- reads --------------------------------------------------------------
    def wire_blocks(self, want: str = "params") -> dict[str, dict]:
        """This shard's blocks, wire-encoded.  want='average' serves the
        model-averaging slots (ref: AverageOptimizer — what eval uses)."""
        if want == "average":
            if not self.use_average:
                raise ValueError("this configuration trains without model "
                                 "averaging (settings average_window=0) — "
                                 "pull want='params'")
            src = self.state["average"]
        else:
            src = self.params
        with self.lock:
            src = dict(src)
        return {bid: encode_array(np.asarray(v)) for bid, v in src.items()}

    def wire_blocks_bin(self, want: str = "params") -> tuple[dict, bytes]:
        """wire_blocks, binary flavor: (meta, raw payload) for a binary
        frame — the hot-path encoding peers negotiate via the
        "bin_blocks" hello capability (no base64 on every pull)."""
        if want == "average":
            if not self.use_average:
                raise ValueError("this configuration trains without model "
                                 "averaging (settings average_window=0) — "
                                 "pull want='params'")
            src = self.state["average"]
        else:
            src = self.params
        with self.lock:
            src = dict(src)
        return encode_blocks_bin({bid: np.asarray(v)
                                  for bid, v in src.items()})

    def capture(self) -> dict:
        """Consistent snapshot by reference (copy-on-write: commits swap
        whole arrays, never mutate) — the streaming checkpointer's read."""
        with self.lock:
            return {"params": dict(self.params), "state": dict(self.state),
                    "version": self.version}

    def assemble_full(self, snap: Optional[dict] = None
                      ) -> tuple[dict, dict]:
        """(params, opt_state) at PARAMETER granularity — only meaningful
        when this shard holds every block (n_shards == 1); the sharded
        layout goes through `assemble_sharded_checkpoint` instead."""
        snap = snap or self.capture()
        bm = self.block_map
        np_blocks = {bid: np.asarray(v) for bid, v in snap["params"].items()}
        params = bm.assemble_all(np_blocks)
        state = snap["state"]
        opt: dict = {k: np.asarray(v) for k, v in state.items()
                     if k not in ("slots", "average")}
        slots: dict = {}
        for name in bm.names():
            refs = bm.blocks[name]
            if refs[0].bid not in state["slots"]:
                continue                       # static: no slots
            keys = state["slots"][refs[0].bid].keys()
            slots[name] = {
                k: bm.assemble(name, {r.bid: np.asarray(
                    state["slots"][r.bid][k]) for r in refs})
                for k in keys}
        opt["slots"] = slots
        if "average" in state:
            opt["average"] = {
                name: bm.assemble(name, {
                    r.bid: np.asarray(state["average"][r.bid])
                    for r in bm.blocks[name]})
                for name in bm.names()}
        return params, opt


def _config_hash(bm_config: dict, opt_dict: dict, param_dicts: dict) -> str:
    blob = json.dumps({"map": bm_config, "opt": opt_dict,
                       "params": param_dicts}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def assemble_sharded_checkpoint(save_dir: str, pass_label: str
                                ) -> tuple[dict, dict]:
    """Merge the per-shard pass dirs a multi-shard pserver fleet wrote
    (`<save_dir>/shard-NN/<pass_label>/`) back into full (params,
    opt_state) trees.  The shard-0 dir carries `blockmap.json`."""
    from paddle_tpu.trainer import checkpoint as ckpt

    with open(os.path.join(save_dir, "shard-00", "blockmap.json")) as f:
        bm = BlockMap.from_config(json.load(f))
    blocks: dict = {}
    slot_blocks: dict = {}
    avg_blocks: dict = {}
    scalars: dict = {}
    for s in range(bm.n_shards):
        d = os.path.join(save_dir, f"shard-{s:02d}", pass_label)
        data = ckpt.load_checkpoint(d)
        blocks.update(data["params"])
        opt = data.get("opt") or {}
        for bid, tree in (opt.get("slots") or {}).items():
            slot_blocks[bid] = tree
        for bid, arr in (opt.get("average") or {}).items():
            avg_blocks[bid] = arr
        for k, v in opt.items():
            if k not in ("slots", "average"):
                scalars[k] = v
    params = bm.assemble_all(blocks)
    opt_state: dict = dict(scalars)
    slots: dict = {}
    for name in bm.names():
        refs = bm.blocks[name]
        if refs[0].bid not in slot_blocks:
            continue
        keys = slot_blocks[refs[0].bid].keys()
        slots[name] = {k: bm.assemble(
            name, {r.bid: slot_blocks[r.bid][k] for r in refs})
            for k in keys}
    opt_state["slots"] = slots
    if avg_blocks:
        opt_state["average"] = {
            name: bm.assemble(name, {r.bid: avg_blocks[r.bid]
                                     for r in bm.blocks[name]})
            for name in bm.names()}
    return params, opt_state


class ParameterServer:
    """One parameter-server shard speaking the serving wire protocol."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 shard_index: int = 0, n_shards: int = 1,
                 mode: str = "sync", max_staleness: int = 4,
                 beat_timeout_s: float = 10.0,
                 snapshot_dir: Optional[str] = None,
                 snapshot_every: int = 0, keep_last: int = 2,
                 commit_log_cap: int = 4096, block_size: int = 0,
                 tracer=None, wedge_threshold_s: float = 30.0,
                 straggler_ms: float = 250.0,
                 history_resolution_s: float = 5.0,
                 history_retention_s: float = 1800.0,
                 slo_specs=None):
        from paddle_tpu.pserver.blocks import DEFAULT_BLOCK_SIZE
        assert mode in ("sync", "async"), mode
        if mode == "async" and int(n_shards) > 1:
            # per-shard arrival order makes staleness decisions diverge
            # across shards — a contribution accepted at shard 0 and
            # rejected at shard 1 would be a SILENT half-applied update;
            # refuse loudly until cross-shard async admission lands
            # (ROADMAP "Distributed training, next increments")
            raise ValueError(
                "async mode is single-shard for now: with n_shards > 1 "
                "the per-shard staleness guards could accept a gradient "
                "on some shards and reject it on others (a silent "
                "half-applied update) — run one shard, or use sync mode")
        self.host, self.port = host, int(port)
        self.shard_index, self.n_shards = int(shard_index), int(n_shards)
        assert 0 <= self.shard_index < self.n_shards
        self.block_size = int(block_size) or DEFAULT_BLOCK_SIZE
        self.mode = mode
        self.max_staleness = int(max_staleness)
        self.beat_timeout_s = float(beat_timeout_s)
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = int(snapshot_every)
        self.keep_last = int(keep_last)
        self.is_coordinator = self.shard_index == 0

        self.engine: Optional[UpdateEngine] = None
        self._config_hash: Optional[str] = None
        self._config_json: Optional[str] = None
        self.membership = Membership()
        self._conn_tid: dict[int, str] = {}      # ctl conn seq -> tid
        self._writers: set = set()     # open connections, for _shutdown
        # coordinator window state
        self._next_window = 0
        self._contrib: dict[str, dict] = {}      # tid -> contribution
        self._barriers: dict[str, tuple] = {}    # tid -> (conn, t_arrived)
        self._pass_waiters: dict[str, tuple] = {}
        self._pass_traces: dict[str, str] = {}   # tid -> boundary trace_id
        self._committing = False
        self._after_commit: list = []            # deferred loop callbacks
        # non-coordinator apply state
        self._shard_contrib: dict[int, dict] = {}    # window -> tid -> entry
        self._apply_waiters: dict[int, list] = {}    # window -> [(conn, msg)]
        self._minv_waiters: list = []    # [(min_version, conn, msg)] —
        #                                  joiner pulls parked until this
        #                                  shard caught up to shard 0
        self._pass_relaying = False
        self._pass_relay_waiters: list = []
        self._pass_relay_traces: list = []       # boundary trace_ids
        self._applying = False
        self.commit_log: deque = deque(maxlen=int(commit_log_cap))
        self._async_version: dict[str, int] = {}     # tid -> base at pull

        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._server = None
        self._bg_thread = None
        self._closed: Optional[asyncio.Event] = None
        self._expire_task = None
        self._draining = False
        self._started_t = time.monotonic()

        # update thread + its wedge watchdog: `_job_started` is stamped
        # by the update thread around each job, so the loop-side watchdog
        # (and the pserver_update_lag_s gauge) can see a single apply
        # wedging without touching the jax state — the serving pump-beat
        # pattern, job-shaped
        self._jobs: "queue.Queue" = queue.Queue()
        self._update_thread: Optional[threading.Thread] = None
        self._update_error: Optional[str] = None
        self._updates_done = 0
        self._job_started: Optional[float] = None
        self.wedge_threshold_s = float(wedge_threshold_s)
        self._wedge_dumped = False    # one bundle per wedge episode
        self._watch_task = None
        self.straggler_ms = float(straggler_ms)
        self.last_skew_ms = 0.0

        # snapshot thread
        self._snap_thread: Optional[threading.Thread] = None
        self._snap_event = threading.Event()
        self._snap_write_lock = threading.Lock()   # drain's final write
        #                          vs an in-flight streaming one: the two
        #                          would race save_checkpoint's re-save
        #                          rename dance on the same pass dir
        self._snap_stop = False
        self.snapshot_in_progress = False
        self.snapshots_written = 0
        self.last_snapshot_path: Optional[str] = None
        self.last_snapshot_seconds = 0.0
        self._snap_hook = None          # test seam: runs between capture
        #                                 and write, on the snapshot thread

        # per-server tracer (default: the process-global ring) — in-process
        # multi-shard tests hand each shard its own Tracer, the per-process
        # shape the `trace` RPC snapshots in a real deployment
        self.tracer = tracer if tracer is not None else get_tracer()
        self.flight = get_flight_recorder()
        self._init_metrics()
        # the health plane (obs/timeseries.py + obs/slo.py): pserver_*
        # series history behind the `history` RPC, with the window-skew
        # SLO burning over the skew histogram's per-window mean.  The
        # sampler thread reads only lock-guarded registry state — it
        # never touches the update thread's jax state.
        self.history = MetricHistory(self.metrics,
                                     resolution_s=history_resolution_s,
                                     retention_s=history_retention_s)
        self.metrics.register_collector(history_collector(self.history))
        self.slo = SloEvaluator(
            self.history,
            default_pserver_slos() if slo_specs is None else slo_specs,
            flight=self.flight, registry=self.metrics,
            dump_fn=self._slo_dump)
        self.history_sampler = HistorySampler(self.history,
                                              on_sample=self.slo.evaluate)

    # -- metrics -------------------------------------------------------------
    def _init_metrics(self) -> None:
        self.metrics = MetricsRegistry(strict=True)
        self._m_updates = self.metrics.counter("pserver_updates_total")
        self._m_grads = self.metrics.counter("pserver_grads_received_total")
        self._m_discarded = self.metrics.counter(
            "pserver_grads_discarded_total")
        self._m_async_rej = self.metrics.counter(
            "pserver_async_rejected_total")
        self._m_snapshots = self.metrics.counter("pserver_snapshots_total")
        self._m_staleness = self.metrics.histogram(
            "pserver_async_staleness", buckets=_STALENESS_BUCKETS)
        self._m_barrier_wait = self.metrics.histogram(
            "pserver_barrier_wait_seconds")
        self._m_snap_s = self.metrics.histogram("pserver_snapshot_seconds")
        self._m_skew = self.metrics.histogram(
            "pserver_window_skew_ms",
            buckets=(1.0, 5.0, 25.0, 100.0, 250.0, 1000.0, 5000.0))
        self._m_apply_s = self.metrics.histogram("pserver_apply_seconds")
        g = self.metrics.gauge
        g("pserver_update_lag_s").set_fn(self.update_lag)
        g("pserver_update_alive").set_fn(
            lambda: 1.0 if self.update_alive() else 0.0)
        g("pserver_version").set_fn(
            lambda: float(self.engine.version) if self.engine else 0.0)
        g("pserver_pass_id").set_fn(
            lambda: float(self.engine.pass_id) if self.engine else 0.0)
        g("pserver_trainers_active").set_fn(
            lambda: float(self.membership.counts()[mem.ACTIVE]))
        g("pserver_trainers_draining").set_fn(
            lambda: float(self.membership.counts()[mem.DRAINING]))
        g("pserver_blocks").set_fn(
            lambda: float(len(self.engine.refs)) if self.engine else 0.0)
        g("pserver_block_bytes").set_fn(
            lambda: float(self.engine.block_bytes()) if self.engine else 0.0)
        self.metrics.register_collector(flight_collector(self.flight))
        self.metrics.register_collector(tracer_collector(self.tracer))

    # -- lifecycle -----------------------------------------------------------
    async def start(self) -> tuple[str, int]:
        self._loop = asyncio.get_running_loop()
        self._closed = asyncio.Event()
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        self._update_thread = threading.Thread(
            target=self._update_loop, name="pserver-update", daemon=True)
        self._update_thread.start()
        if self.snapshot_dir:
            self._snap_thread = threading.Thread(
                target=self._snapshot_loop, name="pserver-snapshot",
                daemon=True)
            self._snap_thread.start()
        self._expire_task = self._loop.create_task(self._expire_loop())
        # the wedge watchdog rides the LOOP thread (it must keep running
        # exactly when the update thread cannot) — crossing the threshold
        # records a ps_wedge event and freezes one postmortem bundle
        self._watch_task = self._loop.create_task(self._wedge_watchdog())
        self.history_sampler.start()
        return self.host, self.port

    async def drain(self, final_snapshot: bool = True) -> None:
        """SIGTERM path: refuse new work, fail open barriers honestly,
        write one final checkpoint, close."""
        self._draining = True
        for tid, (conn, _t) in list(self._barriers.items()):
            conn.send({"type": "error", "op": "barrier",
                       "error": "parameter server draining"})
        self._barriers.clear()
        for tid, (conn, _t) in list(self._pass_waiters.items()):
            conn.send({"type": "error", "op": "barrier",
                       "error": "parameter server draining"})
        self._pass_waiters.clear()
        if final_snapshot and self.snapshot_dir and self.engine is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, self._write_snapshot, "drain")
        await self._shutdown()

    async def stop(self) -> None:
        await self.drain(final_snapshot=False)

    async def _shutdown(self) -> None:
        self.history_sampler.stop()
        if self._expire_task is not None:
            self._expire_task.cancel()
            self._expire_task = None
        if self._watch_task is not None:
            self._watch_task.cancel()
            self._watch_task = None
        self._jobs.put(("stop",))
        self._snap_stop = True
        self._snap_event.set()
        if self._server is not None:
            self._server.close()
            # wait_closed() waits for every accepted connection (Python
            # 3.12): hang up on the peers still connected, or one idle
            # client holds the shutdown for as long as it likes
            for writer in list(self._writers):
                writer.close()
            await self._server.wait_closed()
        self._closed.set()

    async def wait_closed(self) -> None:
        await self._closed.wait()

    def start_background(self) -> tuple[str, int]:
        started = threading.Event()
        addr: list = []

        async def _amain():
            addr.extend(await self.start())
            started.set()
            await self.wait_closed()

        self._bg_thread = threading.Thread(
            target=lambda: asyncio.run(_amain()),
            name="pserver-loop", daemon=True)
        self._bg_thread.start()
        if not started.wait(timeout=60):
            raise RuntimeError("parameter server failed to bind within 60s")
        return addr[0], addr[1]

    def stop_background(self, drain: bool = True, timeout: float = 120):
        if self._loop is None:
            return
        fut = asyncio.run_coroutine_threadsafe(
            self.drain() if drain else self.stop(), self._loop)
        fut.result(timeout=timeout)
        if self._bg_thread is not None:
            self._bg_thread.join(timeout=timeout)

    # -- update thread -------------------------------------------------------
    def _update_loop(self) -> None:
        while True:
            job = self._jobs.get()
            if job[0] == "stop":
                return
            self._job_started = time.monotonic()
            try:
                if job[0] == "commit":
                    _, entries, cb, info = job
                    out = self.engine.commit(
                        entries, window=info.get("window"),
                        trace=info.get("trace"))
                    self._m_apply_s.observe(
                        out["timing"]["total_ms"] / 1e3)
                    self._m_updates.inc()
                    self._updates_done += 1
                    if self.snapshot_every and self.snapshot_dir and \
                            self._updates_done % self.snapshot_every == 0:
                        self._snap_event.set()
                elif job[0] == "async":
                    _, tid, samples, blocks, cb, trace = job
                    out = self.engine.async_apply(tid, samples, blocks,
                                                  trace=trace)
                    self._m_apply_s.observe(
                        out["timing"]["total_ms"] / 1e3)
                    self._m_updates.inc()
                    self._updates_done += 1
                    if self.snapshot_every and self.snapshot_dir and \
                            self._updates_done % self.snapshot_every == 0:
                        self._snap_event.set()
                elif job[0] == "pass":
                    # snapshot: the relay path hands the LIVE list so
                    # late-arriving relays still attribute to this pass
                    _, cb, traces = job
                    out = {"pass_id": self.engine.finish_pass(
                        trace_ids=list(traces) or None)}
                else:                  # pragma: no cover — unknown job
                    self._job_started = None
                    continue
            except Exception as e:     # noqa: BLE001 — surfaced to clients
                self._update_error = f"{type(e).__name__}: {e}"
                out = {"error": self._update_error}
            self._job_started = None
            self._loop.call_soon_threadsafe(cb, out)

    def update_alive(self) -> bool:
        return self._update_error is None and \
            self._update_thread is not None and self._update_thread.is_alive()

    def update_lag(self) -> float:
        """Seconds the update thread has been inside its CURRENT job
        (0.0 when idle) — the wedge signal.  A healthy apply is
        milliseconds; a lag crossing `wedge_threshold_s` means a hung
        compiled step / stuck host callback, exactly the state the
        stale-ok stats/metrics/trace frames must stay readable through."""
        t = self._job_started
        return 0.0 if t is None else max(0.0, time.monotonic() - t)

    async def _wedge_watchdog(self) -> None:
        """Loop-side wedge detector (the serving pump watchdog, ported to
        the update thread): when one job's lag crosses the threshold,
        record a ps_wedge event and freeze exactly ONE postmortem bundle
        for the episode; re-arm when the job completes, so a flapping
        apply produces one bundle per episode, not one per poll."""
        period = max(0.05, min(1.0, self.wedge_threshold_s / 4.0))
        while True:
            await asyncio.sleep(period)
            lag = self.update_lag()
            if lag > self.wedge_threshold_s and self.update_alive():
                if not self._wedge_dumped:
                    self._wedge_dumped = True
                    self.flight.record("ps_wedge", lag_s=round(lag, 3),
                                       window=self._next_window)
                    if self.snapshot_dir:
                        try:
                            self.flight.dump(
                                self.snapshot_dir, reason="update_wedge",
                                spans=self.tracer.snapshot(),
                                engine=self._stats_msg(),
                                metrics=self.metrics.snapshot(),
                                config=self._config_snapshot(),
                                history=self.history.snapshot(),
                                error=f"update thread wedged: current "
                                      f"job running {lag:.1f}s "
                                      f"(threshold "
                                      f"{self.wedge_threshold_s:g}s)")
                        except OSError as e:
                            print(f"pserver: wedge dump failed: {e}",
                                  file=sys.stderr, flush=True)
            elif lag <= self.wedge_threshold_s:
                self._wedge_dumped = False

    # -- snapshot thread -----------------------------------------------------
    def _snapshot_loop(self) -> None:
        while True:
            self._snap_event.wait()
            self._snap_event.clear()
            if self._snap_stop:
                return
            if self.engine is None:
                continue
            try:
                self._write_snapshot("stream")
            except Exception as e:     # noqa: BLE001 — a failed snapshot
                # must not kill the tier; the next trigger retries
                print(f"pserver: snapshot failed: {type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)

    def _write_snapshot(self, why: str) -> str:
        """Capture by reference (brief lock), then serialize WITHOUT
        pausing the update thread — `send_grad` keeps committing while
        the npz writes (the no-stall regression pins this)."""
        with self._snap_write_lock:
            return self._write_snapshot_locked(why)

    def _write_snapshot_locked(self, why: str) -> str:
        from paddle_tpu.trainer import checkpoint as ckpt

        t0 = time.perf_counter()
        snap = self.engine.capture()
        self.snapshot_in_progress = True
        try:
            if self._snap_hook is not None:
                self._snap_hook(snap)
            pass_id = self.engine.pass_id
            if self.n_shards == 1:
                params, opt = self.engine.assemble_full(snap)
                out_dir = self.snapshot_dir
            else:
                # block-granular shard dir + the map to reassemble with
                out_dir = os.path.join(self.snapshot_dir,
                                       f"shard-{self.shard_index:02d}")
                os.makedirs(out_dir, exist_ok=True)
                with open(os.path.join(out_dir, "blockmap.json"), "w") as f:
                    json.dump(self.engine.block_map.config(), f)
                params = {bid: np.asarray(v)
                          for bid, v in snap["params"].items()}
                state = snap["state"]
                opt = {k: np.asarray(v) for k, v in state.items()
                       if k not in ("slots", "average")}
                opt["slots"] = {bid: {k: np.asarray(v)
                                      for k, v in tree.items()}
                                for bid, tree in state["slots"].items()}
                if "average" in state:
                    opt["average"] = {bid: np.asarray(v) for bid, v
                                      in state["average"].items()}
            path = ckpt.save_checkpoint(
                out_dir, pass_id - 1, params, opt_state=opt,
                config_json=self._config_json, keep_last=self.keep_last)
            dt = time.perf_counter() - t0
            self.snapshots_written += 1
            self.last_snapshot_path = path
            self.last_snapshot_seconds = dt
            self._m_snapshots.inc()
            self._m_snap_s.observe(dt)
            if self.tracer.enabled:
                self.tracer.add("snapshot", t0, dt, track="snapshot",
                                attrs={"why": why,
                                       "version": snap["version"]})
            self.flight.record("ps_snapshot", path=path, why=why,
                               version=snap["version"],
                               seconds=round(dt, 4))
            return path
        finally:
            self.snapshot_in_progress = False

    # -- membership plumbing (loop thread) -----------------------------------
    async def _expire_loop(self) -> None:
        while True:
            await asyncio.sleep(max(self.beat_timeout_s / 3.0, 0.05))
            for m in self.membership.expire(self.beat_timeout_s):
                self._trainer_gone(m.tid, "heartbeat expired")

    def _trainer_gone(self, tid: str, why: str) -> None:
        """Dead trainer: discard in-flight work, re-size the barrier."""
        m = self.membership.drop_dead(tid) or \
            SimpleNamespace(tid=tid, rank=-1)
        if self._contrib.pop(tid, None) is not None:
            self._m_discarded.inc()
        self._barriers.pop(tid, None)
        self._pass_waiters.pop(tid, None)
        self._pass_traces.pop(tid, None)
        self._async_version.pop(tid, None)
        self.flight.record("trainer_leave", tid=tid, rank=m.rank, why=why)
        self._maybe_commit()

    # -- sync window commit (coordinator, loop thread) -----------------------
    def _maybe_commit(self) -> None:
        if self._committing or self._draining or not self.is_coordinator:
            return
        arrived = set(self._barriers) | set(self._pass_waiters)
        if self._barriers and not self.membership.required(arrived):
            self._commit_window()
        elif self._pass_waiters and not self._barriers and \
                not self.membership.required(set(self._pass_waiters)):
            self._commit_pass()

    def _window_skew(self, waiters: dict) -> float:
        """Per-rank barrier-arrival skew for the closing window: last
        arriver minus first, in ms, observed into the histogram; past
        `straggler_ms` a `straggler` flight event NAMES the late rank —
        the 1605.08695 lesson that PS-architecture stragglers are the
        scaling killer you must measure before you tune."""
        arrivals = [(t_arr, tid) for tid, (_c, t_arr) in waiters.items()]
        if not arrivals:
            return 0.0
        t_first = min(t for t, _ in arrivals)
        t_last, tid_last = max(arrivals)
        skew_ms = (t_last - t_first) * 1e3
        self._m_skew.observe(skew_ms)
        self.last_skew_ms = skew_ms
        if len(arrivals) >= 2 and skew_ms > self.straggler_ms:
            m = self.membership.get(tid_last)
            rank = m.rank if m is not None else -1
            self.flight.record("straggler", tid=tid_last, rank=rank,
                               window=self._next_window,
                               skew_ms=round(skew_ms, 3))
            if self.tracer.enabled:
                self.tracer.instant("straggler", track="pserver",
                                    rank=rank, window=self._next_window,
                                    skew_ms=round(skew_ms, 3))
        return skew_ms

    def _commit_window(self) -> None:
        w = self._next_window
        order = self.membership.in_rank_order(list(self._barriers))
        entries = []
        members = []
        traces = []
        for tid in order:
            c = self._contrib.get(tid)
            if c is None:
                continue               # barrier'd without grads: no-op rank
            m = self.membership.get(tid)
            entries.append((m.rank, tid, c["samples"], c["blocks"],
                            c.get("pre", False)))
            members.append([tid, m.rank, c["samples"], c.get("tag")])
            if c.get("trace"):
                traces.append(c["trace"]["trace_id"])
            m.windows_joined += 1
        waiters = dict(self._barriers)
        skew_ms = self._window_skew(waiters)
        self._barriers.clear()
        self._contrib.clear()
        self._committing = True

        def done(out: dict) -> None:
            self._committing = False
            if "error" in out:
                for tid, (conn, _t) in waiters.items():
                    conn.send({"type": "error", "op": "barrier",
                               "error": f"update failed: {out['error']}"})
                # joins/reads parked against this commit must not hang
                # until their socket timeout — replay them against the
                # (unchanged — commit applies atomically) state
                pend, self._after_commit = self._after_commit, []
                for cb in pend:
                    cb()
                return
            version = out.get("version",
                              self.engine.version if self.engine else 0)
            self._next_window = w + 1
            self.commit_log.append({"window": w, "version": version,
                                    "members": members})
            self.flight.record("ps_commit", window=w, version=version,
                               n=len(members))
            now = time.monotonic()
            # the window's server-side timing breakdown rides the barrier
            # reply: the trainer folds apply_ms into its per-window
            # attribution (nested inside its own barrier_wait_ms)
            timing = dict(out.get("timing") or {})
            timing["skew_ms"] = round(skew_ms, 3)
            reply = {"type": "barrier", "window": w, "version": version,
                     "members": members, "timing": timing}
            for tid, (conn, t_arr) in waiters.items():
                self._m_barrier_wait.observe(now - t_arr)
                conn.send(dict(reply, tid=tid))
            pend, self._after_commit = self._after_commit, []
            for cb in pend:
                cb()
            self._maybe_commit()

        if entries:
            self._jobs.put(("commit", entries, done,
                            {"window": w, "trace": traces or None}))
        else:
            # every barrierer arrived grad-less (possible but degenerate):
            # advance the window without an optimizer apply
            done({"version": self.engine.version if self.engine else 0})

    def _commit_pass(self) -> None:
        if self._contrib:
            # contributions without barriers at pass end mirror the local
            # updater's drop-last convention: discarded, loudly counted
            self._m_discarded.inc(len(self._contrib))
            self._contrib.clear()
        waiters = dict(self._pass_waiters)
        self._pass_waiters.clear()
        traces = [self._pass_traces.pop(tid) for tid in waiters
                  if tid in self._pass_traces]
        self._committing = True

        def done(out: dict) -> None:
            self._committing = False
            if "error" in out:
                for tid, (conn, _t) in waiters.items():
                    conn.send({"type": "error", "op": "barrier",
                               "error": f"finish_pass failed: "
                                        f"{out['error']}"})
                pend, self._after_commit = self._after_commit, []
                for cb in pend:
                    cb()
                return
            # the commit log records pass boundaries too: the churn
            # soak's replay oracle must re-run finish_pass at the same
            # point in the update sequence (LR pass schedules)
            self.commit_log.append({"pass": out["pass_id"],
                                    "window": self._next_window})
            for tid, (conn, t_arr) in waiters.items():
                self._m_barrier_wait.observe(time.monotonic() - t_arr)
                conn.send({"type": "barrier", "kind": "pass", "tid": tid,
                           "pass_id": out["pass_id"],
                           "window": self._next_window})
            pend, self._after_commit = self._after_commit, []
            for cb in pend:
                cb()
            self._maybe_commit()

        self._jobs.put(("pass", done, traces))

    # -- non-coordinator apply (loop thread) ---------------------------------
    def _maybe_apply_shard(self, w: int) -> None:
        if self._applying or w != self._next_window:
            return
        waiting = self._apply_waiters.get(w) or []
        if not waiting:
            return
        members = waiting[0][1]["apply"]["members"]
        have = self._shard_contrib.get(w, {})
        if any(tid not in have for tid, *_rest in members):
            return                     # a member's send_grad is in flight
        entries = [(rank, tid, have[tid]["samples"], have[tid]["blocks"],
                    have[tid].get("pre", False))
                   for tid, rank, _samples, *_tag in members]
        traces = [have[tid]["trace"]["trace_id"]
                  for tid, *_rest in members if have[tid].get("trace")]
        # a dead trainer's buffered contribution (it never made the
        # commit set) dies with the window bucket
        extra = len(have) - len(entries)
        if extra > 0:
            self._m_discarded.inc(extra)
        self._shard_contrib.pop(w, None)
        self._applying = True

        def done(out: dict) -> None:
            self._applying = False
            # pop at COMPLETION, not at queue time: a second trainer's
            # relay arriving while the apply is in flight joins this
            # list and must be answered here, not orphaned
            waiters = self._apply_waiters.pop(w, [])
            if "error" in out:
                for conn, msg in waiters:
                    conn.send({"type": "error", "id": msg.get("id"),
                               "op": "get_params",
                               "error": f"update failed: {out['error']}"})
                # a version-gated joiner pull can never be satisfied by
                # a shard whose update thread just failed — error it
                # out instead of letting it ride to the socket timeout
                parked, self._minv_waiters = self._minv_waiters, []
                for _v, conn, msg in parked:
                    conn.send({"type": "error", "id": msg.get("id"),
                               "op": "get_params",
                               "error": f"update failed: {out['error']}"})
                return
            self._next_window = w + 1
            self.commit_log.append({"window": w,
                                    "version": self.engine.version,
                                    "members": members})
            self.flight.record("ps_commit", window=w,
                               version=self.engine.version, n=len(members))
            timing = out.get("timing")
            for conn, msg in waiters:
                self._reply_params(conn, msg, timing=timing)
            # joiner pulls parked on a minimum version: answer the ones
            # this apply satisfied
            still, ready = [], []
            for v, conn, msg in self._minv_waiters:
                (ready if self.engine.version >= v else still).append(
                    (v, conn, msg))
            self._minv_waiters = still
            for _v, conn, msg in ready:
                self._reply_params(conn, msg)
            self._maybe_apply_shard(self._next_window)

        if entries:
            self._jobs.put(("commit", entries, done,
                            {"window": w, "trace": traces or None}))
        else:
            done({})

    # -- connection handling -------------------------------------------------
    async def _handle(self, reader, writer) -> None:
        conn = FrameConn(writer)
        first = True
        self._writers.add(writer)
        try:
            while True:
                try:
                    msg = await wire.read_frame(reader)
                except wire.FrameError as e:
                    err = str(e)
                    if first:
                        # a peer speaking the wrong protocol deserves to
                        # be told what this socket is
                        err += (f"; this is a parameter server — expected "
                                f"the {wire.PROTO_DESC}")
                    conn.send({"type": "error", "error": err})
                    break
                if msg is None:
                    break
                first = False
                try:
                    self._dispatch(conn, msg)
                except Exception as e:  # noqa: BLE001 — conn must survive
                    conn.send({"type": "error", "id": msg.get("id"),
                               "error": f"{type(e).__name__}: {e}"})
        finally:
            tid = self._conn_tid.pop(conn.seq, None)
            if tid is not None and self.membership.get(tid) is not None:
                self._trainer_gone(tid, "connection lost")
            self._writers.discard(writer)
            try:
                writer.close()
            except (ConnectionError, RuntimeError):
                pass

    # -- frame dispatch (loop thread) ----------------------------------------
    def _dispatch(self, conn: FrameConn, msg: dict) -> None:
        t = msg.get("type")
        if t == "ping":
            conn.send({"type": "pong"})
        elif t == "hello":
            conn.send(wire.hello_msg(
                "pserver", shard=self.shard_index, n_shards=self.n_shards,
                mode=self.mode, block_size=self.block_size,
                initialized=self.engine is not None,
                version=self.engine.version if self.engine else 0,
                capabilities=sorted([
                    "hello", "ping", "ps_init", "ps_join", "ps_beat",
                    "ps_drain", "ps_leave", "send_grad", "barrier",
                    "get_params", "stats", "metrics", "dump", "ps_log",
                    "trace", "bin_blocks", "pre_accum", "history"])))
        elif t == "ps_init":
            self._handle_init(conn, msg)
        elif t == "ps_join":
            self._handle_join(conn, msg)
        elif t == "ps_beat":
            self.membership.beat(str(msg.get("tid")))
        elif t == "ps_drain":
            tid = str(msg.get("tid"))
            ok = self.membership.drain(tid)
            if ok:
                m = self.membership.get(tid)
                self.flight.record("trainer_drain", tid=tid, rank=m.rank)
            conn.send({"type": "ps_drain", "tid": tid, "ok": ok})
            self._maybe_commit()
        elif t == "ps_leave":
            tid = str(msg.get("tid"))
            m = self.membership.leave(tid)
            if m is not None:
                self._contrib.pop(tid, None)
                self._barriers.pop(tid, None)
                self._pass_waiters.pop(tid, None)
                self._pass_traces.pop(tid, None)
                self.flight.record("trainer_leave", tid=tid, rank=m.rank,
                                   why="left")
            conn.send({"type": "ps_leave", "tid": tid,
                       "ok": m is not None})
            self._maybe_commit()
        elif t == "send_grad":
            self._handle_send_grad(conn, msg)
        elif t == "barrier":
            self._handle_barrier(conn, msg)
        elif t == "get_params":
            self._handle_get_params(conn, msg)
        elif t == "stats":
            conn.send(self._stats_msg())
        elif t == "metrics":
            conn.send({"type": "metrics", "text": self.metrics.render()})
        elif t == "ps_log":
            n = int(msg.get("last", 0)) or len(self.commit_log)
            conn.send({"type": "ps_log",
                       "commits": list(self.commit_log)[-n:],
                       "next_window": self._next_window})
        elif t == "dump":
            self._handle_dump(conn, msg)
        elif t == "trace":
            # trace collection over the wire — loop thread, stale-ok like
            # `metrics`/`stats`: snapshot() is safe concurrent with the
            # update thread, so trace_dump --pull works against a wedged
            # or dead optimizer apply (exactly when an operator pulls).
            # `enable` flips tracing LIVE (no restart) — the train_dist
            # overhead probe's same-fleet A/B switch; the flip applies
            # before the snapshot, so enable:false returns the spans it
            # just froze.
            conn.send(trace_reply(self.tracer, msg, "pserver",
                                  self.host, self.port,
                                  shard=self.shard_index))
        elif t == "history":
            # the health plane's ring — loop thread, stale-ok like
            # `trace`: reads only lock-guarded ring state, so it answers
            # against a wedged update thread (obs/timeseries.py)
            conn.send(history_reply(self.history, msg, "pserver",
                                    self.host, self.port,
                                    shard=self.shard_index))
        elif t in ("generate", "cancel", "fleet"):
            conn.send({"type": "error", "id": msg.get("id"),
                       "error": f"{t!r} belongs to a serving replica/"
                                f"router — this is a parameter server "
                                f"(hello role 'pserver', tools/pserver.py)"
                                f"; point serving clients at tools/"
                                f"serve.py"})
        else:
            conn.send({"type": "error", "id": msg.get("id"),
                       "error": f"unknown message type {t!r}"})

    def _handle_init(self, conn: FrameConn, msg: dict) -> None:
        from paddle_tpu.config.schema import (OptimizationConfig,
                                              ParameterConfig)

        cfg = msg["config"]
        h = _config_hash(cfg["map"], cfg["opt"], cfg["params"])
        if self.engine is not None:
            if h != self._config_hash:
                conn.send({"type": "error", "op": "ps_init",
                           "error": f"configuration mismatch: this server "
                                    f"was initialized with config hash "
                                    f"{self._config_hash}, the joining "
                                    f"trainer sent {h} — all trainers of "
                                    f"one job must share the exact model/"
                                    f"optimizer configuration"})
                return
            conn.send({"type": "ps_init", "initialized": False,
                       "version": self.engine.version})
            return
        bm = BlockMap.from_config(cfg["map"])
        if bm.block_size != self.block_size:
            conn.send({"type": "error", "op": "ps_init",
                       "error": f"trainer block map uses block_size "
                                f"{bm.block_size}, this server announced "
                                f"{self.block_size} — derive the map from "
                                f"the hello frame"})
            return
        if bm.n_shards != self.n_shards:
            conn.send({"type": "error", "op": "ps_init",
                       "error": f"trainer derived a {bm.n_shards}-shard "
                                f"block map but this server runs "
                                f"{self.n_shards} shard(s) — the "
                                f"--pserver list and the fleet size "
                                f"disagree"})
            return
        opt = OptimizationConfig.from_dict(cfg["opt"])
        pcfgs = {n: ParameterConfig.from_dict(d)
                 for n, d in cfg["params"].items()}
        blocks = {bid: decode_array(d)
                  for bid, d in (msg.get("blocks") or {}).items()}
        self.engine = UpdateEngine(bm, self.shard_index, opt, pcfgs, blocks)
        self.engine.tracer = self.tracer
        self._config_hash = h
        self._config_json = msg.get("config_json")
        conn.send({"type": "ps_init", "initialized": True, "version": 0})

    def _handle_join(self, conn: FrameConn, msg: dict) -> None:
        if not self.is_coordinator:
            conn.send({"type": "error", "op": "ps_join",
                       "error": f"shard {self.shard_index} is not the "
                                f"membership coordinator — join at shard "
                                f"0 and only push/pull blocks here"})
            return
        if self._draining:
            conn.send({"type": "error", "op": "ps_join",
                       "error": "parameter server draining"})
            return
        if self._committing:
            # a joiner must observe post-commit state: park the join
            # until the in-flight window lands
            self._after_commit.append(
                lambda c=conn, m=msg: self._handle_join(c, m))
            return
        rank = msg.get("rank")
        try:
            m = self.membership.join(rank=rank)
        except ValueError as e:
            conn.send({"type": "error", "op": "ps_join", "error": str(e)})
            return
        self._conn_tid[conn.seq] = m.tid
        self.flight.record("trainer_join", tid=m.tid, rank=m.rank)
        conn.send({"type": "ps_join", "tid": m.tid, "rank": m.rank,
                   "window": self._next_window,
                   "version": self.engine.version if self.engine else 0,
                   "pass_id": self.engine.pass_id if self.engine else 0,
                   "n_trainers": len(self.membership)})

    def _handle_send_grad(self, conn: FrameConn, msg: dict) -> None:
        if self.engine is None:
            conn.send({"type": "error", "op": "send_grad",
                       "error": "server not initialized — ps_init first"})
            return
        t0 = time.perf_counter()
        tid = str(msg.get("tid"))
        w = int(msg.get("window", -1))
        samples = int(msg.get("samples", 0))
        if wire.PAYLOAD_KEY in msg:
            # binary frame (bin_blocks capability): block meta in the
            # header, raw bytes behind it — no per-block base64 decode
            blocks = decode_blocks_bin(msg["blocks"],
                                       msg[wire.PAYLOAD_KEY])
        else:
            blocks = {bid: decode_array(d)
                      for bid, d in msg["blocks"].items()}
        # wire-level trace context: the trainer minted one trace_id for
        # this window and stamped it on the frame; adopting it as span
        # attrs is what joins this shard's recv/apply spans to the
        # trainer's window span in a stitched trace
        trace = wire.get_trace(msg)
        self._m_grads.inc()
        if self.tracer.enabled:
            self.tracer.add("recv_grad", t0, time.perf_counter() - t0,
                            track="pserver",
                            attrs={"tid": tid, "window": w,
                                   **(trace or {})})
        if self.mode == "async":
            self._handle_async_grad(conn, msg, tid, samples, blocks,
                                    trace)
            return
        if self.is_coordinator:
            m = self.membership.get(tid)
            if m is None:
                conn.send({"type": "error", "op": "send_grad", "tid": tid,
                           "error": f"trainer {tid!r} is not a member — "
                                    f"it was evicted (heartbeat expiry or "
                                    f"connection loss) or never joined; "
                                    f"rejoin with ps_join and pull fresh "
                                    f"parameters"})
                return
            if w != self._next_window:
                conn.send({"type": "error", "op": "send_grad", "tid": tid,
                           "error": f"window {w} is stale: the fleet is "
                                    f"at window {self._next_window} (this "
                                    f"trainer was evicted mid-window?) — "
                                    f"rejoin and pull fresh parameters"})
                return
            m.grads_sent += 1
            self._contrib[tid] = {"samples": samples, "blocks": blocks,
                                  "tag": msg.get("tag"), "trace": trace,
                                  "pre": bool(msg.get("pre_accum"))}
        else:
            self._shard_contrib.setdefault(w, {})[tid] = {
                "samples": samples, "blocks": blocks, "trace": trace,
                "pre": bool(msg.get("pre_accum"))}
            self._maybe_apply_shard(w)
        conn.send({"type": "grad_ack", "tid": tid, "window": w})

    def _handle_async_grad(self, conn, msg, tid, samples, blocks,
                           trace=None) -> None:
        base = int(msg.get("base_version", 0))
        staleness = self.engine.version - base
        if staleness > self.max_staleness:
            self._m_async_rej.inc()
            conn.send({"type": "grad_ack", "tid": tid, "rejected": "stale",
                       "staleness": staleness,
                       "version": self.engine.version,
                       "max_staleness": self.max_staleness})
            return
        self._m_staleness.observe(float(max(staleness, 0)))

        def done(out: dict) -> None:
            if "error" in out:
                conn.send({"type": "error", "op": "send_grad", "tid": tid,
                           "error": out["error"]})
            else:
                conn.send({"type": "grad_ack", "tid": tid,
                           "version": out["version"],
                           "staleness": staleness,
                           "timing": out.get("timing")})

        self._jobs.put(("async", tid, samples, blocks, done,
                        [trace["trace_id"]] if trace else None))

    def _handle_barrier(self, conn: FrameConn, msg: dict) -> None:
        if not self.is_coordinator:
            if msg.get("kind") == "pass":
                # the pass-boundary RELAY: trainers forward the
                # coordinator's finish_pass to every shard (like window
                # commit sets ride get_params) so pass-dependent LR
                # schedules and snapshot pass labels stay in lockstep
                # fleet-wide
                self._handle_pass_relay(conn, msg)
                return
            conn.send({"type": "error", "op": "barrier",
                       "error": f"shard {self.shard_index} is not the "
                                f"membership coordinator — barrier at "
                                f"shard 0"})
            return
        tid = str(msg.get("tid"))
        if self.membership.get(tid) is None:
            conn.send({"type": "error", "op": "barrier", "tid": tid,
                       "error": f"trainer {tid!r} is not a member — "
                                f"rejoin with ps_join"})
            return
        if msg.get("kind") == "pass":
            # both modes synchronize pass boundaries (the LR pass
            # schedule and finish_pass bookkeeping live server-side)
            self._pass_waiters[tid] = (conn, time.monotonic())
            tr = wire.get_trace(msg)
            if tr:
                self._pass_traces[tid] = tr["trace_id"]
        elif self.mode == "async":
            conn.send({"type": "error", "op": "barrier",
                       "error": "async mode has no batch barrier — "
                                "send_grad applies immediately"})
            return
        else:
            w = int(msg.get("window", -1))
            if w != self._next_window:
                conn.send({"type": "error", "op": "barrier", "tid": tid,
                           "error": f"window {w} is stale (fleet at "
                                    f"{self._next_window}) — rejoin and "
                                    f"pull fresh parameters"})
                return
            self._barriers[tid] = (conn, time.monotonic())
        self._maybe_commit()

    def _handle_pass_relay(self, conn: FrameConn, msg: dict) -> None:
        """Non-coordinator pass boundary (idempotent: a pass_id already
        reached replies immediately, concurrent relays share one job)."""
        if self.engine is None:
            conn.send({"type": "error", "op": "barrier",
                       "error": "server not initialized — ps_init first"})
            return
        target = int(msg.get("pass_id", 0))
        # ONE read: the update thread moves pass_id while a relay's job is
        # in flight, so a second read can see the pass the first one did
        # not.  A relay that read `target - 1` then is answered by that
        # job's `done`, which runs on this thread after it.
        at = self.engine.pass_id
        if at >= target:
            conn.send({"type": "barrier", "kind": "pass", "pass_id": at,
                       "window": self._next_window})
            return
        if at != target - 1:
            conn.send({"type": "error", "op": "barrier",
                       "error": f"pass relay for {target} but this shard "
                                f"is at pass {at} — a boundary was skipped "
                                f"(restarted shard?)"})
            return
        self._pass_relay_waiters.append(conn)
        tr = wire.get_trace(msg)
        if tr:
            self._pass_relay_traces.append(tr["trace_id"])
        if self._pass_relaying:
            return
        self._pass_relaying = True

        def done(out: dict) -> None:
            self._pass_relaying = False
            # waiters AND traces swap together here (not at enqueue): a
            # relay arriving while the job is in flight is answered by
            # THIS done, so its boundary trace_id must ride this pass's
            # commit span, not the next one's
            waiters, self._pass_relay_waiters = \
                self._pass_relay_waiters, []
            self._pass_relay_traces = []
            for c in waiters:
                if "error" in out:
                    c.send({"type": "error", "op": "barrier",
                            "error": f"finish_pass failed: "
                                     f"{out['error']}"})
                else:
                    c.send({"type": "barrier", "kind": "pass",
                            "pass_id": out["pass_id"],
                            "window": self._next_window})

        self._jobs.put(("pass", done, self._pass_relay_traces))

    def _handle_get_params(self, conn: FrameConn, msg: dict) -> None:
        if self.engine is None:
            conn.send({"type": "error", "op": "get_params",
                       "error": "server not initialized — ps_init first"})
            return
        apply = msg.get("apply")
        if apply is not None and not self.is_coordinator:
            w = int(apply["window"])
            if w > self._next_window:
                conn.send({"type": "error", "op": "get_params",
                           "error": f"apply for future window {w} (shard "
                                    f"at {self._next_window}) — windows "
                                    f"commit in order"})
                return
            if w == self._next_window:
                self._apply_waiters.setdefault(w, []).append((conn, msg))
                self._maybe_apply_shard(w)
                return
            # w < next: already applied; fall through to a plain read
        minv = msg.get("min_version")
        if minv is not None and not self.is_coordinator and \
                self.engine.version < int(minv):
            # a joiner pulling between a coordinator commit and the
            # commit-set relay would read a parameter state that never
            # existed fleet-wide — park until this shard catches up
            self._minv_waiters.append((int(minv), conn, msg))
            return
        if self.is_coordinator and self._committing:
            # reads during a commit would hand a joiner pre-commit
            # parameters for a post-commit window
            self._after_commit.append(
                lambda c=conn, m=msg: self._handle_get_params(c, m))
            return
        self._reply_params(conn, msg)

    def _reply_params(self, conn: FrameConn, msg: dict,
                      timing: Optional[dict] = None) -> None:
        want = msg.get("want", "params")
        binary = bool(msg.get("bin"))
        reply = {"type": "params", "id": msg.get("id"), "want": want,
                 "version": self.engine.version,
                 "window": self._next_window,
                 "pass_id": self.engine.pass_id,
                 "bin": binary}
        if timing is not None:
            # the window reply a commit-set relay triggered carries this
            # shard's apply breakdown (accum/apply/total ms)
            reply["timing"] = timing
        if binary:
            # the client asked for the raw-bytes reply (it saw the
            # bin_blocks capability in our hello): block meta rides in
            # the header, the concatenated bytes behind it
            meta, payload = self.engine.wire_blocks_bin(want)
            reply["blocks"] = meta
            conn.send_bin(reply, payload)
        else:
            reply["blocks"] = self.engine.wire_blocks(want)
            conn.send(reply)

    # -- ops frames ----------------------------------------------------------
    def _stats_msg(self) -> dict:
        counts = self.membership.counts()
        return {
            "type": "stats", "role": "pserver",
            "shard": self.shard_index, "n_shards": self.n_shards,
            "mode": self.mode,
            "initialized": self.engine is not None,
            "version": self.engine.version if self.engine else 0,
            "window": self._next_window,
            "pass_id": self.engine.pass_id if self.engine else 0,
            "trainers_active": counts[mem.ACTIVE],
            "trainers_draining": counts[mem.DRAINING],
            "trainers": self.membership.summary(),
            "pending_grads": len(self._contrib) + sum(
                len(v) for v in self._shard_contrib.values()),
            "pending_barriers": len(self._barriers),
            "pending_pass_barriers": len(self._pass_waiters),
            "blocks": len(self.engine.refs) if self.engine else 0,
            "block_bytes": self.engine.block_bytes() if self.engine else 0,
            "update_alive": self.update_alive(),
            "update_error": self._update_error,
            "update_lag_s": round(self.update_lag(), 3),
            "wedge_threshold_s": self.wedge_threshold_s,
            "straggler_ms": self.straggler_ms,
            "last_skew_ms": round(self.last_skew_ms, 3),
            "draining": self._draining,
            "snapshot": {
                "dir": self.snapshot_dir,
                "every": self.snapshot_every,
                "in_progress": self.snapshot_in_progress,
                "written": self.snapshots_written,
                "last_path": self.last_snapshot_path,
                "last_seconds": round(self.last_snapshot_seconds, 4),
            },
            "uptime_s": round(time.monotonic() - self._started_t, 3),
        }

    def _slo_dump(self, fired: list) -> None:
        """One proactive bundle per SLO episode (obs/slo.py calls this
        on the sampler thread at the firing transition) — gated on the
        snapshot dir like every other pserver dump."""
        if not self.snapshot_dir:
            return
        names = ",".join(sorted({str(f.get("slo", "?")) for f in fired}))
        try:
            self.flight.dump(
                self.snapshot_dir, reason=f"slo:{names}",
                spans=self.tracer.snapshot(),
                engine=self._stats_msg(),
                metrics=self.metrics.snapshot(),
                config=self._config_snapshot(),
                history=self.history.snapshot(),
                error=f"slo firing: {names}")
        except OSError as e:
            print(f"pserver: slo dump failed: {e}",
                  file=sys.stderr, flush=True)

    def _handle_dump(self, conn: FrameConn, msg: dict) -> None:
        self.flight.record("dump_rpc", id=str(msg.get("id")))
        if not self.snapshot_dir:
            conn.send({"type": "error", "id": msg.get("id"),
                       "error": "no snapshot/postmortem directory "
                                "configured — start the server with "
                                "snapshot_dir= (tools/pserver.py "
                                "--snapshot-dir)"})
            return
        try:
            path = self.flight.dump(
                self.snapshot_dir, reason="dump_rpc",
                spans=self.tracer.snapshot(),
                engine=self._stats_msg(),
                metrics=self.metrics.snapshot(),
                config=self._config_snapshot(),
                history=self.history.snapshot())
        except OSError as e:
            conn.send({"type": "error", "id": msg.get("id"),
                       "error": f"dump failed: {e}"})
            return
        conn.send({"type": "dump", "id": msg.get("id"), "path": path,
                   "events": self.flight.recorded,
                   "spans": self.tracer.recorded})

    def _config_snapshot(self) -> dict:
        return {"shard": self.shard_index, "n_shards": self.n_shards,
                "mode": self.mode, "config_hash": self._config_hash,
                "wedge_threshold_s": self.wedge_threshold_s,
                "straggler_ms": self.straggler_ms}
