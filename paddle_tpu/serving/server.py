"""RPC serving front end: asyncio TCP server + continuous engine pump.

The reference's layer 5 is a length-prefixed RPC socket server in front of
the compute (ref: paddle/pserver/ProtoServer.h:37, LightNetwork.h:41);
this is its TPU-native serving echo — a request-lifecycle front end
(admission, deadlines, cancellation, streaming, drain — the architecture
production TPU serving stacks put in front of a continuous-batching core,
arXiv:2605.25645) over `serving/engine.py`:

  * ONE background PUMP THREAD owns the ServingEngine and drives step()
    continuously — requests arrive mid-flight, per-token completions
    stream back as they decode.  All engine access goes through the pump:
    the asyncio side never touches scheduler state, it posts commands
    (add/cancel) to a thread-safe queue the pump drains between steps, and
    the engine's on_token/on_finish hooks append to the pump's ORDERED
    OUTBOX, which the pump hands to the loop with one call_soon_threadsafe
    a step; the loop writes each connection's token frames of that step
    as one transport write.  No locks around the scheduler, no torn state.
  * BOUNDED ADMISSION: the server accepts at most
    `num_slots + max_queue` unfinished requests; one more gets an explicit
    `overload` response instead of unbounded queueing (the client backs
    off; the queue never eats the host).
  * DEADLINES and CANCELLATION free the request's slot and KV pages
    mid-flight (engine.cancel / the per-step deadline sweep) — freed pages
    are reusable by waiting requests on the very next step, and surviving
    requests stay token-exact against the per-request lm_generate oracle
    (tests/test_server.py).
  * GRACEFUL DRAIN: stop admitting (new requests get
    `overload/reason=draining`), finish everything in flight, stop the
    pump, close the listener.  tools/serve.py wires SIGTERM to this and
    exits 0.
  * STATS RPC: queue depth, slot/page occupancy, preemptions, and
    per-request / per-token latency percentiles from a utils/stat.py
    StatSet (bounded sample windows — a week-old server reports recent
    latency, not its lifetime average).  The engine-state part of the
    snapshot is built ON THE PUMP THREAD via a command-queue round trip,
    so `slots_in_use`/`pages_in_use`/`queue_depth` are mutually
    consistent (between-steps view); `{"stale_ok": true}` keeps the old
    loop-thread fast path for pollers that must never wait on the pump
    (the watchdog's path — it also works when the pump is wedged).
  * METRICS + WATCHDOG: a Prometheus-style `metrics` frame (obs.metrics
    registry — engine counters, admission state, latency quantiles,
    tracer accounting) answered on the LOOP thread so it stays readable
    while the pump is wedged; the pump heartbeats every loop iteration
    and `pump_last_step_age_s` exposes a hung engine in metrics before
    clients time out.

Wire protocol: serving/wire.py (4-byte big-endian length + JSON body);
message schemas in docs/serving.md.  The blocking-socket client is
serving/client.py.
"""

from __future__ import annotations

import asyncio
import json
import queue
import sys
import threading
import time
from typing import Optional

import numpy as np

from paddle_tpu.obs import (MetricsRegistry, statset_collector,
                            tracer_collector)
from paddle_tpu.obs.compile_watch import compile_collector, get_compile_watch
from paddle_tpu.obs.flight import flight_collector, get_flight_recorder
from paddle_tpu.obs.hbm import hbm_collector, hbm_snapshot
from paddle_tpu.obs.metrics import (process_counter_collector,
                                    process_counters, split_labels)
from paddle_tpu.obs.slo import SloEvaluator, default_serving_slos
from paddle_tpu.obs.timeseries import (HistorySampler, MetricHistory,
                                       history_collector, history_reply)
from paddle_tpu.obs.trace import annotation, trace_reply
from paddle_tpu.serving import wire
from paddle_tpu.serving.engine import Request, ServingEngine

from paddle_tpu.utils.stat import StatSet


class _ReqState:
    """Server-side lifecycle of one accepted request."""

    __slots__ = ("conn", "cid", "stream", "t_submit", "t_last", "next_idx",
                 "push_to", "prompt")

    def __init__(self, conn, cid, stream):
        self.conn = conn
        self.cid = cid                # the client's id (frame field)
        self.stream = bool(stream)
        # disaggregated prefill (docs/serving.md): a prefill_only request
        # carries the decode replica to kv_push the committed pages to —
        # the done frame is then DELAYED until the push resolves, so the
        # router learns push_ok before it sends the real generate
        self.push_to = None           # {"host", "port"} or None
        self.prompt = None            # np.int32 prompt (prefill_only only)
        self.t_submit = time.monotonic()
        self.t_last = self.t_submit   # last token emission (TTFT base)
        self.next_idx = 0             # next UNSEEN token index — a
                                      # preempted request replays identical
                                      # tokens from 0; indexes below this
                                      # are dropped, not re-streamed


class _Conn(wire.FrameConn):
    """One client connection (asyncio side): the shared slow-reader-
    severing frame connection — hoisted to wire.py so the fleet router's
    client face can never drift from this server's (conn.rids maps client
    id -> engine req_id here).  The replica times the encode + write of
    every WRITE (one frame, or a step's token frames for this connection)
    as `pt.loop.send` on the loop thread (here, not in wire.py, which the
    JAX-free client imports) — for the profiler alone: a span a write
    would wrap the ring within minutes — and, always, into the step
    clock's `serving_loop_send_seconds_total` / `serving_loop_sends_total`:
    two clock reads a write, whatever the write carries."""

    def send(self, msg: dict) -> None:
        t0 = time.perf_counter()
        with annotation("pt.loop.send"):
            self._write(wire.encode(msg))
        _count_send(t0)

    def send_many(self, msgs: list) -> None:
        t0 = time.perf_counter()
        with annotation("pt.loop.send"):
            super().send_many(msgs)
        _count_send(t0)


def _count_send(t0: float) -> None:
    process_counters().add_many({
        "serving_loop_send_seconds_total": time.perf_counter() - t0,
        "serving_loop_sends_total": 1})


#: the step clock's families: they live in the process's counters alone
#: (docs/observability.md "The step clock"); `metrics` and the `stats`
#: frame's `steps` block read them there
STEP_CLOCK_COUNTERS = (
    "serving_pump_seconds_total", "serving_pump_spans_total",
    "serving_step_flight_seconds_total", "serving_steps_landed_total",
    "serving_loop_send_seconds_total", "serving_loop_sends_total")

#: the pump checkpoints the process's counters this often (seconds), so a
#: reader can take any of them over a window (ProcessCounters.between)
CHECKPOINT_EVERY_S = 0.1


def step_clock_stats() -> dict:
    """The `stats` frame's `steps` block: the step clock's counters, by
    family and label value (cumulative, and the PROCESS's: two servers in
    one process share them)."""
    out: dict = {}
    for key, value in process_counters().snapshot().items():
        name, labels = split_labels(key)
        if name not in STEP_CLOCK_COUNTERS:
            continue
        short = name[len("serving_"):-len("_total")]
        value = round(value, 6) if isinstance(value, float) else value
        if labels:
            out.setdefault(short, {})[next(iter(labels.values()))] = value
        else:
            out[short] = value
    return out


def _kv_push_frames(cid, toks, meta: dict, payload: bytes) -> list[bytes]:
    """Split one kv_push blob into encoded BIN frames, each under the
    receiver's MAX_BIN_PAYLOAD bin_cap.  The cap bounds the WHOLE
    declared body (header-length word + JSON header + chunk), and part
    0's header carries the full token list + per-layer meta — for long
    prompts that header alone runs to hundreds of KiB, so the part-0
    chunk is sized from the ENCODED header rather than a fixed headroom
    (a fixed 64 KiB reserve silently busts the cap past ~9k tokens —
    exactly the prompts --disagg-min-prompt selects for).  Raises
    wire.FrameError when even an empty-chunk part 0 would exceed the cap
    (the caller degrades to push_ok:false)."""
    tokens = [int(t) for t in toks]
    probe = {"type": "kv_push", "id": cid, "seq": 0, "last": False,
             "tokens": tokens, "meta": meta}
    h0 = len(json.dumps(probe, separators=(",", ":")).encode("utf-8"))
    # 64 bytes absorb the real header's drift from this probe (the
    # length word, last:true vs false)
    room0 = wire.MAX_BIN_PAYLOAD - h0 - 64
    if room0 < 0:
        raise wire.FrameError(
            f"kv_push part-0 header is {h0} bytes, over the "
            f"{wire.MAX_BIN_PAYLOAD}-byte binary-frame cap")
    # later parts carry a tiny header; 4096 bytes of slack covers it at
    # any seq digit count
    chunk = wire.MAX_BIN_PAYLOAD - 4096
    parts = [payload[:room0]]
    parts += [payload[i:i + chunk]
              for i in range(len(parts[0]), len(payload), chunk)]
    frames = []
    for i, part in enumerate(parts):
        hdr = {"type": "kv_push", "id": cid, "seq": i,
               "last": i == len(parts) - 1}
        if i == 0:
            hdr["tokens"] = tokens
            hdr["meta"] = meta
        frames.append(wire.encode_bin(hdr, part))
    return frames


class ServingServer:
    """TCP front end over one ServingEngine.

    >>> eng = ServingEngine(tr.executor, tr.params, num_slots=4)
    >>> srv = ServingServer(eng, port=0)           # 0 = ephemeral
    >>> host, port = srv.start_background()
    >>> ...                                        # serving/client.py
    >>> srv.stop_background(drain=True)

    `max_queue` bounds requests accepted beyond the engine's slots:
    admission cap = num_slots + max_queue unfinished requests.
    """

    def __init__(self, engine: ServingEngine, host: str = "127.0.0.1",
                 port: int = 0, max_queue: int = 32,
                 postmortem_dir: Optional[str] = None,
                 wedge_threshold_s: float = 30.0, role: str = "both",
                 kv_push_timeout_s: float = 10.0,
                 history_resolution_s: float = 5.0,
                 history_retention_s: float = 1800.0, slo_specs=None):
        assert role in ("prefill", "decode", "both"), role
        if role != "both":
            engine.kv.refuse("role")
        self.engine = engine
        self.host = host
        self.port = port
        self.max_inflight = len(engine.slots) + int(max_queue)
        # disaggregated prefill/decode (docs/serving.md): the replica's
        # advertised placement role — ADVISORY, any replica can serve any
        # request; the router's placement tiers read it off hello
        self.role = role
        self.kv_push_timeout_s = float(kv_push_timeout_s)
        # kv_push accounting (loop thread): outbound pushes attempted /
        # failed; page counts live on the engine/kv counters
        self._kv_pushes = 0
        self._kv_push_failures = 0
        # in-progress inbound multi-part kv_push blobs, keyed by
        # (conn.seq, client id) — dropped wholesale when the conn closes
        self._kv_parts: dict = {}
        # the server exports/dumps the ENGINE's tracer (the process-global
        # one unless the embedder gave the engine its own ring), so the
        # `trace` RPC snapshot, the metrics accounting, and the
        # postmortem spans all describe the same spans
        self.tracer = engine.tracer
        self.stats = StatSet("serving_server")
        # flight recorder (obs/flight.py): lifecycle events always record
        # while a server exists (they are per-request, not per-token);
        # postmortem BUNDLES are written only when a directory is
        # configured — on pump death, on the watchdog-wedge threshold
        # (pump_last_step_age_s > wedge_threshold_s), and on an operator
        # `dump` frame.
        self.flight = get_flight_recorder()
        self.flight.enabled = True
        self.postmortem_dir = postmortem_dir
        self._last_dump_error = "unknown"
        self.wedge_threshold_s = float(wedge_threshold_s)
        self._wedge_dumped = False    # one bundle per wedge episode
        self._last_beat_event = 0.0   # flight beats sampled at ~1/s
        self._last_checkpoint = 0.0   # process counters, every 0.1 s
        self._inflight = 0            # accepted, not finished (loop thread)
        self._draining = False
        # pump heartbeat: (monotonic time, engine step count) written by
        # the pump once per loop iteration — a single tuple rebind, so any
        # thread reads it torn-free.  None until the pump first runs.
        self._pump_beat: Optional[tuple] = None
        self._conns: set = set()      # open connections (loop thread)
        self._routes: dict[str, _ReqState] = {}
        self._cmds: queue.Queue = queue.Queue()
        self._wake = threading.Event()
        # the pump's ordered outbox (pump thread only): everything the
        # pump has for the loop since its last hand-off, as (conn, fn,
        # args) records — fn None = one token frame (args) for conn.
        # _flush_outbox hands the whole list over with ONE
        # call_soon_threadsafe; the pump never waits, stops or dies with
        # records in it.  _tok_lat is the same idea for token_latency:
        # a step's samples go in under one lock, before the hand-off.
        self._outbox: list = []
        self._tok_lat: list = []
        # token delivery accounting (loop thread): frames written and the
        # transport writes that carried them
        self.n_token_frames = 0
        self.n_frame_writes = 0
        self._pump_thread: Optional[threading.Thread] = None
        self._pump_error: Optional[BaseException] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._server: Optional[asyncio.AbstractServer] = None
        self._idle: Optional[asyncio.Event] = None
        self._closed: Optional[asyncio.Event] = None
        self._crashed: Optional[asyncio.Event] = None
        self._watch_task = None       # the loop-side wedge watchdog
        self._bg_thread: Optional[threading.Thread] = None
        engine.on_token = self._on_token
        engine.on_finish = self._on_finish
        # one step in flight (engine.py "ONE STEP IN FLIGHT"): the pump
        # drives step() and looks at nothing it banked, and the loop
        # thread's encoding wants the GIL the pump gives up while it waits
        # for tokens — so the engine may launch step N+1 before it lands
        # step N.  A direct caller of step() keeps the engine's default.
        engine.lookahead = 1
        self._init_metrics()
        # the health plane (docs/observability.md "Health plane"): a
        # bounded time-series ring over the registry, fed by a background
        # sampler thread, with SLO burn-rate alerting riding each
        # sampling pass.  `slo_specs=None` takes the serving defaults;
        # pass () to disable alerting while keeping history.
        self.history = MetricHistory(self.metrics,
                                     resolution_s=history_resolution_s,
                                     retention_s=history_retention_s)
        self.metrics.register_collector(history_collector(self.history))
        self.slo = SloEvaluator(
            self.history,
            default_serving_slos() if slo_specs is None else slo_specs,
            flight=self.flight, registry=self.metrics,
            dump_fn=self._slo_dump)
        self.history_sampler = HistorySampler(self.history,
                                              on_sample=self.slo.evaluate)

    def _init_metrics(self) -> None:
        """The unified registry behind the `metrics` frame.  Rendered on
        the LOOP thread: engine-derived values are advisory stale-ok
        reads of pump-owned state (each individually GIL-atomic; the
        CONSISTENT view is the stats RPC's pump round trip) — that is
        what keeps metrics answerable while the pump is wedged, which is
        the whole point of the watchdog gauges."""
        reg = self.metrics = MetricsRegistry(strict=True)
        self._m_accepted = reg.counter("serving_requests_accepted_total")
        self._m_overload = reg.counter("serving_overload_total")
        # disaggregated prefill/decode: outbound kv_push attempts/failures
        # (loop thread increments, mirrored in self._kv_pushes for stats)
        self._m_kv_pushes = reg.counter("serving_kv_xfer_pushes_total")
        self._m_kv_push_fail = \
            reg.counter("serving_kv_xfer_push_failures_total")
        reg.gauge("serving_inflight").set_fn(lambda: float(self._inflight))
        reg.gauge("serving_max_inflight").set(float(self.max_inflight))
        reg.gauge("serving_draining").set_fn(
            lambda: 1.0 if self._draining else 0.0)
        reg.gauge("pump_alive").set_fn(
            lambda: 1.0 if self.pump_alive() else 0.0)
        reg.gauge("pump_last_step_age_s").set_fn(self.pump_last_step_age)
        eng = self.engine

        def engine_state():
            return [
                ("serving_queue_depth", "gauge", None,
                 float(len(eng.queue))),
                ("serving_slots_in_use", "gauge", None,
                 float(sum(1 for s in eng.slots if s is not None))),
                ("serving_num_slots", "gauge", None, float(len(eng.slots))),
                ("serving_pages_in_use", "gauge", None,
                 float(eng.kv.pages_in_use)),
                ("serving_free_pages", "gauge", None,
                 float(eng.kv.free_page_count)),
                ("serving_num_pages", "gauge", None,
                 float(eng.kv.num_pages)),
                ("serving_decode_steps_total", "counter", None,
                 float(eng.n_decode_steps)),
                ("serving_tokens_generated_total", "counter", None,
                 float(eng.tokens_generated)),
                ("serving_preemptions_total", "counter", None,
                 float(eng.n_preemptions)),
                ("serving_cancelled_total", "counter", None,
                 float(eng.n_cancelled)),
                ("serving_expired_total", "counter", None,
                 float(eng.n_expired)),
                # prefix caching: hit/miss/saved counters plus the
                # private/shared/cached page-accounting split
                ("serving_private_pages_in_use", "gauge", None,
                 float(eng.kv.private_pages_in_use)),
                ("serving_shared_pages_in_use", "gauge", None,
                 float(eng.kv.shared_pages_in_use)),
                ("serving_prefix_cached_pages", "gauge", None,
                 float(eng.kv.cached_page_count)),
                ("serving_prefix_nodes", "gauge", None,
                 float(eng.prefix.n_nodes if eng.prefix else 0)),
                ("serving_prefix_hits_total", "counter", None,
                 float(eng.n_prefix_hits)),
                ("serving_prefix_misses_total", "counter", None,
                 float(eng.n_prefix_misses)),
                ("serving_prefix_tokens_saved_total", "counter", None,
                 float(eng.prefill_tokens_saved)),
                ("serving_prefix_evictions_total", "counter", None,
                 float(eng.prefix.n_evictions if eng.prefix else 0)),
                # the kept eviction frontier: calls, what left the heap
                # (victims over all pops = its hit share), entries held
                ("serving_prefix_evict_calls_total", "counter", None,
                 float(eng.prefix.n_evict_calls if eng.prefix else 0)),
                *(("serving_prefix_frontier_pops_total", "counter",
                   {"outcome": outcome}, float(n))
                  for outcome, n in sorted(
                      (eng.prefix.frontier_pops if eng.prefix
                       else {}).items())),
                ("serving_prefix_frontier_size", "gauge", None,
                 float(eng.prefix.frontier_size if eng.prefix else 0)),
                ("serving_prefix_cow_total", "counter", None,
                 float(eng.kv.n_cow)),
                # KV spill tier: device->host spills, host->device
                # restores, and the host-RAM bytes currently resident
                # (bounded by spill_bytes_budget)
                ("serving_spill_pages_total", "counter", None,
                 float(eng.kv.n_spilled)),
                ("serving_restore_pages_total", "counter", None,
                 float(eng.kv.n_restored)),
                ("serving_spill_bytes", "gauge", None,
                 float(eng.kv.host_bytes)),
                # chunked prefill: mixed-step/chunk counters plus the
                # engine-owned token-budget histograms (step_tokens_hist /
                # decode_gap_hist keep their own locks; their samples()
                # splice straight into the frame)
                ("serving_prefill_chunks_total", "counter", None,
                 float(eng.n_prefill_chunks)),
                # how a step's rows were shared out: prompt rows packed,
                # those given past a slot's prefill_chunk share, and rows
                # of a mixed or verify step that carried nothing
                ("serving_chunk_rows_total", "counter", None,
                 float(eng.n_chunk_rows)),
                ("serving_chunk_extra_rows_total", "counter", None,
                 float(eng.n_chunk_extra_rows)),
                ("serving_step_pad_rows_total", "counter", None,
                 float(eng.n_step_pad_rows)),
                ("serving_mixed_steps_total", "counter", None,
                 float(eng.n_mixed_steps)),
                # one step in flight: steps launched beside a pending
                # one, and rows computed for a request that had ended
                ("serving_lookahead_steps_total", "counter", None,
                 float(eng.n_lookahead_steps)),
                ("serving_lookahead_dropped_rows_total", "counter", None,
                 float(eng.n_lookahead_dropped_rows)),
                # mixture-of-experts: the load of the experts held here
                ("serving_moe_pairs_total", "counter", None,
                 float(eng.moe_pairs_total)),
                ("serving_moe_pairs_max_total", "counter", None,
                 float(eng.moe_pairs_max_sum)),
                ("serving_moe_layer_pairs_max_total", "counter", None,
                 float(eng.moe_layer_pairs_max_sum)),
                ("serving_moe_overflow_tiles_total", "counter", None,
                 float(eng.moe_overflow_tiles)),
                ("serving_moe_steps_total", "counter", None,
                 float(eng.moe_steps)),
                *(("serving_moe_grouped_steps_total", "counter",
                   {"kind": kind}, float(n))
                  for kind, n in sorted(eng.moe_grouped_steps.items())),
                # recurrent layers: rows that advanced a slot state, slot
                # states read and written, steps counted; the state's bytes
                ("serving_recurrent_rows_total", "counter", None,
                 float(eng.recurrent_rows)),
                ("serving_recurrent_slot_updates_total", "counter", None,
                 float(eng.recurrent_slot_updates)),
                ("serving_recurrent_steps_total", "counter", None,
                 float(eng.recurrent_steps)),
                ("serving_slot_state_bytes", "gauge", None,
                 float(eng.kv.slot_state_bytes)),
                ("serving_attn_gated_layers", "gauge", None,
                 float(eng.attn_gated_layers)),
                # window layers held as rings of pages: pages written
                # over, rows sent through them, and by kind the pages that
                # hold live tokens and the pools' bytes
                ("serving_window_pages_recycled_total", "counter", None,
                 float(eng.n_window_pages_recycled)),
                ("serving_window_rows_total", "counter", None,
                 float(eng.n_window_rows)),
                ("serving_window_steps_total", "counter", None,
                 float(eng.n_window_steps)),
                # hyper-connections: rows and calls of the stream pass
                ("serving_mhc_rows_total", "counter", None,
                 float(eng.n_mhc_rows)),
                ("serving_mhc_calls_total", "counter", None,
                 float(eng.n_mhc_calls)),
                ("serving_residual_streams", "gauge", None,
                 float(eng.residual_streams)),
                # query rows the paged kernel's calls carried, and those
                # whose tile walked its slot's blocks once for all its rows
                ("serving_kv_rows_total", "counter", None,
                 float(eng.n_kv_rows)),
                ("serving_kv_shared_rows_total", "counter", None,
                 float(eng.n_kv_shared_rows)),
                # rows the steps ran the vocabulary head on (those sampled)
                ("serving_head_rows_total", "counter", None,
                 float(eng.n_head_rows)),
                ("serving_kv_tokens_attended_total", "counter", None,
                 float(eng.kv_tokens_attended)),
                ("serving_kv_tokens_fetched_total", "counter", None,
                 float(eng.kv_tokens_fetched)),
                *(("serving_kv_pages_resident", "gauge", {"kind": kind},
                   float(n))
                  for kind, n in sorted(eng.kv_pages_resident().items())),
                *(("serving_kv_pool_bytes", "gauge", {"kind": kind},
                   float(n))
                  for kind, n in sorted(
                      eng.kv.pool_bytes_by_kind.items())),
                # tokens the recurrent layers ran as decode rows (`step`)
                # and as prompt chunks' runs (`segment`), one layer's worth
                *(("serving_recurrent_tokens_total", "counter",
                   {"kind": kind}, float(n))
                  for kind, n in sorted(eng.recurrent_tokens.items())),
                ("serving_recurrent_segment_chunks_total", "counter", None,
                 float(eng.recurrent_segment_chunks)),
                # speculative decoding: drafted/accepted counters + the
                # lifetime accept rate (the throughput-multiplier dial)
                ("serving_spec_drafted_total", "counter", None,
                 float(eng.n_spec_drafted)),
                ("serving_spec_accepted_total", "counter", None,
                 float(eng.n_spec_accepted)),
                ("serving_spec_accept_rate", "gauge", None,
                 float(eng.spec_accept_rate)),
                # tensor-parallel sharded decode: shard count + per-device
                # pool residency (the HBM split sharding exists for)
                ("serving_tp_shards", "gauge", None, float(eng.tp)),
                ("serving_kv_pool_bytes_per_shard", "gauge", None,
                 float(eng.kv.pool_bytes_per_shard)),
                # speculative drafting: the drafter's host+device wall
                # per proposal pass and the per-slot chosen depth (the
                # dynamic-k policy's OUTPUT — an operator reads this
                # histogram to see whether the workload sustains depth)
                ("serving_draft_steps_total", "counter", None,
                 float(eng.n_draft_steps)),
                # cross-replica kv transfer: pages serialized to / scattered
                # from the wire, and blob mounts into the prefix tree
                ("serving_kv_xfer_pages_shipped_total", "counter", None,
                 float(eng.kv.n_exported)),
                ("serving_kv_xfer_pages_received_total", "counter", None,
                 float(eng.kv.n_imported)),
                ("serving_kv_xfer_mounts_total", "counter", None,
                 float(eng.n_kv_mounts)),
                # token delivery: frames over the writes that carried
                # them = how far a step's tokens coalesce per connection
                ("serving_token_frames_total", "counter", None,
                 float(self.n_token_frames)),
                ("serving_frame_writes_total", "counter", None,
                 float(self.n_frame_writes)),
            ] + eng.step_tokens_hist.samples() \
              + eng.decode_gap_hist.samples() \
              + eng.draft_ms_hist.samples() \
              + eng.spec_k_hist.samples()

        reg.register_collector(engine_state)
        # families that live in the process's counters alone: the step
        # clock's, and the weight casts of `ServingEngine.params`
        reg.register_collector(process_counter_collector(
            STEP_CLOCK_COUNTERS + ("serving_step_weight_casts_total",
                                   "serving_step_weight_cast_bytes_total")))
        reg.register_collector(statset_collector(
            self.stats, "serving_latency_seconds", "serving_latency_count"))
        reg.register_collector(tracer_collector(self.tracer))
        # deep introspection: per-site jit compile counters (the recompile-
        # storm fuel), device-memory accounting (KV pool / param / live-
        # array bytes, CPU-safe), and flight-recorder ring accounting —
        # all render-time reads, nothing on the token hot path
        reg.register_collector(compile_collector())
        reg.register_collector(hbm_collector(
            params_fn=lambda: eng.params, kv_fn=lambda: eng.kv,
            step_weight_bytes_fn=lambda: eng.step_weight_bytes))
        reg.register_collector(flight_collector(self.flight))

    def pump_alive(self) -> bool:
        """False the moment the pump has fatally errored, even while its
        thread is still unwinding (recording the death, writing the
        bundle): `_pump_error` is written BEFORE the death is announced
        to the loop, so a client that just saw its routes failed must
        never read `pump_alive: true` in the next stats frame."""
        return (self._pump_error is None
                and self._pump_thread is not None
                and self._pump_thread.is_alive())

    def pump_last_step_age(self) -> float:
        """Seconds since the pump last completed a loop iteration; -1.0
        when it has not run yet.  Healthy: < ~0.6s even when idle (the
        idle wait is bounded at 0.5s).  Growing: the engine is wedged
        inside step() — visible here (and in the metrics frame) while
        generate streams merely stall."""
        beat = self._pump_beat
        if beat is None:
            return -1.0
        return time.monotonic() - beat[0]

    # -- lifecycle (asyncio side) -----------------------------------------
    async def start(self, start_pump: bool = True) -> tuple[str, int]:
        """Bind the listener (port 0 = ephemeral; self.port is updated to
        the bound port) and start the engine pump."""
        self._loop = asyncio.get_running_loop()
        self._idle = asyncio.Event()
        self._closed = asyncio.Event()
        self._crashed = asyncio.Event()
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        # the wedge watchdog rides the LOOP thread (it must keep running
        # while the pump is stuck inside step()): past the threshold it
        # records a wedge event and freezes one postmortem bundle
        self._watch_task = self._loop.create_task(self._wedge_watchdog())
        # the health plane's sampler is a daemon thread like the pump: it
        # reads lock-guarded registry state, so it keeps the time-series
        # (and SLO evaluation) running while the pump is wedged
        self.history_sampler.start()
        if start_pump:
            self.start_pump()
        return self.host, self.port

    async def wait_crashed(self) -> None:
        """Resolves when the engine pump dies (tools/serve.py races this
        against its signal wait so a crashed server flushes its trace and
        exits nonzero instead of idling forever)."""
        await self._crashed.wait()

    def start_pump(self) -> None:
        """Start (or no-op if running) the engine pump thread.  Split from
        start() so tests can stage deterministic admission states before
        any scheduling happens."""
        if self._pump_thread is not None and self._pump_thread.is_alive():
            return
        self._pump_thread = threading.Thread(
            target=self._pump, name="serving-engine-pump", daemon=True)
        self._pump_thread.start()

    async def drain(self) -> None:
        """Graceful shutdown: stop admitting (new generates get an
        `overload/reason=draining` response), let every accepted request
        finish (deadlines still fire on schedule), then stop the pump and
        close the listener."""
        self._draining = True
        if self._inflight > 0:
            self._ensure_pump_for_inflight()
            self._idle.clear()
            await self._idle.wait()
        await self._shutdown()

    async def stop(self) -> None:
        """Hard shutdown: cancel everything in flight, then close."""
        self._draining = True
        for rid in list(self._routes):
            self._cmds.put(("cancel", rid))
        self._wake.set()
        if self._inflight > 0:
            self._ensure_pump_for_inflight()
            self._idle.clear()
            await self._idle.wait()
        await self._shutdown()

    def _ensure_pump_for_inflight(self) -> None:
        """Waiting on in-flight work with no pump running would wedge the
        drain forever (start_background(start_pump=False) is a public
        path).  Accepted work is drain's to finish — start the pump; a
        pump that DIED already failed every route via _pump_died_on_loop,
        so don't resurrect it."""
        if self._pump_error is None and (
                self._pump_thread is None or not self._pump_thread.is_alive()):
            self.start_pump()

    async def _shutdown(self) -> None:
        if self._watch_task is not None:
            self._watch_task.cancel()
            self._watch_task = None
        self.history_sampler.stop()
        if self._pump_thread is not None and self._pump_thread.is_alive():
            self._cmds.put(("stop",))
            self._wake.set()
            await asyncio.get_running_loop().run_in_executor(
                None, self._pump_thread.join)
        # TOCTOU sweep, mirroring _pump_died_on_loop: _handle_stats may
        # have seen the pump alive and enqueued AFTER the pump's own
        # stop-drain ran.  We are on the loop thread, so any such put
        # either already happened (visible here) or its _handle_stats
        # runs after this and sees the dead pump (stale fast path).
        try:
            while True:
                cmd = self._cmds.get_nowait()
                if cmd[0] == "stats":
                    self._stats_on_loop(cmd[1], None)
                elif cmd[0] == "kv_import":
                    cmd[2].send({"type": "kv_push", "id": cmd[1]["cid"],
                                 "ok": False, "error": "replica stopping"})
        except queue.Empty:
            pass
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
        # close every live connection EXPLICITLY: a client blocked on a
        # read must see EOF now, not hang until its socket timeout because
        # the loop died with the transport still open
        for conn in list(self._conns):
            conn.dead = True
            try:
                conn.writer.close()
            except (ConnectionError, RuntimeError):
                pass
        self._closed.set()

    async def wait_closed(self) -> None:
        await self._closed.wait()

    # -- lifecycle (thread-facing wrappers) --------------------------------
    def start_background(self, start_pump: bool = True) -> tuple[str, int]:
        """Run the asyncio loop on a daemon thread; returns (host, port)
        once bound.  For embedders and tests — tools/serve.py runs the
        loop in the foreground instead."""
        started = threading.Event()
        addr: list = []

        async def _amain():
            addr.extend(await self.start(start_pump=start_pump))
            started.set()
            await self.wait_closed()

        self._bg_thread = threading.Thread(
            target=lambda: asyncio.run(_amain()),
            name="serving-server-loop", daemon=True)
        self._bg_thread.start()
        if not started.wait(timeout=60):
            raise RuntimeError("serving server failed to bind within 60s")
        return addr[0], addr[1]

    def stop_background(self, drain: bool = True, timeout: float = 120):
        """Drain (or hard-stop) a start_background() server and join its
        loop thread."""
        if self._loop is None:
            return
        fut = asyncio.run_coroutine_threadsafe(
            self.drain() if drain else self.stop(), self._loop)
        fut.result(timeout=timeout)
        if self._bg_thread is not None:
            self._bg_thread.join(timeout=timeout)
        if self._pump_error is not None:
            raise RuntimeError("engine pump died") from self._pump_error

    # -- the engine pump (its own thread; sole owner of the engine) --------
    def _drain_commands(self) -> bool:
        """Apply every queued command between steps (the pump thread is the
        engine's sole owner); True when one of them was "stop"."""
        try:
            while True:
                cmd = self._cmds.get_nowait()
                if cmd[0] == "stop":
                    # commands queued behind "stop" must not be orphaned: a
                    # consistent-stats client is blocking on its reply —
                    # answer it here (we ARE between steps on the pump
                    # thread, so the snapshot is consistent); _shutdown
                    # sweeps anything put after this drain
                    try:
                        while True:
                            cmd = self._cmds.get_nowait()
                            if cmd[0] == "stats":
                                self._post(cmd[1], self._stats_on_loop,
                                           cmd[1], self._engine_stats())
                            elif cmd[0] == "kv_import":
                                self._post(
                                    cmd[2], cmd[2].send,
                                    {"type": "kv_push", "id": cmd[1]["cid"],
                                     "ok": False,
                                     "error": "replica stopping"})
                    except queue.Empty:
                        pass
                    return True
                if cmd[0] == "add":
                    req = cmd[1]
                    try:
                        self.engine.add_request(req)
                    except (ValueError, AssertionError) as e:
                        # validate() ran at admission, so only a race with
                        # a reconfigured engine lands here — still must
                        # answer the client
                        self._post(None, self._fail_on_loop, req.req_id,
                                   str(e))
                elif cmd[0] == "cancel":
                    self.engine.cancel(cmd[1])
                elif cmd[0] == "kv_import":
                    # between steps kv.pools is authoritative (the engine
                    # rebuilds its state pytree from it at every dispatch),
                    # so the mount's scatter is exactly as safe as an
                    # admission-time restore
                    push, conn = cmd[1], cmd[2]
                    try:
                        added = self.engine.import_prefix(
                            push["tokens"], push["meta"],
                            b"".join(push["parts"]))
                        reply = {"type": "kv_push", "id": push["cid"],
                                 "ok": True,
                                 "pages": int(push["meta"]["n_pages"]),
                                 "mounted": int(added)}
                    except (ValueError, AssertionError) as e:
                        reply = {"type": "kv_push", "id": push["cid"],
                                 "ok": False,
                                 "error": f"{type(e).__name__}: {e}"}
                    self._post(conn, conn.send, reply)
                elif cmd[0] == "stats":
                    # between-steps = the consistent view: no
                    # slot/page/queue mutation can interleave
                    self._post(cmd[1], self._stats_on_loop, cmd[1],
                               self._engine_stats())
        except queue.Empty:
            pass
        return False

    def _post(self, conn, fn, *args) -> None:
        """Pump thread: queue `fn(*args)` for the loop thread, in order
        behind everything already in the outbox.  `conn` is the connection
        whose earlier token frames must be on the wire before fn runs
        (None = every connection's)."""
        self._outbox.append((conn, fn, args))

    def _flush_outbox(self) -> None:
        """Pump thread: hand the loop everything banked since the last
        hand-off — ONE call_soon_threadsafe (one Handle, one self-pipe
        write) however many tokens the step banked.  The step's
        token_latency samples go in first, under one lock, so a client
        that has a token can read its latency."""
        if self._tok_lat:
            lat, self._tok_lat = self._tok_lat, []
            self.stats.get("token_latency").add_many(lat)
        if self._outbox:
            batch, self._outbox = self._outbox, []
            self._loop.call_soon_threadsafe(self._deliver_on_loop, batch)

    def _deliver_on_loop(self, batch: list) -> None:
        """Loop thread: one hand-off's records, in order.  Token frames
        gather per CONNECTION (slot order interleaves connections) and go
        out as one write each — when the batch ends, or before a callback
        that concerns their connection runs, so a `done`, an `error` or a
        stats reply never overtakes a token banked before it.  The bytes
        a connection reads are the frames, in the order, that one send a
        token would have produced."""
        pending: dict = {}             # conn -> its token frames, in order
        frames = writes = 0

        def write(conn) -> None:
            nonlocal frames, writes
            msgs = pending.pop(conn)
            conn.send_many(msgs)
            frames += len(msgs)
            writes += 1

        for conn, fn, args in batch:
            if fn is None:
                pending.setdefault(conn, []).append(args)
                continue
            if conn is None:
                for c in list(pending):
                    write(c)
            elif conn in pending:
                write(conn)
            try:
                fn(*args)
            except Exception as e:             # noqa: BLE001 — one record's
                # failure must not strand the rest of the batch (each was
                # its own loop callback once, and failed alone)
                self._loop.call_exception_handler({
                    "message": f"serving delivery {fn!r} failed",
                    "exception": e})
        for c in list(pending):
            write(c)
        if writes:
            self.n_token_frames += frames
            self.n_frame_writes += writes
            process_counters().add_many({
                "serving_token_frames_total": frames,
                "serving_frame_writes_total": writes})

    def _pump(self) -> None:
        """The pump loop.  Its phases are spans on the `pump` lane (names
        `pt.pump.*`, `pt.engine.step`; the engine's own `pt.step.*` nest
        inside the latter): a profiler trace splits the device's idle time
        by what this thread was doing.  The outbox is flushed wherever the
        engine may have banked something: after the command drain (a
        cancel finishes a request there) and after the step — so it is
        empty whenever the pump waits, stops or dies.  Each span also
        feeds the engine's step clock (always on), and every 0.1 s the
        heartbeat checkpoints the process's counters, flushed first, so
        any of them can be read over a window."""
        span = self.tracer.span
        clock = self.engine.step_clock
        commands, step, wait = (clock.sink(name) for name in (
            "pt.pump.commands", "pt.engine.step", "pt.pump.wait"))
        try:
            self._checkpoint()
            while True:
                with span("pt.pump.commands", track="pump", sink=commands):
                    # heartbeat FIRST: written once per loop iteration, so
                    # a wedge anywhere below (a hung compiled step, a stuck
                    # host sync) freezes it and pump_last_step_age_s grows
                    now = time.monotonic()
                    self._pump_beat = (now, self.engine.n_decode_steps)
                    if now - self._last_checkpoint >= CHECKPOINT_EVERY_S:
                        self._last_checkpoint = now
                        self._checkpoint()
                    if now - self._last_beat_event >= 1.0:
                        # SAMPLED into the flight ring (~1/s): a postmortem
                        # shows how recently, and at what step, the pump
                        # was demonstrably alive — without beats evicting
                        # the lifecycle events the ring exists for
                        self._last_beat_event = now
                        self.flight.record(
                            "pump_beat", step=self.engine.n_decode_steps,
                            queue_depth=len(self.engine.queue),
                            inflight=self._inflight)
                    stop = self._drain_commands()
                    if stop:
                        # what the step in flight holds is banked (and
                        # handed over) before the pump is gone
                        self.engine.settle()
                    self._flush_outbox()
                    if stop:
                        break
                with span("pt.engine.step", track="pump", sink=step):
                    busy = self.engine.step()
                    self._flush_outbox()
                if not busy:
                    # idle: nothing queued or in flight — sleep until a
                    # command arrives (bounded wait as a safety net)
                    with span("pt.pump.wait", track="pump", sink=wait):
                        self._wake.wait(timeout=0.5)
                        self._wake.clear()
            self._checkpoint()
        except BaseException as e:                     # noqa: BLE001
            self._pump_error = e
            # the black-box moment: the pump thread is dying with the
            # engine state frozen exactly as the failure left it — record
            # the death and freeze one bundle HERE, before the loop-side
            # cleanup mutates anything (routes, inflight)
            import traceback

            err = f"{type(e).__name__}: {e}"
            try:
                self.flight.record("pump_death", error=err)
                self._write_bundle("pump_death",
                                   error=err + "\n" + traceback.format_exc())
            finally:
                try:
                    # the step in flight is waited for and forgotten: the
                    # mirrors it would bank into may be half-written
                    self.engine.drop_pending()
                except Exception:                  # noqa: BLE001
                    pass
                if self._loop is not None:
                    # what the dying step banked goes out first, then
                    # every route still open is failed
                    self._post(None, self._pump_died_on_loop)
                    self._flush_outbox()

    def _checkpoint(self) -> None:
        """Pump thread: what the step clock holds goes to the process's
        counters, and they are checkpointed (the pump's start, every 0.1 s
        of its heartbeat, its stop)."""
        self.engine.step_clock.flush()
        process_counters().checkpoint()

    def _pump_died_on_loop(self) -> None:
        """A dead pump strands every accepted request — fail them all so
        no client hangs on a stream that will never finish.  Pending
        consistent-stats round trips must answer too (stale): draining
        them HERE, on the loop thread, closes the TOCTOU where
        _handle_stats checks pump health, the pump dies and drains, and
        only then does the command land in the queue — any such late put
        happens on this thread, so it is either already in the queue now
        or its _handle_stats saw _pump_error set (the pump writes it
        before scheduling this callback) and took the stale path."""
        try:
            while True:
                cmd = self._cmds.get_nowait()     # nobody else reads now
                if cmd[0] == "stats":
                    self._stats_on_loop(cmd[1], None)
                elif cmd[0] == "kv_import":
                    cmd[2].send({"type": "kv_push", "id": cmd[1]["cid"],
                                 "ok": False, "error": "engine pump died"})
        except queue.Empty:
            pass
        for rid in list(self._routes):
            self._fail_on_loop(rid, f"engine pump died: "
                                    f"{type(self._pump_error).__name__}: "
                                    f"{self._pump_error}")
        if self._crashed is not None:
            self._crashed.set()

    # -- the flight recorder / postmortem bundles --------------------------
    async def _wedge_watchdog(self) -> None:
        """Loop-side wedge detector: when the pump is ALIVE but its beat
        age crosses `wedge_threshold_s`, record a wedge event and freeze
        one postmortem bundle (engine reads are stale-ok — the pump is
        stuck, not racing).  Re-arms when the beat recovers, so a flapping
        engine produces one bundle per episode, not one per poll."""
        period = max(0.05, min(1.0, self.wedge_threshold_s / 4.0))
        while True:
            await asyncio.sleep(period)
            age = self.pump_last_step_age()
            # pump_alive() is False once _pump_error is set: a DEAD pump
            # already froze its own pump_death bundle — the watchdog must
            # not stack a wedge bundle on top of it
            if self.pump_alive() and age > self.wedge_threshold_s:
                if not self._wedge_dumped:
                    self._wedge_dumped = True
                    self.flight.record("wedge", age_s=round(age, 3),
                                       step=(self._pump_beat or (0, -1))[1])
                    self._write_bundle(
                        "wedge", error=f"pump wedged: last beat "
                                       f"{age:.1f}s ago "
                                       f"(threshold "
                                       f"{self.wedge_threshold_s:g}s)")
            elif age >= 0.0 and age <= self.wedge_threshold_s:
                self._wedge_dumped = False

    def _engine_snapshot(self) -> dict:
        """Engine state for a bundle: per-slot occupancy, queued request
        ids, pool accounting.  Stale-ok reads from whatever thread dumps
        (the pump is dead or wedged in every trigger path); a racing
        mutation degrades one field to an error string, never the dump."""
        eng = self.engine

        def _safe(fn):
            try:
                return fn()
            except Exception as e:             # noqa: BLE001 — see above
                return f"snapshot_error: {type(e).__name__}: {e}"

        return {
            "slots": _safe(lambda: [
                None if sl is None else {
                    "slot": i, "req_id": str(sl.req.req_id),
                    "pos": int(sl.pos), "generated": int(sl.gen),
                    "max_new": int(sl.req.max_new),
                    "replay_until": int(sl.replay_until),
                } for i, sl in enumerate(list(eng.slots))]),
            "queued": _safe(lambda: [str(r.req_id)
                                     for r in list(eng.queue)]),
            "inflight_routes": _safe(lambda: [str(r)
                                              for r in list(self._routes)]),
            "pages_in_use": _safe(lambda: int(eng.kv.pages_in_use)),
            "free_pages": _safe(lambda: int(eng.kv.free_page_count)),
            "num_pages": int(eng.kv.num_pages),
            "page_size": int(eng.kv.page_size),
            "num_slots": len(eng.slots),
            "tp_shards": int(eng.tp),
            "kv_pool_bytes_per_shard": _safe(
                lambda: int(eng.kv.pool_bytes_per_shard)),
            "slot_state_bytes": _safe(
                lambda: int(eng.kv.slot_state_bytes)),
            "layer_specs": _safe(lambda: {
                "paged": {n: list(r) for n, r in eng.kv.layer_specs.items()},
                "slot": {n: {p: list(r) for p, r in parts.items()}
                         for n, parts in eng.kv.slot_specs.items()}}),
            "n_decode_steps": eng.n_decode_steps,
            # a step launched and not yet landed: the slots' pos/generated
            # above are then one step behind the device
            "step_in_flight": eng._pending is not None,
            "tokens_generated": eng.tokens_generated,
            "n_preemptions": eng.n_preemptions,
            "n_cancelled": eng.n_cancelled,
            "n_expired": eng.n_expired,
            "speculation": _safe(lambda: {
                "spec_k": eng.spec_k,
                "drafter": eng.drafter_kind,
                "dynamic": bool(eng.spec_dynamic),
                "draft_steps": eng.n_draft_steps,
                "steps": eng.n_spec_steps,
                "chains": eng.n_spec_chains,
                "drafted": eng.n_spec_drafted,
                "accepted": eng.n_spec_accepted,
                "tokens": eng.n_spec_tokens,
                "accept_rate": round(eng.spec_accept_rate, 4),
                # per-slot dynamic-k state: the learned accept EWMA each
                # live slot steers its draft depth by (null = cold/idle)
                "slot_accept_ewma": [
                    None if sl is None or sl.accept_ewma is None
                    else round(float(sl.accept_ewma), 4)
                    for sl in eng.slots],
            }),
            "prefix_cache": _safe(lambda: {
                "enabled": eng.prefix is not None,
                "nodes": eng.prefix.n_nodes if eng.prefix else 0,
                "cached_pages": int(eng.kv.cached_page_count),
                "shared_pages_in_use": int(eng.kv.shared_pages_in_use),
                "private_pages_in_use": int(eng.kv.private_pages_in_use),
                "hits": eng.n_prefix_hits,
                "misses": eng.n_prefix_misses,
                "tokens_saved": eng.prefill_tokens_saved,
                "evictions": eng.prefix.n_evictions if eng.prefix else 0,
                "evict_calls": (eng.prefix.n_evict_calls
                                if eng.prefix else 0),
                "frontier_pops": (dict(eng.prefix.frontier_pops)
                                  if eng.prefix else {}),
                "frontier_size": (eng.prefix.frontier_size
                                  if eng.prefix else 0),
                "cow": int(eng.kv.n_cow),
                # KV spill tier (docs/serving.md): host-resident pages/
                # bytes + the spill/restore lifecycle counters
                "spill_bytes_budget": int(eng.kv.spill_bytes_budget),
                "host_pages": int(eng.kv.host_page_count),
                "spill_bytes": int(eng.kv.host_bytes),
                "spilled_pages": int(eng.kv.n_spilled),
                "restored_pages": int(eng.kv.n_restored),
                "host_evicted_pages": int(eng.kv.n_host_evicted),
                "restore_hits": eng.n_restore_hits,
                "restore_tokens_saved": eng.restore_tokens_saved,
            }),
            "compile_watch": get_compile_watch().snapshot(),
            "hbm": hbm_snapshot(params=eng.params, kv=eng.kv,
                                step_weight_bytes=eng.step_weight_bytes),
        }

    def _config_snapshot(self) -> dict:
        return {
            "host": self.host, "port": self.port,
            "max_inflight": self.max_inflight,
            "num_slots": len(self.engine.slots),
            "page_size": int(self.engine.kv.page_size),
            "num_pages": int(self.engine.kv.num_pages),
            "capacity_tokens": int(self.engine.kv.capacity_tokens),
            "prefix_cache": self.engine.prefix is not None,
            "spill_bytes_budget": int(self.engine.kv.spill_bytes_budget),
            "tp_shards": int(self.engine.tp),
            "spec_k": int(self.engine.spec_k),
            "spec_dynamic": bool(self.engine.spec_dynamic),
            "drafter": self.engine.drafter_kind,
            "role": self.role,
            "wedge_threshold_s": self.wedge_threshold_s,
            "postmortem_dir": self.postmortem_dir,
        }

    def _slo_dump(self, fired) -> None:
        """The SLO evaluator's episode hook (sampler thread): freeze the
        bundle with the offending series attached while the pump is
        still ALIVE — the proactive counterpart of the wedge dump, same
        stale-ok snapshot paths."""
        names = ",".join(sorted({str(f.get("slo", "?")) for f in fired}))
        self._write_bundle(f"slo:{names}", error=f"slo firing: {names}")

    def _write_bundle(self, reason: str,
                      error: Optional[str] = None) -> Optional[str]:
        """Freeze one postmortem bundle; returns its path, or None when no
        directory is configured or the dump itself failed (a broken dump
        must never mask the failure being documented)."""
        if not self.postmortem_dir:
            return None
        try:
            path = self.flight.dump(
                self.postmortem_dir, reason,
                spans=self.tracer.snapshot(),
                engine=self._engine_snapshot(),
                metrics=self.metrics.snapshot(),
                config=self._config_snapshot(),
                history=self.history.snapshot(),
                error=error)
            print(f"postmortem bundle ({reason}): {path}", file=sys.stderr,
                  flush=True)
            return path
        except Exception as e:                 # noqa: BLE001
            self._last_dump_error = f"{type(e).__name__}: {e}"
            print(f"postmortem dump failed ({reason}): "
                  f"{self._last_dump_error}", file=sys.stderr, flush=True)
            return None

    # -- engine hooks (pump thread) ----------------------------------------
    def _on_token(self, rid: str, tok: int, idx: int) -> None:
        st = self._routes.get(rid)
        if st is None:
            return
        now = time.monotonic()
        if idx >= st.next_idx:                 # fresh, not a preempt replay
            if idx == 0:
                self.stats.get("first_token_latency").add(now - st.t_submit)
            else:
                self._tok_lat.append(now - st.t_last)
            # t_last advances on FRESH tokens only: replayed (deduped)
            # emissions reach no client, so the first post-replay fresh
            # token must charge the whole preempt+re-prefill+replay stall
            # to token_latency — that stall is exactly what the stats
            # RPC's p99 exists to expose
            st.t_last = now
            st.next_idx = idx + 1
            if st.stream:
                self._outbox.append(
                    (st.conn, None, {"type": "token", "id": st.cid,
                                     "token": int(tok), "index": int(idx)}))

    def _on_finish(self, rid: str, toks: np.ndarray, reason: str) -> None:
        # the server owns delivery — keep the engine's archive empty so a
        # long-lived process holds no unbounded result map
        self.engine.results.pop(rid, None)
        self.engine.finish_reasons.pop(rid, None)
        timing = self.engine.finish_timing.pop(rid, None)
        st = self._routes.get(rid)
        if st is None:
            return
        wall = time.monotonic() - st.t_submit
        self.stats.get("request_latency").add(wall)
        if timing is not None:
            # the server-observed request wall time (accept -> finish)
            # rides next to the engine-phase sum: the gap between them is
            # command-queue/pump-pickup latency, and the gap between
            # request_ms and the CLIENT's wall time is the wire + front
            # tier — per-hop attribution with no trace viewer needed
            timing["request_ms"] = round(wall * 1e3, 3)
        if st.push_to is not None and reason in ("stop", "length"):
            # disaggregated prefill: the prompt's pages were just donated
            # (_retire runs _donate before _finish), so the committed
            # prefix is exportable RIGHT HERE on the pump thread; the
            # loop side then ships it and delays the done frame until the
            # push resolves, so the router learns push_ok from `done`.
            # A cancelled/expired prefill finishes NORMALLY — shipping
            # pages nobody will decode would only burn wire and counters.
            export = self.engine.export_prefix(st.prompt)
            self._post(st.conn, self._push_then_finish_on_loop, rid,
                       np.asarray(toks).astype(int).tolist(), reason, timing,
                       export)
            return
        self._post(st.conn, self._finish_on_loop, rid,
                   np.asarray(toks).astype(int).tolist(), reason, timing)

    # -- loop-side completion/error delivery -------------------------------
    def _finish_on_loop(self, rid: str, tokens: list, reason: str,
                        timing: Optional[dict] = None,
                        extra: Optional[dict] = None) -> None:
        st = self._routes.pop(rid, None)
        if st is None:
            return
        st.conn.rids.pop(st.cid, None)
        # accounting settles BEFORE the terminal frame can reach the
        # client: asyncio flushes small writes inside send(), so a client
        # acting on `done` (e.g. polling stats, or a test asserting
        # inflight) must never observe the request still counted
        self._dec_inflight()
        out = {"type": "done", "id": st.cid, "tokens": tokens,
               "reason": reason}
        if timing is not None:
            out["timing"] = timing
        if extra:
            out.update(extra)
        st.conn.send(out)

    # -- the kv_push sender (prefill side, loop thread) --------------------
    def _push_then_finish_on_loop(self, rid: str, tokens: list, reason: str,
                                  timing: Optional[dict],
                                  export) -> None:
        """Ship a finished prefill_only request's committed prefix to its
        decode replica, then deliver the (delayed) done frame carrying
        the push outcome.  Every failure mode — nothing cached, connect
        refused, peer error, timeout — degrades to push_ok:false on the
        done frame; the ROUTER owns the fallback placement."""
        st = self._routes.get(rid)
        if st is None:
            return

        async def _run():
            ok, err, pages, nbytes = False, "nothing cached to ship", 0, 0
            if export is not None:
                xtoks, meta, payload = export
                pages, nbytes = int(meta["n_pages"]), len(payload)
                try:
                    ok, err = await asyncio.wait_for(
                        self._kv_push(st.push_to, st.cid, xtoks, meta,
                                      payload),
                        timeout=self.kv_push_timeout_s)
                except asyncio.TimeoutError:
                    ok, err = False, f"kv_push timed out after " \
                                     f"{self.kv_push_timeout_s:g}s"
                except (OSError, wire.FrameError) as e:
                    # FrameError: the peer closed mid-frame or replied
                    # malformed/over-cap — same degradation as a socket
                    # error, NOT a task-killing exception
                    ok, err = False, f"kv_push failed: {e}"
                except Exception as e:       # noqa: BLE001 — this task is
                    # fire-and-forget: an exception escaping here would
                    # swallow the done frame (the router's prefill leg
                    # hangs with no retry), leak the route, and pin an
                    # inflight slot forever; ANY failure must degrade to
                    # push_ok:false so _finish_on_loop always runs
                    ok, err = False, f"kv_push failed: " \
                                     f"{type(e).__name__}: {e}"
            self._kv_pushes += 1
            self._m_kv_pushes.inc()
            if ok:
                self.flight.record(
                    "kv_ship", pages=pages, bytes=nbytes,
                    dest=f"{st.push_to.get('host')}:"
                         f"{st.push_to.get('port')}")
            else:
                self._kv_push_failures += 1
                self._m_kv_push_fail.inc()
            extra = {"push_ok": ok, "pushed_pages": pages if ok else 0}
            if not ok:
                extra["push_error"] = err
            self._finish_on_loop(rid, tokens, reason, timing, extra=extra)

        self._loop.create_task(_run())

    async def _kv_push(self, push_to: dict, cid, toks, meta: dict,
                       payload: bytes) -> tuple[bool, str]:
        """One outbound kv_push: connect to the decode replica, stream
        the blob as BIN frames chunked under the serving binary-frame cap
        (part 0 carries tokens + meta; the receiver mounts on `last`),
        await the single kv_push reply.  The caller bounds the whole
        exchange with kv_push_timeout_s."""
        frames = _kv_push_frames(cid, toks, meta, payload)
        reader, writer = await asyncio.open_connection(
            str(push_to.get("host")), int(push_to.get("port")))
        try:
            for frame in frames:
                writer.write(frame)
                await writer.drain()
            while True:
                reply = await wire.read_frame(
                    reader, bin_cap=wire.MAX_BIN_PAYLOAD)
                if reply is None:
                    return False, "peer closed during kv_push"
                if reply.get("type") == "kv_push":
                    return (bool(reply.get("ok")),
                            str(reply.get("error", "")))
                if reply.get("type") == "error":
                    return False, str(reply.get("error"))
        finally:
            try:
                writer.close()
            except ConnectionError:
                pass

    def _fail_on_loop(self, rid: str, message: str) -> None:
        st = self._routes.pop(rid, None)
        if st is None:
            return
        st.conn.rids.pop(st.cid, None)
        self._dec_inflight()
        st.conn.send({"type": "error", "id": st.cid, "error": message})

    def _dec_inflight(self) -> None:
        self._inflight -= 1
        if self._inflight == 0 and self._idle is not None:
            self._idle.set()

    # -- connection handling (asyncio side) --------------------------------
    async def _handle(self, reader, writer) -> None:
        conn = _Conn(writer)
        self._conns.add(conn)
        first_frame = True
        try:
            while True:
                try:
                    msg = await wire.read_frame(
                        reader, bin_cap=wire.MAX_BIN_PAYLOAD)
                except wire.FrameError as e:
                    # a malformed FIRST frame is usually a peer speaking the
                    # wrong protocol entirely (an HTTP probe, a bare JSON
                    # line) — name what this socket expects instead of a
                    # bare parse error, so the peer (and the fleet router's
                    # classification path) learns what it reached
                    err = str(e)
                    if first_frame:
                        err += f"; expected the {wire.PROTO_DESC}"
                    conn.send({"type": "error", "error": err})
                    break
                if msg is None:
                    break
                first_frame = False
                try:
                    self._dispatch(conn, msg)
                except Exception as e:         # noqa: BLE001 — protocol
                    # garbage (e.g. an unhashable JSON id) must answer an
                    # error frame, not tear down the connection and every
                    # other request multiplexed on it
                    bad_id = msg.get("id")
                    conn.send({"type": "error",
                               "id": bad_id if isinstance(bad_id, (str, int))
                               else None,
                               "error": f"bad {msg.get('type')!r} frame: "
                                        f"{type(e).__name__}: {e}"})
                try:
                    await writer.drain()
                except ConnectionError:
                    break
        finally:
            conn.dead = True
            self._conns.discard(conn)
            # client went away: everything it still has in flight is a
            # client-initiated cancel — slots and pages must not stay
            # pinned to a dead socket
            for rid in list(conn.rids.values()):
                self._cmds.put(("cancel", rid))
            # half-shipped kv_push blobs die with their connection — the
            # buffered parts must not outlive the peer that was sending
            for key in [k for k in self._kv_parts if k[0] == conn.seq]:
                del self._kv_parts[key]
            self._wake.set()
            try:
                writer.close()
            except ConnectionError:
                pass

    def _dispatch(self, conn: _Conn, msg: dict) -> None:
        t = msg.get("type")
        if t == "generate":
            self._handle_generate(conn, msg)
        elif t == "kv_push":
            self._handle_kv_push(conn, msg)
        elif t == "cancel":
            cid = msg.get("id")
            rid = conn.rids.get(cid) if isinstance(cid, (str, int)) else None
            if rid is not None:
                self._cmds.put(("cancel", rid))
                self._wake.set()
            # unknown/already-finished id: the done frame already answered
        elif t == "stats":
            self._handle_stats(conn, msg)
        elif t == "metrics":
            # answered on the LOOP thread on purpose: the Prometheus view
            # (incl. pump_last_step_age_s) must stay readable while the
            # pump is wedged — engine-derived values are stale-ok reads
            conn.send({"type": "metrics", "text": self.metrics.render(),
                       "content_type": "text/plain; version=0.0.4"})
        elif t == "dump":
            # operator-initiated postmortem: freeze a bundle NOW (loop
            # thread, stale-ok engine reads — works against a wedged or
            # dead pump, which is exactly when an operator wants one)
            self.flight.record("dump_rpc")
            if not self.postmortem_dir:
                conn.send({"type": "error", "id": msg.get("id"),
                           "error": "no postmortem dir configured "
                                    "(ServingServer(postmortem_dir=...) / "
                                    "tools/serve.py --postmortem-dir)"})
                return
            path = self._write_bundle("rpc")
            if path is None:
                # configured but the dump itself failed (disk full, bad
                # permissions, ...) — tell the operator the REAL cause,
                # not "go configure the directory you already configured"
                conn.send({"type": "error", "id": msg.get("id"),
                           "error": f"postmortem dump failed: "
                                    f"{self._last_dump_error}"})
            else:
                conn.send({"type": "dump", "id": msg.get("id"),
                           "path": path,
                           "events": self.flight.recorded,
                           "spans": self.tracer.recorded})
        elif t == "trace":
            # trace collection over the wire (loop thread, stale-ok like
            # `metrics` — snapshot() is safe concurrent with the pump, so
            # this answers even against a wedged engine): the retained
            # span ring plus the process identity a merger needs to put
            # these spans on their own track group, and a perf_counter
            # sample for ping-RTT clock alignment (the span timebase is
            # THIS process's perf_counter epoch).  `enable` flips tracing
            # LIVE (no restart — the operator's "start tracing NOW on the
            # misbehaving replica" move, and the bench overhead probe's
            # same-fleet A/B switch); the flip applies before the
            # snapshot, so enable:false returns the spans it just froze.
            conn.send(trace_reply(self.tracer, msg, "replica",
                                  self.host, self.port))
        elif t == "history":
            # the health plane's time-series pull (loop thread, stale-ok
            # like `metrics`/`trace`: the ring is fed by its own sampler
            # thread and read here under its lock — no pump round trip,
            # no shared lock with any other reply type — so it answers
            # against a wedged pump; staleness shows as last_sample_unix)
            conn.send(history_reply(self.history, msg, "replica",
                                    self.host, self.port))
        elif t == "hello":
            # version/capabilities negotiation: answered on connect so a
            # peer (the fleet router, a ctl, a probing operator) can
            # classify this end before sending work at it.  `page_size`
            # rides along because the router's prefix-affinity index keys
            # on the first page_size-aligned token run — the granularity
            # must match the replica's prefix tree for affinity to pay.
            conn.send(wire.hello_msg(
                "replica",
                server="paddle_tpu-serving",
                capabilities=sorted(["hello", "generate", "cancel", "stats",
                                     "metrics", "dump", "ping", "trace",
                                     "history", "kv_xfer"]),
                role_mode=self.role,
                num_slots=len(self.engine.slots),
                max_inflight=self.max_inflight,
                page_size=int(self.engine.kv.page_size),
                prefix_cache=self.engine.prefix is not None,
                tp_shards=int(self.engine.tp),
                spec_k=int(self.engine.spec_k),
                spec_dynamic=bool(self.engine.spec_dynamic),
                drafter=self.engine.drafter_kind,
                draining=self._draining))
        elif t == "ping":
            conn.send({"type": "pong"})
        else:
            conn.send({"type": "error", "id": msg.get("id"),
                       "error": f"unknown message type {t!r}"})

    def _handle_kv_push(self, conn: _Conn, msg: dict) -> None:
        """Inbound cross-replica KV blob (decode side).  Multi-part BIN
        frames accumulate per (connection, id) — part 0 carries tokens +
        meta and declares the page count, later parts append payload
        bytes, `last` hands the whole blob to the pump for an
        import_prefix mount between steps.  Buffering is bounded twice:
        each accumulation by its DECLARED blob (itself bounded by the
        receiver's own pool size), and the SUM of declared blobs across
        all live accumulations by one pool's worth of bytes — so a peer
        opening many connections (or interleaving many ids) cannot
        buffer multiples of the pool in host RAM.  A sender that
        overruns its declaration, skips part 0, or repeats part 0 for a
        live id is refused immediately — never buffered unboundedly."""
        cid = msg.get("id")
        if not isinstance(cid, (str, int)):
            conn.send({"type": "error", "id": None,
                       "error": "kv_push needs a string or int 'id'"})
            return
        key = (conn.seq, cid)

        def refuse(err: str) -> None:
            self._kv_parts.pop(key, None)
            conn.send({"type": "kv_push", "id": cid, "ok": False,
                       "error": err})

        if self.engine.prefix is None:
            refuse("prefix cache disabled on this replica")
            return
        if self._draining:
            refuse("replica is draining")
            return
        if not self.pump_alive():
            refuse("engine pump is not running")
            return
        payload = msg.get(wire.PAYLOAD_KEY) or b""
        if int(msg.get("seq", 0)) == 0:
            if key in self._kv_parts:
                # a repeated part 0 means the sender's stream is confused
                # — refuse (dropping the half-built blob) rather than
                # silently restarting the accumulation mid-flight
                refuse(f"kv_push part 0 repeated for id {cid!r} while "
                       f"its blob is still accumulating")
                return
            meta = msg.get("meta") or {}
            n = int(meta.get("n_pages", 0))
            if n <= 0 or n >= self.engine.kv.num_pages:
                refuse(f"blob declares {n} pages; this replica's pool "
                       f"holds {self.engine.kv.num_pages}")
                return
            expect = n * self.engine.kv.page_nbytes
            # server-wide budget: total DECLARED bytes across every live
            # accumulation stays under one pool's worth — any single
            # blob fits (it declares < num_pages), so only concurrent
            # pushes that could never all mount anyway are refused
            pending = sum(s["expect"] for s in self._kv_parts.values())
            budget = self.engine.kv.num_pages * self.engine.kv.page_nbytes
            if pending + expect > budget:
                refuse(f"kv_push buffer budget exhausted: {pending} "
                       f"bytes already accumulating, blob declares "
                       f"{expect} more, budget is {budget}")
                return
            self._kv_parts[key] = {
                "cid": cid, "tokens": msg.get("tokens") or [],
                "meta": meta, "parts": [], "bytes": 0,
                "expect": expect}
        st = self._kv_parts.get(key)
        if st is None:
            refuse("kv_push part arrived with no part 0")
            return
        st["parts"].append(payload)
        st["bytes"] += len(payload)
        if st["bytes"] > st["expect"]:
            refuse(f"kv_push accumulated {st['bytes']} bytes, over the "
                   f"{st['expect']}-byte declared blob")
            return
        if msg.get("last"):
            self._kv_parts.pop(key, None)
            self._cmds.put(("kv_import", st, conn))
            self._wake.set()

    def _handle_generate(self, conn: _Conn, msg: dict) -> None:
        cid = msg.get("id")
        if not isinstance(cid, (str, int)):
            # echo whatever id the client sent (it came off the wire, so it
            # is JSON-serializable) — an id-less error frame could never be
            # routed by the client and would stall its collect()
            conn.send({"type": "error", "id": cid,
                       "error": "generate needs a string or int 'id'"})
            return
        if cid in conn.rids:
            conn.send({"type": "error", "id": cid,
                       "error": f"id {cid!r} is already in flight on this "
                                f"connection"})
            return
        if self._pump_error is not None:
            # a dead pump can never serve this — fail fast instead of
            # letting the client block on frames that will never come
            conn.send({"type": "error", "id": cid,
                       "error": f"engine pump died: "
                                f"{type(self._pump_error).__name__}: "
                                f"{self._pump_error}"})
            return
        if self._draining:
            self._m_overload.inc()
            self.flight.record("overload", reason="draining")
            conn.send({"type": "overload", "id": cid, "reason": "draining"})
            return
        if self._inflight >= self.max_inflight:
            # the explicit backpressure contract: never queue unboundedly
            self._m_overload.inc()
            self.flight.record("overload", reason="queue_full",
                               inflight=self._inflight)
            conn.send({"type": "overload", "id": cid, "reason": "queue_full",
                       "inflight": self._inflight,
                       "max_inflight": self.max_inflight})
            return
        if msg.get("prefill_only"):
            # disaggregated prefill: run the prompt through admission
            # (prefill + donation) but generate nothing beyond the one
            # token sampling requires — the router discards it; streaming
            # is forced off so the decode replica's run owns every token
            msg = dict(msg, max_new=1, stream=False)
        try:
            req = self._build_request(conn, cid, msg)
            self.engine.validate(req)
        except (ValueError, AssertionError, TypeError) as e:
            conn.send({"type": "error", "id": cid, "error": str(e)})
            return
        st = _ReqState(conn, cid, msg.get("stream", True))
        if msg.get("prefill_only"):
            push_to = msg.get("push_to")
            st.push_to = push_to if isinstance(push_to, dict) else None
            st.prompt = req.prompt_ids
        self._routes[req.req_id] = st
        conn.rids[cid] = req.req_id
        self._inflight += 1
        self._m_accepted.inc()
        self.flight.record("accept", req=str(req.req_id),
                           inflight=self._inflight)
        self._cmds.put(("add", req))
        self._wake.set()

    def _build_request(self, conn: _Conn, cid, msg: dict) -> Request:
        prompt = np.asarray(msg.get("prompt", []), np.int32)
        rng = None
        if msg.get("seed") is not None:
            import jax

            rng = jax.random.PRNGKey(int(msg["seed"]))
        deadline = None
        if msg.get("timeout_s") is not None:
            # absolute on the ENGINE clock — the deadline sweep in step()
            # compares against engine.clock(), not the server's wall clock
            deadline = self.engine.clock() + float(msg["timeout_s"])
        # distributed-trace context: a router (or a tracing client)
        # stamps {"trace": {"trace_id", "parent"?}} on the generate frame;
        # adopting it here is what joins the engine's lifecycle spans to
        # the sender's trace (wire.get_trace drops malformed contexts —
        # shared with the pserver's send_grad/barrier adoption).
        trace = wire.get_trace(msg)
        # engine req_ids are namespaced per connection so two clients
        # picking "0" can never collide inside the scheduler; the type tag
        # keeps JSON id 1 and id "1" distinct too (conn.rids already does)
        tag = "i" if isinstance(cid, int) else "s"
        return Request(f"c{conn.seq}:{tag}:{cid}", prompt,
                       max_new=int(msg.get("max_new", 32)),
                       temperature=float(msg.get("temperature", 0.0)),
                       top_k=int(msg.get("top_k", 0)),
                       top_p=float(msg.get("top_p", 0.0)),
                       eos_id=int(msg.get("eos_id", -1)),
                       rng=rng, deadline=deadline, trace=trace)

    def _handle_stats(self, conn: _Conn, msg: dict) -> None:
        """Default path: the engine-state half of the snapshot is built
        BETWEEN STEPS on the pump thread (command-queue round trip), so
        `slots_in_use`/`pages_in_use`/`queue_depth` can never tear across
        a step boundary.  `{"stale_ok": true}` (or a pump that is dead /
        never started) answers immediately from the loop thread with
        GIL-atomic-but-unsynchronized reads — the watchdog's fast path,
        which must not block behind a wedged or absent pump."""
        if msg.get("stale_ok") or not self.pump_alive():
            conn.send(self._stats_msg(engine_part=None))
            return
        self._cmds.put(("stats", conn))
        self._wake.set()

    def _stats_on_loop(self, conn: _Conn, engine_part: Optional[dict]):
        conn.send(self._stats_msg(engine_part=engine_part))

    def _engine_stats(self) -> dict:
        """The engine-owned snapshot half.  Mutually consistent ONLY when
        called on the pump thread between steps; the stale fast path
        calls it from the loop thread and labels the result."""
        eng = self.engine
        return {
            "queue_depth": len(eng.queue),
            "slots_in_use": sum(1 for s in eng.slots if s is not None),
            "num_slots": len(eng.slots),
            "pages_in_use": int(eng.kv.pages_in_use),
            "free_pages": int(eng.kv.free_page_count),
            "num_pages": int(eng.kv.num_pages),
            "decode_steps": eng.n_decode_steps,
            "tokens_generated": eng.tokens_generated,
            "preemptions": eng.n_preemptions,
            "cancelled": eng.n_cancelled,
            "expired": eng.n_expired,
            "prefix_hits": eng.n_prefix_hits,
            "prefix_misses": eng.n_prefix_misses,
            "prefix_tokens_saved": eng.prefill_tokens_saved,
            "prefix_cached_pages": int(eng.kv.cached_page_count),
            "prefix_evictions": (eng.prefix.n_evictions
                                 if eng.prefix else 0),
            # host spill tier: pages parked in host RAM + restore traffic
            "spill_pages": int(eng.kv.host_page_count),
            "spill_bytes": int(eng.kv.host_bytes),
            "spilled_pages_total": int(eng.kv.n_spilled),
            "restored_pages_total": int(eng.kv.n_restored),
            "restore_hits": eng.n_restore_hits,
            "restore_tokens_saved": eng.restore_tokens_saved,
            # cross-replica kv transfer (the disagg plane's operator view)
            "kv_pages_shipped": int(eng.kv.n_exported),
            "kv_pages_received": int(eng.kv.n_imported),
            "kv_mounts": eng.n_kv_mounts,
            "prefill_chunk": eng.prefill_chunk,
            "max_step_tokens": eng.max_step_tokens,
            "prefill_chunks": eng.n_prefill_chunks,
            "mixed_steps": eng.n_mixed_steps,
            # one step in flight: the depth, how many steps were launched
            # beside a pending one (over decode_steps: the engaged share),
            # and rows computed for a request that had already ended
            "lookahead": eng.lookahead,
            "lookahead_steps": eng.n_lookahead_steps,
            "lookahead_dropped_rows": eng.n_lookahead_dropped_rows,
            # the paged kernel's reads: tokens its rows attended against
            # tokens it fetched in whole blocks (their ratio = block fill)
            "kv_tokens_attended": eng.kv_tokens_attended,
            "kv_tokens_fetched": eng.kv_tokens_fetched,
            # its query rows, and those that shared a tile's one walk
            "kv_rows": eng.n_kv_rows,
            "kv_shared_rows": eng.n_kv_shared_rows,
            # rows that reached the vocabulary head: the rows steps sample
            "head_rows": eng.n_head_rows,
            # routed pairs the held experts drew (0 without MoE layers)
            "moe_pairs_total": eng.moe_pairs_total,
            "moe_pairs_max_sum": eng.moe_pairs_max_sum,
            # the busiest expert of a step's busiest LAYER, and the tiles
            # the grouped form's overflow loop ran
            "moe_layer_pairs_max_sum": eng.moe_layer_pairs_max_sum,
            "moe_overflow_tiles": eng.moe_overflow_tiles,
            "moe_steps": eng.moe_steps,
            # of those, the steps whose program ran the grouped expert
            # product, by step kind (the rule: parallel/moe.py)
            "moe_grouped_steps": dict(eng.moe_grouped_steps),
            # recurrent layers (0 without them): rows that advanced a slot
            # state, slot states read and written, steps counted
            "recurrent_rows": eng.recurrent_rows,
            "recurrent_slot_updates": eng.recurrent_slot_updates,
            "recurrent_steps": eng.recurrent_steps,
            # of those rows, the decode rows and the prompt chunks' rows
            "recurrent_tokens": dict(eng.recurrent_tokens),
            # chunks of 64 the KDA segment kernel folded (0 without it)
            "recurrent_segment_chunks": eng.recurrent_segment_chunks,
            # speculative decoding: the A/B-able knobs + the counters the
            # accept rate reconciles from, plus the adaptive state
            # (drafter kind, dynamic-k flag, per-slot learned EWMAs)
            "spec_k": eng.spec_k,
            "spec_drafter": eng.drafter_kind,
            "spec_dynamic": bool(eng.spec_dynamic),
            "spec_draft_steps": eng.n_draft_steps,
            "spec_drafted": eng.n_spec_drafted,
            "spec_accepted": eng.n_spec_accepted,
            "spec_accept_rate": round(eng.spec_accept_rate, 4),
            "spec_slot_accept_ewma": [
                None if sl is None or sl.accept_ewma is None
                else round(float(sl.accept_ewma), 4)
                for sl in eng.slots],
            # sharding: model-axis shard count + per-device pool bytes
            "tp_shards": eng.tp,
            "kv_pool_bytes_per_shard": int(eng.kv.pool_bytes_per_shard),
            "slot_state_bytes": int(eng.kv.slot_state_bytes),
            # every pool's bytes, `<layer>.<part>`; attention layers whose
            # result is gated in front of the output projection
            "cache_bytes_by_part": eng.kv.bytes_by_part,
            # the page pools' bytes by kind (full / window rings), the
            # rings' pages a slot, and what the rings recycled
            "kv_pool_bytes_by_kind": eng.kv.pool_bytes_by_kind,
            "ring_pages": dict(eng.kv.ring_specs),
            "window_pages_recycled": eng.n_window_pages_recycled,
            "window_rows": eng.n_window_rows,
            "residual_streams": eng.residual_streams,
            "mhc_rows": eng.n_mhc_rows,
            "mhc_calls": eng.n_mhc_calls,
            "attn_gated_layers": eng.attn_gated_layers,
        }

    def _stats_msg(self, engine_part: Optional[dict]) -> dict:
        # Loop-thread half (admission state, latency percentiles, pump
        # health) merged with the engine half — either the pump-built
        # consistent one, or a fresh stale read (engine_part=None).
        ms = 1e3
        lat = {name: {k: round(v * ms, 3) for k, v in
                      self.stats.percentiles(name, (50.0, 90.0, 99.0)).items()}
               for name in ("request_latency", "first_token_latency",
                            "token_latency")}
        out = {
            "type": "stats",
            "consistent": engine_part is not None,
            "inflight": self._inflight,
            "max_inflight": self.max_inflight,
            "draining": self._draining,
            "role": self.role,
            "kv_pushes": self._kv_pushes,
            "kv_push_failures": self._kv_push_failures,
            # token delivery: frames / the writes that carried them
            "token_frames": self.n_token_frames,
            "frame_writes": self.n_frame_writes,
            # the step clock: seconds and counts of the pump's spans, a
            # step's flight by kind, the loop thread's sends
            "steps": step_clock_stats(),
            "pump_alive": self.pump_alive(),
            "pump_last_step_age_s": round(self.pump_last_step_age(), 3),
            "latency_ms": lat,
        }
        out.update(engine_part if engine_part is not None
                   else self._engine_stats())
        return out
