"""Continuous-batching LM decode engine.

The batch-at-a-time `lm_decode.lm_generate` compiles per (B, P, max_new)
shape and always runs max_new steps; mixed-length production traffic either
pads everything to the worst case or recompiles constantly.  This engine is
the serving answer (the slot configuration studied in arXiv:2605.25645):

  * a fixed set of S decode SLOTS, each holding at most one in-flight
    request; the decode step is ONE jitted function of fixed shape, compiled
    once and reused for the whole workload — freed slots refill mid-flight,
    so the chip never waits for the longest request of a batch;
  * KV context lives in the paged pool (serving/paged_kv.py) behind
    per-slot page tables — HBM proportional to tokens actually held;
  * prompts PREFILL in fixed-size CHUNKS processed INSIDE the regular
    step (the mixed prefill/decode shape of arXiv:2604.15464): decode
    rows and prompt-chunk rows pack into one ragged [max_step_tokens]
    dispatch, so a cold multi-thousand-token prompt no longer stalls
    every decoding slot's inter-token latency behind its own prefill
    program, and the per-step token budget bounds p99 inter-token
    latency by construction.  Chunk count derives from prompt length —
    any prompt the page pool can hold is admissible, no bucket ceiling;
  * per-slot rng streams and sampling knobs are preserved EXACTLY: request
    r's tokens are identical to `lm_generate(..., use_cache=True)` run on r
    alone (same rng key schedule, same sampler semantics via
    serving/sampler.py, same eos early-stop) — the oracle contract
    tests/test_serving.py enforces token-for-token.

Scheduling is a host loop (numpy metadata, device pools): admit from the
FIFO queue into free slots, run one compiled step over all S slots, retire
finished slots, repeat.  A decode or mixed step is two halves — LAUNCH
(plan, pack, dispatch) and LAND (read back, bank, emit, retire) — and a
driver that does not look at what step() banked (the server's pump:
`lookahead` 1) gets step N+1 launched before step N is landed, so the host
works beside the device instead of before it (docs/serving.md "The step
loop").  A slot that cannot get its next page (overcommitted
pool) is PAUSED — excluded from that step's key consumption and token
banking — and resumes bit-identically once a page frees, because its key
schedule is indexed by its own generation counter, not by wall-clock steps.

ENGINE STATE AS A PYTREE: everything the compiled steps read or write is an
explicit, jittable `EngineState` — the per-layer KV page pools, the page
table (with the mixed step's virtual trash row), and the per-slot
pos/last-token/generation/rng-key/sampling-knob arrays, all device-resident
with donated in/out buffers so pools and slot arrays update IN PLACE.  The
steps are pure functions (state, run-mask) -> (state', next-tokens): pos,
gen and last-token advance ON DEVICE for the slots the run mask marks, and
each slot's sampling key is state.keys[s, gen[s]] — so a steady pure-decode
run re-stages NOTHING from the host.  All host-side scheduling (allocator,
prefix tree, preemption, admission) mutates host mirrors that sync to the
pytree only at boundaries: a page-table write bumps `PagedKVCache.version`,
a slot lifecycle event (admit/retire/preempt/abort/restore) sets the
slots-dirty flag, and the run mask re-uploads only when its membership
changes.  `n_host_stages` counts every host->device staging transfer —
tests/test_engine_state.py asserts it stays flat across pure-decode steps.
The same pytree is the serving checkpoint/restore + fleet-migration unit:
`checkpoint_state()` / `restore_state()` freeze and resume an engine
MID-FLIGHT (queued + decoding + mid-chunk slots) bit-exactly.

SPECULATIVE DECODING (`spec_k > 0`): decode is one token per step per slot
— the dispatch rate is the throughput ceiling.  The speculative path lifts
it without changing a single emitted token: a host-side DRAFTER
(serving/drafter.py — prompt-lookup n-grams over the slot's own committed
tokens by default, pluggable for a small draft model) proposes up to k
tokens per decoding slot, and the target model scores ALL k+1 positions
per slot in ONE ragged dispatch (the verify step — the PR 8 packed-row
machinery pointed at the future instead of the prompt).  Draft K/V is
written optimistically; every chain position samples with the slot's OWN
key for that generation index (`keys[s, gen+i]` — sampler.py
`pick_next_chain`), so position i's sample IS the token the sequential
engine would emit there, and acceptance is exact by construction: the
emitted stream is the accepted draft prefix plus the first mismatching
sample — token-for-token identical to spec-off across greedy/top-k/
nucleus/full sampling, prefix hits, chunked mixed steps, preempt/replay
and tensor parallelism (the rejection-sampling equivalence degenerates to
prefix agreement once the randomness is a fixed per-slot key schedule).
Rollback: rejected-suffix K/V on device needs NO cleanup (causally masked
now, overwritten before it could ever be attended); the host returns the
unjustified tail pages via `kv.uncommit_tail` — the same page-granular
rollback preempt/replay already exercises.  Chunk rows coexist with spec
chains under the same token budget (mode-aware packing), and the compiled
set stays bounded: ONE verify signature per budget next to the one decode
+ one mixed signature.  `set_speculation()` is the idle A/B toggle.

TENSOR-PARALLEL DECODE (`mesh=` with a `model` axis of size > 1): attention
heads and the per-layer KV pools partition over the mesh's `model` axis —
w_q/w_k/w_v column-shard, the pools shard on their kv-head axis, w_o
row-shards so the out-projection's partial sums meet in ONE all-reduce per
layer (the Megatron split), and everything else (page tables, slot arrays,
non-attention params, logits, sampling) stays replicated.  The paged
attention core runs under shard_map (ops/attention.py), so the pools are
NEVER all-gathered — each device reads and writes only its head shard
(tools/hlo_shard_check.py proves it on the lowered HLO).  One replica then
serves a model larger than a chip's HBM and decodes with every chip's
FLOPs, still through ONE compiled decode signature.
"""

from __future__ import annotations

import time
from collections import deque
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.graph.context import TEST
from paddle_tpu.graph.layers_moe import expert_form_of
from paddle_tpu.graph.lm_decode import _is_probs, _resolve_io_names
from paddle_tpu.obs.compile_watch import get_compile_watch
from paddle_tpu.obs.flight import get_flight_recorder
from paddle_tpu.obs.metrics import (SpanSeconds, counter_key,
                                    process_counters)
from paddle_tpu.obs.trace import get_tracer
from paddle_tpu.parallel.mesh import MODEL_AXIS, axis_size
from paddle_tpu.parameter.argument import Argument
from paddle_tpu.serving.paged_kv import (RECURRENT_REFUSALS, RING_REFUSALS,
                                         PagedKVCache,
                                         refuse_for_recurrent,
                                         slot_state_specs)
from paddle_tpu.serving.prefix_tree import PrefixTree
from paddle_tpu.serving.sampler import pick_next_chain, pick_next_per_slot

# Dynamic-speculation policy constants (see ServingEngine._dyn_k).
# _EWMA_ALPHA weights the newest chain's accept rate into the slot's
# running estimate — 0.25 adapts within ~4 chains without thrashing on a
# single unlucky draft.  _PROBE_EVERY paces the k=1 re-probe of a slot
# whose depth decayed to 0: often enough to notice a workload turning
# repetitive, rare enough that a hostile workload pays ~1/16th of a
# wasted verify row per window.
_EWMA_ALPHA = 0.25
_PROBE_EVERY = 16

_NO_UNCHUNKED = ("prefill_chunk=None (whole-prompt prefill) no longer "
                 "exists: chunked prefill is the only admission path — "
                 "pass a positive chunk size")


class EngineState(NamedTuple):
    """The decode/mixed steps' ENTIRE device state — one jittable pytree.

    Donated into every compiled step and rebound from its output, so pools
    and slot arrays update in place (no copies, no stale aliases).  Under
    tensor parallelism the pools shard on their kv-head axis over the mesh
    `model` axis; every other leaf is replicated."""

    pools: dict      # {layer: {"k"/"v": [num_pages, page_size, h_kv, dh]}}
    table: jax.Array  # [S+1, pages_per_slot] int32 — row S is the mixed
                      # step's virtual all-trash row (always zeros)
    pos: jax.Array    # [S] int32 tokens resident in the paged cache
    toks: jax.Array   # [S] int32 last emitted token (decode-step input)
    gen: jax.Array    # [S] int32 tokens emitted — indexes `keys`
    keys: jax.Array   # [S, capacity_tokens, 2] uint32 per-slot key schedule
    temp: jax.Array   # [S] float32 sampling temperature
    topk: jax.Array   # [S] int32
    topp: jax.Array   # [S] float32


class Request:
    """One generation request — the per-row knobs of `lm_generate`."""

    def __init__(self, req_id, prompt_ids, max_new: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 0.0, eos_id: int = -1, rng=None,
                 deadline: Optional[float] = None,
                 trace: Optional[dict] = None):
        self.req_id = req_id
        # inbound distributed-trace context ({"trace_id": ..., "parent":
        # ...}, normally stamped by the fleet router at ingress): the
        # engine's lifecycle spans carry it as attrs, so one trace_id
        # threads the request through every process it crossed
        self.trace = dict(trace) if trace else None
        self.prompt_ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_id = int(eos_id)
        # absolute time on the ENGINE's clock (engine.clock(), default
        # time.monotonic) after which the request is expired — swept at the
        # top of every step(), whether the request is queued or in flight
        self.deadline = None if deadline is None else float(deadline)
        # tokens this request had generated when it was preempted back
        # into the queue: a re-admission replays them identically (and
        # KEEPS the stash — see _admit), and a cancel/deadline that lands
        # while it waits or mid-replay must report at least them — the
        # front end already streamed them to the client
        self._preempted_gen: Optional[list] = None
        # default PRNGKey(0) — the same default lm_generate uses, so the
        # parity oracle needs no special-casing
        self.rng = jax.random.PRNGKey(0) if rng is None else rng
        if self.prompt_ids.size < 1:
            # ValueError, not assert: requests arrive off the NETWORK
            # (serving/server.py) and `python -O` strips asserts — an
            # empty prompt must never reach the pump
            raise ValueError(f"request {req_id!r}: empty prompt")
        if self.temperature <= 0.0 and (self.top_k > 0 or
                                        0.0 < self.top_p < 1.0):
            raise ValueError(
                f"top_k={self.top_k}/top_p={self.top_p} need temperature "
                f"> 0 — temperature=0 means greedy argmax, which would "
                f"silently ignore them")


class _Slot:
    """Host-side state of one occupied decode slot.

    Two modes, distinguished by `gen`: `gen == 0` is PREFILL mode — the
    slot is still committing its prompt chunk-by-chunk through the mixed
    step (`pos` = prompt tokens committed so far, nothing emitted yet);
    `gen >= 1` is DECODE mode — token 0 was sampled from the last prompt
    position's logits and the slot advances one token per step."""

    __slots__ = ("req", "keys", "pos", "gen", "last_tok", "generated",
                 "admit_seq", "replay_until", "accept_ewma", "probe_tick")

    def __init__(self, req: Request, keys: np.ndarray, pos: int,
                 admit_seq: int):
        self.req = req
        self.keys = keys          # [max_new, 2] uint32 — key g samples token g
        self.pos = pos            # tokens resident in the paged cache
        self.gen = 0              # tokens emitted so far (0 = prefill mode)
        self.last_tok = -1        # emitted but not yet in the cache
        self.generated = []
        self.admit_seq = admit_seq  # admission order — preemption victims
                                    # are youngest-first (least work lost)
        # tokens below this generation index are a post-preemption REPLAY
        # of already-emitted output (deduped downstream) — the lifecycle
        # trace shows them as a `replay` span, flipping to `decode` at the
        # first genuinely fresh token.  0 = never preempted / caught up.
        self.replay_until = 0
        # dynamic speculation (spec_dynamic=True): EWMA of this slot's
        # per-chain accept fraction (None = cold, no chain verified yet)
        # steers the per-slot draft depth k_s; probe_tick paces the k=1
        # re-probes a decayed-to-0 slot still gets, so a workload that
        # turns repetitive mid-request can climb back out of plain decode
        self.accept_ewma: Optional[float] = None
        self.probe_tick = 0


#: a landed step's two counters, by its kind
_FLIGHT_COUNTERS = {
    kind: (counter_key("serving_step_flight_seconds_total", kind=kind),
           counter_key("serving_steps_landed_total", kind=kind))
    for kind in ("decode", "mixed", "spec")}


def _is_abstract(params: dict) -> bool:
    """A parameter tree of shapes, not arrays (tools/serve.py `--weights
    deferred`)."""
    return any(isinstance(v, jax.ShapeDtypeStruct) for v in params.values())


class _Pending:
    """One compiled decode or mixed step between its LAUNCH and its LAND:
    the device array of sampled tokens (the counts of `_with_counts`
    behind them) and what the host needs to bank them without looking at
    anything the next launch may have moved — per slot the `_Slot` that
    owned it at launch, the decode rows, the chunk runs, and how far the
    step advances each slot's pos and gen (`ServingEngine._cursor` adds
    them to the banked cursors while the step is in flight) — and the
    step's clock: its kind, its number and when its launch span began, so
    the land can say how long the step was in flight."""

    __slots__ = ("nxt", "owners", "rows", "advanced", "adv", "emit",
                 "kind", "step", "t_launch")

    def __init__(self, nxt, owners, rows, advanced, adv, emit, kind, step,
                 t_launch):
        self.kind = kind            # "decode" | "mixed"
        self.step = step            # n_decode_steps once launched
        self.t_launch = t_launch    # its pt.step.<kind> span's own start
        self.nxt = nxt              # device [S (+ counts)] int32
        self.owners = owners        # [S] the _Slot in each slot at launch
        self.rows = rows            # slots that ran a decode row
        self.advanced = advanced    # (slot, n_rows, final) per chunk run
        self.adv = adv.tolist()     # [S] tokens each slot commits
        self.emit = emit.tolist()   # [S] a sampled token is banked


class ServingEngine:
    """Slot scheduler + paged KV + one compiled decode step.

    >>> eng = ServingEngine(tr.executor, tr.params, num_slots=4)
    >>> eng.add_request(Request("a", prompt, max_new=16, eos_id=2))
    >>> results = eng.run()          # {"a": np.int32 prompt+generated}
    """

    def __init__(self, executor, params, num_slots: int = 4,
                 page_size: int = 16, max_context: int = 256,
                 num_pages: Optional[int] = None,
                 input_name: Optional[str] = None,
                 logits_name: Optional[str] = None,
                 prefix_cache: bool = True,
                 spill_bytes_budget: int = 0,
                 prefill_chunk: int = -1,
                 max_step_tokens: Optional[int] = None,
                 spec_k: int = 0, drafter=None,
                 spec_dynamic: bool = False,
                 mesh=None, tracer=None):
        self.executor = executor
        self.input_name, self.logits_name = _resolve_io_names(
            executor.model, input_name, logits_name)
        self._probs = _is_probs(executor.model, self.logits_name)
        # the head's ops carry this scope in every step program's trace
        self._head_scope = {self.logits_name: "lm.head"}
        # tensor parallelism: a mesh whose `model` axis exceeds 1 shards
        # attention heads + KV pools over it (docs/serving.md "Sharded
        # decode").  The executor must see the same mesh — layers_attn
        # routes the paged attention core through shard_map off ctx.mesh.
        self.mesh = mesh if mesh is not None else getattr(executor, "mesh",
                                                          None)
        self.tp = axis_size(self.mesh, MODEL_AXIS)
        self._repl_sharding = None
        self._param_shardings_tree = None
        self._tp_ffn_pairs: list = []
        self._tp_lm_head: Optional[str] = None
        if self.tp > 1:
            self._validate_tp(executor.model)
            if executor.mesh is not None and executor.mesh is not self.mesh:
                raise ValueError(
                    "ServingEngine(mesh=...) conflicts with the executor's "
                    "own mesh — build the executor meshless (or with the "
                    "same mesh) for tensor-parallel serving")
            executor.mesh = self.mesh
            from jax.sharding import NamedSharding, PartitionSpec
            self._repl_sharding = NamedSharding(self.mesh, PartitionSpec())
            # params placed ONCE: attention projections sharded (w_q/w_k/
            # w_v by column = head, w_o by row), everything else
            # replicated — the tree is reused verbatim as the compiled
            # steps' in_shardings, so placement and jit can never diverge
            self._param_shardings_tree = self._tp_param_shardings(params)
            if _is_abstract(params):
                raise ValueError(
                    "an engine built around abstract parameters (tools/"
                    "serve.py --weights deferred) places no weights: "
                    "--mesh model=N needs them when it is built")
            params = jax.device_put(params, self._param_shardings_tree)
        self.params = params        # the property: derives the steps' tree
        pages_per_slot = -(-int(max_context) // int(page_size))
        # the most rows one slot can get in one step (`set_chunking`'s own
        # budget, by its own rule where none is given): what a window
        # layer's ring of pages must hold beside its window
        if prefill_chunk == -1:
            prefill_chunk = 4 * int(page_size)
        step_tokens = int(max_step_tokens) if max_step_tokens is not None \
            else self._default_budget(
                min(int(prefill_chunk or 0), pages_per_slot * int(page_size)),
                num_slots, spec_k)
        self.kv = PagedKVCache(executor, num_slots, page_size,
                               pages_per_slot, num_pages,
                               mesh=self.mesh if self.tp > 1 else None,
                               spill_bytes_budget=spill_bytes_budget,
                               step_tokens=step_tokens)
        # RECURRENT LAYERS (graph/layers_kda.py): their context is a state
        # a slot in the cache manager's slot-indexed parts, with no
        # snapshot at a page boundary.  Everything that assumes the pages
        # ARE the context is refused by name, each at one place
        # (paged_kv.RECURRENT_REFUSALS: the mesh in _validate_tp above, the
        # spill budget by the cache itself)
        self._recurrent = list(self.kv.slot_specs)
        # attention layers whose result passes a sigmoid gate in front of
        # the output projection (dsl `out_gate`): a gauge, for a reader
        # that holds a configuration's layer table to the program
        self.attn_gated_layers = sum(
            1 for l in executor.model.layers
            if l.attrs.get("out_gate") is not None)
        # WINDOW LAYERS held as rings of pages (paged_kv "WINDOW LAYERS"):
        # their tables, constants of the compiled steps; and like a
        # recurrent state a ring is not the whole context
        self._ring_tables = {name: self.kv.ring_table(name)
                             for name in self.kv.ring_specs}
        self.n_window_pages_recycled = 0
        self.n_window_rows = 0
        self.n_window_steps = 0
        # HYPER-CONNECTIONS (graph/layers_hc.py): the sublayers whose
        # residual path is a stream pass (`mhc_mix`), and the streams they
        # mix — 0 and 1 for a model whose blocks add into one stream
        self._mhc_writes = [l for l in executor.model.layers
                            if l.type == "hyper_write"]
        self.residual_streams = max(
            [int(l.attrs["streams"]) for l in self._mhc_writes] or [1])
        self.n_mhc_rows = 0
        self.n_mhc_calls = 0
        if prefix_cache and (self._recurrent or self._ring_tables):
            import logging
            what, why = (
                (f"{len(self._recurrent)} recurrent layers",
                 RECURRENT_REFUSALS["prefix"][1]) if self._recurrent else
                (f"{len(self._ring_tables)} window layers held as rings",
                 RING_REFUSALS["prefix"]))
            logging.getLogger(__name__).info(
                "model has %s: the prefix index is off (%s) — every "
                "admission prefills from position 0", what, why)
            prefix_cache = False
        # the ONE canonical pool sharding, derived by the cache that owns
        # the pools — every jit that hands pools back pins to it
        self._pool_sharding = self.kv.pool_sharding
        # prefix caching (serving/prefix_tree.py): retired requests donate
        # their fully-committed pages to a radix index keyed on token-id
        # runs; admission walks it and prefills ONLY the uncached suffix.
        # Sharing is entirely host-side allocator/table state — the decode
        # step's one compiled signature is untouched.  The tree's LRU
        # eviction is the allocator's page-pressure hook, so cached
        # prefixes are reclaimed BEFORE slots pause or preempt.
        self.prefix: Optional[PrefixTree] = \
            PrefixTree(self.kv) if prefix_cache else None
        if self.prefix is not None:
            self.kv.on_page_pressure = self._evict_for
        self.n_prefix_hits = 0
        self.n_prefix_misses = 0
        self.prefill_tokens_saved = 0
        # KV spill tier admission accounting (the page-level counters —
        # n_spilled/n_restored/host_bytes — live on the kv allocator):
        # hits whose prefix needed a host->device restore, and the
        # prefill tokens among `C` served from restored pages — the
        # number kv.n_restored * page_size must bound
        self.n_restore_hits = 0
        self.restore_tokens_saved = 0
        # cross-replica kv transfer plane (docs/serving.md "Disaggregated
        # prefill/decode"): mounts = import_prefix calls that attached at
        # least one run; pages count what came over the wire (the
        # byte-level n_exported/n_imported live on the kv allocator)
        self.n_kv_mounts = 0
        self.kv_pages_mounted = 0
        self.queue: deque[Request] = deque()
        self.slots: list[Optional[_Slot]] = [None] * num_slots
        # finished-but-uncollected outputs: run() POPS what completed on
        # its watch, so a long-lived engine does not accumulate results
        self.results: dict = {}
        # req_id -> why it finished: "stop" (eos) / "length" (max_new) /
        # "cancelled" / "deadline" — popped alongside results in run()
        self.finish_reasons: dict = {}
        # request-lifecycle hooks for a front end driving step() directly
        # (serving/server.py): on_token(req_id, token, index) fires for
        # every emitted token (index 0 = the prefill-sampled token),
        # on_finish(req_id, tokens, reason) once per request.  Both run on
        # the thread calling step() — keep them cheap.  A preempted request
        # REPLAYS its (identical) tokens from index 0 on re-admission:
        # streaming consumers must dedup by index (server.py does).
        self.on_token = None
        self.on_finish = None
        # the deadline clock — injectable so tests can expire requests
        # deterministically (e.g. clock = lambda: engine.n_decode_steps)
        self.clock = time.monotonic
        # request-lifecycle tracing (paddle_tpu/obs): spans are recorded
        # ONLY while tracer.enabled — every emission site checks first, so
        # the disabled cost is one attribute read.  All spans record on
        # the step()-driving thread (the pump), matching the tracer's
        # single-writer ring contract.  `tracer=` lets an embedder (or an
        # in-process test fleet) give each engine its own ring, so a
        # per-process `trace` RPC snapshot stays per-process.
        self.tracer = tracer if tracer is not None else get_tracer()
        self._obs_open: dict = {}   # req_id -> open span handle (one phase
                                    # open per request at any moment)
        self._plan_span = None      # the running step's open pt.step.plan
        # the step clock (docs/observability.md "The step clock"): every
        # span of the pump thread — this engine's, and the server's around
        # them — hands its seconds to `step_clock` through the span's own
        # sink, ring and profiler on or off; step() flushes it to the
        # process's counters, once
        self.step_clock = SpanSeconds("serving_pump_seconds_total",
                                      "serving_pump_spans_total")
        self._req_trace: dict = {}  # req_id -> inbound trace context
        # per-request latency attribution (ALWAYS on — the phase
        # transitions below are a handful of clock reads per request
        # LIFECYCLE, never per token, so there is no flag to forget):
        # _req_phase holds the open phase, _req_attr the per-phase wall
        # accumulators + occurrence counters; _finish folds them into
        # finish_timing[req_id] — the `done` frame's `timing` breakdown
        # (docs/serving.md), popped by the server/run() like results.
        self._req_phase: dict = {}
        self._req_attr: dict = {}
        self.finish_timing: dict = {}
        # black box (obs/flight.py): request-lifecycle transitions recorded
        # when the front end (or a test) enables the process-global
        # recorder — events are per-request, never per-token, so the
        # disabled AND enabled costs both stay off the token hot path
        self.flight = get_flight_recorder()
        self.n_decode_steps = 0
        self.n_preemptions = 0
        self.n_cancelled = 0
        self.n_expired = 0
        self.tokens_generated = 0
        self.occupancy_sum = 0.0              # sum of live/S over steps
        # what the paged kernel reads, summed over compiled steps (one
        # layer's worth a step): the KV tokens its rows attend, and the
        # tokens it fetches for them in whole blocks (ops/pallas_paged.py
        # block_tokens), a block once a RUN of rows — a tile's (tile_rows)
        # consecutive rows that are one slot's.  attended / fetched is the
        # block fill; kv_shared_rows of kv_rows rode such a shared walk.
        self.kv_tokens_attended = 0
        self.kv_tokens_fetched = 0
        self.n_kv_rows = 0
        self.n_kv_shared_rows = 0
        # rows compiled steps ran the vocabulary head on: the rows they
        # sample (over n_kv_rows: the share of a step's rows that reach it)
        self.n_head_rows = 0
        self._admit_seq = 0
        # ONE STEP IN FLIGHT (docs/serving.md "The step loop"): a decode
        # or mixed step is two halves, LAUNCH (plan, pack, dispatch) and
        # LAND (read back, bank, emit, retire).  `lookahead` is how many
        # compiled steps step() may leave in flight when it returns: 0
        # (the default — a direct caller looks at what step() banked)
        # lands each step where it was launched; 1 (set by ServingServer,
        # which owns the pump and the loop thread that make the overlap
        # worth having) launches step N+1 BEFORE it lands step N, so the
        # host's emit/admit/plan run beside the device instead of before
        # it.  `_pending` is the step in flight.
        self.lookahead = 0
        self._pending: Optional[_Pending] = None
        self._landing = False       # inside _land: settle() is a no-op
        self.n_lookahead_steps = 0  # steps launched with one in flight
        self.n_lookahead_dropped_rows = 0   # rows of an in-flight step
                                    # whose request had ended by its land
        # -- device-resident EngineState + its host sync machinery --------
        # The compiled steps advance pos/gen/toks on device, so the hot
        # path re-stages NOTHING: the page table re-uploads only when a
        # host-side table write bumps kv.version, the per-slot arrays only
        # when a slot lifecycle event sets _slots_dirty, and the run mask
        # only when its membership changes.  n_host_stages counts every
        # host->device transfer (the test_engine_state.py regression).
        self.n_host_stages = 0
        S = num_slots
        self._kk = self.kv.capacity_tokens     # keys per slot (> max_new)
        from paddle_tpu.ops.pallas_paged import block_tokens, query_tile
        # the kernel's shapes at the first layer under the logical table (a
        # ring's rows each read a table row of their own: no tile is shared)
        paged = [l for l in executor.model.layers
                 if l.name in self.kv.layer_specs]
        if paged:
            layer = min(paged, key=lambda l: l.name in self.kv.ring_specs)
            pool = next(iter(self.kv.pools[layer.name].values()))
            # a latent pool's row is one [W] vector: one KV "head" of
            # width W; a page's rows over its tokens, however it folds them
            h_kv = pool.shape[1] * pool.shape[2] // self.kv.page_size \
                if pool.ndim == 4 else 1
            h_kv //= self.kv.tp_shards
            self._kv_block = block_tokens(
                self.kv.page_size, h_kv, pool.shape[-1],
                pool.dtype.itemsize, self.kv.pages_per_slot)
            # what `tile_rows` takes after the call's rows
            heads = int(layer.attrs["num_heads"])
            kv_heads = int(layer.attrs.get("num_kv_heads", 0) or heads) \
                if pool.ndim == 4 else 1
            self._kv_tile = query_tile(
                heads // self.kv.tp_shards,
                max(kv_heads // self.kv.tp_shards, 1),
                (h_kv, pool.shape[-1]), self._kv_block, pool.dtype)
        else:       # no page-indexed part: no kernel fetches any block
            self._kv_block = self.kv.page_size
            self._kv_tile = None
        # routed-pair counters of the held experts (docs/observability.md):
        # the steps return, behind the tokens they already read back, the
        # pairs each held expert drew, summed over the MoE layers
        self._moe_layers = [l.name for l in executor.model.layers
                            if l.type == "moe"]
        self.moe_pairs_total = 0       # routed pairs the held experts drew
        self.moe_pairs_max_sum = 0     # sum over steps of the busiest's
        self.moe_overflow_tiles = 0    # tiles the grouped overflow ran
        self.moe_layer_pairs_max_sum = 0   # ... of the busiest of a LAYER
        self.moe_steps = 0             # steps counted
        # of those, the steps whose program runs the expert block's grouped
        # form (parallel/moe.py: the rule answers from a step's rows when
        # its program is traced), by step kind
        self.moe_grouped_steps: dict[str, int] = {}
        self._moe_grouped_at: dict[int, bool] = {}     # rows -> grouped
        # recurrent-state counters, returned behind the tokens (and the
        # MoE pairs) the same way: rows that advanced a state, slot states
        # read and written summed over the recurrent layers, steps counted
        self.recurrent_rows = 0
        self.recurrent_slot_updates = 0
        self.recurrent_steps = 0
        # tokens the recurrent layers ran, one layer's worth a step, by the
        # call that ran them: `step` one a decode row, `segment` a prompt
        # chunk's consecutive rows (counted on the host, where the step is
        # packed)
        self.recurrent_tokens = {"step": 0, "segment": 0}
        # chunks the KDA layers' segment kernel folded (`kda_seg`: a run's
        # cdiv(rows, 64)), one layer's worth a step; stays 0 where no KDA
        # layer's chunk rows go through the kernel
        self.recurrent_segment_chunks = 0
        from paddle_tpu.graph.slot_steps import use_step_kernel
        self._kda_seg = any(
            l.type == "kda_attention" and use_step_kernel(l)
            for l in executor.model.layers)
        self._kv_synced = -1                   # kv.version last uploaded
        self._slots_dirty = True
        self._run_host: Optional[np.ndarray] = None
        self._d_run = None
        self._d_table = self._d_pos = self._d_toks = self._d_gen = None
        self._d_keys = self._d_temp = self._d_topk = self._d_topp = None
        # every engine jit reports to the compile watcher (obs/
        # compile_watch.py): the decode step must stay at ONE signature,
        # the mixed step at one per max_step_tokens value
        dec_jit = jax.jit(self._decode_impl, donate_argnums=(1,),
                          **self._step_sharding_kwargs(n_extra=1))
        self._decode_step = get_compile_watch().wrap_jit(
            "serving.decode_step", dec_jit)
        # CHUNKED PREFILL (the module docstring's mixed step; rows through
        # ops/attention.py:ragged_paged_attention_step).  Compiled
        # signatures: the [S,1] decode step (pure-decode steps keep it) +
        # ONE mixed-step signature per max_step_tokens value.
        # prefill_chunk=-1 (the default) picks 4*page_size.
        mix_jit = jax.jit(self._mixed_impl, donate_argnums=(1,),
                          **self._step_sharding_kwargs(n_extra=6))
        self._mixed_step = get_compile_watch().wrap_jit(
            "serving.mixed_step", mix_jit)
        self.set_chunking(prefill_chunk, max_step_tokens)
        self.n_prefill_chunks = 0
        # prompt rows packed, those of them given past a slot's share, and
        # rows of a mixed or verify step that carried nothing
        self.n_chunk_rows = 0
        self.n_chunk_extra_rows = 0
        self.n_step_pad_rows = 0
        self.n_mixed_steps = 0
        # SPECULATIVE DECODING (the verify step): ONE extra compiled
        # signature per (token budget, spec_k) — created lazily like the
        # others, compiled only when speculation is actually on.  The
        # drafter runs on the host between steps; the verify step scores
        # every slot's k+1-position chain (plus any prefill chunk rows)
        # in one ragged dispatch and computes acceptance ON DEVICE, so
        # pos/gen advance by the accepted length without a host round
        # trip inside the step.
        spec_jit = jax.jit(self._spec_impl, donate_argnums=(1,),
                           **self._step_sharding_kwargs(n_extra=9,
                                                        n_out=2))
        self._spec_step = get_compile_watch().wrap_jit(
            "serving.spec_step", spec_jit)
        self.spec_k = 0
        self.drafter = None
        self.spec_dynamic = False
        self._drafter_takes_eos = False
        self.n_spec_steps = 0       # verify dispatches run
        self.n_spec_chains = 0      # (slot, step) chains that emitted
        self.n_spec_drafted = 0     # draft tokens scored by the target
        self.n_spec_accepted = 0    # draft tokens that matched exactly
        self.n_spec_tokens = 0      # tokens banked through chains —
                                    # == accepted + chains unless an eos
                                    # truncated a chain (reconciliation)
        self.n_draft_steps = 0      # draft passes that proposed anything
        self.set_speculation(spec_k, drafter, dynamic=spec_dynamic)
        # token-budget observability: per-step scheduled-token histogram
        # and the pump-step gap decoding slots actually saw (ms) — the
        # HOL-blocking number chunking exists to bound.  Standalone
        # Histogram objects (obs/metrics.py shape); the server's engine
        # collector splices their samples into the metrics frame.
        from paddle_tpu.obs.metrics import Histogram as _Hist
        import threading as _threading
        self.step_tokens_hist = _Hist(
            "serving_step_tokens", "", (), _threading.Lock(),
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048))
        self.decode_gap_hist = _Hist(
            "serving_decode_gap_ms", "", (), _threading.Lock(),
            buckets=(0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000,
                     2500, 5000))
        # speculation observability: wall ms per draft pass (host lookup
        # or the batched serving.draft_step dispatch — the overhead the
        # accept rate must out-earn), and the CHOSEN per-slot draft depth
        # at every propose opportunity (the dynamic-k policy's output —
        # mass at 0 means slots degraded to plain decode)
        self.draft_ms_hist = _Hist(
            "serving_draft_ms", "", (), _threading.Lock(),
            buckets=(0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100,
                     250))
        self.spec_k_hist = _Hist(
            "serving_spec_k_effective", "", (), _threading.Lock(),
            buckets=(0, 1, 2, 3, 4, 6, 8, 12, 16, 24, 32))
        self._t_prev_decode: Optional[float] = None

    # -- tensor-parallel sharding trees ------------------------------------
    def _validate_tp(self, model) -> None:
        """Head counts must divide over the `model` axis: each device owns
        whole query heads and whole kv heads (the shard_map attention core
        and the pool's kv-head partition both depend on it); recurrent and
        latent layers have no sharded layout at all."""
        refuse_for_recurrent(slot_state_specs(model), "mesh")
        for l in model.layers:
            if l.type == "mla_attention":
                raise ValueError(
                    f"layer {l.name!r}: latent attention has no "
                    f"tensor-parallel path yet (--mesh model={self.tp}): "
                    f"its cache row is shared by every head")
            if l.type != "multi_head_attention":
                continue
            heads = int(l.attrs["num_heads"])
            h_kv = int(l.attrs.get("num_kv_heads", 0) or heads)
            if heads % self.tp or h_kv % self.tp:
                raise ValueError(
                    f"layer {l.name!r}: num_heads={heads} / "
                    f"num_kv_heads={h_kv} must both divide the mesh model "
                    f"axis ({self.tp}) — tensor-parallel decode gives each "
                    f"device whole heads")

    def _tp_param_shardings(self, params) -> dict:
        """NamedSharding per parameter: attention projections partition
        over `model` (w_q/w_k/w_v by output column — whole heads per
        device; w_o by input row, so the out-projection is partial sums
        meeting in one all-reduce), the FFN pairs get the same Megatron
        column/row split (first fc by output column — its bias and the
        elementwise activation stay column-local; second fc by input
        row — one more all-reduce per layer, and the wide [dim, 4*dim]
        hidden activation never materializes whole on any device), the
        LM head row-shards (partial logits meet in one all-reduce —
        replicated logits with ZERO all-gathers, so sampling is
        untouched), and everything else is replicated.

        FFN pairs are detected structurally: an fc layer feeding
        directly into another fc layer is the Megatron pattern; the
        hidden dim must divide the mesh (skipped — left replicated —
        otherwise, same divisibility discipline as the head counts).
        `_tp_ffn_pairs` / `_tp_lm_head` record what actually sharded so
        tools/hlo_shard_check.py can derive the exact expected
        all-reduce count instead of guessing."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        col = NamedSharding(self.mesh, P(None, "model"))
        row = NamedSharding(self.mesh, P("model", None))
        sh = {name: self._repl_sharding for name in params}
        self._tp_ffn_pairs: list[tuple[str, str]] = []
        self._tp_lm_head: Optional[str] = None
        layers = {l.name: l for l in self.executor.model.layers}
        for l in layers.values():
            if l.type != "multi_head_attention":
                continue
            names = [l.inputs[i].input_parameter_name for i in range(4)]
            for n in names[:3]:                       # w_q, w_k, w_v
                sh[n] = col
            sh[names[3]] = row                        # w_o
        for l in layers.values():                     # Megatron FFN pairs
            if l.type != "fc" or len(l.inputs) != 1:
                continue
            src = layers.get(l.inputs[0].input_layer_name)
            if src is None or src.type != "fc" or len(src.inputs) != 1:
                continue
            w1 = src.inputs[0].input_parameter_name
            w2 = l.inputs[0].input_parameter_name
            hidden = int(params[w1].shape[1])
            if hidden % self.tp or sh[w1] is not self._repl_sharding \
                    or sh[w2] is not self._repl_sharding:
                continue
            sh[w1] = col                              # up-projection
            if src.bias_parameter_name:
                # the bias adds to a column-sharded activation — shard
                # its LAST axis the same way (biases are stored
                # [1, out]) so the add stays collective-free
                b = src.bias_parameter_name
                sh[b] = NamedSharding(self.mesh, P(
                    *([None] * (params[b].ndim - 1) + ["model"])))
            sh[w2] = row                              # down-projection
            # stamp the Megatron layout on the layers themselves —
            # fc_layer pins the activations (hidden stays sharded, the
            # down-projection's partial sums all-reduce immediately), so
            # GSPMD cannot trade the one clean all-reduce for a
            # reduce-scattered residual stream full of small collectives
            src.attrs["tp_out"] = MODEL_AXIS
            l.attrs["tp_out"] = "replicated"
            self._tp_ffn_pairs.append((w1, w2))
        head = layers.get(self.logits_name)           # vocab projection
        if head is not None and head.type == "fc" and \
                len(head.inputs) == 1:
            w = head.inputs[0].input_parameter_name
            if int(params[w].shape[0]) % self.tp == 0 and \
                    sh[w] is self._repl_sharding:
                sh[w] = row
                head.attrs["tp_out"] = "replicated"
                feed_l = layers.get(head.inputs[0].input_layer_name)
                if feed_l is not None:
                    # pin the head's INPUT sharded on the contraction
                    # axis: with only the output pinned, GSPMD's cost
                    # model may satisfy it by ALL-GATHERING the weight —
                    # at production vocab the head is the largest param
                    # in the model, and reassembling it per step is the
                    # exact failure this sharding exists to prevent.  A
                    # replicated input slices locally for free, the dot
                    # goes partial, and the pinned-replicated output
                    # forces the one all-reduce.
                    feed_l.attrs["tp_out"] = MODEL_AXIS
                self._tp_lm_head = w
        if self._tp_ffn_pairs or self._tp_lm_head:
            # the residual stream and its layer norms are REPLICATED in
            # the Megatron layout — pin them, or GSPMD propagation will
            # happily shard the residual and pay partial-LN reductions
            # plus activation all-gathers at every projection input
            for l in layers.values():
                if l.type in ("layer_norm", "addto"):
                    l.attrs.setdefault("tp_out", "replicated")
        return sh

    # -- the weights a step reads -----------------------------------------
    @property
    def params(self):
        """The weights exactly as given (dtype included): what checkpoints,
        the drafter, `hbm_param_bytes` and an embedder's own checks read."""
        return self._params

    @params.setter
    def params(self, params):
        """Take a weight tree and derive the compiled steps' operands from
        it ONCE, in the executor's compute dtype (`cast_params`, the
        predicate `prepare` applies inside a step): weights do not change
        between steps, so a cast inside the step would read the whole
        given tree every step.  A leaf already in the compute dtype is
        shared, not copied — given bf16 under bf16 compute, or no compute
        dtype, the two trees are one.  Per-leaf `astype` keeps each leaf's
        sharding, so `_param_shardings_tree` describes the derived tree
        too.  The previous derived tree is dropped first: a swap peaks at
        the old and new given trees plus one derived tree.  Pages the
        prefix index cached under the previous weights are left alone:
        assign before serving, or to an engine without the index.

        An ABSTRACT tree (leaves `jax.ShapeDtypeStruct`: tools/serve.py
        `--weights deferred`) is kept for its names, shapes and dtypes and
        holds no bytes; the engine derives nothing from it and `step`
        refuses by name until a real tree is assigned."""
        self._step_params = None
        self._params = params
        self._moe_grouped_at = {}
        self.step_weight_bytes = 0
        if _is_abstract(params):
            return
        self._step_params = self.executor.cast_params(params)
        self.step_weight_bytes = sum(
            int(v.nbytes) for k, v in self._step_params.items()
            if v is not params[k])
        if self.step_weight_bytes:
            pc = process_counters()
            pc.add("serving_step_weight_casts_total", 1)
            pc.add("serving_step_weight_cast_bytes_total",
                   self.step_weight_bytes)

    def _state_shardings(self) -> "EngineState":
        pool = self.kv.pool_shardings()
        r = self._repl_sharding
        return EngineState(pools=pool, table=r, pos=r, toks=r, gen=r,
                           keys=r, temp=r, topk=r, topp=r)

    def _step_sharding_kwargs(self, n_extra: int, n_out: int = 1) -> dict:
        """Explicit in/out sharding trees for the compiled steps (the
        compile_step_with_plan discipline): (params, EngineState,
        n_extra replicated operands) -> (EngineState, n_out replicated
        outputs — sampled tokens, and for the verify step the accepted
        count too).  Empty off-mesh — the single-device jits stay
        exactly as before."""
        if self.tp <= 1:
            return {}
        st = self._state_shardings()
        r = self._repl_sharding
        return {"in_shardings": (self._param_shardings_tree, st)
                + (r,) * n_extra,
                "out_shardings": (st,) + (r,) * n_out}

    def _pools_out_kwargs(self) -> dict:
        """out_shardings pinning a pool-writing jit's output to the
        canonical pool sharding (tensor-parallel only): prefill packs and
        COW copies must hand pools back in the exact layout the donated
        decode-step state expects."""
        if self.tp <= 1:
            return {}
        return {"out_shardings": self.kv.pool_shardings()}

    # -- host mirror -> device pytree sync ---------------------------------
    def _stage(self, x):
        """Host -> device staging chokepoint: every upload the engine ever
        performs goes through here, so `n_host_stages` is an exact
        transfer count (the zero-restaging regression reads it) and
        tensor-parallel runs commit replicated copies up front instead of
        paying a reshard inside the step dispatch."""
        self.n_host_stages += 1
        if self._repl_sharding is not None:
            return jax.device_put(np.asarray(x), self._repl_sharding)
        return jnp.asarray(x)

    def _sync_device_state(self) -> None:
        """Re-upload exactly the device arrays whose HOST mirrors changed:
        the page table when any allocator write bumped kv.version
        (admission/COW/preempt/retire), the per-slot arrays when a slot
        lifecycle event set _slots_dirty.  A steady pure-decode run
        re-stages nothing.

        With a step in flight the host is one step behind the device, so
        the upload must not put back what that step moves: pos and gen go
        up as `_cursor` has them (banked plus the step in flight — exact,
        they advance by counts the host chose), and `toks` is not uploaded
        at all once it exists.  Every compiled step writes `toks` where it
        banks a token, so the device's copy is the newest for every
        decoding slot; an admitted slot's is set by its final chunk before
        any row reads it, an empty slot's is never used.  Only
        `restore_state` makes the host's copy the authority (it drops
        `_d_toks`)."""
        if self.kv.version != self._kv_synced:
            # the mixed step's virtual trash row (row S, all pages
            # unmapped -> physical page 0) rides permanently at the end
            tbl = np.concatenate(
                [self.kv.table,
                 np.zeros((1, self.kv.pages_per_slot), np.int32)], axis=0)
            self._d_table = self._stage(tbl)
            self._kv_synced = self.kv.version
        if self._slots_dirty:
            S = len(self.slots)
            pos = np.zeros(S, np.int32)
            gen = np.zeros(S, np.int32)
            keys = np.zeros((S, self._kk, 2), np.uint32)
            temp = np.zeros(S, np.float32)
            topk = np.zeros(S, np.int32)
            topp = np.zeros(S, np.float32)
            for s, sl in enumerate(self.slots):
                if sl is None:
                    continue
                pos[s], gen[s] = self._cursor(s)
                keys[s, :sl.keys.shape[0]] = sl.keys
                temp[s] = sl.req.temperature
                topk[s] = sl.req.top_k
                topp[s] = sl.req.top_p
            self._d_pos = self._stage(pos)
            if self._d_toks is None:
                self._d_toks = self._stage(np.array(
                    [0 if sl is None else sl.last_tok for sl in self.slots],
                    np.int32))
            self._d_gen = self._stage(gen)
            self._d_keys = self._stage(keys)
            self._d_temp = self._stage(temp)
            self._d_topk = self._stage(topk)
            self._d_topp = self._stage(topp)
            self._slots_dirty = False

    def _sync_run_mask(self, runnable) -> None:
        """The step's advance mask, device-cached: re-uploaded only when
        which slots advance actually changes (a pause, an admission, a
        retire) — constant across a steady decode run."""
        mask = np.zeros(len(self.slots), bool)
        mask[list(runnable)] = True
        if self._run_host is None or not np.array_equal(mask,
                                                        self._run_host):
            self._run_host = mask
            self._d_run = self._stage(mask)

    def _build_state(self) -> EngineState:
        """Assemble the step's state pytree from the current device
        components — pure host-side tuple construction, no transfers
        (pools enter via kv.pools so admission-time pack/COW rebinds are
        picked up automatically)."""
        return EngineState(pools=self.kv.pools, table=self._d_table,
                           pos=self._d_pos, toks=self._d_toks,
                           gen=self._d_gen, keys=self._d_keys,
                           temp=self._d_temp, topk=self._d_topk,
                           topp=self._d_topp)

    def _unpack_state(self, st: EngineState) -> None:
        """Rebind every component from a step's (donated-buffer) output —
        the old arrays were just consumed, no stale aliases may survive."""
        self.kv.pools = st.pools
        self._d_table = st.table
        self._d_pos = st.pos
        self._d_toks = st.toks
        self._d_gen = st.gen
        self._d_keys = st.keys
        self._d_temp = st.temp
        self._d_topk = st.topk
        self._d_topp = st.topp

    # -- phase spans (the pump thread's `pt.step.*` / `pt.kv.*` family) ----
    def _phase(self, name: str, also=None, **attrs):
        """One phase of a step on the engine lane: `pt.step.<name>`, fed to
        the ring and the profiler alike (obs/trace.py "Two sinks"), and to
        the step clock whether or not either is on (`also`: one more taker
        of the same seconds)."""
        name = "pt.step." + name
        sink = self.step_clock.sink(name)
        if also is not None:
            def sink(seconds, clock=sink):
                clock(seconds)
                also(seconds)
        return self.tracer.span(name, track="engine", sink=sink, **attrs)

    def _compiled_step(self, kind: str, **attrs):
        """The span of ONE compiled step — `pt.step.decode` / `.mixed` /
        `.spec`, the kind the scheduler chose in the name.  A decode or
        mixed step's covers its LAUNCH: the call into the compiled program
        and the bookkeeping behind it (its tokens are read under a later
        `pt.step.readback`, see `_land`); a verify step's still runs to
        the host token read.  Closes the step's `pt.step.plan` first:
        planning ends where the dispatch begins."""
        self._end_plan()
        return self._phase(kind, **attrs)

    def _end_plan(self) -> None:
        plan, self._plan_span = self._plan_span, None
        self.tracer.end(plan)

    def _evict_for(self, n_pages: int) -> int:
        """The allocator's page-pressure hook: PrefixTree.evict_for under
        its own span.  A full pool asks for ONE page at nearly every page
        boundary of every slot, so a call frees a batch: at least 1/256 of
        the pool (64 pages of 16,385; nothing extra in a pool under 256
        pages), the coldest leaves first as ever.  The tree keeps its
        frontier, so a call costs its victims alone (PERF.md section 6:
        0.5 ms for 64) and the floor saves only a span and a call a page;
        it stays because with it the victims are the ones they always
        were.  What it gives up is the prefix cache's 0.4%
        least-recently-used tail (PERF.md section 7)."""
        n_pages = max(int(n_pages), self.kv.num_pages // 256)
        with self.tracer.span("pt.kv.evict", track="engine",
                              sink=self.step_clock.sink("pt.kv.evict"),
                              pages=n_pages):
            return self.prefix.evict_for(n_pages)

    # -- lifecycle tracing helpers ----------------------------------------
    def _tr_on(self) -> bool:
        t = self.tracer
        return t is not None and t.enabled

    def _trace_attrs(self, req_id, attrs: dict) -> dict:
        """Merge the request's inbound trace context (trace_id + the
        sender's span id) into span attrs — the cross-process stitch."""
        tc = self._req_trace.get(req_id)
        if tc:
            attrs = dict(attrs)
            attrs.setdefault("trace_id", tc.get("trace_id"))
            if tc.get("parent"):
                attrs.setdefault("parent", tc["parent"])
        return attrs

    def _tr_begin(self, req_id, phase: str, **attrs) -> None:
        """Open the request's next lifecycle phase (queued / prefill /
        decode / replay).  At most one phase is open per request; the
        previous one must have been closed by _tr_end.  The phase clock
        runs UNCONDITIONALLY (per-request latency attribution is always
        on); the span records only while the tracer is enabled."""
        now = time.perf_counter()
        self._req_phase[req_id] = (phase, now)
        if self._tr_on():
            self._obs_open[req_id] = [
                phase, f"req:{req_id}", now,
                self._trace_attrs(req_id, attrs) or None]

    def _tr_end(self, req_id, **attrs) -> None:
        now = time.perf_counter()
        ph = self._req_phase.pop(req_id, None)
        if ph is not None:
            a = self._req_attr.setdefault(req_id, {})
            a[ph[0]] = a.get(ph[0], 0.0) + (now - ph[1])
        h = self._obs_open.pop(req_id, None)
        if h is not None:
            name, track, t0, sattrs = h
            if attrs:
                sattrs = dict(sattrs or (), **attrs)
            self.tracer.add(name, t0, now - t0, track=track, attrs=sattrs)

    def _tr_instant(self, req_id, name: str, **attrs) -> None:
        if self._tr_on():
            self.tracer.instant(name, track=f"req:{req_id}",
                                **self._trace_attrs(req_id, attrs))

    def _bump_attr(self, req_id, key: str, by: int = 1) -> None:
        """Occurrence counter feeding the timing breakdown (preempts,
        prefill chunks, spec drafted/accepted)."""
        a = self._req_attr.setdefault(req_id, {})
        a[key] = a.get(key, 0) + by

    def _finish_timing(self, req_id) -> dict:
        """Fold the request's phase accumulators into the `timing`
        breakdown the done frame carries: per-phase wall ms + occurrence
        counts.  The phases are contiguous (each _tr_end is immediately
        followed by the next _tr_begin), so their sum IS the engine-side
        request wall time — `total_ms` restates it for SLO debugging
        without a trace viewer."""
        self._req_phase.pop(req_id, None)     # closed by the final _tr_end
        a = self._req_attr.pop(req_id, {})
        ms = {k: round(a.get(p, 0.0) * 1e3, 3) for k, p in
              (("queue_ms", "queued"), ("prefill_ms", "prefill"),
               ("decode_ms", "decode"), ("replay_ms", "replay"))}
        ms["total_ms"] = round(sum(ms.values()), 3)
        for k, src in (("prefill_chunks", "chunks"),
                       ("preempts", "preempts"),
                       ("spec_drafted", "spec_drafted"),
                       ("spec_accepted", "spec_accepted")):
            if a.get(src):
                ms[k] = int(a[src])
        return ms

    # -- public API -------------------------------------------------------
    def validate(self, req: Request) -> None:
        """Raise ValueError if `req` can never be served by this engine's
        capacity — pure read of construction-time constants, so a front
        end on another thread can reject before enqueueing."""
        if req.max_new < 0:
            # jax.random.split(rng, -1) inside _admit would kill the pump
            raise ValueError(
                f"request {req.req_id!r}: max_new {req.max_new} is negative")
        if req.max_new == 0:
            return
        p = req.prompt_ids.size
        cap = self.kv.capacity_tokens
        if p + req.max_new > cap:
            raise ValueError(
                f"request {req.req_id!r}: prompt {p} + max_new "
                f"{req.max_new} exceeds the {cap}-token slot capacity "
                f"(pages_per_slot * page_size) — raise max_context")
        # guaranteed-completion bound: the last decode step writes KV at
        # position p + max_new - 2, so a request that never hits eos needs
        # pages covering p + max_new - 1 tokens.  A pool below that can
        # only preempt-and-replay the request forever once it is alone.
        need = self.kv.pages_for(max(p + req.max_new - 1, p))
        if need > self.kv.num_pages - 1:
            raise ValueError(
                f"request {req.req_id!r} needs up to {need} pages to "
                f"complete but the pool holds {self.kv.num_pages - 1} — "
                f"raise num_pages")

    def add_request(self, req: Request) -> None:
        """Enqueue; admission happens inside step()/run()."""
        self.validate(req)
        if req.trace:
            self._req_trace[req.req_id] = req.trace
        if req.max_new == 0:
            # lm_generate(max_new=0) returns the prompt unchanged whatever
            # its length — resolve before any capacity/page validation,
            # since this request never touches a slot or a page
            self._finish(req.req_id, req.prompt_ids.copy(), "length")
            return
        self._tr_begin(req.req_id, "queued",
                       prompt_len=int(req.prompt_ids.size),
                       max_new=req.max_new)
        self.flight.record("queued", req=str(req.req_id),
                           prompt_len=int(req.prompt_ids.size),
                           max_new=req.max_new)
        self.queue.append(req)

    def cancel(self, request_id, reason: str = "cancelled") -> bool:
        """Abort a queued or in-flight request: its slot and pages return
        to the pool THIS call (reusable by waiting requests on the very
        next step), its tokens-so-far land in results with the given
        finish reason.  False when the id is unknown or already finished.
        Call from the step()-driving thread only (the scheduler state is
        not locked)."""
        for i, r in enumerate(self.queue):
            if r.req_id == request_id:
                del self.queue[i]
                self._count_abort(reason)
                stash = r._preempted_gen or []
                if stash:
                    # the preempt rollback un-banked these on the promise
                    # the restart would re-emit them; an abort breaks that
                    # promise, and they WERE genuinely emitted (and
                    # possibly streamed) — restore the count
                    self.tokens_generated += len(stash)
                toks = np.concatenate(
                    [r.prompt_ids,
                     np.asarray(stash, np.int32)]).astype(np.int32)
                self._finish(request_id, toks, reason)
                return True
        if self._pending is not None and any(
                sl is not None and sl.req.req_id == request_id
                for sl in self.slots):
            # its row may be in flight: land it first, so the abort reports
            # every token that was computed (and may find the request done)
            self.settle()
        for s, sl in enumerate(self.slots):
            if sl is not None and sl.req.req_id == request_id:
                gen = sl.generated
                stash = sl.req._preempted_gen or []
                if len(stash) > len(gen):
                    # cancelled MID-REPLAY after a preemption: the replay
                    # has not yet caught up to what was already emitted
                    # (and streamed) before the preempt.  Determinism
                    # makes both identical prefixes of one stream — report
                    # the longer one and restore the still-un-rebanked
                    # remainder of the preempt rollback
                    self.tokens_generated += len(stash) - len(gen)
                    gen = stash
                toks = np.concatenate(
                    [sl.req.prompt_ids,
                     np.asarray(gen, np.int32)]).astype(np.int32)
                self._donate(s)
                self.kv.release(s)
                self.slots[s] = None
                self._slots_dirty = True
                self._count_abort(reason)
                self._finish(request_id, toks, reason)
                return True
        return False

    def _count_abort(self, reason: str) -> None:
        if reason == "deadline":
            self.n_expired += 1
        else:
            self.n_cancelled += 1

    def _sweep_deadlines(self) -> None:
        """Expire every queued/in-flight request whose deadline passed on
        the engine clock — runs at the top of step(), BEFORE admission, so
        an expired queued request never takes a slot and an expired slot's
        pages free up for this very step's admissions."""
        now = self.clock()
        expired = [r.req_id for r in self.queue
                   if r.deadline is not None and r.deadline <= now]
        expired += [sl.req.req_id for sl in self.slots
                    if sl is not None and sl.req.deadline is not None
                    and sl.req.deadline <= now]
        for rid in expired:
            self.cancel(rid, reason="deadline")

    def step(self) -> bool:
        """One scheduler iteration: sweep deadlines -> admit -> plan ->
        LAUNCH one compiled step over all slots -> LAND (read back, bank,
        emit, retire).  Returns False when idle (nothing in flight and
        nothing admittable).

        A step with any slot mid-prefill runs the MIXED step: decode rows
        and prompt-chunk rows pack into one ragged [max_step_tokens]
        dispatch under the token budget.  Steps
        with only decoding slots keep the classic [S, 1] decode step —
        the steady state pays nothing for the chunk machinery.

        What lands is the step just launched (`lookahead` 0) or the one
        launched by the previous call (`lookahead` 1: the device starts
        step N+1 the moment step N ends, and this thread banks N beside
        it).  Everything that needs the tokens lands first — see
        `settle`.

        Phases, each a span (docs/observability.md "The span model"):
        `pt.step.admit` -> `pt.step.plan` -> the compiled step under its
        kind's name (`pt.step.dispatch` inside) -> `pt.step.readback` ->
        `pt.step.emit`.  Each also feeds the step clock, flushed here."""
        try:
            return self._step()
        finally:
            self.step_clock.flush()

    def _step(self) -> bool:
        with self._phase("admit"):
            self._sweep_deadlines()
            self._admit_from_queue()
        live = [s for s in range(len(self.slots)) if self.slots[s] is not None]
        if not live:
            if self._pending is not None:
                self.settle()            # a retired request's last row
                return True
            self._t_prev_decode = None   # idle: don't charge the idle gap
            return False
        if self._step_params is None:
            raise RuntimeError(
                "this engine holds no weights: it was built around the "
                "parameter tree's shapes (tools/serve.py --weights "
                "deferred) — assign `engine.params` before the first step")
        self._plan_span = self.tracer.begin(
            "pt.step.plan", track="engine",
            sink=self.step_clock.sink("pt.step.plan"))
        try:
            return self._plan_and_run(live)
        finally:
            if self._plan_span is not None:  # no compiled step ran (preempt
                self._end_plan()             # to empty, or an exception)

    def settle(self) -> None:
        """Land the step in flight, if there is one: afterwards the host
        mirrors, the banked tokens and the device agree, as they do after
        every step() at `lookahead` 0.  Whatever reads or moves what a
        land moves calls this first — cancel and the deadline sweep (the
        abort reports every computed token), preemption, the speculative
        step (the drafter reads tokens), checkpoint_state, the kv transfer
        plane, every idle-engine knob, and the pump when it stops.  Inside
        a land it does nothing: `on_finish` may export a prefix there, which
        reads only donated pages, and those no row in flight writes."""
        if self._pending is not None and not self._landing:
            pend, self._pending = self._pending, None
            self._land(pend)

    def drop_pending(self) -> None:
        """Wait for the step in flight and forget it, banking nothing: for
        a pump that died mid-step, whose mirrors may be half-banked."""
        pend, self._pending = self._pending, None
        if pend is not None:
            jax.block_until_ready(pend.nxt)

    def _cursor(self, s: int) -> tuple:
        """(pos, gen) of slot `s` once the step in flight has landed: the
        banked cursors plus what that step advances them by, for the
        request it was launched for.  Both advance by counts the host
        chose at launch (1 a decode row, n a chunk), so planning step N+1
        needs nothing of step N but its tokens — and those stay on the
        device (`EngineState.toks`).  With nothing in flight: the banked
        cursors."""
        sl, pend = self.slots[s], self._pending
        if pend is not None and pend.owners[s] is sl:
            return sl.pos + pend.adv[s], sl.gen + pend.emit[s]
        return sl.pos, sl.gen

    def _plan_and_run(self, live) -> bool:
        """step() after admission: secure pages (preempting on a wedged
        pool), choose the step's kind, launch it, land what is due."""
        while live:
            cur = {s: self._cursor(s) for s in live}
            # a slot whose LAST token is in flight (retirement by max_new
            # is known at launch) takes no further row; decode-mode slots
            # need their next page; prefill-mode slots (gen == 0) had
            # their prompt pages secured at reservation and can always
            # take chunk rows
            going = [s for s in live
                     if cur[s][1] < self.slots[s].req.max_new]
            decoding = [s for s in going if cur[s][1] > 0]
            filling = [s for s in going if cur[s][1] == 0]
            runnable = [s for s in decoding
                        if self.kv.try_grow(s, cur[s][0] + 1)]
            if runnable or (filling and not decoding):
                # chunk-only steps are progress ONLY while nothing is
                # decoding: if every decoding slot is page-starved, letting
                # a filler keep chunking would stall their inter-token
                # latency for its whole remaining prefill — the exact
                # HOL blocking the budget exists to bound — and the wedge
                # preemption below would then evict the filler anyway,
                # discarding a just-finished prefill
                break
            if self._pending is not None:
                # nothing to launch until the step in flight lands: every
                # slot is finishing in it, or its retirements hold the
                # pages the others wait for
                self.settle()
                live = [s for s in live if self.slots[s] is not None]
                continue
            # overcommitted-pool wedge: every decoding slot needs its next
            # page and the free list is dry (eviction included).  Preempt
            # the YOUNGEST live slot (the recompute policy of
            # arXiv:2605.25645-style engines) — usually the mid-prefill
            # filler holding the reserved pages: release its pages and
            # requeue its request at the queue front.  A decode victim's
            # deterministic per-request key schedule regenerates the exact
            # same tokens on re-admission; a mid-prefill victim donates its
            # committed chunk pages and prefix-hits them on replay — either
            # way preemption is invisible in the output (and in the parity
            # oracle).
            victim = max(live, key=lambda s: self.slots[s].admit_seq)
            self._preempt(victim)
            live.remove(victim)
        if not live:
            return True            # pages freed; next step() re-admits
        if self.spec_k > 0:
            # speculative mode: the drafter proposes per decoding slot
            # (dynamic k may choose 0 for cold/low-accept slots); any
            # drafts (or chunk rows) route through the verify step — a
            # zero-draft pure-decode step keeps the cheap [S, 1]
            # signature, so an unhelpful drafter costs nothing steady-state
            # beyond the draft pass itself
            drafts = self._propose_drafts(runnable)
            if drafts or filling:
                return self._run_spec_step(live, runnable, filling,
                                           drafts)
        if filling:
            # (a speculative engine's chunks rode the verify step above)
            launched = self._launch_mixed(going, runnable, filling, cur)
        else:
            launched = self._launch_decode(going, runnable, cur)
        # launch N+1, THEN land N: the device has its next step queued
        # before this thread blocks on the last one's tokens
        due, self._pending = self._pending, launched
        if due is not None:
            self._land(due)
        if self.lookahead == 0 or self.spec_k > 0:
            # a direct caller looks at what step() banked and the drafter
            # reads banked tokens: such an engine keeps nothing in flight
            self.settle()
        return True

    def _launch_decode(self, going, runnable, cur) -> _Pending:
        """Launch ONE pure decode step: every runnable slot advances one
        token from the device's own `toks`.  `going` are the slots the
        step works for (occupancy counts them; a slot whose last token is
        already in flight is not among them), `cur` the plan's cursors."""
        S = len(self.slots)
        lengths = self._slot_lengths()
        for s in runnable:
            # a shared page is never written: the page receiving this
            # step's K/V write must be private to the slot (admission's
            # COW guarantees it — this tripwire catches refcount bugs
            # before they corrupt a cached prefix)
            assert self.kv.page_writable(int(self.kv.table[
                s, cur[s][0] // self.kv.page_size])), \
                f"slot {s} would write a shared page"
        # per-slot pos/toks/gen/keys/knobs already live on device; a
        # steady decode run enters the compiled step with ZERO host
        # staging (sync uploads only what admissions/retires/pauses
        # actually changed).  The state buffers are donated — rebind
        # every component so no stale (deleted-buffer) aliases survive.
        self._sync_run_mask(runnable)
        self._sync_device_state()
        with self._compiled_step("decode", live=len(going),
                                 step=self.n_decode_steps + 1) as launch:
            with self._phase("dispatch"):
                st, nxt = self._decode_step(
                    self._step_params, self._build_state(), self._d_run)
            self._unpack_state(st)
            self._count_launch(len(going) / S)
            self._count_kv(lengths)
            self._note_step_metrics(len(runnable), decoded=True)
            self._count_recurrent_tokens(len(runnable), 0)
        adv = np.zeros(S, np.int32)
        adv[runnable] = 1
        self._count_window(cur, adv, len(runnable))
        return _Pending(nxt, list(self.slots), runnable, [], adv,
                        adv.astype(bool), "decode", self.n_decode_steps,
                        launch.t0)

    def _count_launch(self, occupancy: float) -> None:
        """The counters of one launched decode or mixed step."""
        self.n_decode_steps += 1
        self.occupancy_sum += occupancy
        if self._pending is not None:
            self.n_lookahead_steps += 1

    def _count_recurrent_tokens(self, step: int, segment: int) -> None:
        """The tokens one dispatch hands the recurrent layers (nothing for
        a model without them): `step` its decode rows, one token a slot
        state, `segment` its chunk rows, a run of tokens a slot state."""
        if not self._recurrent:
            return
        for kind, n in (("step", int(step)), ("segment", int(segment))):
            self.recurrent_tokens[kind] += n
            process_counters().add(counter_key(
                "serving_recurrent_tokens_total", kind=kind), n)

    def _land(self, pend: _Pending) -> None:
        """The other half of a decode or mixed step: read its tokens back
        (this is where the host waits for the device), bank and emit them,
        advance the chunk cursors, retire.  A row whose slot no longer
        holds the request it was launched for — the request ended on eos
        at the previous land — is dropped: not banked, not emitted, not
        counted.  Its K/V write went to a page the slot had secured and
        `_donate` never offers (only whole pages strictly below the banked
        `pos`), ahead in device order of any write by the page's next
        owner; a recurrent slot state it moved is reset by the next
        admission's first row (position 0)."""
        S = len(self.slots)
        self._landing = True
        try:
            with self._phase("readback", step=pend.step, kind=pend.kind):
                nxt = self._count_moe(np.asarray(pend.nxt), S,
                                      pend.kind)            # host sync
            self._landed(pend.kind, pend.step, pend.t_launch,
                         len(pend.rows))
            with self._phase("emit", n=len(pend.rows), step=pend.step,
                             kind=pend.kind):
                for s in pend.rows:
                    if self.slots[s] is pend.owners[s]:
                        self._bank_token(s, int(nxt[s]))
                        continue
                    self.n_lookahead_dropped_rows += 1
                    self.flight.record(
                        "lookahead_drop", slot=s,
                        req=str(pend.owners[s].req.req_id))
                # a slot still committing its prompt cannot have ended
                assert all(self.slots[s] is pend.owners[s]
                           for s, _, _ in pend.advanced)
                self._advance_chunks(pend.advanced, lambda s: int(nxt[s]))
        finally:
            self._landing = False

    def _landed(self, kind: str, step: int, t_launch: float,
                rows: int) -> None:
        """A compiled step's tokens are on the host: close its flight —
        launch to here — into the step clock, and while the ring is on as
        a `pt.step.flight` record on a lane of its own (flights of
        consecutive steps overlap at `lookahead` 1, so they cannot nest on
        the engine's lane; `step=` pairs one with the launch span and with
        the read-back and emit that landed it)."""
        dur = time.perf_counter() - t_launch
        seconds, landed = _FLIGHT_COUNTERS[kind]
        self.step_clock.add(seconds, dur)
        self.step_clock.add(landed, 1)
        if self.tracer.enabled:
            self.tracer.add("pt.step.flight", t_launch, dur, track="flight",
                            attrs={"step": step, "kind": kind,
                                   "rows": rows})

    def _bank_token(self, s: int, tok: int) -> None:
        """Record one decoded token for slot `s` (shared by the pure
        decode step and the mixed step's decode rows): replay-phase flip,
        stream hook, eos/max_new retirement."""
        sl = self.slots[s]
        if sl.replay_until and sl.gen >= sl.replay_until:
            # the next token is the first FRESH one after a preempt
            # replay — flip the lifecycle phase
            sl.replay_until = 0
            self._tr_end(sl.req.req_id)
            self._tr_begin(sl.req.req_id, "decode")
        sl.generated.append(tok)
        sl.pos += 1
        sl.gen += 1
        sl.last_tok = tok
        self.tokens_generated += 1
        if self.on_token is not None:
            self.on_token(sl.req.req_id, tok, sl.gen - 1)
        if tok == sl.req.eos_id or sl.gen >= sl.req.max_new:
            self._retire(s)

    def _note_step_metrics(self, n_tokens: int, decoded: bool) -> None:
        """Token-budget observability: scheduled rows this step, and the
        pump-step gap decoding slots saw (time between consecutive steps
        that advanced at least one decode row — the inter-token latency
        floor HOL-blocking prefill used to blow up)."""
        self.step_tokens_hist.observe(float(n_tokens))
        if decoded:
            now = time.perf_counter()
            if self._t_prev_decode is not None:
                self.decode_gap_hist.observe(
                    (now - self._t_prev_decode) * 1e3)
            self._t_prev_decode = now

    def _slot_lengths(self) -> np.ndarray:
        """Tokens each slot's decode row attends: pos + 1, past the step
        in flight (an empty slot sits at pos 0 and reads one token of the
        trash page)."""
        return np.fromiter((1 if sl is None else self._cursor(s)[0] + 1
                            for s, sl in enumerate(self.slots)), np.int64,
                           len(self.slots))

    def _count_kv(self, lengths: np.ndarray,
                  row_slot: Optional[np.ndarray] = None,
                  also: Optional[dict] = None,
                  head_rows: Optional[int] = None) -> None:
        """Add one compiled step's rows to the kernel's counters: `lengths`
        the tokens each row attends, `row_slot` the table row it reads
        (None: the rows are the slots, and no two share a walk); `also` the
        step's other process-wide counts, added under the same lock;
        `head_rows` the rows that reach the vocabulary head (None: every
        row samples — a decode step)."""
        from paddle_tpu.ops.pallas_paged import tile_rows, walked_blocks
        # the tile of the call's rows: a decode call's last tile is padded
        # with dead rows
        bq = 1 if self._kv_tile is None else \
            tile_rows(lengths.size, *self._kv_tile)
        blocks, shared = walked_blocks(lengths, row_slot, bq, self._kv_block)
        attended, fetched = int(lengths.sum()), blocks * self._kv_block
        self.kv_tokens_attended += attended
        self.kv_tokens_fetched += fetched
        self.n_kv_rows += lengths.size
        self.n_kv_shared_rows += shared
        if head_rows is None:
            head_rows = lengths.size
        self.n_head_rows += head_rows
        counts = {"serving_kv_rows_total": lengths.size,
                  "serving_head_rows_total": head_rows,
                  "serving_kv_shared_rows_total": shared,
                  "serving_kv_tokens_attended_total": attended,
                  "serving_kv_tokens_fetched_total": fetched, **(also or {})}
        if self._mhc_writes:
            # every row of the step, padding included, passes each
            # sublayer's stream pass
            writes = len(self._mhc_writes)
            self.n_mhc_rows += writes * lengths.size
            self.n_mhc_calls += writes
            counts.update(serving_mhc_rows_total=writes * lengths.size,
                          serving_mhc_calls_total=writes)
        process_counters().add_many(counts)

    def _launch_mixed(self, going, runnable, filling, cur) -> _Pending:
        """Launch ONE mixed prefill/decode dispatch: pack each runnable
        decode slot's single row plus the mid-prefill slots' prompt rows
        (`_pack_chunk_rows`: a share of `prefill_chunk` each, then the
        step's free rows) into a flat [max_step_tokens] ragged row list
        (padding rows aim at a virtual all-trash table row) and run the
        compiled mixed step; `_land` banks the decode tokens and advances
        the chunk cursors.  A slot whose FINAL chunk ran this step emits
        token 0 from the last prompt position's logits (keys[0] — the same
        key schedule lm_generate consumes), so chunk rows emit nothing
        until their final chunk.

        A decode row's input token is the slot's last sampled one, which
        the host may not have yet (its step can be in flight): the row is
        staged as -1 and the compiled step takes `state.toks[slot]`.

        The per-step token budget is the HOL-blocking bound: decode rows
        are packed FIRST (every decoding slot advances every step it has
        pages for), chunk rows only fill what remains — so no single
        step, whatever the prompt mix, exceeds max_step_tokens rows."""
        S = len(self.slots)
        T = self.max_step_tokens
        ps = self.kv.page_size
        row_ids = np.zeros(T, np.int32)
        row_slot = np.full(T, S, np.int32)   # S = the virtual trash row
        row_pos = np.zeros(T, np.int32)
        sample_row = np.zeros(S, np.int32)
        # device-state advance masks: adv[s] = tokens slot s commits this
        # step (1 per decode row, chunk length per chunk run), emit[s] =
        # slot s banks a sampled token (decode rows + final chunks).  The
        # compiled step advances pos/gen/toks from these; keys and knobs
        # already live in the EngineState (keys[s, gen[s]] — gen 0 at a
        # final chunk IS lm_generate's keys[0] decision).
        adv = np.zeros(S, np.int32)
        emit = np.zeros(S, bool)
        r = 0
        for s in runnable:
            pos = cur[s][0]
            # same shared-page write tripwire as the pure decode step
            assert self.kv.page_writable(
                int(self.kv.table[s, pos // ps])), \
                f"slot {s} would write a shared page"
            row_ids[r] = -1
            row_slot[r] = s
            row_pos[r] = pos
            sample_row[s] = r
            adv[s] = 1
            emit[s] = True
            r += 1
        if self._recurrent:
            # the recurrent layers' packing contract (graph/layers_kda.py):
            # rows [0, S) are single rows, the chunks start at row S
            r = S
        advanced, r = self._pack_chunk_rows(
            filling, row_ids, row_slot, row_pos, sample_row, adv, emit,
            r, T - r)
        # the state table already carries the virtual trash row (row S) —
        # padding rows gather/scatter only page 0.  Row packing is this
        # step's scheduling decision, so the six row/mask operands stage
        # per mixed step; the EngineState (donated, rebound) does not.
        self._sync_device_state()
        with self._compiled_step("mixed", live=len(going), rows=r,
                                 decode_rows=len(runnable),
                                 step=self.n_decode_steps + 1) as launch:
            with self._phase("dispatch"):
                st, nxt = self._mixed_step(
                    self._step_params, self._build_state(),
                    self._stage(row_ids),
                    self._stage(row_slot), self._stage(row_pos),
                    self._stage(sample_row), self._stage(adv),
                    self._stage(emit))
            self._unpack_state(st)
            self._count_launch(len(going) / S)
            self.n_mixed_steps += 1
            chunk_rows = sum(n for _, n, _ in advanced)
            self.n_step_pad_rows += T - len(runnable) - chunk_rows
            self._count_kv(row_pos + 1, row_slot, {  # a padding row reads 1
                "serving_mixed_steps_total": 1,
                "serving_chunk_rows_total": chunk_rows,
                "serving_step_pad_rows_total":
                    T - len(runnable) - chunk_rows}, head_rows=S)
            self._note_step_metrics(r, decoded=bool(runnable))
            self._count_recurrent_tokens(len(runnable), chunk_rows)
            if self._kda_seg and advanced:
                from paddle_tpu.ops.pallas_kda_seg import folded_chunks
                chunks = folded_chunks((n for _, n, _ in advanced), T - S)
                self.recurrent_segment_chunks += chunks
                process_counters().add(
                    "serving_recurrent_segment_chunks_total", chunks)
            self._count_window(cur, adv, len(runnable) + chunk_rows)
        return _Pending(nxt, list(self.slots), runnable, advanced, adv,
                        emit, "mixed", self.n_decode_steps, launch.t0)

    def _pack_chunk_rows(self, filling, row_ids, row_slot, row_pos,
                         sample_row, adv, emit, r: int, budget: int):
        """Share `budget` rows out among the mid-prefill slots and pack
        each slot's prompt rows as ONE contiguous run into the ragged row
        list, starting at row `r` — the chunk-scheduling half SHARED by
        the mixed and speculative verify steps, so the final-chunk
        emission rule, the shared-page tripwire, and the chunk_sched
        accounting can never diverge between them.  `prefill_chunk` is a
        filling slot's SHARE of a step, not a cap on its run: every slot
        gets its share first (`_chunk_shares`), then what the step still
        has left goes, oldest admission first, to the same slots up to
        the rest of their prompts — the oldest finishes soonest and emits
        its first token soonest, and a row left over would be computed as
        padding anyway.  A slot whose FINAL chunk lands this step gets its
        sampling row pointed at the last prompt position (`sample_row[s]`;
        the verify step's chain position 0) and `emit[s]` set — token 0
        sampled with keys[gen=0].  Returns (advanced, r')."""
        ps = self.kv.page_size
        shares = self._chunk_shares(filling, budget)
        left = budget - sum(n for _, n in shares)
        advanced = []                        # (slot, n_rows, final)
        for s, n in shares:
            sl = self.slots[s]
            p = sl.req.prompt_ids.size
            pos = self._cursor(s)[0]        # past any chunk in flight
            extra = min(p - pos - n, left)
            left -= extra
            n += extra
            # every page this chunk writes must be private to the slot
            # (reservation COW'd the shared boundary page; mapped prefix
            # pages below the cursor are never written)
            for j in range(pos // ps, (pos + n - 1) // ps + 1):
                assert self.kv.page_writable(int(self.kv.table[s, j])), \
                    f"slot {s} chunk would write shared page " \
                    f"{int(self.kv.table[s, j])}"
            row_ids[r:r + n] = sl.req.prompt_ids[pos:pos + n]
            row_slot[r:r + n] = s
            row_pos[r:r + n] = np.arange(pos, pos + n)
            final = pos + n == p
            adv[s] = n
            if final:
                sample_row[s] = r + n - 1
                emit[s] = True
            self.n_prefill_chunks += 1
            self.n_chunk_rows += n
            self.n_chunk_extra_rows += extra
            self._bump_attr(sl.req.req_id, "chunks")
            self.flight.record("chunk_sched", req=str(sl.req.req_id),
                               slot=s, start=int(pos), tokens=int(n),
                               final=final)
            advanced.append((s, n, final))
            r += n
        return advanced, r

    def _chunk_shares(self, filling, budget: int) -> list:
        """[(slot, rows)] in admit order: each mid-prefill slot's share of
        a step with `budget` rows for chunks, until the budget runs out —
        what the verify step reserves ahead of its drafts, and the first
        pass of `_pack_chunk_rows`, so the reserve can never under-count
        what the packing will schedule."""
        shares = []
        for s in sorted(filling, key=lambda s: self.slots[s].admit_seq):
            if budget <= 0:
                break
            n = self._chunk_rows_for(s, budget)
            shares.append((s, n))
            budget -= n
        return shares

    def _chunk_rows_for(self, s: int, budget: int) -> int:
        """Rows of slot `s`'s share of a step under `budget`: the rest of
        its prompt, up to `prefill_chunk` — the ONE formula of a share."""
        return min(self.slots[s].req.prompt_ids.size - self._cursor(s)[0],
                   self.prefill_chunk, budget)

    def _advance_chunks(self, advanced, tok0_of) -> None:
        """Post-step chunk bookkeeping shared by the mixed and verify
        steps: advance each chunked slot's cursor, and emit token 0
        (`tok0_of(s)` — that slot's sampled row) for final chunks."""
        for s, n, final in advanced:
            sl = self.slots[s]
            sl.pos += n
            if final:
                self._emit_first(s, tok0_of(s))

    # -- speculative decoding (docs/serving.md "Speculative decoding") ----
    def _dyn_k(self, sl) -> int:
        """Per-slot draft depth for this flush window.  Static mode:
        always spec_k.  Dynamic mode (`spec_dynamic=True`): the slot's
        accept-rate EWMA picks k_s ∈ {0..spec_k} — a cold slot pays a
        ONE-row probe (not k wasted verify rows), a low-accept slot
        decays to plain decode (k=0) with a paced k=1 re-probe every
        `_PROBE_EVERY` windows so a workload that turns repetitive can
        climb back, and a high-accept slot rides the full depth.  The
        choice is host-side data (chain packing is ragged by row count),
        so dynamic k adds ZERO verify-step signatures."""
        if not self.spec_dynamic:
            return self.spec_k
        if sl.accept_ewma is None:
            return min(1, self.spec_k)           # cold: cheapest probe
        k = int(round(sl.accept_ewma * self.spec_k))
        if k <= 0:
            sl.probe_tick += 1
            if sl.probe_tick >= _PROBE_EVERY:
                sl.probe_tick = 0
                return 1
            return 0
        return min(k, self.spec_k)

    def _draft_ctx(self, s: int, W: int) -> np.ndarray:
        """Slot `s`'s drafting context: the most recent W tokens of
        prompt + generated, newest last — the drafter's search window's
        tail, so the host cost stays O(window) per slot, not O(context)
        as generation grows."""
        sl = self.slots[s]
        gen_tail = sl.generated[-W:]
        need = W - len(gen_tail)
        if need > 0 and sl.req.prompt_ids.size:
            return np.concatenate(
                [sl.req.prompt_ids[-need:],
                 np.asarray(gen_tail, np.int32)])
        return np.asarray(gen_tail, np.int32)

    def _propose_drafts(self, runnable) -> dict:
        """Ask the drafter for lookahead tokens per decoding slot (host
        side, between steps).  The per-slot cap is exact-by-construction:
        a chain emits at most k+1 tokens, so k never exceeds the tokens
        the request may still emit (max_new - gen - 1), and the deepest
        draft write (pos + k) never
        exceeds slot capacity — the same `p + max_new - 2` bound
        validate() already guarantees pages for.  Empty proposals drop
        out entirely (their slot rides the plain decode row).

        Drafters exposing `propose_batch` (ModelDrafter) get ALL slots'
        windowed contexts in ONE call — one jitted [S, W] -> [S, spec_k]
        dispatch at site `serving.draft_step`, ALWAYS at depth spec_k so
        dynamic per-slot k (applied by host-side slicing) never mints a
        new signature.  Per-slot `propose` drafters own the clamp
        contract (<= k tokens, nothing past eos) — the tripwire below
        fails loudly instead of silently truncating, so a drafter bug
        can no longer masquerade as a low accept rate."""
        out = {}
        if not runnable or self.spec_k <= 0:
            return out
        cap = self.kv.capacity_tokens
        W = int(getattr(self.drafter, "window", 0)) or cap
        want = {}
        for s in runnable:
            sl = self.slots[s]
            k = min(self._dyn_k(sl), sl.req.max_new - sl.gen - 1,
                    cap - 1 - sl.pos)
            self.spec_k_hist.observe(float(max(k, 0)))
            if k > 0:
                want[s] = k
        if not want:
            return out
        took: list = []                  # the span's own clock pair
        with self._phase("draft", also=took.append, k=self.spec_k,
                         drafter=self.drafter_kind):
            if hasattr(self.drafter, "propose_batch"):
                out = self._propose_batched(want, W)
            else:
                for s, k in want.items():
                    sl = self.slots[s]
                    ctx = self._draft_ctx(s, W)
                    if self._drafter_takes_eos:
                        d = self.drafter.propose(ctx, k,
                                                 eos_id=sl.req.eos_id)
                    else:
                        d = self.drafter.propose(ctx, k)
                    d = np.asarray(d, np.int32).reshape(-1)
                    assert d.size <= k, \
                        f"drafter returned {d.size} tokens for k={k} — " \
                        f"the clamp contract is the drafter's (see " \
                        f"serving/drafter.py); truncating here would skew " \
                        f"accept-rate stats"
                    if d.size:
                        out[s] = d
        dt = took[0]
        self.draft_ms_hist.observe(dt * 1e3)
        if out:
            self.n_draft_steps += 1
            self.flight.record("draft_step", slots=len(out),
                               drafter=self.drafter_kind,
                               ms=round(dt * 1e3, 3))
        return out

    def _propose_batched(self, want: dict, W: int) -> dict:
        """ONE batched draft dispatch for every drafting slot: assemble
        the [S, W] windowed-context matrix (idle rows ride as length-1
        zero rows — S is the engine's slot count, fixed, so the
        draft-step signature is stable), call `propose_batch` at depth
        spec_k, then slice each slot's row to ITS dynamic k and cut at
        the -1 padding the drafter's eos clamp left."""
        S = len(self.slots)
        ctx = np.zeros((S, W), np.int32)
        lens = np.ones(S, np.int32)
        eos = np.full(S, -1, np.int32)
        for s in want:
            c = self._draft_ctx(s, W)
            ctx[s, :c.size] = c[-W:]
            lens[s] = max(int(c.size), 1)
            eos[s] = int(self.slots[s].req.eos_id)
        props = np.asarray(self.drafter.propose_batch(
            ctx, lens, self.spec_k, eos_ids=eos))
        out = {}
        for s, k in want.items():
            row = np.asarray(props[s, :k], np.int32).reshape(-1)
            stop = np.flatnonzero(row < 0)       # -1 = post-eos padding
            if stop.size:
                row = row[:int(stop[0])]
            if row.size:
                out[s] = row
        return out

    def _run_spec_step(self, live, runnable, filling, drafts) -> bool:
        """ONE speculative verify dispatch: every decoding slot packs a
        CHAIN of consecutive rows — its regular next-token row at `pos`
        plus up to k draft rows at pos+1..pos+k — and mid-prefill slots'
        chunk rows share the same dispatch (mode-aware packing).  Budget
        priority: decode base rows first (every decoder advances), then
        the chunk rows' RESERVE (each filling slot's share of a step, the
        mixed step's first pass — drafting can never starve a prompt's
        first token), and drafts spend only what is left.  The
        ragged attention core scatters ALL rows' K/V before reading, so
        draft row i attends the committed context plus drafts 1..i-1
        under the causal mask — precisely the context the sequential
        engine would have if those drafts were the true tokens.

        Acceptance is computed ON DEVICE (no host round trip inside the
        step): every chain position samples with the slot's own key for
        that generation index, the accepted length is the leading run of
        draft agreement, and pos/gen/last-token advance by accepted+1.
        The host then banks the emitted tokens through the ordinary
        `_bank_token` path (eos/max_new semantics unchanged — a chain
        truncates at eos exactly where the sequential stream would) and
        rolls back the page tail the rejection left unjustified
        (`kv.uncommit_tail` — the allocator's preempt-grade rollback).

        Chains need page cover for their deepest write; a page-starved
        slot verifies fewer drafts instead of stalling (the plain row
        needs only the page the runnable check already secured)."""
        S = len(self.slots)
        K = self.spec_k
        T = self.max_step_tokens
        ps = self.kv.page_size
        row_ids = np.zeros(T, np.int32)
        row_slot = np.full(T, S, np.int32)   # S = the virtual trash row
        row_pos = np.zeros(T, np.int32)
        first_row = np.zeros(S, np.int32)
        n_draft = np.zeros(S, np.int32)
        draft_toks = np.zeros((S, K), np.int32)
        spec = np.zeros(S, bool)
        emit = np.zeros(S, bool)
        adv_chunk = np.zeros(S, np.int32)
        r = 0
        # every decoding slot's base row is reserved BEFORE any draft or
        # chunk row spends budget — decoders advance every step whatever
        # the speculation does (the mixed step's HOL discipline)
        budget = T - len(runnable)
        assert budget >= 0, \
            "token budget below the decoding slot count (set_chunking " \
            "guarantees max_step_tokens > num_slots)"
        # ...and the chunk rows' share is reserved BEFORE any draft row:
        # speculation spends only what prefill leaves over, so drafting
        # decoders can never starve a mid-prefill prompt's chunks — the
        # first-token HOL bound chunked prefill exists for.  The reserve
        # is each filling slot's share of a step, as the mixed step's.
        budget -= sum(n for _, n in self._chunk_shares(filling, budget))
        for s in runnable:
            sl = self.slots[s]
            d = drafts.get(s)
            nd = 0 if d is None else min(int(d.size), budget)
            if nd > 0 and not self.kv.try_grow(s, sl.pos + nd + 1,
                                               evict=False):
                # page-starved chain: verify only what the slot's pages
                # cover (pages already grabbed stay with the slot — the
                # post-step uncommit returns whatever acceptance cannot
                # justify, so a dry pool shrinks ambition, never
                # wedges).  evict=False: optimistic draft pages must
                # never cost a committed cached prefix its retention —
                # a rejection would hand them back this very step
                nd = min(nd, max(0, int(self.kv._n_pages[s]) * ps
                                 - sl.pos - 1))
            for j in range(sl.pos // ps, (sl.pos + nd) // ps + 1):
                # the chain's whole write span must be private pages
                # (the decode tripwire, stretched over the draft tail)
                assert self.kv.page_writable(int(self.kv.table[s, j])), \
                    f"slot {s} chain would write shared page " \
                    f"{int(self.kv.table[s, j])}"
            row_ids[r] = sl.last_tok
            row_slot[r] = s
            row_pos[r] = sl.pos
            first_row[s] = r
            spec[s] = True
            emit[s] = True
            r += 1
            if nd > 0:
                row_ids[r:r + nd] = d[:nd]
                row_slot[r:r + nd] = s
                row_pos[r:r + nd] = np.arange(sl.pos + 1,
                                              sl.pos + 1 + nd)
                draft_toks[s, :nd] = d[:nd]
                n_draft[s] = nd
                self.n_spec_drafted += nd
                self.flight.record("spec_propose",
                                   req=str(sl.req.req_id), slot=s,
                                   k=int(nd), pos=int(sl.pos))
                budget -= nd
                r += nd
        # chunk rows take their reserve plus whatever the drafts left
        # unspent (T - r is exactly that); a final chunk's chain
        # position 0 is its last prompt row, sampled with keys[gen=0]
        advanced, r = self._pack_chunk_rows(
            filling, row_ids, row_slot, row_pos, first_row, adv_chunk,
            emit, r, T - r)
        self._sync_device_state()
        with self._compiled_step("spec", live=len(live), rows=r,
                                 decode_rows=len(runnable),
                                 step=self.n_decode_steps + 1) as launch:
            with self._phase("dispatch"):
                st, sampled, acc = self._spec_step(
                    self._step_params, self._build_state(),
                    self._stage(row_ids),
                    self._stage(row_slot), self._stage(row_pos),
                    self._stage(first_row), self._stage(n_draft),
                    self._stage(draft_toks), self._stage(spec),
                    self._stage(emit), self._stage(adv_chunk))
            self._unpack_state(st)
            self.n_decode_steps += 1
            self.n_spec_steps += 1
            if advanced:
                self.n_mixed_steps += 1
            self.n_step_pad_rows += T - r
            self.occupancy_sum += len(live) / S
            self._count_kv(row_pos + 1, row_slot, {  # a padding row reads 1
                "serving_mixed_steps_total": int(bool(advanced)),
                "serving_chunk_rows_total": sum(n for _, n, _ in advanced),
                "serving_step_pad_rows_total": T - r},
                head_rows=S * (K + 1))
            step = self.n_decode_steps
            with self._phase("readback", step=step, kind="spec"):
                sampled = np.asarray(sampled)              # host sync
                acc = np.asarray(acc)
            self._landed("spec", step, launch.t0, len(runnable))
            self._note_step_metrics(r, decoded=bool(runnable))
        with self._phase("emit", n=len(runnable), step=step, kind="spec"):
            for s in runnable:
                sl = self.slots[s]
                a = int(acc[s])
                nd = int(n_draft[s])
                self.n_spec_accepted += a
                self.n_spec_chains += 1
                if self.spec_dynamic and nd:
                    # feed the slot's accept EWMA BEFORE banking may retire
                    # it — the next flush window's _dyn_k steers by this.
                    # Draft-free rows (nd == 0) carry no signal: skipped, so
                    # a k=0 slot's estimate moves only on its paced probes.
                    rate = a / nd
                    sl.accept_ewma = rate if sl.accept_ewma is None else \
                        (1.0 - _EWMA_ALPHA) * sl.accept_ewma \
                        + _EWMA_ALPHA * rate
                if nd:
                    rid = str(sl.req.req_id)
                    self._bump_attr(sl.req.req_id, "spec_drafted", nd)
                    if a:
                        self._bump_attr(sl.req.req_id, "spec_accepted", a)
                        self.flight.record("spec_accept", req=rid, slot=s,
                                           accepted=a, drafted=nd)
                    if nd > a:
                        self.flight.record("spec_reject", req=rid, slot=s,
                                           rejected=nd - a, drafted=nd)
                # host page rollback BEFORE banking: banking may retire the
                # slot (eos / max_new), and retire releases every mapping —
                # while the slot is live, pages past pages_for(pos + a + 1)
                # hold only rejected-draft garbage
                self.kv.uncommit_tail(s, sl.pos + a + 1)
                for i in range(a + 1):
                    self._bank_token(s, int(sampled[s, i]))
                    self.n_spec_tokens += 1
                    if self.slots[s] is None:     # retired mid-chain (eos)
                        break
            self._advance_chunks(advanced, lambda s: int(sampled[s, 0]))
        return True

    def run(self, requests=()) -> dict:
        """Add `requests`, drive step() to completion, and POP
        {req_id: np.int32 tokens (prompt + generated, eos included)} for
        everything that completed during this call (including requests
        queued before it) — earlier, already-collected runs don't bleed
        in, and a long-lived engine holds no unbounded result archive."""
        done_before = set(self.results)
        for r in requests:
            self.add_request(r)
        while self.step():
            pass
        out = {k: self.results.pop(k) for k in list(self.results)
               if k not in done_before}
        for k in out:
            self.finish_reasons.pop(k, None)
            self.finish_timing.pop(k, None)
        return out

    # -- scheduling internals --------------------------------------------
    def _admit_from_queue(self) -> None:
        for s in range(len(self.slots)):
            if not self.queue:
                return
            if self.slots[s] is not None:
                continue
            req = self.queue[0]
            res = self._reserve(s, req)
            if res is None:
                # page-starved: keep FIFO order, retry later (_reserve
                # already rolled the slot back to empty — pages stranded
                # on it would be invisible to a retry on a different slot)
                return
            self.queue.popleft()
            self._admit(s, req, *res)

    def _reserve(self, s: int, req: Request):
        """Map any cached prefix into empty slot `s` and allocate the
        remaining pages for the whole prompt.  Returns (matched_tokens,
        matched_pages) on success, None on page starvation (slot rolled
        back to empty).

        The prefix walk caps at prompt_len - 1 tokens: at least one token
        always prefills, because sampling token 0 needs the last prompt
        position's logits.  A partial-run boundary match maps one page the
        request will WRITE into mid-run, so it is copy-on-written here, at
        reservation time — the request's divergent suffix must never touch
        the shared original.  The COW runs AFTER the suffix pages are
        secured: a page-starved reservation then fails at try_grow before
        paying the device copy, instead of repeating copy + n_cow +
        flight event on every retry step while the queue head is stuck.

        If the shared mapping cannot be completed (COW page or suffix
        pages unavailable even after eviction), the whole reservation
        rolls back and admission retries COLD: the just-unmapped prefix
        pages drop to refcount zero, so the cold attempt's page-pressure
        eviction can reclaim them — holding them mapped would starve the
        very admission they were meant to speed up (livelock).

        KV SPILL TIER: when the matched path ends in spilled (HOST) runs,
        _restore_spilled faults them back to device FIRST — fresh pages,
        one batched host->device scatter, promote — and the hit then maps
        exactly like an always-device one.  Every restore failure mode
        (budget-starved allocation, a stale host generation, the matched
        device path lost to the restore's own pressure eviction) rolls
        back completely and falls through to cold admission, which the
        exactness oracles prove produces identical tokens."""
        p = req.prompt_ids.size
        if self.prefix is not None:
            nodes, partial = self.prefix.match_nodes(req.prompt_ids[:p - 1])
            path = list(nodes) + ([partial[0]] if partial is not None
                                  else [])
            host_tail = [nd for nd in path if nd.host_id is not None]
            if host_tail and not self._restore_spilled(req, path, host_tail):
                path, partial = [], None        # rolled back: admit cold
            if path:
                mapped = [nd.page for nd in path]
                self.kv.map_shared(s, mapped)
                C = len(nodes) * self.kv.page_size + \
                    (partial[1] if partial is not None else 0)
                ok = self.kv.try_grow(s, p)
                if ok and partial is not None:
                    cow = self.kv.ensure_writable(s, len(mapped) - 1)
                    ok = cow is not None
                    if cow:
                        self.flight.record("prefix_cow",
                                           req=str(req.req_id),
                                           page=int(mapped[-1]),
                                           matched_in_page=int(partial[1]))
                if ok:
                    if host_tail:
                        self.n_restore_hits += 1
                        # tokens of C served from restored pages: the
                        # device-resident full runs cover the first
                        # dev_full * page_size of the match, the rest
                        # (full HOST runs + a HOST boundary's partial
                        # tokens) came back from the host tier
                        dev_full = sum(1 for nd in nodes
                                       if nd not in host_tail)
                        self.restore_tokens_saved += \
                            C - dev_full * self.kv.page_size
                    return (C, len(mapped))
                self.kv.release(s)
        if self.kv.try_grow(s, p):
            return (0, 0)
        self.kv.release(s)
        return None

    def _restore_spilled(self, req: Request, path, host_tail) -> bool:
        """Fault a matched path's spilled tail back to device: take fresh
        pages (spill inhibited, so the host tier — and these very entries
        — can't churn under the allocation's pressure evictions), one
        batched scatter, re-mark cached, promote the nodes.  False = full
        rollback happened and the caller admits cold.  Page counts here
        ride a bucketed jit at the admission boundary — the decode/mixed/
        spec step signatures never move (the compile-watch oracle)."""
        kv, tree = self.kv, self.prefix
        if not all(kv.host_entry_live(nd.host_id) for nd in host_tail):
            # a dead generation (kv.reset without tree.clear — the
            # checkpoint/restore seam) must never resurrect: drop the
            # zombie subtree from its topmost host node and admit cold
            tree.drop_host_subtree(host_tail[0])
            return False
        dev_nodes = [nd for nd in path if nd.host_id is None]
        tree._spill_inhibit = True
        try:
            pages = kv.take_pages(len(host_tail))
        finally:
            tree._spill_inhibit = False
        if pages is None:
            return False
        # the allocation's own eviction ran over the tree: verify the
        # matched DEVICE prefix survived (LRU makes just-touched nodes
        # the last victims, so this only trips when the pool is so small
        # the reservation is infeasible anyway) and the host entries too
        # (destroying a device ancestor drops its host subtree)
        if any(nd.page <= 0 or nd.host_id is not None
               for nd in dev_nodes) or \
                not all(kv.host_entry_live(nd.host_id)
                        for nd in host_tail):
            kv.untake_pages(pages)
            return False
        kv.restore_pages([nd.host_id for nd in host_tail], pages)
        kv.adopt_restored(pages)
        tree.promote(host_tail, pages)
        self.flight.record("restore", req=str(req.req_id),
                           pages=len(pages),
                           host_pages=kv.host_page_count)
        return True

    # -- cross-replica kv transfer (docs/serving.md "Disaggregated
    # prefill/decode") -----------------------------------------------------
    def export_prefix(self, tokens):
        """Serialize the longest DEVICE-resident whole-page cached prefix
        of `tokens` for a kv_push: returns (covered_tokens, meta, payload)
        or None when nothing is cached.  Pump thread only (walks the
        prefix tree and gathers from the pools between steps)."""
        self.kv.refuse("export")
        if self.prefix is None:
            return None
        self.settle()
        toks = np.asarray(tokens, np.int32).reshape(-1)
        pages, _ = self.prefix.match(toks)
        if not pages:
            return None
        n_tok = len(pages) * self.kv.page_size
        meta, payload = self.kv.export_pages(pages)
        return toks[:n_tok], meta, payload

    def import_prefix(self, tokens, meta: dict, payload: bytes) -> int:
        """Mount a kv_push blob into the prefix tree: take fresh pages,
        scatter the wire bytes in (one bucketed dispatch — the spill
        tier's restore jit), adopt + insert so the NEXT admission of this
        prompt is a prefix hit instead of a re-prefill.  Raises ValueError
        — with the allocator rolled back exactly (`check()` green) — on
        a malformed blob or page starvation; returns nodes newly added.
        Pump thread only: kv.pools is authoritative between steps, so the
        scatter is exactly as safe as an admission-time spill restore."""
        self.kv.refuse("import")
        if self.prefix is None:
            raise ValueError("kv import: prefix cache is disabled")
        self.settle()
        toks = np.asarray(tokens, np.int32).reshape(-1)
        n = int(meta.get("n_pages", 0))
        ps = self.kv.page_size
        if n <= 0 or toks.size != n * ps:
            raise ValueError(
                f"kv import: {toks.size} tokens do not cover "
                f"{n} pages x {ps}")
        pages = self.kv.take_pages(n)
        if pages is None:
            raise ValueError(
                f"kv import: pool cannot cover {n} fresh pages")
        try:
            self.kv.import_pages(meta, payload, pages)
        except (ValueError, AssertionError):
            # import_pages' freshness preconditions are asserts; the
            # server's pump handler treats both as a clean refusal, so
            # both must roll the taken pages back or they leak
            self.kv.untake_pages(pages)
            raise
        self.kv.adopt_restored(pages)
        added = self.prefix.insert(toks, pages, adopted=True)
        self.n_kv_mounts += 1
        self.kv_pages_mounted += n
        self.flight.record("kv_recv", pages=n, mounted=added)
        return added

    def _admit(self, s: int, req: Request, C: int = 0,
               n_pp: int = 0) -> None:
        """Chunk-granular admission — NO prefill dispatch: the slot enters
        PREFILL mode (gen=0) with its prompt pages already reserved, and
        the prompt commits in runs of rows inside the next mixed steps
        (_launch_mixed: `prefill_chunk` a step at least, more where the
        step has rows free).  A prefix hit just means the first
        `C` tokens are already mapped — the chunk cursor starts at C, and
        a mid-page start writes into the boundary page _reserve COW'd.
        Token 0 is sampled by the step that runs the FINAL chunk; until
        then the slot emits nothing.

        A re-admission after preemption keeps req._preempted_gen: until the
        deterministic replay catches up, an abort must still report those
        already-delivered tokens (cancel's mid-replay branch).  A later
        preemption simply overwrites it with the longer prefix."""
        self._tr_end(req.req_id)                       # queued ends here
        p = req.prompt_ids.size
        keys = np.asarray(jax.random.split(req.rng, req.max_new))
        if self.prefix is not None and C > 0:
            self.n_prefix_hits += 1
            self.prefill_tokens_saved += C
            self._tr_instant(req.req_id, "prefix_hit", n_pages=n_pp,
                             tokens=C)
            self.flight.record("prefix_hit", req=str(req.req_id),
                               pages=n_pp, tokens=C, suffix=p - C)
        elif self.prefix is not None:
            self.n_prefix_misses += 1
            self.flight.record("prefix_miss", req=str(req.req_id),
                               prompt_len=int(p))
        self._admit_seq += 1
        self.slots[s] = _Slot(req, keys, pos=C, admit_seq=self._admit_seq)
        self._slots_dirty = True
        self._tr_begin(req.req_id, "prefill",
                       chunk=int(self.prefill_chunk), prompt_len=p,
                       prefix_tokens=C)
        self.flight.record("admit", req=str(req.req_id), slot=s,
                           prompt_len=p, chunk=int(self.prefill_chunk),
                           prefix_tokens=C,
                           pages=int(self.kv.pages_for(p)))

    def _emit_first(self, s: int, tok0: int) -> None:
        """Final-chunk emission: the slot's whole prompt is committed and
        `tok0` was sampled from the last prompt position's logits with
        keys[0] — the same key schedule lm_generate consumes.  Flips the
        slot into decode mode and streams token 0: opens the decode/replay
        lifecycle phase, fires on_token(.., 0), retires on eos/max_new=1."""
        sl = self.slots[s]
        req = sl.req
        sl.gen = 1
        sl.last_tok = tok0
        sl.generated = [tok0]
        self._tr_end(req.req_id)                       # prefill ends here
        stash = req._preempted_gen or []
        if stash:
            # tokens 0..len(stash)-1 re-emit deterministically — a replay
            # span until the first fresh token (step()'s flip)
            sl.replay_until = len(stash)
            self._tr_begin(req.req_id, "replay", replays=len(stash))
        else:
            self._tr_begin(req.req_id, "decode")
        self.tokens_generated += 1
        if self.on_token is not None:
            self.on_token(req.req_id, tok0, 0)
        if tok0 == req.eos_id or req.max_new == 1:
            self._retire(s)

    def _preempt(self, s: int) -> None:
        assert self._pending is None, "preempt with a step in flight"
        sl = self.slots[s]
        rid = sl.req.req_id
        self._tr_end(rid, tokens=sl.gen)      # decode/replay ends here
        self._tr_instant(rid, "preempt")
        self._tr_begin(rid, "queued", requeued=True)
        self.queue.appendleft(sl.req)
        old = sl.req._preempted_gen or []
        if len(sl.generated) >= len(old):     # a re-preempt mid-replay
            sl.req._preempted_gen = list(sl.generated)  # keeps the longer
        self.tokens_generated -= sl.gen       # the restart re-emits them
        self._bump_attr(rid, "preempts")
        self.n_preemptions += 1
        self.flight.record("preempt", req=str(rid), slot=s,
                           tokens=sl.gen,
                           free_pages=int(self.kv.free_page_count))
        # donate before releasing: the victim's committed pages become
        # cached refcount-zero (evictable under the very pressure that
        # caused this preempt), and its re-admission prefix-hits its own
        # prompt — the deterministic replay skips the prefill it already
        # paid for
        self._donate(s)
        self.kv.release(s)
        self.slots[s] = None
        self._slots_dirty = True

    def _donate(self, s: int) -> None:
        """Offer the slot's fully-committed clean pages to the prefix
        index (retire/preempt/abort).  Only WHOLE pages strictly below
        `pos` qualify — every position in them holds committed K/V; the
        partial boundary page (and the not-yet-written last token) stay
        private and free normally.  The index retains via the allocator's
        cached mark, so the subsequent release drops these pages to
        cached-only instead of freeing them."""
        if self.prefix is None:
            return
        sl = self.slots[s]
        full = int(sl.pos) // self.kv.page_size
        if full <= 0:
            return
        seq = np.concatenate([sl.req.prompt_ids,
                              np.asarray(sl.generated, np.int32)])
        self.prefix.insert(seq[:full * self.kv.page_size],
                           [int(self.kv.table[s, j]) for j in range(full)])

    def _assert_idle(self, what: str) -> None:
        """The knobs that need an idle engine: nothing queued, no slot
        held — and no step in flight (a request that ended on eos may
        have left its last row there)."""
        self.settle()
        assert all(sl is None for sl in self.slots) and not self.queue, \
            f"{what} requires an idle engine"

    def reset_prefix_cache(self) -> None:
        """Full allocator cold start (idle engine only): release every
        slot mapping, forget all prefix retention, rebuild the free list
        in canonical order (kv.reset) AND clear the index — page
        placement afterwards is bit-reproducible across engine restarts
        (exactness tests and postmortem engine.json snapshots stay
        stable)."""
        self._assert_idle("reset_prefix_cache")
        self.kv.reset()
        if self.prefix is not None:
            self.prefix.clear()

    def set_chunking(self, prefill_chunk: int,
                     max_step_tokens: Optional[int] = None) -> None:
        """Configure chunked prefill (idle engine only — a live slot may
        be mid-chunk).  `prefill_chunk` (default 4*page_size) is a
        filling prompt's SHARE of a mixed step and `max_step_tokens`
        (default prefill_chunk + num_slots) the step's bound: one row per
        decoding slot, then each chunking prompt's share, then the rows
        still free to the oldest prompt — never more than the budget per
        step, the p99 inter-token bound.  Each distinct max_step_tokens
        value is one mixed-step signature; hold it fixed in production."""
        self._assert_idle("set_chunking")
        if prefill_chunk is None:
            raise ValueError(_NO_UNCHUNKED)
        self._mst_explicit = max_step_tokens is not None
        prefill_chunk = int(prefill_chunk)
        if prefill_chunk <= 0:
            raise ValueError(
                f"prefill_chunk must be positive, got {prefill_chunk}")
        prefill_chunk = min(prefill_chunk, self.kv.capacity_tokens)
        S = len(self.slots)
        mst = self._default_budget(prefill_chunk, S,
                                   getattr(self, "spec_k", 0)) \
            if max_step_tokens is None else int(max_step_tokens)
        if mst <= S:
            raise ValueError(
                f"max_step_tokens {mst} must exceed num_slots {S}: every "
                f"decoding slot takes one row per step, and prefill "
                f"chunks need at least one row of headroom to ever make "
                f"progress")
        if self.kv.ring_specs and mst > self.kv.step_tokens:
            raise ValueError(
                f"max_step_tokens {mst} exceeds the {self.kv.step_tokens} "
                f"rows a step the window layers' rings of pages were sized "
                f"for when the engine was built "
                f"({max(self.kv.ring_specs.values())} pages a slot): build "
                f"the engine with the larger budget")
        self.prefill_chunk = prefill_chunk
        self.max_step_tokens = mst

    @staticmethod
    def _default_budget(prefill_chunk: int, num_slots: int,
                        spec_k: int = 0) -> int:
        """The defaulted token budget: one chunk of prefill headroom
        plus a FULL chain per slot — `chunk + S` with speculation off
        (the classic default), `chunk + S*(spec_k+1)` with it on, so a
        default deployment's draft depth is never silently throttled to
        the chunk headroom."""
        return int(prefill_chunk) + int(num_slots) * (int(spec_k) + 1)

    def set_speculation(self, spec_k: int, drafter=None,
                        dynamic: Optional[bool] = None) -> None:
        """Configure speculative decoding (idle engine only — a live
        chain would straddle the toggle).  `spec_k=0` disables; `spec_k > 0`
        drafts up to k lookahead tokens per decoding slot per step
        (serving/drafter.py's prompt-lookup NgramDrafter by default;
        pass `drafter` for anything with a `.propose(ctx, k)` — a
        ModelDrafter slots in here and additionally gets the batched
        `propose_batch` path).  Emitted tokens are IDENTICAL either way;
        only steps-per-token changes.  Each distinct (token budget,
        spec_k) pair is ONE verify-step signature — hold both fixed in
        production.  `dynamic=True` turns on the per-slot EWMA depth
        policy (see `_dyn_k`); it changes HOST-side slicing only, so it
        adds zero signatures and — by the verify step's exactness — zero
        token differences."""
        self._assert_idle("set_speculation")
        spec_k = int(spec_k)
        if spec_k < 0:
            raise ValueError(
                f"spec_k must be >= 0 (0 = speculation off), got {spec_k}")
        if spec_k > 0:
            self.kv.refuse("spec")
        self.spec_k = spec_k
        if dynamic is not None:
            self.spec_dynamic = bool(dynamic)
        if not self._mst_explicit:
            # a DEFAULTED budget follows the speculation depth (chunk +
            # S*(k+1)): otherwise `--spec-k` deployments would silently
            # throttle draft rows to the chunk headroom.
            # An explicit budget is the operator's pin — untouched.
            self.max_step_tokens = self._default_budget(
                self.prefill_chunk, len(self.slots), spec_k)
        if drafter is not None:
            self.drafter = drafter
        elif self.drafter is None and spec_k > 0:
            from paddle_tpu.serving.drafter import NgramDrafter
            self.drafter = NgramDrafter()
        # the eos clamp rides propose(ctx, k, eos_id=...) — but drafters
        # predate that parameter (tests and deployments define 2-arg
        # propose), so sniff the signature ONCE here, not per proposal
        self._drafter_takes_eos = False
        if self.drafter is not None and \
                not hasattr(self.drafter, "propose_batch"):
            import inspect
            try:
                self._drafter_takes_eos = "eos_id" in \
                    inspect.signature(self.drafter.propose).parameters
            except (TypeError, ValueError):
                self._drafter_takes_eos = False

    @property
    def drafter_kind(self) -> Optional[str]:
        """The configured drafter's self-declared kind ("ngram",
        "model", ... — stats/hello frames report it), or None."""
        return getattr(self.drafter, "kind", None) \
            if self.drafter is not None else None

    @property
    def spec_accept_rate(self) -> float:
        """Accepted / drafted over the engine lifetime (0.0 before any
        draft was scored) — the number PERF.md 'Reading the accept
        rate' interprets."""
        return (self.n_spec_accepted / self.n_spec_drafted
                if self.n_spec_drafted else 0.0)

    def set_prefix_cache(self, enabled: bool) -> None:
        """A/B knob (the same engine with the prefix cache off, then
        on): disabling detaches AND empties the
        index — every node's page drops its cached retention, so pages
        still mapped by live slots stay with their slots and free through
        the normal release flow — leaving nothing for a baseline run to
        match; enabling attaches a fresh empty index."""
        if enabled == (self.prefix is not None):
            return
        self.settle()
        if enabled:
            self.kv.refuse("prefix")
            self.prefix = PrefixTree(self.kv)
            self.kv.on_page_pressure = self._evict_for
            return
        stack = list(self.prefix.root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.host_id is not None:
                # spilled nodes drain the HOST tier, not the device
                # allocator — leaving the entry would orphan host bytes
                # against the budget forever (no node names them again)
                self.kv.drop_host_page(node.host_id, reason="drain")
                node.host_id = None
            else:
                self.kv.uncache_page(node.page)
        self.prefix = None
        self.kv.on_page_pressure = None
        self.kv.on_cached_unmapped = None

    def set_spill_budget(self, spill_bytes_budget: int) -> None:
        """A/B knob (the same engine spill-off, then on): sets the host
        tier's byte budget.
        Shrinking below current residency drops LRU HOST leaves until
        the tier fits (0 drains it entirely) — never device state, so
        an idle-engine flip is allocator-exact either way."""
        self._assert_idle("set_spill_budget")
        self.kv.spill_bytes_budget = int(spill_bytes_budget or 0)
        while self.prefix is not None and \
                self.kv.host_bytes > self.kv.spill_bytes_budget:
            leaves = self.prefix._host_leaves()
            assert leaves, "host tier non-empty but no HOST leaf found"
            self.prefix._drop_host_node(
                min(leaves, key=lambda n: n.last_use))

    # -- serving-state checkpoint/restore (fleet-migration primitive) ------
    #: the scheduling counters a snapshot carries; restore_state sets these
    #: and no other key an older snapshot's "counters" may hold
    _SNAPSHOT_COUNTERS = (
        "_admit_seq", "n_decode_steps", "n_preemptions",
        "n_cancelled", "n_expired", "tokens_generated",
        "occupancy_sum", "kv_tokens_attended", "kv_tokens_fetched",
        "n_kv_rows", "n_kv_shared_rows", "n_head_rows",
        "n_prefix_hits", "n_prefix_misses",
        "prefill_tokens_saved", "n_restore_hits",
        "restore_tokens_saved", "n_prefill_chunks",
        "n_chunk_rows", "n_chunk_extra_rows", "n_step_pad_rows",
        "n_window_pages_recycled", "n_window_rows", "n_window_steps",
        "n_mhc_rows", "n_mhc_calls",
        "n_mixed_steps", "n_spec_steps", "n_spec_chains",
        "n_spec_drafted", "n_spec_accepted", "n_spec_tokens",
        "n_draft_steps")

    def checkpoint_state(self) -> dict:
        """Freeze the ENTIRE serving state MID-FLIGHT — device pytree
        (pools as host copies), allocator, slots, queue, prefix index,
        scheduling counters — as one picklable dict.  A fresh engine of
        the same configuration restored from it resumes and finishes
        BIT-EXACTLY what the uninterrupted engine would have produced
        (tests/test_engine_state.py): per-slot key schedules, admit_seq
        preemption order, free-list order and page placement all survive.
        Call between steps on the step()-driving thread (the pump), like
        every other scheduler access.  This is the checkpoint/restore +
        live-replica-migration unit the EngineState refactor unlocks."""

        def req_snap(r: Request) -> dict:
            return {"req_id": r.req_id, "prompt_ids": r.prompt_ids.copy(),
                    "max_new": r.max_new, "temperature": r.temperature,
                    "top_k": r.top_k, "top_p": r.top_p, "eos_id": r.eos_id,
                    "deadline": r.deadline, "trace": r.trace,
                    "preempted_gen": (None if r._preempted_gen is None
                                      else list(r._preempted_gen)),
                    "rng": np.asarray(r.rng).copy()}

        self.settle()       # the mirrors below are one step behind else
        kv = self.kv
        prefix = None
        if self.prefix is not None:
            nodes = []
            stack = [(self.prefix.root, -1)]
            while stack:
                node, pidx = stack.pop()
                idx = len(nodes)
                nodes.append({"run": list(node.run), "page": node.page,
                              "host_id": node.host_id,
                              "last_use": node.last_use, "parent": pidx})
                stack.extend((ch, idx) for ch in node.children.values())
            prefix = {"nodes": nodes, "clock": self.prefix._clock,
                      "n_evictions": self.prefix.n_evictions}
        return {
            "config": {"num_slots": len(self.slots),
                       "page_size": kv.page_size,
                       "pages_per_slot": kv.pages_per_slot,
                       "num_pages": kv.num_pages,
                       "prefill_chunk": self.prefill_chunk,
                       "max_step_tokens": self.max_step_tokens,
                       "spec_k": self.spec_k,
                       "prefix_cache": self.prefix is not None,
                       "spill_bytes_budget": kv.spill_bytes_budget,
                       "layer_specs": dict(kv.layer_specs),
                       **({"slot_specs": dict(kv.slot_specs)}
                          if kv.slot_specs else {})},
            "pools": {name: {p: np.asarray(a).copy()
                             for p, a in pool.items()}
                      for name, pool in kv.pools.items()},
            "kv": {"table": kv.table.copy(), "free": list(kv._free),
                   "n_pages": kv._n_pages.copy(), "ref": kv._ref.copy(),
                   "cached": kv._cached.copy(), "n_cow": kv.n_cow,
                   # host spill tier SERIALIZES INTO the bundle (the
                   # documented choice over re-faulting: a migrated
                   # replica keeps its whole effective cache, and the
                   # spilled runs' restore-on-hit stays bit-exact on the
                   # target) — generations re-stamp on restore
                   "host": {hid: {"nbytes": e["nbytes"],
                                  "data": {name: {p: a.copy() for p, a
                                                  in parts.items()}
                                           for name, parts
                                           in e["data"].items()}}
                            for hid, e in kv._host.items()},
                   "next_hid": kv._next_hid,
                   "spill_counters": (kv.n_spilled, kv.n_restored,
                                      kv.n_host_evicted,
                                      kv._host_drained)},
            "slots": [None if sl is None else
                      {"req": req_snap(sl.req),
                       "keys": np.asarray(sl.keys).copy(),
                       "pos": int(sl.pos), "gen": int(sl.gen),
                       "last_tok": int(sl.last_tok),
                       "generated": list(sl.generated),
                       "admit_seq": int(sl.admit_seq),
                       "replay_until": int(sl.replay_until),
                       # dynamic-speculation estimate rides the slot: a
                       # migrated replica keeps its learned per-slot k
                       # instead of re-probing from cold
                       "accept_ewma": sl.accept_ewma,
                       "probe_tick": int(sl.probe_tick)}
                      for sl in self.slots],
            "queue": [req_snap(r) for r in self.queue],
            "prefix": prefix,
            "counters": {k: getattr(self, k)
                         for k in self._SNAPSHOT_COUNTERS},
            "results": {k: np.asarray(v).copy()
                        for k, v in self.results.items()},
            "finish_reasons": dict(self.finish_reasons),
        }

    def restore_state(self, snap: dict) -> None:
        """Resume a `checkpoint_state()` snapshot on THIS engine (fresh or
        idle; its construction-time configuration must match the donor's
        — restoring onto a differently-shaped engine would silently
        corrupt page accounting, so it raises instead).  Device state
        re-uploads lazily through the ordinary dirty-sync paths."""
        cfg = snap["config"]
        if cfg.get("prefill_chunk") is None:
            raise ValueError("restore_state: " + _NO_UNCHUNKED)
        mine = {"num_slots": len(self.slots),
                "page_size": self.kv.page_size,
                "pages_per_slot": self.kv.pages_per_slot,
                "num_pages": self.kv.num_pages,
                "prefill_chunk": self.prefill_chunk,
                "max_step_tokens": self.max_step_tokens,
                "spec_k": self.spec_k,
                "prefix_cache": self.prefix is not None,
                "spill_bytes_budget": self.kv.spill_bytes_budget,
                "layer_specs": dict(self.kv.layer_specs),
                **({"slot_specs": dict(self.kv.slot_specs)}
                   if self.kv.slot_specs else {})}
        if mine != cfg:
            diff = {k: (cfg[k], mine[k]) for k in cfg if cfg[k] != mine[k]}
            raise ValueError(
                f"restore_state: engine configuration mismatch "
                f"(snapshot vs this engine): {diff}")
        self.settle()
        if any(sl is not None for sl in self.slots) or self.queue:
            raise ValueError("restore_state requires an idle engine — it "
                             "replaces every slot and queue entry")

        def req_restore(d: dict) -> Request:
            r = Request(d["req_id"], d["prompt_ids"],
                        max_new=d["max_new"], temperature=d["temperature"],
                        top_k=d["top_k"], top_p=d["top_p"],
                        eos_id=d["eos_id"], deadline=d["deadline"],
                        trace=d.get("trace"))
            r.rng = jnp.asarray(d["rng"])
            r._preempted_gen = (None if d["preempted_gen"] is None
                                else list(d["preempted_gen"]))
            return r

        kv = self.kv
        for name in kv.pools:
            put = ((lambda a: jax.device_put(a, self._pool_sharding))
                   if self._pool_sharding is not None else jnp.asarray)
            kv.pools[name] = {
                p: put(np.asarray(snap["pools"][name][p], a.dtype))
                for p, a in kv.pools[name].items()}
        kv.table[:, :] = snap["kv"]["table"]
        kv._free = list(snap["kv"]["free"])
        kv._n_pages[:] = snap["kv"]["n_pages"]
        kv._ref[:] = snap["kv"]["ref"]
        kv._cached[:] = snap["kv"]["cached"]
        kv.n_cow = snap["kv"]["n_cow"]
        # host spill tier: adopt the bundle's entries under THIS engine's
        # current generation (the donor's gen counter is process-local;
        # every serialized entry was live by construction — its tree node
        # rebuilds below and names it).  Drain any pre-restore tree FIRST
        # — its nodes' hids would otherwise collide with the bundle's hid
        # space when the post-rebuild clear() walks them
        if self.prefix is not None:
            self.prefix.clear()
        kv._host_drained += len(kv._host)
        kv._host = {int(hid): {"gen": kv._host_gen,
                               "nbytes": int(e["nbytes"]),
                               "data": {name: {p: np.asarray(a) for p, a
                                               in parts.items()}
                                        for name, parts
                                        in e["data"].items()}}
                    for hid, e in snap["kv"].get("host", {}).items()}
        kv._host_bytes = sum(e["nbytes"] for e in kv._host.values())
        kv._next_hid = int(snap["kv"].get("next_hid", kv._next_hid))
        (kv.n_spilled, kv.n_restored, kv.n_host_evicted,
         kv._host_drained) = snap["kv"].get(
            "spill_counters", (kv.n_spilled, kv.n_restored,
                               kv.n_host_evicted, kv._host_drained))
        kv.version += 1
        self.slots = [None if d is None else
                      _Slot.__new__(_Slot) for d in snap["slots"]]
        for sl, d in zip(self.slots, snap["slots"]):
            if sl is None:
                continue
            sl.req = req_restore(d["req"])
            sl.keys = np.asarray(d["keys"], np.uint32)
            sl.pos, sl.gen = d["pos"], d["gen"]
            sl.last_tok = d["last_tok"]
            sl.generated = list(d["generated"])
            sl.admit_seq = d["admit_seq"]
            sl.replay_until = d["replay_until"]
            sl.accept_ewma = d.get("accept_ewma")
            sl.probe_tick = int(d.get("probe_tick", 0))
        self.queue = deque(req_restore(d) for d in snap["queue"])
        if self.prefix is not None:
            self.prefix.clear()
            if snap["prefix"] is not None:
                from paddle_tpu.serving.prefix_tree import _Node
                built = []
                for nd in snap["prefix"]["nodes"]:
                    node = _Node(tuple(nd["run"]), nd["page"],
                                 None if nd["parent"] < 0
                                 else built[nd["parent"]])
                    node.host_id = nd.get("host_id")
                    node.last_use = nd["last_use"]
                    if node.parent is not None:
                        node.parent.add_child(node)
                    built.append(node)
                self.prefix.root = built[0]
                self.prefix.n_nodes = len(built) - 1
                self.prefix._clock = snap["prefix"]["clock"]
                self.prefix.n_evictions = snap["prefix"]["n_evictions"]
                self.prefix.rebuild()
        for k in self._SNAPSHOT_COUNTERS:
            if k in snap["counters"]:
                setattr(self, k, snap["counters"][k])
        self.results = {k: np.asarray(v).copy()
                        for k, v in snap["results"].items()}
        self.finish_reasons = dict(snap["finish_reasons"])
        self._slots_dirty = True
        self._d_toks = None             # the snapshot's last tokens rule
        self._run_host = None
        self._t_prev_decode = None
        # latency attribution across a migration: perf_counter epochs are
        # per-process, so pre-restore phase clocks cannot carry over —
        # re-open each live request's CURRENT phase at now (the breakdown
        # charges post-restore time only; the donor's time was reported
        # by the donor had it finished there)
        now = time.perf_counter()
        self._req_phase = {}
        self._req_attr = {}
        self._req_trace = {}
        for r in self.queue:
            self._req_phase[r.req_id] = ("queued", now)
            if r.trace:
                self._req_trace[r.req_id] = r.trace
        for sl in self.slots:
            if sl is None:
                continue
            phase = ("prefill" if sl.gen == 0 else
                     "replay" if sl.replay_until and
                     sl.gen < sl.replay_until else "decode")
            self._req_phase[sl.req.req_id] = (phase, now)
            if sl.req.trace:
                self._req_trace[sl.req.req_id] = sl.req.trace
        kv.check()                      # allocator oracle on the restored
                                        # tables/refcounts — fail loudly
        if self.prefix is not None:     # and the index's, on what it kept
            self.prefix.check_invariants()
        self.flight.record("restore", slots=sum(
            1 for sl in self.slots if sl is not None),
            queued=len(self.queue))

    def save_state(self, path: str) -> None:
        """checkpoint_state() to disk with the repo's atomic-commit
        discipline (stage + fsync + os.replace): a crash mid-save leaves
        the previous checkpoint intact, never a torn one."""
        import os
        import pickle

        tmp = f"{path}.tmp"
        with open(tmp, "wb") as f:
            pickle.dump(self.checkpoint_state(), f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def load_state(self, path: str) -> None:
        import pickle

        with open(path, "rb") as f:
            self.restore_state(pickle.load(f))

    def _retire(self, s: int) -> None:
        sl = self.slots[s]
        toks = np.concatenate(
            [sl.req.prompt_ids,
             np.asarray(sl.generated, np.int32)]).astype(np.int32)
        reason = "stop" if sl.last_tok == sl.req.eos_id else "length"
        self._donate(s)
        self.kv.release(s)
        self.slots[s] = None
        self._slots_dirty = True
        self._finish(sl.req.req_id, toks, reason)

    def _finish(self, req_id, toks: np.ndarray, reason: str) -> None:
        # close whatever lifecycle phase is open (queued for an aborted
        # waiter, decode/replay for an in-slot finish) and mark the
        # terminal event: done (stop/length), cancelled, or deadline
        self._tr_end(req_id, reason=reason)
        self._tr_instant(req_id,
                         "done" if reason in ("stop", "length") else reason,
                         reason=reason, tokens=int(toks.size))
        self.flight.record("finish", req=str(req_id), reason=reason,
                           tokens=int(toks.size))
        self.finish_timing[req_id] = self._finish_timing(req_id)
        self._req_trace.pop(req_id, None)
        self.results[req_id] = toks
        self.finish_reasons[req_id] = reason
        if self.on_finish is not None:
            self.on_finish(req_id, toks, reason)

    # -- compiled pieces --------------------------------------------------
    def _slot_keys(self, st: EngineState) -> jnp.ndarray:
        """Each slot's key for THIS step: keys[s, gen[s]] — key g samples
        token g, so a paused slot (gen frozen) consumes nothing and a
        final prompt chunk (gen still 0) samples with keys[0], exactly
        lm_generate's first decision."""
        g = jnp.clip(st.gen, 0, st.keys.shape[1] - 1)
        return jnp.take_along_axis(st.keys, g[:, None, None], axis=1)[:, 0]

    def _decode_impl(self, params, st: EngineState, run):
        """THE decode step — one signature for the whole workload: every
        slot advances one token against its paged context; per-slot
        knobs/keys make sampling data-dependent, not program-dependent.
        A pure function over the EngineState pytree: slots the run mask
        marks advance pos/gen/last-token ON DEVICE (non-running slots'
        sampled values are computed-and-discarded garbage — their rows are
        batch-independent and their writes land in the trash page)."""
        S = st.toks.shape[0]
        table = st.table[:S]                  # drop the virtual trash row
        state = self._layer_state(st, run, page_table=table, pos=st.pos)
        feed = {self.input_name: Argument(ids=st.toks[:, None],
                                          lengths=jnp.ones((S,), jnp.int32))}
        outputs, _, state_out = self.executor.forward(
            params, feed, state, TEST, None, scopes=self._head_scope)
        last = outputs[self.logits_name].value[:, 0, :]
        nxt = pick_next_per_slot(last, self._slot_keys(st), st.temp,
                                 st.topk, st.topp, is_probs=self._probs)
        new_pools = self._pools_out(st, state_out)
        nxt = self._with_counts(nxt, state_out, run)
        runi = run.astype(jnp.int32)
        new_st = EngineState(pools=new_pools, table=st.table,
                             pos=st.pos + runi,
                             toks=jnp.where(run, nxt[:S], st.toks),
                             gen=st.gen + runi, keys=st.keys, temp=st.temp,
                             topk=st.topk, topp=st.topp)
        return new_st, nxt

    def _mixed_impl(self, params, st: EngineState, row_ids, row_slot,
                    row_pos, sample_row, adv, emit):
        """THE mixed prefill/decode step — one signature per
        max_step_tokens value, whatever the prefill/decode row mix: the
        packed ragged token rows run the stack as one [1, T] batch (every
        non-attention layer is per-token; attention routes through
        layers_attn._paged_ragged_step via the `row_slot` cache marker),
        then per-slot sampling reads each slot's designated logits row.
        `adv`/`emit` are the host scheduler's advance masks: pos moves by
        the rows each slot committed, gen/last-token move where a token
        was banked (decode rows and final chunks).  Non-emitting slots
        (mid-prefill, paused, empty) sample a padding/decode row's logits
        — computed and discarded, their state frozen by the masks."""
        T = row_ids.shape[0]
        S = st.toks.shape[0]
        # a decode row is staged as -1: its token is the slot's last
        # sampled one, which only the device is sure to have
        row_ids = jnp.where(row_ids < 0,
                            st.toks[jnp.minimum(row_slot, S - 1)], row_ids)
        state = self._layer_state(st, None, page_table=st.table,
                                  row_slot=row_slot, row_pos=row_pos)
        feed = {self.input_name: Argument(
            ids=row_ids[None, :], lengths=jnp.full((1,), T, jnp.int32))}
        # the head runs on the S rows the step samples, not on its T rows
        outputs, _, state_out = self.executor.forward(
            params, feed, state, TEST, None,
            rows={self.logits_name: sample_row}, scopes=self._head_scope)
        last = outputs[self.logits_name].value[0]      # [S, V]
        nxt = pick_next_per_slot(last, self._slot_keys(st), st.temp,
                                 st.topk, st.topp, is_probs=self._probs)
        new_pools = self._pools_out(st, state_out)
        new_st = EngineState(pools=new_pools, table=st.table,
                             pos=st.pos + adv,
                             toks=jnp.where(emit, nxt, st.toks),
                             gen=st.gen + emit.astype(jnp.int32),
                             keys=st.keys, temp=st.temp, topk=st.topk,
                             topp=st.topp)
        # a padding row aims at the virtual trash table row S
        return new_st, self._with_counts(nxt, state_out, row_slot < S)

    def _layer_state(self, st: EngineState, run, **shared) -> dict:
        """The state dict a paged step hands the executor: each attention
        layer's pool parts as `<part>_pages` (k_pages and v_pages; a latent
        layer's kv_pages) beside the step's shared operands; a recurrent
        layer's slot-indexed parts under their own names, with the step's
        `run` mask where the step has one (a paused slot's state must not
        advance; the K/V layers never see it); and an entry for each MoE
        layer — the request for its routed pairs — that holds, where the
        step packs rows (`row_slot`), which of them are `live`: padding
        rows all route alike, and the expert block's grouped form gives
        them no slots."""
        rec = set(self._recurrent)
        state = {name: dict({part + "_pages": a for part, a in pool.items()},
                            **shared)
                 for name, pool in st.pools.items() if name not in rec}
        for name, ring in self._ring_tables.items():
            # a window layer reads its slots' rings where the others read
            # the logical table (as many rows of it: the decode step has
            # dropped the trash row)
            del state[name]["page_table"]
            state[name]["ring_table"] = jnp.asarray(
                ring[:shared["page_table"].shape[0]])
        if run is not None:
            shared = dict(shared, run=run)
        state.update({name: dict(st.pools[name], **shared) for name in rec})
        live = {"live": shared["row_slot"] < len(self.slots)} \
            if "row_slot" in shared else {}
        state.update({name: dict(live) for name in self._moe_layers})
        return state

    def _pools_out(self, st: EngineState, state_out: dict) -> dict:
        rec = set(self._recurrent)
        return {name: {part: state_out[name][
                           part if name in rec else part + "_pages"]
                       for part in pool}
                for name, pool in st.pools.items()}

    def _with_counts(self, nxt, state_out: dict, live_rows):
        """`nxt` with the step's device-side counts behind it, one array
        and one read-back: [S + E_held + 2 (+ 2)] int32 — the routed pairs
        of the held experts, summed over the MoE layers and the live rows,
        the overflow tiles the layers' grouped form ran and the busiest
        expert's pairs in its busiest LAYER, then for a model with
        recurrent layers the rows that advanced a state and the slot states
        read and written, summed over those layers.  `nxt` itself for a
        model with neither."""
        tail = []
        if self._moe_layers:
            live = live_rows.reshape(-1, 1)
            pairs = [jnp.sum(jnp.logical_and(state_out[name]["pairs"], live),
                             axis=0, dtype=jnp.int32)
                     for name in self._moe_layers]
            tail += [sum(pairs), jnp.stack([
                sum(state_out[name]["overflow_tiles"]
                    for name in self._moe_layers),
                jnp.max(jnp.stack(pairs))])]
        if self._recurrent:
            tail.append(jnp.stack([
                state_out[self._recurrent[0]]["rows"],
                sum(state_out[name]["updates"]
                    for name in self._recurrent)]))
        if not tail:
            return nxt
        return jnp.concatenate([nxt.astype(jnp.int32)] + tail)

    def _moe_grouped(self, kind: str) -> bool:
        """Whether the step program of `kind` runs the expert block's
        grouped form: the layers' own rule (graph/layers_moe.py:
        expert_form_of) at the rows that program is traced with — the
        slots for a decode step, the token budget for a mixed step."""
        rows = self.max_step_tokens if kind == "mixed" else len(self.slots)
        if rows not in self._moe_grouped_at:
            self._moe_grouped_at[rows] = any(
                expert_form_of(l, self._step_params, rows,
                               self.mesh) == "grouped"
                for l in self.executor.model.layers if l.type == "moe")
        return self._moe_grouped_at[rows]

    def _count_window(self, cur: dict, adv: np.ndarray, rows: int) -> None:
        """One launched step's share of the window layers' counters
        (nothing for a model without rings): the ring pages its rows write
        over — `cur` the plan's cursors, `adv` the tokens each slot commits
        — and the rows it sends through the window layers."""
        if not self._ring_tables:
            return
        before = np.fromiter((cur[s][0] if s in cur else 0
                              for s in range(len(adv))), np.int64, len(adv))
        recycled = self.kv.ring_pages_recycled(before, before + adv)
        rows *= len(self._ring_tables)
        self.n_window_pages_recycled += recycled
        self.n_window_rows += rows
        self.n_window_steps += 1
        pc = process_counters()
        pc.add("serving_window_pages_recycled_total", recycled)
        pc.add("serving_window_rows_total", rows)
        pc.add("serving_window_steps_total", 1)

    def kv_pages_resident(self) -> dict:
        """Pages that hold live tokens, by kind: `full` = the allocator's
        pages in use (each backs every full layer), `window` = the slots'
        ring pages in use (each backs every window layer)."""
        lengths = [0 if sl is None else sl.pos for sl in self.slots]
        return {"full": self.kv.pages_in_use,
                "window": self.kv.ring_pages_resident(lengths)}

    def _count_moe(self, nxt: np.ndarray, n_rows: int,
                   kind: str) -> np.ndarray:
        """Split a step's read-back into its tokens and the counts behind
        them (the MoE pairs, overflow tiles and layer maximum, then the two
        recurrent counts); bank the counts.  Returns the tokens."""
        if self._recurrent:
            rows, updates = int(nxt[-2]), int(nxt[-1])
            nxt = nxt[:-2]
            self.recurrent_rows += rows
            self.recurrent_slot_updates += updates
            self.recurrent_steps += 1
            pc = process_counters()
            pc.add("serving_recurrent_rows_total", rows)
            pc.add("serving_recurrent_slot_updates_total", updates)
            pc.add("serving_recurrent_steps_total", 1)
        if nxt.size > n_rows:
            pairs = nxt[n_rows:-2]
            total, busiest = int(pairs.sum()), int(pairs.max())
            tiles, layer_max = int(nxt[-2]), int(nxt[-1])
            self.moe_pairs_total += total
            self.moe_pairs_max_sum += busiest
            self.moe_overflow_tiles += tiles
            self.moe_layer_pairs_max_sum += layer_max
            self.moe_steps += 1
            pc = process_counters()
            pc.add("serving_moe_pairs_total", total)
            pc.add("serving_moe_pairs_max_total", busiest)
            pc.add("serving_moe_overflow_tiles_total", tiles)
            pc.add("serving_moe_layer_pairs_max_total", layer_max)
            pc.add("serving_moe_steps_total", 1)
            if self._moe_grouped(kind):
                self.moe_grouped_steps[kind] = 1 + \
                    self.moe_grouped_steps.get(kind, 0)
                pc.add(counter_key("serving_moe_grouped_steps_total",
                                   kind=kind), 1)
        return nxt[:n_rows]

    def _spec_impl(self, params, st: EngineState, row_ids, row_slot,
                   row_pos, first_row, n_draft, draft_toks, spec, emit,
                   adv_chunk):
        """THE speculative verify step — one signature per (token
        budget, spec_k), whatever the chain/chunk row mix: the packed
        ragged rows run the stack exactly like the mixed step (all K/V
        scattered before the read, so draft rows see each other
        causally), then every slot samples its k+1-position CHAIN —
        position i's logits row is `first_row[s] + i` and its key is
        `keys[s, gen[s] + i]` (sampler.py pick_next_chain), making
        sample i bit-equal to the token the sequential engine would
        emit at generation gen+i given the prefix matched.

        Acceptance on device: `acc[s]` = leading run of draft agreement
        (`sampled[:, :k] == draft_toks`, masked to the real draft
        count), and chain slots commit acc+1 tokens — pos/gen advance
        by it, last-token becomes sampled[s, acc] (the first
        non-drafted sample: the bonus token on full acceptance, the
        corrected token on a rejection).  Chunk slots advance by their
        host-scheduled masks exactly as in the mixed step.  Rejected
        rows' K/V stays in the pools as causally-invisible garbage the
        next chain overwrites — the device needs no rollback; the host
        returns the unjustified page tail (kv.uncommit_tail).

        Returns (state', sampled [S, k+1], acc [S])."""
        T = row_ids.shape[0]
        S = st.toks.shape[0]
        K = draft_toks.shape[1]
        state = self._layer_state(st, None, page_table=st.table,
                                  row_slot=row_slot, row_pos=row_pos)
        feed = {self.input_name: Argument(
            ids=row_ids[None, :], lengths=jnp.full((1,), T, jnp.int32))}
        idx = jnp.clip(first_row[:, None] + jnp.arange(K + 1)[None, :],
                       0, T - 1)
        # the head runs on the chains' S * (K + 1) rows alone
        outputs, _, state_out = self.executor.forward(
            params, feed, state, TEST, None,
            rows={self.logits_name: idx.reshape(-1)},
            scopes=self._head_scope)
        chain = outputs[self.logits_name].value[0].reshape(S, K + 1, -1)
        g = jnp.clip(st.gen[:, None] + jnp.arange(K + 1)[None, :], 0,
                     st.keys.shape[1] - 1)
        keys = st.keys[jnp.arange(S)[:, None], g]      # [S, K+1, 2]
        sampled = pick_next_chain(chain, keys, st.temp, st.topk,
                                  st.topp, is_probs=self._probs)
        ok = jnp.logical_and(sampled[:, :K] == draft_toks,
                             jnp.arange(K)[None, :] < n_draft[:, None])
        acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        n_new = (acc + 1) * spec.astype(jnp.int32)
        committed = jnp.where(spec, n_new, adv_chunk)
        gen_adv = jnp.where(spec, n_new, emit.astype(jnp.int32))
        last = sampled[jnp.arange(S), acc]
        toks_new = jnp.where(spec, last,
                             jnp.where(emit, sampled[:, 0], st.toks))
        new_pools = self._pools_out(st, state_out)
        new_st = EngineState(pools=new_pools, table=st.table,
                             pos=st.pos + committed, toks=toks_new,
                             gen=st.gen + gen_adv, keys=st.keys,
                             temp=st.temp, topk=st.topk, topp=st.topp)
        return new_st, sampled, acc
