"""Paged KV cache: a fixed pool of [num_pages, page_size, h_kv, dh] pages
per attention layer plus per-slot page tables (heads narrower than 128
lanes are stored several a lane tile, [.., h_kv * dh // 128, 128]:
ops/pallas_paged.py:kv_row_shape — lane-dense in HBM, the same bytes in
the same order).  A latent-attention layer
(mla_attention) holds ONE tensor of [num_pages, page_size, W] — W =
kv_lora_rank + qk_rope_head_dim rounded up to 128 lanes (576 -> 640, the
width HBM tiling gives the row anyway) — instead of a K and a V pool: its cache row is the latent
every head reads (ops/mla.py); allocation, sharing, COW, spill and transfer
below walk each layer's own parts, so they are the same code for both.

TWO FAMILIES OF PARTS.  The above are PAGE-indexed: a token's row lives in
the page its slot's table maps.  A recurrent layer holds SLOT-indexed
parts instead, one row a slot (row S is the trash row padding and paused
rows aim at), whatever the context's length.  Three kinds today, each
declaring its parts, their row shapes and dtypes beside its registration
(graph/registry.py:register_slot_state — nothing here names a layer type
or a part to decide a shape or a dtype): kda_attention
(graph/layers_kda.py) `state` [S+1, H, dk, dv] float32 and `conv` [S+1,
taps-1, C] in the compute dtype; short_conv (graph/layers_sconv.py) `conv`
[S+1, taps-1, d] alone, its whole context; mamba2 (graph/layers_ssm.py)
`state` [S+1, H, P, N] float32 (2 MiB a slot a layer at 64 x 64 x 128) and
`conv` [S+1, taps-1, H P + 2 G N] in the compute dtype.  A stack may hold
NO page-indexed part at all (every token mixer recurrent: one pipeline
stage of a hybrid model): then no pool is built, the table and the
allocator keep their logical pages, and admission is bound by slots and
`max_context` as ever.  Both families sit in
`self.pools` under the
layer's name and thread through the engine's steps donated alike;
`layer_specs` names the page-indexed layers, `slot_specs` the slot-indexed
ones.  Allocation, COW, spill, export/import and `check()` walk the
page-indexed parts (`paged_pools()`): a slot's state is never shared,
copied or moved, and it holds no snapshot at a page boundary — which is
why a model with recurrent layers serves without the prefix index, the
spill tier and the transfer plane (`RECURRENT_REFUSALS`: one sentence and
one place of refusal each).  The memory accounting covers both
(`pool_bytes`, `slot_state_bytes`).

WINDOW LAYERS.  A `multi_head_attention` layer with a `window` reads the
last `window` keys only, so it need not hold a slot's whole context: built
with `step_tokens` (the most rows one slot can get in one step — the
engine's `max_step_tokens`), such a layer's pool is `1 + num_slots *
ring_pages` pages, a RING of `ring_pages` a slot: logical page j of slot s
is physical page `ring_table[s, j % ring_pages]` = 1 + s * ring_pages + j %
ring_pages, written and read in place, the oldest page recycled as the slot
advances.  The ring holds window + step_tokens tokens and a page more (a
chunk's rows are all written before any is read, and neither end of that
span sits on a page boundary); where that is `pages_per_slot` or more the
ring would drop no page, so the layer stays under the logical table with
the full layers and nothing below refuses it.  The
assignment is static: a ring page is never on the free list, the table of
rings is a constant the compiled steps fold in (`ring_table`: row S the
all-trash row padding aims at), and release, preemption and
`uncommit_tail` have nothing of a ring to undo — what a slot leaves in its
ring is overwritten by the next request before any of its queries can see
it.  The FULL layers keep the logical table, the allocator and the prefix
index as they are.  A ring's pages are not the whole context, so what
assumes they are refuses a model with rings as it refuses one with
recurrent state (`RING_REFUSALS` beside `RECURRENT_REFUSALS`, one
function, the same places).  Without `step_tokens` (a cache built by hand)
a window layer holds whole contexts under the logical table and the window
is a mask of its read.

Replaces the dense `lm_decode.init_kv_caches` layout for SERVING: a dense
cache sizes every row at P+max_new whatever the row actually holds, and its
[B, total, ...] shape bakes the request mix into the compiled program.
Here the pool shape is fixed forever — one compiled decode step serves any
request mix — and HBM cost is proportional to pages actually allocated
(Ragged Paged Attention, arXiv:2604.15464; the slot/page serving
configuration of arXiv:2605.25645).

Device side: per-attention-layer page pools (`pools[name]["k"/"v"]`) that
thread through the engine's jitted decode step, and ONE logical page table
shared by every layer (all layers hold the same tokens).  Host side: the
page allocator — a free list plus the per-slot table mirror the scheduler
consults and mutates between steps.  PHYSICAL PAGE 0 IS RESERVED as the
trash page: unmapped table entries are 0, so inactive/paused slots' writes
land there and reads of unallocated logical pages gather finite garbage
that causal masking weighs to exactly 0 (see
ops/attention.py:paged_attention_step).

PREFIX SHARING (PR 7): physical pages are REFCOUNTED so one committed page
can back the same prompt prefix in many slots at once (and sit in the
prefix index, serving/prefix_tree.py, between requests).  The contract:

  * `_ref[p]` counts slot-table mappings of physical page p; `_cached[p]`
    marks pages held read-only by the prefix index.  A page returns to the
    free list only when BOTH drop away.
  * a page with `_ref > 1` or `_cached` set is SHARED and must never be
    written — the engine calls `ensure_writable` before any write into a
    mapped page, which COWs a private copy (device page copy + remap) when
    the page is shared.
  * when the free list runs dry the allocator first asks
    `on_page_pressure(n)` (the prefix index's LRU eviction) to reclaim
    cached refcount-zero pages — eviction before pausing slots, preemption
    stays last resort.  So that the index need not search for those
    pages, `_unref` tells it (`on_cached_unmapped(p)`) the moment a cached
    page's last mapping goes.

HOST SPILL TIER (docs/serving.md "KV spill tier"): with a non-zero
`spill_bytes_budget`, a cold refcount-zero cached page that the prefix
index would otherwise destroy under page pressure is instead COPIED to a
host-RAM buffer (one `[page_size, h_kv, dh]` ndarray per layer per page)
and the device page freed — the effective prefix cache grows past HBM.
The tier is bounded by the byte budget with LRU eviction INSIDE it (the
prefix index drops its least-recently-used host-resident leaves to make
room), and an admission that prefix-hits a spilled run restores the
pages: `take_pages` allocates fresh device pages, `restore_pages`
scatters the host copies back in ONE batched dispatch (page-count
bucketed to powers of two, pad rows writing zeros to trash page 0, so
signatures stay bounded), and `adopt_restored` re-marks them cached
before the slot maps them read-only.  Restores are MOVES — the host copy
is dropped, a later re-spill re-copies.  All of it is admission-boundary
host/allocator work: the decode/mixed/spec step signatures never
see the tier.  `_host_gen` stamps every entry and bumps on reset(), so a
stale spilled page can never restore tokens from a dead tree generation.

TENSOR PARALLELISM (PR 11): constructed with a mesh whose `model` axis
exceeds 1, the pools shard on their kv-head axis (`PartitionSpec(None,
None, "model", None)`) — each device's HBM holds only its heads' slice of
every page, so the servable KV grows with the mesh while the ALLOCATOR is
untouched: tables, refcounts, the free list and the prefix index are
host-side and shard-agnostic (a physical page is one logical unit whose
storage happens to be split).  `version` stamps every host table write so
the engine re-uploads its device-resident table only when something
actually changed (the hot decode loop's zero-restaging contract).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

# What a model with recurrent layers cannot use, and the one sentence that
# says why (refuse_for_recurrent): a slot's recurrent state has no snapshot
# at a page boundary, so pages alone are not the context.  Each
# is refused at ONE place: the spill budget here, where it is set; the mesh
# (with the other layers' tensor-parallel checks), the prefix index,
# speculation and the transfer plane's two ends in serving/engine.py; the
# role in serving/server.py.
RECURRENT_REFUSALS = {
    "mesh": ("--mesh model=N (tensor-parallel serving)",
             "the slot state has no head-sharded layout"),
    "spill": ("the KV spill tier",
              "a spilled page could not bring its slot's recurrent state "
              "back"),
    "prefix": ("the prefix index",
               "a prefix hit would need the recurrent state at the hit's "
               "last page boundary, and none is kept"),
    "export": ("export_prefix (--role prefill)",
               "exported pages would arrive without the recurrent state "
               "that goes with them"),
    "import": ("import_prefix (--role decode)",
               "imported pages carry no recurrent state to resume from"),
    "spec": ("--spec-k > 0 (speculative decoding)",
             "a rejected draft would need the recurrent state rolled back, "
             "and the verify step keeps no copy"),
    "role": ("--role prefill|decode (disaggregated prefill/decode)",
             "pages pushed between replicas carry no recurrent state"),
}


# The same for a model whose WINDOW layers hold rings of pages (module
# docstring "WINDOW LAYERS"): the ring is not the whole context either.
# Refused at the same places through the same function; `--mesh model=N` is
# not among them (a ring shards on its kv-head axis like any pool).
RING_REFUSALS = {
    "spill": "a spilled page could not bring the slot's ring back",
    "prefix": "a prefix hit would need the ring as it stood at the hit's "
              "last page boundary, and only its newest state is kept",
    "export": "exported pages would arrive without the ring that goes with "
              "them",
    "import": "imported pages carry no ring to resume from",
    "spec": "a rejected draft's rows have already recycled pages of the "
            "ring that the accepted prefix still needs",
    "role": "pages pushed between replicas carry no ring",
}


def slot_state_specs(model, compute_dtype=jnp.float32) -> dict:
    """{layer name: {part: (row shape, dtype)}} of the model's recurrent
    layers' slot-indexed parts, in layer order, as each layer type declares
    them (module docstring "TWO FAMILIES OF PARTS"); empty for a model
    without recurrent layers."""
    from paddle_tpu.graph.registry import slot_state_types
    return {l.name: slot_state_types[l.type](l, compute_dtype)
            for l in model.layers if l.type in slot_state_types}


def refuse_for_recurrent(slot_specs: dict, mechanism: str,
                         ring_specs: Optional[dict] = None) -> None:
    """Raise, for a model some of whose layers' pages are NOT the whole
    context — recurrent layers (`slot_specs` not empty) or window layers
    held as rings (`ring_specs`) — for a mechanism that assumes they are (a
    key of RECURRENT_REFUSALS): one sentence naming what is missing."""
    what, why = RECURRENT_REFUSALS[mechanism]
    if slot_specs:
        raise ValueError(
            f"{what} is not available for a model with recurrent layers "
            f"({len(slot_specs)} here, of any kind that keeps a slot "
            f"state): {why} (ROADMAP R5: state snapshots at page "
            f"boundaries)")
    if ring_specs and mechanism in RING_REFUSALS:
        raise ValueError(
            f"{what} is not available for a model with window layers held "
            f"as rings of pages ({len(ring_specs)} here): "
            f"{RING_REFUSALS[mechanism]} (ROADMAP R2: ring snapshots at "
            f"page boundaries)")


class PagedKVCache:
    """Device page pools + host page allocator for `num_slots` decode slots.

    `pages_per_slot * page_size` bounds one slot's context (prompt +
    generated); `num_pages` bounds the whole pool (default: worst case,
    every slot full, plus the trash page — pass something smaller to
    overcommit, the engine then evicts cached prefixes / pauses slots /
    defers admission when the free list runs dry)."""

    def __init__(self, executor, num_slots: int, page_size: int,
                 pages_per_slot: int, num_pages: Optional[int] = None,
                 mesh=None, spill_bytes_budget: int = 0,
                 step_tokens: Optional[int] = None):
        assert page_size > 0 and pages_per_slot > 0
        self.page_size = int(page_size)
        self.pages_per_slot = int(pages_per_slot)
        self.num_slots = int(num_slots)
        self.num_pages = int(num_pages) if num_pages else \
            1 + num_slots * pages_per_slot
        assert self.num_pages >= 2, "pool needs the trash page + 1 real page"

        # tensor parallelism: pools shard on their kv-head axis over the
        # mesh `model` axis — each device's HBM holds only its heads'
        # pages, so the servable KV grows with the mesh (the engine
        # validates h_kv divisibility; tables stay host/replicated).
        # `pool_sharding` is THE canonical pool placement — the engine's
        # step in_shardings and every pool-writing jit pin to it.
        from paddle_tpu.parallel.mesh import MODEL_AXIS, axis_size

        self.mesh = mesh
        self.pool_sharding = None
        self.tp_shards = axis_size(mesh, MODEL_AXIS)
        if self.tp_shards > 1:
            from jax.sharding import NamedSharding, PartitionSpec
            self.pool_sharding = NamedSharding(
                mesh, PartitionSpec(None, None, MODEL_AXIS, None))

        dtype = jnp.dtype(executor.compute_dtype) if executor.compute_dtype \
            else jnp.float32
        # layer_specs[name] = the row shape every part of that layer's pool
        # shares: (h_kv, dh) for multi_head_attention (parts "k" and "v"),
        # (kv_lora_rank + qk_rope_head_dim,) for mla_attention (ONE part
        # "kv": the latent row [c_kv, k_pe] — V is its first kv_lora_rank
        # columns, so there is no second tensor).  Everything below walks
        # `self.pools[name]`'s own parts and shapes, never a fixed pair.
        self.layer_specs: dict[str, tuple] = {}
        # ring_specs[name] = ring_pages of a WINDOW layer held as rings
        # (module docstring "WINDOW LAYERS"): a subset of layer_specs
        self.ring_specs: dict[str, int] = {}
        self.step_tokens = None if step_tokens is None else int(step_tokens)
        # slot_specs[name] = {part: row shape} of a recurrent layer's
        # slot-indexed parts (module docstring "TWO FAMILIES OF PARTS")
        declared = slot_state_specs(executor.model, dtype)
        self.slot_specs = {name: {part: row for part, (row, _) in ps.items()}
                           for name, ps in declared.items()}
        self.pools: dict[str, dict[str, jnp.ndarray]] = {
            name: {part: jnp.zeros((num_slots + 1,) + row, part_dtype)
                   for part, (row, part_dtype) in ps.items()}
            for name, ps in declared.items()}
        from paddle_tpu.ops.pallas_paged import kv_page_shape, kv_row_shape
        itemsize = jnp.dtype(dtype).itemsize
        for l in executor.model.layers:
            if l.type == "multi_head_attention":
                heads = int(l.attrs["num_heads"])
                h_kv = int(l.attrs.get("num_kv_heads", 0) or heads)
                # narrow heads packed a lane tile; under a model mesh the
                # row axis is what shards, so only whole tiles a shard
                # (each shard then holds its own kv heads, in order)
                row = (h_kv, int(l.size) // heads)
                page = (page_size,) + row
                if kv_row_shape(*row)[0] % self.tp_shards == 0:
                    # a lone row of 128 lanes is stored two tokens a row
                    page = kv_page_shape(page_size, *row, itemsize)
                    row = kv_row_shape(*row)
                parts = ("k", "v")
            elif l.type == "mla_attention":
                if self.tp_shards > 1:
                    raise ValueError(
                        f"layer {l.name!r}: a latent cache row is shared by "
                        f"every head and cannot shard on a kv-head axis — "
                        f"latent attention under --mesh model=N is not "
                        f"supported yet")
                from paddle_tpu.ops.mla import lane_width
                row = (lane_width(int(l.attrs["kv_lora_rank"]) +
                                  int(l.attrs["qk_rope_head_dim"])),)
                page = (page_size,) + row
                parts = ("kv",)
            else:
                continue
            self.layer_specs[l.name] = row
            n_pages = self.num_pages
            if "window" in l.attrs and l.type == "multi_head_attention" \
                    and self.step_tokens is not None:
                ring = self.ring_pages_for(int(l.attrs["window"]))
                # a ring as large as a context drops no page: such a
                # layer stays under the logical table, and nothing refuses
                if ring < self.pages_per_slot:
                    self.ring_specs[l.name] = ring
                    n_pages = 1 + self.num_slots * ring
            shape = (n_pages,) + page

            def _pool():
                # distinct buffers per part — parts are donated side by
                # side, and XLA refuses to donate one buffer twice
                z = jnp.zeros(shape, dtype)
                return jax.device_put(z, self.pool_sharding) \
                    if self.pool_sharding is not None else z

            self.pools[l.name] = {part: _pool() for part in parts}
        # A STACK WITH NO PAGE-INDEXED PART (every token mixer recurrent:
        # a pipeline stage of a hybrid model can be exactly that) builds no
        # pool; the page table and the allocator keep their logical pages,
        # so admission is bound by slots and `max_context` as ever
        assert self.layer_specs or self.slot_specs, \
            "model has no attention layers to page and no recurrent " \
            "layer's slot state to hold: nothing to serve from a cache"

        # host allocator state: table[s, j] = physical page backing logical
        # page j of slot s (0 = unmapped -> trash)
        self.table = np.zeros((num_slots, pages_per_slot), np.int32)
        # monotone table-write stamp: every host-side table/allocator
        # mutation bumps it, and the engine re-uploads its device-resident
        # table ONLY when it moved — the hot decode loop's zero-restaging
        # contract hangs off this counter
        self.version = 0
        self._free = self._canonical_free()
        self._n_pages = np.zeros(num_slots, np.int32)
        # per-physical-page slot-mapping refcount + prefix-index membership
        self._ref = np.zeros(self.num_pages, np.int32)
        self._cached = np.zeros(self.num_pages, bool)
        # called with the page shortfall when the free list runs dry;
        # returns pages reclaimed (the prefix index's LRU eviction —
        # serving/engine.py wires it).  None = no reclaimer, fail dry.
        self.on_page_pressure: Optional[Callable[[int], int]] = None
        # called with a prefix-cached page when its last slot mapping
        # goes (`_unref`): the page just became evictable, which the
        # prefix index records instead of walking for it later
        # (PrefixTree wires itself).  None = no index.
        self.on_cached_unmapped: Optional[Callable[[int], None]] = None
        self.n_cow = 0                 # copy-on-write page copies performed
        self._copy_fn = None           # lazily-jitted device page copy
        # -- host spill tier (module docstring "HOST SPILL TIER") ----------
        # hid -> {"gen", "nbytes", "data": {layer: {part: ndarray}}}; the
        # prefix index owns the POLICY (who spills, who drops) — this is
        # the mechanism + the byte accounting
        self.spill_bytes_budget = spill_bytes_budget
        self._host: dict[int, dict] = {}
        self._next_hid = 1
        self._host_bytes = 0
        self._host_gen = 0             # bumped on reset(): the stale-spill
                                       # generation guard
        self.n_spilled = 0             # pages spilled device -> host (ever)
        self.n_restored = 0            # pages restored host -> device (ever)
        self.n_host_evicted = 0        # host-tier LRU drops (budget pressure)
        self._host_drained = 0         # non-evict, non-restore drops
        self._restore_fns: dict[int, object] = {}   # bucketed jitted scatter
        # -- cross-replica page transfer (docs/serving.md "Disaggregated
        # prefill/decode"): committed pages serialized to/from host bytes
        self.n_exported = 0            # pages exported to wire bytes (ever)
        self.n_imported = 0            # pages imported from wire bytes (ever)

    def ring_pages_for(self, window: int) -> int:
        """Pages a slot's ring holds for a layer of `window`: window +
        step_tokens tokens (the oldest key the step's first row reads to
        the newest the step writes) and a page more (neither end of that
        span sits on a page boundary).  Only a ring SMALLER than a whole
        context is built (`ring_specs`)."""
        return -(-(window + self.step_tokens) // self.page_size) + 1

    def ring_table(self, name: str) -> np.ndarray:
        """[num_slots + 1, ring_pages] int32: the physical page of each
        column of each slot's ring in window layer `name`'s pool — static,
        so the compiled steps take it as a constant; row num_slots is all
        trash (page 0), for padding rows."""
        R = self.ring_specs[name]
        t = np.zeros((self.num_slots + 1, R), np.int32)
        t[:-1] = 1 + np.arange(self.num_slots)[:, None] * R + np.arange(R)
        return t

    def ring_pages_resident(self, lengths) -> int:
        """Ring pages that hold live tokens, one window layer's worth:
        each slot's pages up to its ring's size (`lengths`: tokens a slot
        holds, 0 for an empty one).  0 without rings."""
        if not self.ring_specs:
            return 0
        R = max(self.ring_specs.values())
        pages = -(-np.asarray(lengths, np.int64) // self.page_size)
        return int(np.minimum(pages, R).sum())

    def ring_pages_recycled(self, before, after) -> int:
        """Ring pages written over while slots went from `before` to
        `after` tokens, summed over the window layers: a logical page past
        the ring's size lands on the page of the one ring_pages before it."""
        n = 0
        for R in self.ring_specs.values():
            a = np.maximum(-(-np.asarray(before, np.int64) // self.page_size),
                           R)
            b = np.maximum(-(-np.asarray(after, np.int64) // self.page_size),
                           R)
            n += int(np.maximum(b - a, 0).sum())
        return n

    def _canonical_free(self) -> list:
        """The free list in its construction-time canonical order (pop()
        hands out page 1 first) — reset() rebuilds exactly this, so page
        placement is reproducible across engine restarts."""
        return list(range(self.num_pages - 1, 0, -1))

    # -- capacity ---------------------------------------------------------
    @property
    def capacity_tokens(self) -> int:
        """Max tokens (prompt + generated) one slot can hold."""
        return self.pages_per_slot * self.page_size

    @property
    def free_page_count(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        """Pages not on the free list: slot-mapped (private or shared) plus
        pages retained only by the prefix index."""
        return (self.num_pages - 1) - len(self._free)

    @property
    def private_pages_in_use(self) -> int:
        """Pages mapped by exactly one slot and not in the prefix index."""
        return int(np.sum((self._ref == 1) & ~self._cached))

    @property
    def shared_pages_in_use(self) -> int:
        """Slot-mapped pages that are shared: mapped by >1 slot, or mapped
        while also held by the prefix index (read-only either way)."""
        return int(np.sum((self._ref >= 1) &
                          ((self._ref > 1) | self._cached)))

    @property
    def cached_page_count(self) -> int:
        """Pages held ONLY by the prefix index — reclaimable by eviction."""
        return int(np.sum((self._ref == 0) & self._cached))

    @property
    def spill_bytes_budget(self) -> int:
        return self._spill_bytes_budget

    @spill_bytes_budget.setter
    def spill_bytes_budget(self, nbytes: int) -> None:
        if int(nbytes or 0) > 0:
            self.refuse("spill")
        self._spill_bytes_budget = int(nbytes or 0)

    def refuse(self, mechanism: str) -> None:
        """`refuse_for_recurrent` for THIS cache's layers: raises where a
        recurrent layer or a window layer's ring makes the pages less than
        the context."""
        refuse_for_recurrent(self.slot_specs, mechanism, self.ring_specs)

    def paged_pools(self) -> dict:
        """The page-indexed layers' pools: what allocation, COW, spill and
        transfer walk (a recurrent layer's slot-indexed parts are not
        theirs to touch)."""
        return {name: self.pools[name] for name in self.layer_specs}

    @property
    def bytes_by_part(self) -> dict:
        """Device bytes (all shards) of every pool, `<layer>.<part>`: a
        page-indexed layer's pools and a recurrent layer's slot-indexed
        parts alike — what a configuration's residency table is held to."""
        return {f"{name}.{part}": int(a.size) * a.dtype.itemsize
                for name, p in self.pools.items() for part, a in p.items()}

    @property
    def pool_bytes(self) -> int:
        """Total device bytes of the K/V page pools (all shards)."""
        return sum(int(a.size) * a.dtype.itemsize
                   for p in self.paged_pools().values() for a in p.values())

    @property
    def pool_bytes_by_kind(self) -> dict:
        """`pool_bytes` by page kind: `full` = layers under the logical
        table (a whole context a slot), `window` = layers held as rings."""
        out = {"full": 0, "window": 0}
        for name, p in self.paged_pools().items():
            out["window" if name in self.ring_specs else "full"] += sum(
                int(a.size) * a.dtype.itemsize for a in p.values())
        return out

    @property
    def slot_state_bytes(self) -> int:
        """Total device bytes of the recurrent layers' slot-indexed parts."""
        return sum(int(a.size) * a.dtype.itemsize
                   for name in self.slot_specs
                   for a in self.pools[name].values())

    @property
    def pool_bytes_per_shard(self) -> int:
        """Pool bytes resident PER DEVICE: the kv-head axis splits over
        the mesh model axis, so each shard holds 1/tp of every page."""
        return self.pool_bytes // self.tp_shards

    def pages_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.page_size)

    # -- allocator --------------------------------------------------------
    def _alloc_page(self) -> Optional[int]:
        """Pop one free page, asking the pressure hook (prefix-index LRU
        eviction) to reclaim when the list is dry.  None = genuinely out."""
        if not self._free and self.on_page_pressure is not None:
            self.on_page_pressure(1)
        if not self._free:
            return None
        page = self._free.pop()
        assert self._ref[page] == 0 and not self._cached[page], \
            f"free list held a referenced page {page}"
        return page

    def try_grow(self, slot: int, n_tokens: int, evict: bool = True) -> bool:
        """Ensure `slot` has pages covering `n_tokens` tokens, allocating
        from the free list on demand (evicting cached prefixes under
        pressure).  False (and no change beyond pages already grabbed —
        they stay with the slot for the retry) when the pool is genuinely
        dry: the caller pauses the slot or defers the admission.

        `evict=False` takes FREE pages only — the speculative draft-tail
        growth uses it, because optimistic pages that a rejection hands
        straight back the same step must never cost a committed cached
        prefix its retention (a low-accept spec workload would otherwise
        churn the prefix index to back K/V it immediately discards); the
        caller shrinks its draft ambition to what is genuinely free."""
        need = self.pages_for(n_tokens)
        assert need <= self.pages_per_slot, \
            f"slot {slot}: {n_tokens} tokens exceed the " \
            f"{self.capacity_tokens}-token slot capacity"
        # ask for the whole shortfall in ONE pressure call (one tree walk),
        # not page-by-page through _alloc_page's single-page fallback
        shortfall = (need - int(self._n_pages[slot])) - len(self._free)
        if shortfall > 0 and evict and self.on_page_pressure is not None:
            if shortfall > self.cached_page_count:
                # infeasible even after evicting EVERY reclaimable page:
                # fail fast WITHOUT evicting.  A doomed retry must not
                # destroy cached prefixes it cannot use — in particular a
                # preempted half-chunked prefill's donated pages, which
                # its own re-admission retries against every step until
                # another slot frees the remainder (the retry that can
                # finally succeed still finds them and prefix-hits)
                return False
            self.on_page_pressure(shortfall)
        while self._n_pages[slot] < need:
            if not self._free and not evict:
                return False
            page = self._alloc_page()
            if page is None:
                return False
            self._ref[page] = 1
            self.table[slot, self._n_pages[slot]] = page
            self._n_pages[slot] += 1
            self.version += 1
        return True

    def map_shared(self, slot: int, pages) -> None:
        """Map already-committed (prefix-index) pages read-only into an
        EMPTY slot's table as its first logical pages — the prefix-hit
        admission path.  Bumps each page's refcount; the pages must never
        be written through this slot until `ensure_writable` COWs them."""
        assert self._n_pages[slot] == 0, \
            f"slot {slot} is not empty — shared prefixes map at admission"
        assert len(pages) <= self.pages_per_slot
        for j, page in enumerate(pages):
            page = int(page)
            assert 0 < page < self.num_pages and (
                self._ref[page] > 0 or self._cached[page]), \
                f"page {page} is not a live committed page"
            self._ref[page] += 1
            self.table[slot, j] = page
        self._n_pages[slot] = len(pages)
        self.version += 1

    def page_writable(self, page: int) -> bool:
        return self._ref[page] == 1 and not self._cached[page]

    def ensure_writable(self, slot: int, j: int) -> Optional[bool]:
        """Make logical page `j` of `slot` safe to write: if the mapped
        physical page is shared (multi-mapped or prefix-cached), allocate a
        private page, device-copy the contents, and remap.  Returns True if
        a COW copy happened, False if the page was already private, None if
        a copy was needed but the pool is dry (caller rolls back)."""
        assert j < self._n_pages[slot], f"slot {slot} has no logical page {j}"
        page = int(self.table[slot, j])
        if self.page_writable(page):
            return False
        fresh = self._alloc_page()
        if fresh is None:
            return None
        self.pools = self._page_copy()(self.pools, fresh, page)
        self._ref[fresh] = 1
        self.table[slot, j] = fresh
        self.version += 1
        self._unref(page)
        self.n_cow += 1
        return True

    def _unref(self, page: int) -> None:
        assert self._ref[page] >= 1, \
            f"page {page} unreferenced below zero (double release?)"
        self._ref[page] -= 1
        if self._ref[page] == 0:
            if not self._cached[page]:
                self._free.append(page)
            elif self.on_cached_unmapped is not None:
                self.on_cached_unmapped(page)

    def release(self, slot: int) -> None:
        """Drop every mapping of `slot` (retire/abort): each page's
        refcount decrements, and pages no slot maps and the prefix index
        does not hold return to the free list.  Idempotent — a second
        release (or a release after reset()) is a no-op, it can never
        append the same physical page to the free list twice."""
        for j in range(int(self._n_pages[slot])):
            self._unref(int(self.table[slot, j]))
        self.table[slot, :] = 0
        self._n_pages[slot] = 0
        self.version += 1

    def uncommit_tail(self, slot: int, n_tokens: int) -> int:
        """Release the slot's trailing pages beyond `pages_for(n_tokens)`
        — the SPECULATIVE-DECODE page rollback: the verify step wrote
        draft K/V optimistically into pages grown past the slot's
        committed length, and a rejected suffix leaves those tail pages
        holding only garbage the causal mask already excludes.  The
        on-device state needs no cleanup (future writes overwrite the
        garbage positions before any query can attend them); THIS is the
        host half — hand the unjustified pages back to the pool so a
        rejection never inflates occupancy past what preempt/replay
        would charge.  Tail pages are always PRIVATE (drafts never write
        shared pages; growth allocates fresh ones) — asserted, since
        releasing a shared page here would corrupt a cached prefix.
        Returns the number of pages released."""
        keep = self.pages_for(n_tokens)
        freed = 0
        while int(self._n_pages[slot]) > keep:
            j = int(self._n_pages[slot]) - 1
            page = int(self.table[slot, j])
            assert self.page_writable(page), \
                f"slot {slot}: uncommit_tail hit shared page {page} at " \
                f"logical index {j} — draft writes must never target " \
                f"shared pages"
            self.table[slot, j] = 0
            self._n_pages[slot] -= 1
            self._unref(page)
            freed += 1
        if freed:
            self.version += 1
        return freed

    def reset(self) -> None:
        """Release every slot AND forget all prefix-index retention, then
        rebuild the free list in CANONICAL order — page placement after a
        reset is bit-reproducible across engine restarts (exactness tests
        and postmortem engine.json snapshots stay stable).  The caller
        owning a prefix index must clear it too (its nodes' pages are no
        longer retained here); ServingEngine.reset_prefix_cache does both.
        Pool contents need no zeroing: stale pages are unreachable once
        unmapped, and masked if ever gathered."""
        self.table[:, :] = 0
        self._n_pages[:] = 0
        self._ref[:] = 0
        self._cached[:] = False
        self._free = self._canonical_free()
        # drain the host tier and bump the generation: a spilled page
        # surviving a cache reset would restore K/V from a dead tree
        # generation — any hid a caller still holds now fails
        # host_entry_live and the admission falls back to cold prefill
        self._host_drained += len(self._host)
        self._host.clear()
        self._host_bytes = 0
        self._host_gen += 1
        self.version += 1

    # -- prefix-index retention -------------------------------------------
    def cache_page(self, page: int) -> None:
        """Mark `page` as held by the prefix index (called at donation —
        the donor slot still maps it, so it cannot be on the free list)."""
        page = int(page)
        assert 0 < page < self.num_pages
        assert self._ref[page] >= 1, \
            f"page {page} donated to the prefix index without a live mapping"
        self._cached[page] = True

    def uncache_page(self, page: int) -> None:
        """Drop prefix-index retention of `page` (eviction); frees it when
        no slot maps it either."""
        page = int(page)
        assert self._cached[page], f"page {page} is not prefix-cached"
        self._cached[page] = False
        if self._ref[page] == 0:
            self._free.append(page)

    # -- host spill tier ---------------------------------------------------
    @property
    def page_nbytes(self) -> int:
        """Host bytes one spilled page costs: every part of every layer."""
        return sum(int(np.prod(a.shape[1:])) * a.dtype.itemsize
                   for p in self.paged_pools().values() for a in p.values())

    @property
    def host_page_count(self) -> int:
        return len(self._host)

    @property
    def host_bytes(self) -> int:
        return self._host_bytes

    def host_entry_live(self, hid) -> bool:
        """The generation guard: an entry from before the last reset()
        (or one already dropped) must never restore."""
        e = self._host.get(int(hid))
        return e is not None and e["gen"] == self._host_gen

    def spill_page(self, page: int) -> Optional[int]:
        """Copy a cold cached page's K/V to the host tier and free the
        device page — the evict-to-host half of two-level eviction.
        Returns the host id the caller (the prefix index) stores on its
        node, or None when the budget cannot hold one page (the caller
        destroys instead).  The caller makes budget room FIRST by
        dropping its own host-LRU victims via drop_host_page.  The
        device->host copy forces a device sync, which is fine here: page
        pressure fires at admission boundaries, never inside a step."""
        page = int(page)
        assert self._ref[page] == 0 and self._cached[page], \
            f"page {page} is not a cold cached page — only refcount-zero " \
            f"prefix-index pages spill"
        nbytes = self.page_nbytes
        if self._host_bytes + nbytes > self.spill_bytes_budget:
            return None
        data = {name: {part: np.asarray(a[page]) for part, a in pool.items()}
                for name, pool in self.pools.items()}
        hid = self._next_hid
        self._next_hid += 1
        self._host[hid] = {"gen": self._host_gen, "nbytes": nbytes,
                           "data": data}
        self._host_bytes += nbytes
        self.n_spilled += 1
        self._cached[page] = False          # uncache_page for ref==0, but
        self._free.append(page)             # the contents live on as `hid`
        self.version += 1
        return hid

    def drop_host_page(self, hid, reason: str = "evict") -> None:
        """Forget one host entry.  `reason` keeps the conservation ledger
        exact: "evict" = host-tier LRU budget pressure (n_host_evicted),
        "drain" = cache clear / re-donation / stale-gen cleanup
        (_host_drained), "restore" = the move to device (restore_pages
        counts it as n_restored).  Tolerates an already-drained entry —
        reset() empties the tier wholesale and the tree's clear() walk
        follows it."""
        e = self._host.pop(int(hid), None)
        if e is None:
            return
        self._host_bytes -= e["nbytes"]
        if reason == "evict":
            self.n_host_evicted += 1
        elif reason == "drain":
            self._host_drained += 1

    def take_pages(self, n: int) -> Optional[list]:
        """Pop `n` free pages for a host-tier restore WITHOUT binding
        them to a slot table (the engine scatters the host copies in,
        then adopt_restored + the tree's promote re-establish prefix
        retention).  One pressure call for the whole shortfall, like
        try_grow.  Returns None — nothing taken — when the pool cannot
        cover it; untake_pages rolls back a taken batch exactly."""
        n = int(n)
        shortfall = n - len(self._free)
        if shortfall > 0 and self.on_page_pressure is not None:
            if shortfall > self.cached_page_count:
                return None
            self.on_page_pressure(shortfall)
        if len(self._free) < n:
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            assert self._ref[p] == 0 and not self._cached[p], \
                f"free list held a referenced page {p}"
        self.version += 1
        return pages

    def untake_pages(self, pages) -> None:
        """Return a take_pages batch to the free list in the exact order
        it came off — page placement stays reproducible on rollback."""
        for p in reversed(pages):
            self._free.append(int(p))
        self.version += 1

    def adopt_restored(self, pages) -> None:
        """Mark freshly-restored pages as prefix-index retained.  Unlike
        cache_page (donation: the donor slot still maps the page) a
        restored page has no mapping yet — the restoring slot's
        map_shared follows immediately."""
        for p in pages:
            p = int(p)
            assert 0 < p < self.num_pages and self._ref[p] == 0 and \
                not self._cached[p], f"page {p} is not a fresh taken page"
            self._cached[p] = True

    def restore_pages(self, hids, pages) -> None:
        """Batched host->device restore: scatter each host entry's K/V
        into its taken device page in ONE jitted dispatch per
        reservation.  Page count buckets to the next power of two (pad
        rows write zeros to trash page 0) so compiled signatures are
        bounded by log2(num_pages), never by restore-batch diversity.
        MOVE semantics: the host copies drop here — a later re-spill
        re-copies."""
        n = len(hids)
        assert n == len(pages) and n > 0
        bucket = 1
        while bucket < n:
            bucket *= 2
        idx = np.zeros(bucket, np.int32)            # pad -> trash page 0
        idx[:n] = pages
        rows: dict = {}
        for name, pool in self.pools.items():
            rows[name] = {}
            for part, a in pool.items():
                buf = np.zeros((bucket,) + a.shape[1:], np.dtype(a.dtype))
                for i, hid in enumerate(hids):
                    buf[i] = self._host[int(hid)]["data"][name][part]
                rows[name][part] = buf
        self.pools = self._restore_fn(bucket)(
            self.pools, jnp.asarray(idx), rows)
        for hid in hids:
            self.drop_host_page(hid, reason="restore")
        self.n_restored += n

    def _restore_fn(self, bucket: int):
        if bucket not in self._restore_fns:
            def scatter(pools, pages, rows):
                # duplicate pad indices all write zeros to the trash
                # page, so the scatter's write order is immaterial
                return {name: {part: a.at[pages].set(rows[name][part])
                               for part, a in pool.items()}
                        for name, pool in pools.items()}

            from paddle_tpu.obs.compile_watch import get_compile_watch
            kw = {}
            if self.pool_sharding is not None:
                # same canonical-pool-sharding pin as the COW copy — a
                # drifted layout would reshard every pool next step
                kw["out_shardings"] = self.pool_shardings()
            self._restore_fns[bucket] = get_compile_watch().wrap_jit(
                "serving.spill_restore",
                jax.jit(scatter, donate_argnums=(0,), **kw))
        return self._restore_fns[bucket]

    # -- cross-replica page transfer ---------------------------------------
    def export_pages(self, pages) -> tuple[dict, bytes]:
        """Serialize live committed pages to host bytes — the kv_push
        transfer plane's sender half (docs/serving.md "Disaggregated
        prefill/decode").  One batched device->host gather per layer part
        in the spill tier's per-layer ndarray layout: the payload is the
        concatenation, over layers in SORTED name order, of each of the
        layer's parts in turn (the k block then the v block; a latent
        layer's one kv block), each `[n, page_size, *row]` row-major.
        Returns `(meta, payload)` where meta names the shapes/dtypes the
        importer must match exactly.  Pages must be live (slot-mapped or
        prefix-cached) — exporting a free page would ship garbage."""
        pages = [int(p) for p in pages]
        assert pages, "export_pages needs at least one page"
        for p in pages:
            assert 0 < p < self.num_pages and (
                self._ref[p] > 0 or self._cached[p]), \
                f"page {p} is not a live committed page"
        idx = np.asarray(pages, np.int32)
        names = sorted(self.pools)
        parts = []
        layers = []
        for name in names:
            pool = self.pools[name]
            for part in pool:
                parts.append(np.ascontiguousarray(
                    np.asarray(pool[part][idx])).tobytes())
            layers.append({"name": name, **self._row_meta(name),
                           "dtype": str(next(iter(pool.values())).dtype)})
        meta = {"n_pages": len(pages), "page_size": self.page_size,
                "layers": layers}
        self.n_exported += len(pages)
        return meta, b"".join(parts)

    def import_pages(self, meta: dict, payload: bytes, pages) -> None:
        """Scatter an export_pages blob into freshly-taken device pages —
        the kv_push receiver half.  Validates EVERYTHING (page count,
        page size, layer set, per-layer shapes/dtypes, exact payload
        length) before touching any device state and raises ValueError on
        mismatch, so the caller's `untake_pages(pages)` rollback restores
        the allocator exactly (`check()` stays green on partial failure).
        The scatter reuses the spill tier's pow2-bucketed restore jit —
        one dispatch, pad rows writing zeros to trash page 0."""
        n = len(pages)
        if int(meta.get("n_pages", -1)) != n:
            raise ValueError(
                f"kv import: blob holds {meta.get('n_pages')} pages, "
                f"caller took {n}")
        if int(meta.get("page_size", -1)) != self.page_size:
            raise ValueError(
                f"kv import: page_size {meta.get('page_size')} != "
                f"pool page_size {self.page_size}")
        layers = meta.get("layers") or []
        if [l.get("name") for l in layers] != sorted(self.pools):
            raise ValueError(
                f"kv import: layer set {[l.get('name') for l in layers]} "
                f"!= pool layers {sorted(self.pools)}")
        total = 0
        for l in layers:
            pool = self.pools[l["name"]]
            row = self.layer_specs[l["name"]]
            dtype = np.dtype(next(iter(pool.values())).dtype)
            want = dict(self._row_meta(l["name"]), dtype=str(dtype))
            got = {k: l.get(k) for k in want}
            if got != want:
                raise ValueError(
                    f"kv import: layer {l['name']!r} shape/dtype {got} != "
                    f"pool {want}")
            total += len(pool) * n * self.page_size * int(np.prod(row)) \
                * dtype.itemsize
        if len(payload) != total:
            raise ValueError(
                f"kv import: payload is {len(payload)} bytes, "
                f"meta declares {total}")
        for p in pages:
            p = int(p)
            assert 0 < p < self.num_pages and self._ref[p] == 0 and \
                not self._cached[p], f"page {p} is not a fresh taken page"
        bucket = 1
        while bucket < n:
            bucket *= 2
        idx = np.zeros(bucket, np.int32)            # pad -> trash page 0
        idx[:n] = [int(p) for p in pages]
        rows: dict = {}
        off = 0
        for l in layers:
            name = l["name"]
            rows[name] = {}
            for part, a in self.pools[name].items():
                dtype = np.dtype(a.dtype)
                shape = (n,) + tuple(a.shape[1:])
                count = int(np.prod(shape))
                buf = np.zeros((bucket,) + shape[1:], dtype)
                buf[:n] = np.frombuffer(payload, dtype, count=count,
                                        offset=off).reshape(shape)
                off += count * dtype.itemsize
                rows[name][part] = buf
        self.pools = self._restore_fn(bucket)(
            self.pools, jnp.asarray(idx), rows)
        self.n_imported += n

    def _row_meta(self, name: str) -> dict:
        """How a transfer blob names one layer's rows: `h_kv`, `dh` for
        per-head K and V; `parts`, `row` for a latent layer's one tensor."""
        row = self.layer_specs[name]
        if list(self.pools[name]) == ["k", "v"]:
            return {"h_kv": int(row[0]), "dh": int(row[1])}
        return {"parts": list(self.pools[name]), "row": [int(r) for r in row]}

    def pool_shardings(self) -> dict:
        """The canonical pool sharding laid over the pools' own tree."""
        return {name: {part: self.pool_sharding for part in pool}
                for name, pool in self.pools.items()}

    # -- device page copy (COW) -------------------------------------------
    def _page_copy(self):
        if self._copy_fn is None:
            # the allocator's pages are the FULL layers': a ring is its
            # slot's own and never shared
            paged = set(self.layer_specs) - set(self.ring_specs)

            def copy(pools, dst, src):
                return {name: {part: a.at[dst].set(a[src])
                               if name in paged else a
                               for part, a in pool.items()}
                        for name, pool in pools.items()}

            from paddle_tpu.obs.compile_watch import get_compile_watch
            kw = {}
            if self.pool_sharding is not None:
                # sharded pools must come back in the canonical pool
                # sharding — a drifted layout would force the next decode
                # step's explicit in_shardings to reshard every pool
                kw["out_shardings"] = self.pool_shardings()
            self._copy_fn = get_compile_watch().wrap_jit(
                "serving.cow_copy", jax.jit(copy, donate_argnums=(0,), **kw))
        return self._copy_fn

    # -- debugging / test oracle ------------------------------------------
    def check(self) -> None:
        """Assert the allocator invariants (tests call this after
        workloads): refcounts agree with the tables, the free list is
        exactly the unreferenced-and-uncached pages, no duplicates."""
        ref = np.zeros(self.num_pages, np.int32)
        for s in range(self.num_slots):
            for j in range(int(self._n_pages[s])):
                page = int(self.table[s, j])
                assert 0 < page < self.num_pages, \
                    f"slot {s} maps invalid page {page}"
                ref[page] += 1
        assert (ref == self._ref).all(), \
            f"refcounts disagree with tables: {self._ref.tolist()} vs " \
            f"recomputed {ref.tolist()}"
        free = set(self._free)
        assert len(free) == len(self._free), "free list holds duplicates"
        expect = {p for p in range(1, self.num_pages)
                  if self._ref[p] == 0 and not self._cached[p]}
        assert free == expect, \
            f"free list {sorted(free)} != unreferenced pages {sorted(expect)}"
        assert not self._cached[0] and self._ref[0] == 0, \
            "trash page 0 must never be referenced or cached"
        for name, R in self.ring_specs.items():
            # a ring is static: its pool is the slots' rings and the trash
            # page, whatever the allocator did
            for part, a in self.pools[name].items():
                assert a.shape[0] == 1 + self.num_slots * R, \
                    f"window layer {name!r} part {part!r}: {a.shape[0]} " \
                    f"pages, not 1 + {self.num_slots} slots x {R}"
        # host-tier accounting: bytes agree with the entries, every entry
        # belongs to the CURRENT generation (reset drains wholesale, so a
        # stale-gen entry means a drain was skipped), and the tier honors
        # its budget (empty when spilling is off)
        assert self._host_bytes == sum(
            e["nbytes"] for e in self._host.values()), \
            f"host-tier bytes {self._host_bytes} disagree with entries"
        assert all(e["gen"] == self._host_gen
                   for e in self._host.values()), \
            "host tier holds entries from a dead generation"
        assert self._host_bytes <= self.spill_bytes_budget, \
            f"host tier {self._host_bytes}B exceeds the " \
            f"{self.spill_bytes_budget}B spill budget"

    def check_reclaimed(self) -> None:
        """check() plus the end-of-workload invariant: no slot holds
        pages (private or shared), and everything off the free list is
        retained ONLY by the prefix index — evictable on demand, so the
        pool is fully reclaimable even though retired pages stay cached.
        Two-tier conservation: device free + device cached account for
        the whole pool (spilled pages freed their device page the moment
        their contents moved to host), and the spill/restore/evict
        counters reconcile against the host pages still resident."""
        self.check()
        assert self.private_pages_in_use == 0, \
            f"{self.private_pages_in_use} private pages still slot-mapped"
        assert self.shared_pages_in_use == 0, \
            f"{self.shared_pages_in_use} shared pages still slot-mapped"
        assert self.free_page_count + self.cached_page_count == \
            self.num_pages - 1, \
            f"free {self.free_page_count} + cached " \
            f"{self.cached_page_count} != pool {self.num_pages - 1}"
        assert self.host_page_count == \
            self.n_spilled - self.n_restored - self.n_host_evicted - \
            self._host_drained, \
            f"host tier {self.host_page_count} pages != spilled " \
            f"{self.n_spilled} - restored {self.n_restored} - evicted " \
            f"{self.n_host_evicted} - drained {self._host_drained}"
