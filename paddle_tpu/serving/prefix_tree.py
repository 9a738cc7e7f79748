"""Radix prefix index over committed KV pages — host-side prefix caching.

Production serving traffic is dominated by shared prompt prefixes (system
prompts, few-shot templates, multi-turn chat history), yet every admission
used to pay full prefill even when the first N pages of KV were
bit-identical to work already done.  The page-table indirection of
serving/paged_kv.py makes sharing nearly free on the device side: a cached
prefix is just table entries pointing at already-committed physical pages
(the prefix-cache configuration of the Ragged Paged Attention line,
arXiv:2604.15464, on the slot/page serving design of arXiv:2605.25645).

The index is a radix tree keyed on token-id runs at PAGE granularity: each
node covers exactly `page_size` consecutive token ids and names the one
physical page holding their committed K/V.  A path from the root spells a
prompt prefix in whole pages.  On top of the full-page walk, `match` also
probes ONE page deeper for a partial-run match — a child whose run starts
with the remaining (< page_size) tokens.  Mapping that boundary page gives
the admission up to page_size-1 more cached tokens; because the request
will write its own divergent suffix into that page mid-run, the engine
must copy-on-write it first (PagedKVCache.ensure_writable) — the "COW
divergence mid-page" case.

Ownership: the tree holds pages via PagedKVCache's `_cached` mark (no
refcount of its own).  A node whose page no slot maps (`_ref == 0`) is
reclaimable; when the allocator runs out of pages it calls `evict_for`
(wired as `kv.on_page_pressure`), which evicts least-recently-used LEAVES
first — leaf-first keeps the prefix property (a parent outlives its
children), and refcount-zero-first means eviction never steals a page out
from under a live slot.  Eviction runs BEFORE the engine pauses slots;
preemption stays last resort.

THE KEPT FRONTIER: the set `evict_for` draws from — DEVICE nodes with no
DEVICE child whose page no slot maps — lives BETWEEN calls as a min-heap
on `last_use`, at most one entry a node (`_Node.queued`), so a call
costs its victims and not a walk of every node.  An entry is pushed by
the only events that can make a node eligible: its page's last mapping
went (`kv.on_cached_unmapped`, out of `PagedKVCache._unref`, finds the
node through `_by_page`), its last DEVICE child was evicted, or it
received a page nobody maps (an adopted insert, a promote).  Nothing
removes an entry early: `_touch`, a new mapping and a new child leave it
where it is, and the pop validates — an ineligible node is dropped (the
event that makes it eligible again pushes it again), a node touched
since the push goes back in under its present `last_use`.  A recorded
key is never larger than the true one, so eligible nodes still leave in
exact LRU order.  `_evictable_leaves()`, the walk, is the oracle
(`check_invariants`, the tests) and the rebuild where the tree is
loaded from other state (`rebuild`); it is not on the step path.

TWO-LEVEL EVICTION (the KV spill tier, docs/serving.md): with a non-zero
`kv.spill_bytes_budget`, a device-eviction victim is first offered to the
host tier — the node keeps its tokens but trades `page` for `host_id`
(spilled, resident HOST) instead of being destroyed.  Residency obeys ONE
invariant: a HOST node's entire subtree is HOST (spill order is
device-frontier-first), so "no DEVICE child" is exactly "no DEVICE
descendant" and the device-eviction frontier stays cheap to find.  Budget
room inside the host tier comes from dropping the least-recently-used
HOST leaves (LRU *within* the tier); destroying a device node whose
children already spilled drops that HOST subtree with it, keeping the
invariant.  A prefix hit on a spilled run restores through the engine's
admission path (`match_nodes` + `promote`), never here.  Node residency:
DEVICE = `page > 0, host_id None`; HOST = `page == -1, host_id int`;
detached/destroyed nodes zero both, so a stale reference can be told
from a live one.

Single-threaded by design: all calls happen on the engine's step()-driving
thread (the pump), like the rest of the scheduler state.
"""

from __future__ import annotations

import heapq
from typing import Optional

import numpy as np

from paddle_tpu.obs.flight import get_flight_recorder


class _Node:
    __slots__ = ("run", "page", "parent", "children", "by_first",
                 "last_use", "host_id", "queued")

    def __init__(self, run: tuple, page: int, parent: Optional["_Node"]):
        self.run = run                  # page_size token ids (() for root)
        self.page = page                # physical page id (-1 for root
        self.host_id = None             # and HOST/spilled nodes, which
        self.parent = parent            # carry a host-tier id instead)
        self.children: dict[tuple, _Node] = {}
        # first-token index over children: the partial-boundary probe
        # scans only runs sharing the probe's first token — donation adds
        # one divergent-boundary child per retired suffix under a hot
        # prefix node, and a linear scan there would put O(children)
        # admission cost on the hottest prefix exactly
        self.by_first: dict[int, dict[tuple, _Node]] = {}
        self.last_use = 0
        self.queued = False             # holds an entry of the kept frontier

    def add_child(self, child: "_Node") -> None:
        self.children[child.run] = child
        self.by_first.setdefault(child.run[0], {})[child.run] = child

    def drop_child(self, child: "_Node") -> None:
        del self.children[child.run]
        d = self.by_first[child.run[0]]
        del d[child.run]
        if not d:
            del self.by_first[child.run[0]]


class PrefixTree:
    """Radix index over committed pages of one PagedKVCache."""

    def __init__(self, kv):
        self.kv = kv
        self.ps = int(kv.page_size)
        self.root = _Node((), -1, None)
        self._clock = 0
        self.flight = get_flight_recorder()
        self.n_nodes = 0
        self.n_evictions = 0
        # the kept eviction frontier (module docstring): heap entries are
        # (last_use at push, push sequence, node); `_by_page` names the
        # DEVICE node of a physical page for the allocator's notification
        self._frontier: list = []
        self._seq = 0
        self._by_page: dict[int, _Node] = {}
        self.n_evict_calls = 0
        self.frontier_pops = {"victim": 0, "stale": 0, "ineligible": 0}
        kv.on_cached_unmapped = self._page_unmapped
        # the engine's restore path sets this while it allocates fresh
        # device pages: pressure eviction then destroys instead of
        # spilling, so the host tier (and the hids mid-restore) stays
        # stable under the restore's own allocation
        self._spill_inhibit = False

    @property
    def frontier_size(self) -> int:
        """Entries of the kept eviction frontier, valid or not yet
        validated: never more than `n_nodes`."""
        return len(self._frontier)

    # -- LRU ---------------------------------------------------------------
    def _touch(self, node: _Node) -> None:
        self._clock += 1
        node.last_use = self._clock

    # -- lookup ------------------------------------------------------------
    def match_nodes(self, tokens) -> \
            tuple[list["_Node"], Optional[tuple["_Node", int]]]:
        """Longest cached prefix of `tokens` as NODES, residency-blind:
        the full-page path may end in HOST nodes (the residency invariant
        guarantees device-prefix-then-host-suffix order along any path),
        and `partial` is (boundary_node, r) when a child's run
        additionally matches the next r (1 <= r < page_size, or up to the
        tokens left) tokens.  The engine's admission restores any HOST
        tail before mapping; `match` below is the device-only view.  Ties
        between partially-matching children break deterministically
        (longest match, then smallest run).  Touches the matched path for
        LRU."""
        toks = np.asarray(tokens).reshape(-1)
        node, nodes = self.root, []
        i, n = 0, int(toks.size)
        while n - i >= self.ps:
            run = tuple(int(t) for t in toks[i:i + self.ps])
            child = node.children.get(run)
            if child is None:
                break
            node = child
            self._touch(node)
            nodes.append(child)
            i += self.ps
        partial = None
        rest = tuple(int(t) for t in toks[i:i + self.ps])
        if rest:
            best, best_r = None, 0
            # only children whose run starts with the probe's first token
            # can match (r >= 1) — the by_first index skips the rest
            for run, child in node.by_first.get(rest[0], {}).items():
                r = 1
                while r < len(rest) and run[r] == rest[r]:
                    r += 1
                if r > best_r or (r == best_r and
                                  best is not None and run < best.run):
                    best, best_r = child, r
            if best is not None:
                self._touch(best)
                partial = (best, best_r)
        return nodes, partial

    def match(self, tokens) -> tuple[list[int], Optional[tuple[int, int]]]:
        """The DEVICE-resident view of match_nodes: physical page ids of
        the matched whole-page runs up to the first spilled node, plus
        (boundary_page_id, r) when the partial boundary is device-resident
        and every full run before it was.  The caller maps the partial
        page too and MUST copy-on-write it before its first write.
        Spill-unaware callers (and a budget-zero engine) see exactly the
        pre-spill behavior."""
        nodes, partial = self.match_nodes(tokens)
        pages = []
        for nd in nodes:
            if nd.host_id is not None:
                return pages, None
            pages.append(nd.page)
        if partial is not None and partial[0].host_id is None:
            return pages, (partial[0].page, partial[1])
        return pages, None

    # -- insertion (donation at retire/preempt/abort) ----------------------
    def insert(self, tokens, pages, adopted: bool = False) -> int:
        """Register `len(pages)` fully-committed pages: pages[j] holds the
        K/V of tokens[j*ps:(j+1)*ps].  A run already present keeps its
        existing physical page (the donated duplicate stays with the
        donor's normal release flow — it frees when the slot lets go);
        new runs retain their page via kv.cache_page.  Returns the number
        of nodes added.

        `adopted=True` is the cross-replica MOUNT path (a kv_push import,
        docs/serving.md "Disaggregated prefill/decode"): the pages came
        through kv.adopt_restored — already prefix-retained, mapped by no
        slot — so new and promoted runs skip cache_page (which demands a
        donor mapping), and a run already DEVICE-resident frees the
        redundant imported page right here via uncache_page (there is no
        donor slot whose release would reclaim it)."""
        toks = np.asarray(tokens).reshape(-1)
        assert toks.size >= len(pages) * self.ps
        node, added = self.root, 0
        unmapped = []           # nodes handed a page no slot maps (adopted)
        for j, page in enumerate(pages):
            page = int(page)
            run = tuple(int(t) for t in toks[j * self.ps:(j + 1) * self.ps])
            child = node.children.get(run)
            takes_page = True
            if child is None:
                child = _Node(run, page, node)
                node.add_child(child)
                self.n_nodes += 1
                added += 1
            elif child.host_id is not None:
                # re-donation of a spilled run: the donor just committed
                # a bit-identical device page (same token path, same
                # deterministic prefill), so adopt it and drop the host
                # copy — cheaper than ever restoring this one.  Insert
                # walks top-down, so a promoted node's ancestors promoted
                # in this same call: the residency invariant holds.
                self.kv.drop_host_page(child.host_id, reason="drain")
                child.host_id = None
                child.page = page
            else:
                takes_page = False
                if adopted:
                    # the run is already DEVICE-resident: the imported
                    # copy is bit-identical (same token path,
                    # deterministic prefill), keep the incumbent and free
                    # the duplicate now
                    self.kv.uncache_page(page)
            if takes_page:
                self._by_page[page] = child
                if adopted:
                    unmapped.append(child)
                else:
                    self.kv.cache_page(page)
            self._touch(child)
            node = child
        # a donated page is still mapped by its donor, whose release
        # offers the node (`_page_unmapped`); an adopted one is mapped by
        # nobody, so the node may be on the frontier from this moment
        for nd in unmapped:
            self._offer(nd)
        return added

    # -- eviction (the allocator's page-pressure hook) ----------------------
    def _evictable(self, node: _Node) -> bool:
        """`node` is on the device-eviction frontier: DEVICE (the root,
        HOST and destroyed nodes carry no page), mapped by no slot, no
        DEVICE child."""
        if node.page <= 0 or self.kv._ref[node.page] != 0:
            return False
        for ch in node.children.values():
            if ch.host_id is None:
                return False
        return True

    def _offer(self, node: _Node) -> None:
        """Give `node` its entry of the kept frontier if an event just
        made it evictable and it holds none."""
        if not node.queued and self._evictable(node):
            node.queued = True
            self._seq += 1
            heapq.heappush(self._frontier,
                           (node.last_use, self._seq, node))

    def _page_unmapped(self, page: int) -> None:
        """`kv.on_cached_unmapped`: the last slot mapping of a
        prefix-cached page went (release, a COW off it)."""
        node = self._by_page.get(page)
        if node is not None:
            self._offer(node)

    def _pop_victim(self) -> Optional[_Node]:
        """The least-recently-used evictable node, validated as it leaves
        the heap (module docstring); None when the frontier is empty."""
        heap, pops = self._frontier, self.frontier_pops
        while heap:
            key, _, node = heap[0]
            if not self._evictable(node):
                heapq.heappop(heap)
                node.queued = False
                pops["ineligible"] += 1
            elif key != node.last_use:
                self._seq += 1
                heapq.heapreplace(heap, (node.last_use, self._seq, node))
                pops["stale"] += 1
            else:
                heapq.heappop(heap)
                node.queued = False
                pops["victim"] += 1
                return node
        return None

    def _evictable_leaves(self):
        """The device-eviction frontier BY A WALK of the whole tree:
        DEVICE nodes whose page no slot maps and with no DEVICE children.
        By the residency invariant a HOST child has a HOST subtree, so
        "no DEVICE child" is "no DEVICE descendant" — spilling (or
        destroying, host subtree included) a frontier node keeps parents
        outliving device children.  The oracle of the kept frontier and
        its rebuild; `evict_for` does not come here."""
        out = []
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            if node.host_id is not None:
                continue                 # HOST subtree: nothing device below
            dev = [c for c in node.children.values() if c.host_id is None]
            if dev:
                stack.extend(dev)
            elif self.kv._ref[node.page] == 0:
                out.append(node)
        return out

    def _host_leaves(self):
        """Tree leaves resident HOST — the host tier's LRU victim set.
        Non-empty whenever the tier is (every host entry is named by a
        node, and a deepest HOST node is a leaf)."""
        out = []
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            if node.children:
                stack.extend(node.children.values())
            elif node.host_id is not None:
                out.append(node)
        return out

    def _drop_host_node(self, node: "_Node", reason: str = "evict") -> None:
        """Detach one HOST leaf and forget its host entry."""
        node.parent.drop_child(node)
        self.kv.drop_host_page(node.host_id, reason=reason)
        node.host_id = None
        self.n_nodes -= 1
        if reason == "evict":
            self.flight.record("prefix_evict", host=True,
                               nodes_left=self.n_nodes)

    def drop_host_subtree(self, top: "_Node") -> None:
        """Detach `top` and its all-HOST subtree, draining the host
        entries — stale-generation cleanup on the admission path (a node
        whose entry predates a kv.reset must never restore)."""
        top.parent.drop_child(top)
        stack = [top]
        while stack:
            nd = stack.pop()
            stack.extend(nd.children.values())
            if nd.host_id is not None:
                self.kv.drop_host_page(nd.host_id, reason="drain")
                nd.host_id = None
            nd.page = -1
            self.n_nodes -= 1

    def _try_spill(self, victim: "_Node") -> bool:
        """Offer a device-eviction victim to the host tier.  Makes budget
        room first by dropping LRU HOST leaves (the walk per drop is fine:
        one spill displaces at most a page's worth — typically one leaf —
        and pressure paths are admission-boundary, not per-token)."""
        kv = self.kv
        if self._spill_inhibit or kv.spill_bytes_budget <= 0 or \
                kv.page_nbytes > kv.spill_bytes_budget:
            return False
        while kv.host_bytes + kv.page_nbytes > kv.spill_bytes_budget:
            leaves = self._host_leaves()
            assert leaves, "host tier non-empty but no HOST leaf found"
            self._drop_host_node(min(leaves, key=lambda n: n.last_use))
        page = victim.page
        hid = kv.spill_page(page)
        if hid is None:
            return False
        victim.host_id = hid
        victim.page = -1
        del self._by_page[page]
        self.flight.record("spill", page=int(page),
                           host_pages=kv.host_page_count,
                           host_bytes=kv.host_bytes)
        return True

    def evict_for(self, n_pages: int) -> int:
        """Reclaim up to `n_pages` DEVICE pages off the LRU end of the
        kept eviction frontier.  Returns pages actually freed.  Wired as
        `kv.on_page_pressure`, so try_grow/COW call here before failing —
        eviction before pausing slots, preemption last resort.

        Two-level: each victim is offered to the host spill tier first
        (_try_spill — the node survives, resident HOST); only when the
        tier is off, inhibited, or can't make room does the node get
        DESTROYED — and destroying takes any HOST subtree beneath it too
        (an orphaned spilled run could never restore: the tree would no
        longer spell its prefix).  Either way one device page frees.

        NO tree walk: a call costs O((victims + entries dropped or
        re-keyed on the way) · log frontier) and touches no node it does
        not evict, however many the tree holds.  A victim's parent enters
        the heap the moment it has no device children and no slot mapping,
        so the multi-page reclaim an overcommitted admission needs can
        take a whole cold chain in one call.  `frontier_pops` counts what
        left the heap: victims over all pops is the share of the heap's
        work that freed a page."""
        self.n_evict_calls += 1
        n_pages, freed = int(n_pages), 0
        while freed < n_pages:
            victim = self._pop_victim()
            if victim is None:
                break
            parent = victim.parent
            if not self._try_spill(victim):
                for ch in list(victim.children.values()):
                    self.drop_host_subtree(ch)
                parent.drop_child(victim)
                page, victim.page = victim.page, -1
                del self._by_page[page]
                self.kv.uncache_page(page)
                self.n_nodes -= 1
                self.flight.record("prefix_evict", page=int(page),
                                   nodes_left=self.n_nodes)
            self.n_evictions += 1
            freed += 1
            self._offer(parent)         # its last DEVICE child may be gone
        return freed

    # -- restore (the engine's spilled-prefix-hit admission epilogue) -------
    def promote(self, nodes, pages) -> None:
        """Re-attach freshly-restored device pages to their HOST nodes
        (kv.adopt_restored already re-marked the pages cached).  The
        engine restores a contiguous HOST path tail top-down, so every
        promoted node's ancestors are device by the end of the call —
        the residency invariant holds."""
        for nd, page in zip(nodes, pages):
            assert nd.host_id is not None
            nd.host_id = None
            nd.page = int(page)
            self._by_page[nd.page] = nd
            self._touch(nd)
        # restored pages are mapped by nobody until the admission's
        # map_shared: the deepest of them is on the frontier meanwhile
        for nd in nodes:
            self._offer(nd)

    def clear(self) -> None:
        """Forget everything WITHOUT touching device-allocator state —
        pair with kv.reset(), which already drops the `_cached` marks.
        Host entries drain with the nodes that name them (a no-op after
        kv.reset, which empties the tier wholesale; load-bearing for
        set_prefix_cache(False), which must not leave orphaned host
        bytes)."""
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.host_id is not None:
                self.kv.drop_host_page(node.host_id, reason="drain")
                node.host_id = None
        self.root = _Node((), -1, None)
        self.n_nodes = 0
        self.rebuild()

    def rebuild(self) -> None:
        """Derive `_by_page` and the kept frontier from the nodes and the
        allocator's refcounts by a walk — wherever the tree was put
        together from other state (`clear`, the engine's `load_state`)."""
        self._by_page = {}
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.host_id is None:
                self._by_page[node.page] = node
        self._reset_frontier()

    def _reset_frontier(self) -> None:
        """The kept frontier as one walk gives it: exactly the evictable
        nodes, every key current."""
        for _, _, node in self._frontier:
            node.queued = False
        self._frontier = []
        for node in self._evictable_leaves():
            self._seq += 1
            node.queued = True
            self._frontier.append((node.last_use, self._seq, node))
        heapq.heapify(self._frontier)

    def check_invariants(self) -> None:
        """The kept frontier against the walk (tests, `load_state`): every
        evictable node holds an entry, an entry's node is attached and
        DEVICE and flagged, a recorded key is never ahead of `last_use`,
        one entry a node at most — so the heap never outgrows the tree."""
        device, stack = {}, list(self.root.children.values())
        n_nodes = 0
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            n_nodes += 1
            if node.host_id is None:
                assert node.page > 0 and node.page not in device, \
                    f"device page {node.page} named twice or not a page"
                device[node.page] = node
            else:
                assert node.page == -1 and not node.queued, \
                    "a HOST node holds a page or a frontier entry"
                assert all(c.host_id is not None
                           for c in node.children.values()), \
                    "a HOST node has a DEVICE child"
        assert n_nodes == self.n_nodes, \
            f"n_nodes {self.n_nodes} but {n_nodes} attached"
        assert device == self._by_page, "page index disagrees with the tree"
        entries = [node for _, _, node in self._frontier]
        assert len(set(map(id, entries))) == len(entries) <= n_nodes, \
            "a node holds two frontier entries"
        for key, _, node in self._frontier:
            assert node.queued and device.get(node.page) is node, \
                "a frontier entry names a node that is detached or HOST"
            assert key <= node.last_use, "a frontier key ahead of last_use"
        queued = sum(1 for nd in device.values() if nd.queued)
        assert queued == len(entries), "a queued flag without its entry"
        missing = [nd.page for nd in self._evictable_leaves()
                   if not nd.queued]
        assert not missing, f"evictable pages off the frontier: {missing}"
