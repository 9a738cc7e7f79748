"""Time one call of the prefix index's eviction on the HOST's clock, the
kept frontier beside the walk it replaced: what `pt.kv.evict` costs a full
pool before the next launch (`serving/prefix_tree.py`).

    JAX_PLATFORMS=cpu python3 tools/bench_prefix_evict.py
    JAX_PLATFORMS=cpu python3 tools/bench_prefix_evict.py --nodes 1024,4096,16384 --calls 40

The pattern is the decode-saturated cell's: the pool is full, every node a
retired request's donated page in a chain of `--chain` pages with no slot
on it, and each round (1) a page-pressure call evicts `--victims` pages,
the coldest chains leaf to top, (2) a retiring request donates as many
again (`insert` + `release`: the events that feed the kept frontier), and
(3) admissions match `--hits` held chains (their entries go stale).  `kept`
is `PrefixTree.evict_for` as it stands; `walk` rebuilds the frontier from
`_evictable_leaves()` before the same call — the tree as it was when every
call walked every node.  Both free the same pages (`same_victims`).

One line of JSON a size (also appended to
chiprun_out/bench_prefix_evict.jsonl): `*_ms` the median and the p95 of a
call, `donate_us_per_page` the bookkeeping of one donated page (insert, the
release's notification and the push), `pops` what left the heap in `kept`.
A host number wherever it runs; no device is touched after the pool is made.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _machine() -> str:
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return platform.processor() or platform.machine()


def _tree(executor, nodes, chain, victims):
    from paddle_tpu.serving import PagedKVCache, PrefixTree
    kv = PagedKVCache(executor, num_slots=1, page_size=4,
                      pages_per_slot=max(chain, victims),
                      num_pages=nodes + max(chain, victims) + 1)
    tree = PrefixTree(kv)
    kv.on_page_pressure = tree.evict_for
    return kv, tree


def _donate(kv, tree, rng, n_pages, chain, seqs):
    """A retiring request's donation: chains of `chain` fresh pages."""
    import numpy as np
    ps = kv.page_size
    for at in range(0, n_pages, chain):
        n = min(chain, n_pages - at)
        toks = rng.integers(0, 1 << 30, ps * n).astype(np.int32)
        assert kv.try_grow(0, toks.size, evict=False)
        tree.insert(toks, [int(kv.table[0, j]) for j in range(n)])
        kv.release(0)
        seqs.append(toks)


def bench(executor, nodes, chain, victims, calls, hits, mode, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    kv, tree = _tree(executor, nodes, chain, victims)
    seqs = []
    _donate(kv, tree, rng, nodes, chain, seqs)
    assert tree.n_nodes == nodes
    evict_s, donate_s, freed = [], [], []
    pops0 = dict(tree.frontier_pops)
    for _ in range(calls):
        for i in rng.integers(0, len(seqs), hits):   # admissions that hit
            tree.match(seqs[int(i)])
        tail = len(kv._free)
        t0 = time.perf_counter()
        if mode == "walk":
            tree._reset_frontier()
        n = tree.evict_for(victims)
        evict_s.append(time.perf_counter() - t0)
        assert n == victims
        freed.append(tuple(kv._free[tail:]))
        seqs[:] = [t for t in seqs                   # chains still held
                   if tuple(int(x) for x in t[:kv.page_size])
                   in tree.root.children]
        t0 = time.perf_counter()
        _donate(kv, tree, rng, victims, chain, seqs)
        donate_s.append(time.perf_counter() - t0)
    tree.check_invariants()
    pops = {k: v - pops0[k] for k, v in tree.frontier_pops.items()}
    return evict_s, donate_s, pops, freed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--nodes", default="1024,4096,16384")
    ap.add_argument("--chain", type=int, default=32,
                    help="pages a retired request donates (one chain)")
    ap.add_argument("--victims", type=int, default=64)
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--hits", type=int, default=4,
                    help="held chains an admission matches between calls")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.graph import GraphExecutor
    cfg = parse_config(os.path.join(REPO, "demo/model_zoo/transformer_lm.py"),
                       "vocab=23,dim=16,layers=1,heads=2,batch_size=4")
    executor = GraphExecutor(cfg.model_config)

    os.makedirs("chiprun_out", exist_ok=True)
    p95 = lambda xs: sorted(xs)[min(len(xs) - 1, int(0.95 * len(xs)))]
    for nodes in (int(n) for n in args.nodes.split(",")):
        row = {"nodes": nodes, "victims": args.victims, "chain": args.chain,
               "calls": args.calls, "machine": _machine(),
               "python": platform.python_version()}
        freed = {}
        for mode in ("walk", "kept"):
            ev, don, pops, freed[mode] = bench(
                executor, nodes, args.chain, args.victims, args.calls,
                args.hits, mode, args.seed)
            row[f"{mode}_ms"] = round(1e3 * statistics.median(ev), 4)
            row[f"{mode}_p95_ms"] = round(1e3 * p95(ev), 4)
            row[f"{mode}_donate_us_per_page"] = round(
                1e6 * statistics.median(don) / args.victims, 3)
            if mode == "kept":
                row["pops"] = pops
        row["same_victims"] = freed["walk"] == freed["kept"]
        row["speedup"] = round(row["walk_ms"] / row["kept_ms"], 1)
        line = json.dumps(row)
        print(line, flush=True)
        with open("chiprun_out/bench_prefix_evict.jsonl", "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
