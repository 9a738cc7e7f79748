"""Settle the sparse-table GSPMD question with banked HLO evidence.

`parallel/sparse.py:20-25` documents the failure mode the explicit
shard_map path exists for: GSPMD servicing a vocab-sharded embedding
lookup by ALL-GATHERING the table to every device (the opposite of the
reference's touched-rows-only economics, ref: math/SparseRowMatrix.h:211).
Whether XLA actually does that for the movielens step had never been
recorded (VERDICT r3 item 8, r4 item 6).

This tool compiles the full recommendation train step over an 8-device
mesh, inventories every collective in the optimized HLO, specifically
greps for all-gathers whose operand/result shape matches a table's row
space, and prints a JSON verdict.  Run under the virtual CPU mesh
(JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8):
the sharding propagation + SPMD partitioning passes that make this
decision run before backend-specific lowering, so the partitioned
program's collective structure is evidence a single chip cannot
provide (a 1-device mesh partitions nothing).

Usage: [env above] python tools/hlo_sparse_check.py [--save PATH.hlo]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_SHAPE_RE = re.compile(r"[a-z0-9]+\[([0-9,]+)\]")


def gather_spans_table(line: str, tables) -> bool:
    """True iff an all-gather HLO line MATERIALIZES a sharded table: some
    operand/result tensor shape equals the table's full shape, gathered
    along the table's sharded axis.

    Substring-matching a row count anywhere in the line false-positives on
    unrelated collectives that merely carry the number — a logits/feature-
    dimension activation gather, a replica_groups entry, a channel id
    (ADVICE r5).  So: parse the `dtype[d0,d1,...]` shape tokens BEFORE the
    attribute tail (replica_groups=... onward contains bracketed iota
    lists that are not shapes), and flag only when a token's FULL dim
    tuple equals a table shape — the signature of GSPMD reassembling the
    whole table — and the `dimensions={d}` gather axis is that table's
    sharded axis (a coincidentally table-shaped tensor gathered along an
    unsharded dim stays clean).

    GSPMD's grouped lowering may gather into an UNMERGED form — e.g.
    [rows/8, 8, D] (shard axis inserted next to the sharded dim, bitcast
    to [rows, D] afterwards) — so each token is also tried with the gather
    dim merged into either neighbor.

    `tables`: iterable of (shape tuple, sharded-axis index or None)."""
    m = re.search(r"dimensions=\{(\d+)", line)
    gdim = int(m.group(1)) if m else None
    head = line.split("replica_groups=")[0].split("metadata=")[0]
    toks = [tuple(int(x) for x in sm.group(1).split(",") if x)
            for sm in _SHAPE_RE.finditer(head)]

    def candidates(dims):
        """(shape, effective gathered-axis) readings of one token."""
        out = [(dims, gdim)]
        if gdim is not None and gdim < len(dims):
            if gdim > 0:               # merge into the left neighbor
                out.append((dims[:gdim - 1]
                            + (dims[gdim - 1] * dims[gdim],)
                            + dims[gdim + 1:], gdim - 1))
            if gdim < len(dims) - 1:   # merge into the right neighbor
                out.append((dims[:gdim]
                            + (dims[gdim] * dims[gdim + 1],)
                            + dims[gdim + 2:], gdim))
        return out

    for shape, axis in tables:
        shape = tuple(shape)
        for dims in toks:
            for cand, cdim in candidates(dims):
                if cand != shape:
                    continue
                if cdim is not None and axis is not None and cdim != axis:
                    continue
                return True
    return False


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--save", default=os.path.join(REPO, "output",
                                                   "recsys_step.hlo"))
    ap.add_argument("--data", type=int, default=8)
    ap.add_argument("--model", type=int, default=1)
    args = ap.parse_args()

    import jax

    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.trainer.trainer import Trainer

    n = args.data * args.model
    if len(jax.devices()) < n:
        print(json.dumps({"error": f"need {n} devices, have "
                          f"{len(jax.devices())} — run with JAX_PLATFORMS="
                          f"cpu XLA_FLAGS=--xla_force_host_platform_device_"
                          f"count={n}"}))
        return 1

    mesh = make_mesh(data=args.data, model=args.model)
    # the BASELINE bench dims (MovieLens-1M): title_vocab 5100 % 8 != 0 so
    # that one table legitimately stays replicated — the check covers the
    # two big sharded ones (movie 3952, user 6040)
    cfg = parse_config("demo/recommendation/trainer_config.py",
                       "batch_size=64,movie_dim=3952,user_dim=6040,"
                       "title_vocab=5100")
    tr = Trainer(cfg, seed=1, mesh=mesh)

    # which params came out vocab-sharded, their shapes + sharded axis
    sharded = {}
    tables = []
    for k, v in tr.params.items():
        spec = list(getattr(v.sharding, "spec", []) or [])
        if any(s is not None for s in spec):
            sharded[k] = {"shape": list(v.shape), "spec": [str(s) for s in spec]}
            axis = next((i for i, s in enumerate(spec) if s is not None), None)
            tables.append((tuple(v.shape), axis))
    if not sharded:
        print(json.dumps({"error": "no sharded tables under the mesh"}))
        return 1

    batch = next(tr.train_batches())
    hlo = tr._train_step.lower(tr.params, tr.opt_state, tr.net_state, batch,
                               jax.random.PRNGKey(0)).compile().as_text()
    try:
        os.makedirs(os.path.dirname(args.save), exist_ok=True)
        with open(args.save, "w") as f:
            f.write(hlo)
    except OSError:
        pass

    # inventory every collective op in the optimized module, including the
    # async forms (all-gather-start/-done — the standard TPU lowering);
    # -done lines are skipped so async pairs count once
    colls: dict[str, int] = {}
    gathers = []          # full lines — the shape/dimension parse needs
    for ln in hlo.splitlines():   # the attribute tail; truncate on output
        m = re.search(r"(all-gather|all-reduce|reduce-scatter|"
                      r"all-to-all|collective-permute)(-start|-done)?\(", ln)
        if not m or m.group(2) == "-done":
            continue
        op = m.group(1)
        colls[op] = colls.get(op, 0) + 1
        if op == "all-gather":
            gathers.append(ln.strip())

    # does any all-gather materialize a table — full table shape gathered
    # along its sharded axis?  (shape-anchored — see gather_spans_table)
    table_gathers = [ln[:200] for ln in gathers
                     if gather_spans_table(ln, tables)]

    verdict = {
        "mesh": {"data": args.data, "model": args.model},
        "sharded_tables": sharded,
        "collectives": colls,
        "n_all_gathers": len(gathers),
        "table_all_gathers": table_gathers,
        "verdict": ("GSPMD all-gathers a vocab-sharded table — switch the "
                    "config to parallel/sparse.py:sharded_embedding_lookup"
                    if table_gathers else
                    "no table all-gather: GSPMD services the lookup with "
                    "local gather + reduction (touched-rows economics hold)"),
        "hlo_saved": args.save,
    }
    print(json.dumps(verdict), flush=True)
    return 0 if not table_gathers else 2


if __name__ == "__main__":
    sys.exit(main())
