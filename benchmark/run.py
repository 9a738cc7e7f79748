#!/usr/bin/env python3
"""Run ONE cell of the benchmark once.

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for --seconds, checks the outputs against the
plain reference and prints one JSON object as the last line of its output:
`correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end metrics,
or with --trace 1 its per-layer metrics), `device`, and in a traced run
`breakdown`; beside them `checks` (each number compared, with its limit) and
`notes` (readings without a bound that no metric carries).  It fails (no result line, exit code 2) when JAX finds no TPU
or fewer chips than the cell asks for.

--rehearse runs the same control flow on the CPU at the tiny sizes of
benchmark/rehearse.json (Pallas in interpret mode); it exits 3, and its
line says "rehearsal": true: a rehearsal is never a measurement."""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    os.chdir(ROOT)              # the DSL's data source paths are relative

    from benchmark.lib.common import Ctx, load_cell, log
    from benchmark.lib.spec import Benchmark

    bench = Benchmark(ROOT)
    cell, cfg, traffic = load_cell(bench, args.workload, args.rehearse)
    kind = bench.kind(traffic["kind"])
    ctx = Ctx(bench, cell, cfg, traffic, args.seed, args.seconds,
              bool(args.trace), T_PROCESS, args.rehearse)
    os.makedirs(ctx.out_dir, exist_ok=True)

    result = kind.run(ctx)

    e2e = {m["name"]: m for m in bench.end_to_end_for(cell["name"])}
    metrics = {}
    if not args.trace:
        for name, m in e2e.items():
            if name not in ctx.e2e:
                raise RuntimeError(f"cell {cell['name']} did not measure "
                                   f"its end-to-end metric {name}")
            metrics[name] = {"value": ctx.e2e[name], "unit": m["unit"]}
    else:
        for m in bench.per_layer_for(cell["name"]):
            try:
                value = bench.reader(m["name"]).read(ctx)
            except Exception as e:          # noqa: BLE001
                if not args.rehearse:
                    raise
                # the CPU has no kernels to find; a chip run raises
                log(f"METRIC {m['name']}: {type(e).__name__}: {str(e)[:200]}")
                continue
            if value is None:
                log(f"METRIC {m['name']}: nothing to read in this run")
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        tr = ctx.trace_data
        if tr is not None:
            result["device"]["busy_s"] = tr.busy_s()
            result["device"]["window_s"] = ctx.trace_window_s
            result["breakdown"] = tr.breakdown()
    result["metrics"] = metrics
    result["checks"] = ctx.checks
    if ctx.notes:
        result["notes"] = ctx.notes
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
