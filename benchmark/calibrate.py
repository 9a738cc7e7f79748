#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the two numbers a limit of
`correct` is set from: what sound runs of the program give over many seeds,
and what the control gives (the plain reference computed in fp8, the
precision below the configuration's bfloat16, put in the program's place).
Run by hand through the chip tool; the benchmark's own runs do not run it.

  python3 benchmark/calibrate.py --workload <cell> --seeds 11,12,13,...
  python3 benchmark/calibrate.py --workload <open-loop cell> \
      --sweep-rates 2,3,4,5 --seconds 20          # the knee, found once
  python3 benchmark/calibrate.py --workload <serve cell> --seeds 1,2,3 \
      --window-lengths 40,51,100 --seconds 100    # spread against length
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sweep-rates", default="",
                    help="instead: offer an open-loop mix at each of these "
                         "rates in turn (the knee sweep), --seconds each")
    ap.add_argument("--window-lengths", default="",
                    help="instead: offer the mix for --seconds under each "
                         "seed and take the metrics over every disjoint "
                         "stretch of each of these lengths")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    os.chdir(ROOT)

    from benchmark.lib.common import Ctx, load_cell
    from benchmark.lib.spec import Benchmark

    bench = Benchmark(ROOT)
    cell, cfg, traffic = load_cell(bench, args.workload, args.rehearse)
    seeds = [int(s) for s in args.seeds.split(",") if s] or [1]
    ctx = Ctx(bench, cell, cfg, traffic, seeds[0], args.seconds, False,
              T_PROCESS, args.rehearse)
    os.makedirs(ctx.out_dir, exist_ok=True)
    kind = bench.kind(traffic["kind"])
    if args.sweep_rates:
        kind.sweep(ctx, [float(r) for r in args.sweep_rates.split(",")])
    elif args.window_lengths:
        kind.windows(ctx, seeds,
                     [float(v) for v in args.window_lengths.split(",")])
    else:
        kind.calibrate(ctx, seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
