#!/usr/bin/env python3
"""How far does a closed-loop serve cell's tokens/s swing with the seed?

A simulation on the HOST (no JAX, no chip; a time it prints is the model's,
never a device number): the benchmark's own request generator
(benchmark/lib/traffic.py) drives the engine's chunk packing
(serving/engine.py:_chunk_shares, `prefill_chunk` a share and the step's free
rows to the oldest) under a linear cost of a step,

    step = base + kv * (context of the decoding rows)
                + sum over chunk rows of (row + row_pos * position)

whose four numbers come from a cell's own `STEPS` lines (the defaults are the
Olmo-Hybrid cell's: decode step 14.5 ms at 156 k tokens of context, a mixed
step 14 ms + 59 us a chunk row at a mean position of 3.2 k; PERF.md section 6,
PR 59).  It prints, over `--seeds` seeds, tokens/s and `itl_p95_ms` with the
spread the driver judges a new cell by (the quartiles' distance over the
median in sets of six, the run farthest from the median left out, the mean of
two sets): what a window's LENGTH and the server's chunking do to the spread
can be read here before any chip time is spent on six runs.

  python3 tools/closed_loop_spread.py
  python3 tools/closed_loop_spread.py --seconds 120 --chunk 192 --step 216
"""

from __future__ import annotations

import argparse
import json
import os
import random
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import traffic as traffic_mod  # noqa: E402


def simulate(tf: dict, seed: int, seconds: float, chunk: int, step: int,
             base: float, kv: float, row: float, row_pos: float) -> dict:
    """One window: tokens/s, `itl_p95_ms`, requests done, steps by kind."""
    saved = traffic_mod._tokens
    traffic_mod._tokens = lambda rng, n, vocab: [0] * n   # lengths only
    try:
        reqs = traffic_mod.serve_requests(tf, 2, seed, seconds)
    finally:
        traffic_mod._tokens = saved
    queues: dict = {}
    for r in reqs:
        queues.setdefault(r["client"], []).append(r)
    seq = 0

    def admit(c):
        nonlocal seq
        r = queues[c].pop(0)
        seq += 1
        # prompt, cursor, outputs left, admission order, client
        return [len(r["prompt"]), 0, r["max_new"], seq, c]

    live = {c: admit(c) for c in sorted(queues)}
    w0 = float(tf["ramp_s"])
    w1 = w0 + seconds
    t, toks, done, mixed, decode = 0.0, 0, 0, 0, 0
    last: dict = {}
    gaps = []
    while t < w1:
        dec = [s for s in live.values() if s[1] >= s[0]]
        fill = sorted((s for s in live.values() if s[1] < s[0]),
                      key=lambda s: s[3])
        budget = step - len(dec)
        shares = []
        for s in fill:
            if budget <= 0:
                break
            n = min(s[0] - s[1], chunk, budget)
            shares.append([s, n])
            budget -= n
        for sh in shares:
            extra = min(sh[0][0] - sh[0][1] - sh[1], budget)
            budget -= extra
            sh[1] += extra
        dt = base + kv * sum(s[1] for s in dec)
        for s, n in shares:
            dt += n * row + row_pos * (s[1] * n + n * n / 2)
        inside = w0 <= t < w1
        mixed += inside and bool(shares)
        decode += inside and not shares
        t += dt
        inside = w0 <= t < w1
        emit = list(dec)
        for s, n in shares:
            s[1] += n
            if s[1] >= s[0]:
                emit.append(s)          # the final chunk samples token 0
        for s in emit:
            c = s[4]
            if s in dec:
                s[1] += 1
            s[2] -= 1
            if inside:
                toks += 1
                if c in last:
                    gaps.append(t - last[c])
            last[c] = t
            if s[2] == 0:
                done += inside
                last.pop(c, None)
                live[c] = admit(c)
    gaps.sort()
    return {"tokens_per_s": toks / seconds,
            "itl_p95_ms": gaps[int(0.95 * len(gaps))] * 1e3,
            "done": done, "mixed": mixed, "decode": decode}


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def judged_spread(values) -> float:
    """A set's spread as the driver takes it: the run farthest from the
    median left out where that narrows it."""
    m = statistics.median(values)
    far = max(values, key=lambda v: abs(v - m))
    rest = list(values)
    rest.remove(far)
    return min(spread(values), spread(rest))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--traffic", default="long-context-24")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seeds", type=int, default=120)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--step", type=int, default=280)
    ap.add_argument("--base-ms", type=float, default=8.0)
    ap.add_argument("--kv-us-per-token", type=float, default=0.042)
    ap.add_argument("--row-us", type=float, default=31.0)
    ap.add_argument("--row-us-per-kpos", type=float, default=8.5)
    args = ap.parse_args(argv)
    with open(os.path.join(traffic_mod.TRAFFIC_DIR,
                           args.traffic + ".json")) as f:
        tf = json.load(f)
    # a longer window needs more requests a client than the file holds
    tf["requests_per_client"] = max(int(tf["requests_per_client"]),
                                    int((args.seconds + tf["ramp_s"]) / 5))
    rng = random.Random(11)
    runs = [simulate(tf, rng.randrange(2 ** 31, 2 ** 32), args.seconds,
                     args.chunk, args.step, args.base_ms / 1e3,
                     args.kv_us_per_token / 1e6, args.row_us / 1e6,
                     args.row_us_per_kpos / 1e9)
            for _ in range(args.seeds)]
    out = {"traffic": args.traffic, "seconds": args.seconds,
           "chunk": args.chunk, "step": args.step, "seeds": args.seeds,
           "simulated": True}
    for key in ("tokens_per_s", "itl_p95_ms"):
        v = [r[key] for r in runs]
        sets = [judged_spread(v[i:i + 6]) for i in range(0, len(v) - 5, 6)]
        pairs = sorted((a + b) / 2 for a, b in zip(sets[::2], sets[1::2]))
        out[key] = {"median": round(statistics.median(v), 3),
                    "spread_pct": round(100 * spread(v), 2),
                    "two_sets_mean_pct": round(
                        100 * statistics.mean(pairs), 2) if pairs else None,
                    "two_sets_max_pct": round(100 * pairs[-1], 2)
                    if pairs else None}
    for key in ("done", "mixed", "decode"):
        out[key] = round(statistics.mean(r[key] for r in runs), 1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
