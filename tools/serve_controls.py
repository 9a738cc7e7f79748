#!/usr/bin/env python3
"""Read, on the chip and at a serve cell's own widths, the numbers a limit
on `serve_margin_nats` is set from, STRUCTURAL controls among them: ONE seed
a process (a `weights: deferred` configuration holds one weight set:
benchmark/kinds/serve.py:calibrate cannot give it a second, PERF.md section
7 row 27 b), the cell's engine and server as the benchmark builds them, the
mix's first `check_requests` requests that fit `check_max_tokens` served
through the wire; then, teacher-forced on those prompt + served tokens
through ONE full reference forward a request:

  program         the served tokens' margin (what the cell's runs check)
  reference_bf16  the reference in the configuration's own precision
  control_fp8     the reference in fp8, deciding the tokens
  <name>          the reference under each `--controls` setting (a JSON
                  object name -> configuration overrides, merged into nested
                  groups), deciding the tokens

Each control must read above the limit, the program and bf16 under it.
Run by hand through the chip tool; prints one CONTROLS line.

  python3 tools/serve_controls.py --workload <cell> --seed 11 --slots 16 \\
      --controls '{"no_window": {"sliding_window": 0}}'

`--slots` overrides the configuration's (the reading does not depend on
the slots; fewer leave the reference's forward the memory two sets of
[check_max_tokens, vocab] float32 log-probabilities take)."""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import json
import os
import random
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def merged(cfg: dict, over: dict) -> dict:
    """`cfg` with `over` laid in; a dict goes INTO the group of its name."""
    out = dict(cfg)
    for k, v in over.items():
        both = isinstance(v, dict) and isinstance(out.get(k), dict)
        out[k] = merged(out[k], v) if both else v
    return out


def control_margin(jax, ref, cfg, ctl_cfg, w, served, pad_to, quant=""):
    """benchmark/lib/check.py:served_margin with the tokens decided by the
    reference under `ctl_cfg` (and `quant`), in two programs so that only
    one [pad_to, vocab] table of log-probabilities is alive at a time."""
    import jax.numpy as jnp
    import numpy as np

    lp_ref = ref.jitted("log_probs", cfg)
    lp_ctl = ref.jitted("log_probs", ctl_cfg, quant)
    decide = jax.jit(lambda w, ids, rows: jnp.argmax(lp_ctl(w, ids, rows),
                                                     axis=-1))

    @jax.jit
    def margins(w, ids, rows, toks):
        lp = lp_ref(w, ids, rows)
        got = jnp.take_along_axis(lp, toks[:, None], axis=1)[:, 0]
        return jnp.max(lp, axis=-1) - got

    total = n = 0.0
    for prompt, new in served:
        seq, k = list(prompt) + list(new), len(new)
        ids = np.zeros(pad_to, np.int32)
        ids[:len(seq)] = seq
        rows = np.zeros(pad_to, np.int32)
        rows[:k] = np.arange(len(prompt) - 1, len(prompt) - 1 + k)
        with jax.default_matmul_precision("highest"):
            toks = decide(w, jnp.asarray(ids), jnp.asarray(rows))
            m = np.asarray(margins(w, jnp.asarray(ids), jnp.asarray(rows),
                                   toks))[:k]
        total += float(m.sum())
        n += k
    return {"mean_nats": total / n, "tokens": int(n)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--controls", default="{}")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    os.chdir(ROOT)

    from benchmark.lib.common import Ctx, load_cell, log, setup_jax
    from benchmark.lib.spec import Benchmark
    from benchmark.lib import traffic as traffic_mod

    bench = Benchmark(ROOT)
    cell, cfg, tf = load_cell(bench, args.workload, args.rehearse)
    if args.slots:
        cfg = dict(cfg, server_flags=dict(cfg["server_flags"],
                                          slots=args.slots))
    ctx = Ctx(bench, cell, cfg, tf, args.seed, 30.0, False, T_PROCESS,
              args.rehearse)
    os.makedirs(ctx.out_dir, exist_ok=True)
    kind = bench.kind(tf["kind"])
    jax, _ = setup_jax(ctx)
    from benchmark.lib.check import served_margin
    from paddle_tpu.serving.client import ServingClient
    from paddle_tpu.serving.server import ServingServer

    ref = bench.reference(cfg["reference"])
    engine, flags = kind.start_engine(ctx, ref)
    srv = ServingServer(engine, host=flags.host, port=0,
                        max_queue=flags.max_queue)
    host, port = srv.start_background()

    pad = int(tf["check_max_tokens"])
    try:
        # the sample is benchmark/kinds/serve.py:calibrate's, line for line
        # (it has no helper to import, and no file of the benchmark that
        # stands may be edited here: PERF.md section 7 row 27 b asks the
        # next `benchmark` PR to give `calibrate` the controls and one seed
        # a process, and to delete this tool)
        reqs = traffic_mod.serve_requests(tf, cfg["vocab_size"], args.seed, 30)
        reqs = [r for r in reqs if len(r["prompt"]) + r["max_new"] <= pad]
        random.Random(args.seed).shuffle(reqs)
        reqs = reqs[:int(tf["check_requests"])]
        with ServingClient(host, port, timeout=1800.0) as c:
            ids = [c.submit(r["prompt"], max_new=r["max_new"],
                            req_id=f"{args.seed}_{r['id']}") for r in reqs]
            got = c.collect(ids)
        served = [(r["prompt"], got[i]["tokens"][len(r["prompt"]):])
                  for r, i in zip(reqs, ids)]
    finally:
        srv.stop_background(drain=False, timeout=120)
    w = engine.params
    out = {"seed": args.seed,
           "lengths": [len(p) + len(n) for p, n in served],
           "program": served_margin(jax, ref, cfg, w, served, pad)}
    for name, over, quant in [("reference_bf16", {}, "bf16"),
                              ("control_fp8", {}, "fp8")] + [
            (name, over, "") for name, over in
            json.loads(args.controls).items()]:
        out[name] = control_margin(jax, ref, cfg, merged(cfg, over), w,
                                   served, pad, quant)
        log(f"{name}: {out[name]}")
    log("CONTROLS " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
