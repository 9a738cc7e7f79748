"""One distributed trainer over the parameter-server tier.

Runs the standard Trainer with a RemoteParameterUpdater: the jitted step
computes gradients on-device, the optimizer applies on the pserver fleet
(tools/pserver.py), and with K sync trainers on disjoint stride shards
the result is BIT-IDENTICAL to one process training with grad_accum=K
(docs/distributed_training.md "Exactness contract").

  # shard 0 of 2 trainers against a single-shard pserver:
  python tools/train_dist.py --config demo/mnist/mlp_mnist.py \
      --pserver 127.0.0.1:8571 --rank 0 --trainers 2 --passes 2

Data sharding: each trainer takes every K-th batch of the config's data
stream (`--rank`-strided — the disjoint-shard convention the exactness
oracle assumes).  SIGTERM/SIGINT drains: the current batch finishes, the
trainer announces ps_drain + ps_leave (the barrier re-sizes, the fleet
continues), exit 0.  On completion prints one machine-readable line
(sync runs include the last pass's per-window attribution sums —
push/barrier_wait/pull ms):

  TRAIN_JSON:{"rank": 0, "passes": 2, "samples": 4096, ...}

Observability (docs/distributed_training.md "Observability"):
`--trace-out spans.jsonl` enables the span tracer for the run and writes
the retained ring on EVERY exit path (clean, drained, or crashed — the
spans up to a failure are exactly what a postmortem wants), led by a
`{"meta": {"process"}}` identity line so `tools/trace_dump.py --merge`
labels this trainer's track in a stitched fleet trace; `--save-dir`
appends one metrics.jsonl row per pass (the remote-updater timing fields
ride next to the throughput gauges).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_addrs(spec: str) -> list:
    out = []
    for part in spec.replace(";", ",").split(","):
        part = part.strip()
        if not part:
            continue
        host, _, port = part.rpartition(":")
        out.append((host or "127.0.0.1", int(port)))
    if not out:
        raise SystemExit("--pserver needs HOST:PORT[,HOST:PORT...] "
                         "(shard-index order, shard 0 first)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--config-args", default="")
    ap.add_argument("--pserver", required=True,
                    help="HOST:PORT[,HOST:PORT...] — every shard, shard "
                         "0 (the membership coordinator) first")
    ap.add_argument("--rank", type=int, default=None,
                    help="data-shard index = reduction rank (default: "
                         "server-assigned smallest free)")
    ap.add_argument("--trainers", type=int, default=1,
                    help="fleet size K for the stride data shard (this "
                         "trainer takes batches rank, rank+K, ...)")
    ap.add_argument("--passes", type=int, default=1)
    ap.add_argument("--log-period", type=int, default=0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--timeout-s", type=float, default=300.0,
                    help="pserver RPC timeout (a sync barrier waits at "
                         "most this long for straggler trainers)")
    ap.add_argument("--trace-out", default="",
                    help="enable the span tracer and write this "
                         "trainer's spans as JSONL here on every exit "
                         "path (trace_dump --merge food)")
    ap.add_argument("--save-dir", default="",
                    help="append one metrics.jsonl row per pass here "
                         "(remote-updater timing fields included)")
    args = ap.parse_args(argv)

    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.optim.remote_updater import RemoteParameterUpdater
    from paddle_tpu.trainer.trainer import Trainer
    from paddle_tpu.utils import enable_compile_cache

    enable_compile_cache()
    tracer = None
    if args.trace_out:
        from paddle_tpu.obs import get_tracer

        tracer = get_tracer()
        tracer.enabled = True

    cfg = parse_config(args.config, args.config_args)
    updater = RemoteParameterUpdater(
        cfg.model_config, cfg.opt_config, parse_addrs(args.pserver),
        rank=args.rank, timeout=args.timeout_s)
    tr = Trainer(cfg, seed=args.seed, updater=updater)
    rank = updater.rank
    print(f"joined as rank {rank} (tid {updater.client.tid}), "
          f"mode {updater.mode}", file=sys.stderr, flush=True)

    draining = {"flag": False}

    def on_term(_sig, _frm):
        print("SIGTERM: draining after the current batch",
              file=sys.stderr, flush=True)
        draining["flag"] = True

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)

    def shard(batches):
        """rank-strided disjoint shard, halting cleanly on drain."""
        for b in itertools.islice(batches, rank, None,
                                  max(args.trainers, 1)):
            if draining["flag"]:
                return
            yield b

    def flush_trace():
        # EVERY exit path flushes (serve.py's finally discipline): a
        # SIGTERM-drained or crashed trainer must still leave a
        # stitchable trace file with its identity line
        if tracer is not None:
            from paddle_tpu.obs import flush_trace_file

            flush_trace_file(tracer, args.trace_out, "trainer", rank=rank)

    t0 = time.time()
    samples = passes = 0
    stats: dict = {}
    try:
        for _ in range(args.passes):
            if draining["flag"]:
                break
            stats = tr.train_one_pass(batches=shard(tr.train_batches()),
                                      log_period=args.log_period)
            samples += int(stats.get("samples", 0))
            passes += 1
            if args.save_dir:
                tr.append_metrics(args.save_dir, extra=stats)
    finally:
        try:
            updater.drain_and_leave()
        finally:
            flush_trace()
    dt = time.time() - t0
    timing = {k: stats[k] for k in
              ("push_ms", "barrier_wait_ms", "pull_ms", "apply_ms",
               "compute_ms", "remote_windows", "async_stale_rejects")
              if k in stats}
    print("TRAIN_JSON:" + json.dumps({
        "rank": rank, "passes": passes, "samples": samples,
        "seconds": round(dt, 3),
        "samples_per_sec": round(samples / dt, 3) if dt > 0 else 0.0,
        "cost": stats.get("cost"),
        "drained": draining["flag"], **timing}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
