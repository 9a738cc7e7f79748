"""Capture an xplane profile of the VGG/CIFAR-10 train step on TPU.

Evidence tool for the round-4 regression (VERDICT r5 item 3: 51.4k
samples/s @ 37.1% MFU measured r4 vs 56.7k @ ~41% claimed r2 — same code
paths).  Runs the exact bench_vgg step under `jax.profiler.trace`, banks
the raw xplane under output/xplane_vgg/, and prints an op-level
breakdown (top self-time HLO ops) so the evidence is kept with the run.  The r2 profile's signature to compare against (PERF.md): BN
fusions ~25%, max-pool select-and-scatter ~9%, no single op >4.4%.

Usage: python tools/profile_vgg.py [--iters 30] [--batch 128]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")


def capture(iters: int, batch_size: int, outdir: str) -> dict:
    import jax
    import numpy as np

    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.parameter.argument import Argument
    from paddle_tpu.trainer.trainer import Trainer

    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    cfg = parse_config("demo/image_classification/vgg_16_cifar.py",
                       f"batch_size={batch_size},compute_dtype={dtype}")
    tr = Trainer(cfg, seed=1)

    rng = np.random.default_rng(0)
    batches = []
    for _ in range(2 + iters):
        x = rng.random((batch_size, 3 * 32 * 32), np.float32) - 0.5
        y = rng.integers(0, 10, batch_size).astype(np.int32)
        batches.append({"image": Argument(value=x.astype(np.float32)),
                        "label": Argument(ids=y)})

    # compile + warmup OUTSIDE the trace (same shape as the bench's step)
    stats = tr.benchmark(iter(batches[:4]), warmup=2, iters=2, scan=False)
    t0 = time.perf_counter()
    with jax.profiler.trace(outdir):
        s = tr.benchmark(iter(batches), warmup=0, iters=iters, scan=False)
    wall = time.perf_counter() - t0
    return {"samples_per_sec_unscanned": round(s["samples_per_sec"], 1),
            "trace_wall_s": round(wall, 2), "iters": iters,
            "warmup_samples_per_sec": round(stats["samples_per_sec"], 1)}


def analyze(outdir: str, top: int = 25) -> None:
    """Op-level self-time breakdown straight from the xplane protos — the
    tool-data converters (op_profile etc.) are version-fragile, so walk the
    device plane's events directly."""
    paths = sorted(glob.glob(os.path.join(outdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        print(json.dumps({"analyze_error": f"no xplane.pb under {outdir}"}))
        return
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as e:
        print(json.dumps({"analyze_error": f"xplane_pb2 unavailable "
                          f"({type(e).__name__}); raw profile kept at "
                          + outdir}))
        return

    def collect(plane_pred, line_pred):
        agg: dict[str, float] = {}
        total = 0.0
        for path in paths:
            xspace = xplane_pb2.XSpace()
            with open(path, "rb") as f:
                xspace.ParseFromString(f.read())
            for plane in xspace.planes:
                if not plane_pred(plane.name):
                    continue
                names = {mid: m.name
                         for mid, m in plane.event_metadata.items()}
                for line in plane.lines:
                    if not line_pred(line.name):
                        continue
                    for ev in line.events:
                        dur = ev.duration_ps / 1e12
                        nm = names.get(ev.metadata_id, "?")
                        agg[nm] = agg.get(nm, 0.0) + dur
                        total += dur
        return agg, total

    # TPU: per-op events ride the device plane's "XLA Ops" line; on CPU
    # (smoke-test path) they ride tf_XLA* host thread lines instead
    agg, total = collect(
        lambda p: "TPU" in p or "/device:" in p,
        lambda ln: ln == "XLA Ops")
    if total == 0.0:
        agg, total = collect(lambda p: p == "/host:CPU",
                             lambda ln: ln.startswith("tf_XLA"))
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
    print(json.dumps({"op_total_s": round(total, 4), "source": paths}))
    for name, sec in rows:
        print(json.dumps({"op": name[:120], "self_s": round(sec, 4),
                          "pct": round(100 * sec / total, 2) if total else 0}),
              flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--analyze-only", default="")
    ap.add_argument("--outdir",
                    default=os.path.join(REPO, "output", "xplane_vgg"))
    args = ap.parse_args()
    if args.analyze_only:
        analyze(args.analyze_only)
        return 0
    os.makedirs(args.outdir, exist_ok=True)
    info = capture(args.iters, args.batch, args.outdir)
    print(json.dumps(info), flush=True)
    analyze(args.outdir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
