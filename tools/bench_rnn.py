"""Micro-benchmark: pallas LSTM/GRU time-grid kernels vs the lax.scan
fallback — the routing evidence the additive kernel got in round 4
but the RNN kernels never got on hardware.

Measures fwd+bwd training-step time at the shapes that matter:
the sentiment bench (B64 T30-ish D512-class hidden) plus a small and a
long-sequence point.  Prints one JSON line per (cell, impl, shape).

Usage: python tools/bench_rnn.py [--iters 3] [--shapes B,T,D;B,T,D;...]
(--iters = timed reps of the single-dispatch ~250ms scanned region, not
per-call loop iterations)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _time(loss, argnums, args, reps, est_flops):
    """Dispatch-proof timing (tools/_scan_bench): the grads of `loss`
    w.r.t. `argnums` chain into the next iteration's inputs inside one
    jitted scan — the old per-call loop + block_until_ready reported
    dispatch latency, not compute."""
    from _scan_bench import fold, scan_length, timed_chain

    def step(carry):
        l, g = jax.value_and_grad(loss, argnums=argnums)(*carry)
        return fold(carry, g), l

    return timed_chain(step, tuple(args), scan_length(est_flops), reps)


def bench_cell(cell: str, impl: str, B: int, T: int, D: int,
               iters: int) -> dict:
    assert cell in ("lstm", "gru"), f"unknown cell {cell!r}"
    # the scan entry points SELF-ROUTE to the pallas kernels on TPU when
    # D % 128 == 0 (ops/rnn.py _use_fused) — the 'scan' arm must force
    # the real lax.scan fallback or it benchmarks pallas against itself
    prev = os.environ.get("PADDLE_TPU_PALLAS")
    os.environ["PADDLE_TPU_PALLAS"] = "0" if impl == "scan" else "1"
    try:
        return _bench_cell(cell, impl, B, T, D, iters)
    finally:
        if prev is None:
            os.environ.pop("PADDLE_TPU_PALLAS", None)
        else:
            os.environ["PADDLE_TPU_PALLAS"] = prev


def _bench_cell(cell: str, impl: str, B: int, T: int, D: int,
                iters: int) -> dict:
    from paddle_tpu.ops import pallas_rnn, rnn

    rng = np.random.default_rng(0)
    lens = jnp.asarray(rng.integers(max(1, T // 2), T + 1, B), jnp.int32)
    z = jnp.zeros((B, D), jnp.float32)

    if cell == "lstm":
        x = jnp.asarray(rng.standard_normal((B, T, 4 * D)) * 0.5,
                        jnp.float32)
        w = jnp.asarray(rng.standard_normal((D, 4 * D)) * 0.2, jnp.float32)
        peeps = jnp.zeros((3, D), jnp.float32)

        if impl == "pallas":
            def loss(x, w):
                hs, hl, cl = pallas_rnn.lstm_fused(
                    x, lens, w, peeps, z, z, active_type="tanh",
                    gate_active_type="sigmoid", state_active_type="tanh",
                    reverse=False)
                return jnp.sum(hs * hs) + jnp.sum(hl * cl)
        else:
            def loss(x, w):
                hs, hl, cl = rnn.lstm_scan(x, lens, w, None)
                return jnp.sum(hs * hs) + jnp.sum(hl * cl)
        # fwd: T recurrent [B,D]x[D,4D] matmuls; bwd ~2.5x
        est = 3.5 * T * 2 * B * D * 4 * D
        dt = _time(loss, (0, 1), (x, w), iters, est)
    else:
        x = jnp.asarray(rng.standard_normal((B, T, 3 * D)) * 0.5,
                        jnp.float32)
        wg = jnp.asarray(rng.standard_normal((D, 2 * D)) * 0.2, jnp.float32)
        wc = jnp.asarray(rng.standard_normal((D, D)) * 0.2, jnp.float32)

        if impl == "pallas":
            def loss(x, wg, wc):
                hs, hl = pallas_rnn.gru_fused(
                    x, lens, wg, wc, z, active_type="tanh",
                    gate_active_type="sigmoid", reverse=False)
                return jnp.sum(hs * hs) + jnp.sum(hl)
        else:
            def loss(x, wg, wc):
                hs, hl = rnn.gru_scan(x, lens, wg, wc, None)
                return jnp.sum(hs * hs) + jnp.sum(hl)
        est = 3.5 * T * 2 * B * D * 3 * D
        dt = _time(loss, (0, 1, 2), (x, wg, wc), iters, est)

    return {"bench": "rnn", "cell": cell, "impl": impl,
            "B": B, "T": T, "D": D,
            "ms_per_step": round(dt * 1e3, 3),
            "tokens_per_sec": round(B * T / dt, 1)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=3,
                    help="timed reps of the scanned region")
    ap.add_argument("--shapes", default="64,30,512;16,8,64;8,512,256")
    ap.add_argument("--cells", default="lstm,gru")
    args = ap.parse_args()

    shapes = [tuple(int(v) for v in s.split(","))
              for s in args.shapes.split(";") if s]
    ok = True
    for B, T, D in shapes:
        for cell in args.cells.split(","):
            for impl in ("pallas", "scan"):
                try:
                    print(json.dumps(bench_cell(cell, impl, B, T, D,
                                                args.iters)), flush=True)
                except Exception as e:                  # noqa: BLE001
                    ok = False
                    print(json.dumps({
                        "bench": "rnn", "cell": cell, "impl": impl,
                        "B": B, "T": T, "D": D,
                        "error": f"{type(e).__name__}: {str(e)[:300]}"}),
                        flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
