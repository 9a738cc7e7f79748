"""Time the hyper-connections' sublayer boundary alone on the chip at the
Xing4.0 cell's shapes (4 streams of 3,584, bfloat16; a mixed step's 1,088
rows and a decode step's 48): the stream pass through the `mhc_mix` kernel
(ops/pallas_hyper_conn.py) beside its jnp form, and the maps + read
(`mhc.map`: the norm, the [rows, 14336] x [14336, 24] product, 20 Sinkhorn
iterations, u = H_pre X) that stay XLA ops.

    chiprun -- python3 tools/bench_mhc.py

One line of JSON a reading (also appended to chiprun_out/bench_mhc.jsonl).
`us` is the host's clock over ONE program of `--calls` boundaries chained
on the device (each takes the streams the one before wrote, as a stack's
sublayers do), divided by the calls: no dispatch in it.  `gb_per_s` is
benchmark/lib/mhc_latent_moe.py's bytes of a stream pass over that time
(819 is the HBM's rate), `max_abs_diff` the kernel against the jnp form.
Fails off a TPU: a CPU time is no device number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N, C = 4, 3584


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="1088,48")
    ap.add_argument("--calls", type=int, default=200)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import hyper_conn, pallas_hyper_conn
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "no TPU: a CPU time is no device number"}))
        return 2
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out", "bench_mhc.jsonl"), "a")
    width = hyper_conn.map_width(N)
    for rows in (int(r) for r in args.rows.split(",")):
        ks = jax.random.split(jax.random.PRNGKey(rows), 4)
        x = jax.random.normal(ks[0], (rows, N * C), jnp.bfloat16)
        y = jax.random.normal(ks[1], (rows, C), jnp.bfloat16)
        phi = (0.02 * jax.random.normal(ks[2], (N * C, width))).astype(
            jnp.bfloat16)
        bias = jax.random.normal(ks[3], (1, width), jnp.bfloat16)
        alpha = jnp.ones((1, 3), jnp.bfloat16)
        maps = lambda v: hyper_conn.maps(v, phi, bias, alpha, n=N, iters=20,
                                         eps=1e-6, clamp=(-30.0, 30.0))
        m = maps(x)

        def chain(body):
            fn = jax.jit(lambda v: jax.lax.fori_loop(
                0, args.calls, lambda _, c: body(c), v))
            jax.block_until_ready(fn(x))            # compile, warm
            t = time.perf_counter()
            jax.block_until_ready(fn(x))
            return (time.perf_counter() - t) / args.calls * 1e6

        forms = {
            "mhc_mix": lambda v: pallas_hyper_conn.mhc_mix(v, y, m, N),
            "jnp_mix": lambda v: hyper_conn.mix(v, y, m, N),
            # the maps of a boundary and its read, then the kernel: what a
            # sublayer adds to a step beside F itself
            "map_read_mix": lambda v: pallas_hyper_conn.mhc_mix(
                v, (hyper_conn.read(v, maps(v), N) + y).astype(v.dtype),
                maps(v), N),
        }
        diff = float(jnp.max(jnp.abs(
            forms["mhc_mix"](x).astype(jnp.float32)
            - forms["jnp_mix"](x).astype(jnp.float32))))
        moved = rows * ((2 * N + 1) * C * 2 + width * 4)
        for name, body in forms.items():
            us = chain(body)
            rec = {"form": name, "rows": rows, "us": round(us, 2),
                   "gb_per_s": round(moved / us / 1e3, 1),
                   "max_abs_diff": diff}
            print(json.dumps(rec), flush=True)
            log.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
