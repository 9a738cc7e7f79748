"""Time the Mamba-1 selective scan alone on the chip at the Jamba cell's
shapes (256 slots, d_in 5,120, N 16, float32 state): the scan kernel
(ops/pallas_selective_scan.py) against the jnp forms of
ops/selective_scan.py, and both against the bytes a call must move.

    chiprun -- python3 tools/bench_selective_scan.py
    chiprun -- python3 tools/bench_selective_scan.py --runs 2x128,4x64,8x32

One line of JSON a reading (also appended to
chiprun_out/bench_selective_scan.jsonl).  `ms` is the host's clock over
`--calls` calls chained through the donated state pool (each call waits for
the one before it), divided by the calls; `hbm_ms` is the call's bytes
(benchmark/lib/ssm_dense.py) at the chip's published bandwidth.  `step` is
one token a row at 256 rows — the decode step's call; `seg` is the chunk
rows of a mixed step, `AxB` = A runs of B tokens in 256 rows.  Fails off a
TPU: a CPU time is no device number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

S, N, D_IN, P = 256, 16, 5120, 256


def _operands(rows, seed):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[0], (S + 1, N, D_IN), jnp.float32)
    A = -jnp.exp(jnp.log(jnp.arange(1.0, N + 1))[:, None]
                 * jnp.ones((1, D_IN)))
    x = jax.random.normal(ks[2], (rows, D_IN), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[3], (rows, D_IN)) - 4.0)
    return state, A, x, dt, jax.random.normal(ks[4], (rows, N)), \
        jax.random.normal(ks[5], (rows, N))


def _ms(fn, state, rest, calls):
    import jax
    y0, state = fn(state, *rest)                   # compiled; the result
    jax.block_until_ready((y0, state))             # that is compared
    t = time.perf_counter()
    for _ in range(calls):
        y, state = fn(state, *rest)
    jax.block_until_ready((y, state))
    return (time.perf_counter() - t) / calls * 1e3, y0, state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", default="2x128,4x64,3x85,16x16")
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--seed", type=int, default=42)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.lib import ssm_dense
    from benchmark.lib.spec import peaks_for
    from paddle_tpu.ops import selective_scan as ss
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench_selective_scan measures on a TPU, found "
                 f"{dev.platform}")
    bw = peaks_for(dev.device_kind)["hbm_bytes_per_s"]
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/bench_selective_scan.jsonl", "a")

    def say(**row):
        line = json.dumps(dict(row, device=dev.device_kind))
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    # -- one token a row, 256 rows: the decode step's call -------------------
    state, A, x, dt, Bm, Cm = _operands(S, a.seed)
    live = jnp.ones((S,), bool)
    got = {}
    for impl in ("kernel", "jnp"):
        fn = jax.jit(lambda st, x, Bm, Cm, dt, A, impl=impl: ss.step_rows(
            st, None, live, x, Bm, Cm, dt, A, use_kernel=impl == "kernel"),
            donate_argnums=(0,))
        ms, y, st = _ms(fn, jnp.array(state), (x, Bm, Cm, dt, A), a.calls)
        got[impl] = np.asarray(y)
        nbytes = ssm_dense.scan_call_bytes(D_IN, N, runs=S, tokens=S)
        say(call="step", impl=impl, rows=S, ms=ms,
            hbm_ms=nbytes / bw * 1e3, roofline=nbytes / bw * 1e3 / ms)
    say(call="step", max_abs_diff=float(np.abs(got["kernel"]
                                               - got["jnp"]).max()))

    # -- the chunk rows of a mixed step ---------------------------------------
    state, A, x, dt, Bm, Cm = _operands(P, a.seed + 1)
    for spec in a.runs.split(","):
        n_runs, length = (int(v) for v in spec.split("x"))
        seg_slot = np.full(P, S, np.int32)
        seg_pos = np.zeros(P, np.int32)
        for i in range(n_runs):
            seg_slot[i * length:(i + 1) * length] = 7 * i + 3
            seg_pos[i * length:(i + 1) * length] = 128 + np.arange(length)
        sl, sp = jnp.asarray(seg_slot), jnp.asarray(seg_pos)
        got = {}
        for impl in ("kernel", "jnp"):
            fn = jax.jit(lambda st, x, Bm, Cm, dt, A, impl=impl:
                         ss.segment_rows(st, sl, sp, x, Bm, Cm, dt, A,
                                         use_kernel=impl == "kernel")[:2],
                         donate_argnums=(0,))
            calls = a.calls if impl == "kernel" else max(2, a.calls // 10)
            ms, y, st = _ms(fn, jnp.array(state), (x, Bm, Cm, dt, A), calls)
            got[impl] = np.asarray(y)
            tokens = n_runs * length
            nbytes = ssm_dense.scan_call_bytes(D_IN, N, runs=n_runs,
                                               tokens=tokens)
            flops = ssm_dense.scan_call_flops(D_IN, N, tokens)
            say(call="seg", impl=impl, runs=spec, tokens=tokens, ms=ms,
                us_per_token=ms * 1e3 / tokens, hbm_ms=nbytes / bw * 1e3,
                gflops=flops / ms / 1e6)
        say(call="seg", runs=spec,
            max_abs_diff=float(np.abs(got["kernel"] - got["jnp"]).max()))


if __name__ == "__main__":
    main()
