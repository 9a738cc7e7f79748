"""Time the KDA layers' prompt chunks alone on the chip at the two cells'
shapes (128 slots, 192 chunk rows, a state of 128 x 128 float32 a head; 32
heads for Kimi-Linear, 64 for Solar-Open2): `kda.segment_rows` through the
`kda_seg` kernel (ops/pallas_kda_seg.py) against its jnp form (the
chunkwise scan of ops/kda.py under ops/slot_rows.py `advance_segments`).

    chiprun -- python3 tools/bench_kda.py
    chiprun -- python3 tools/bench_kda.py --heads 32 --runs 1x192,3x64
    chiprun -- python3 tools/bench_kda.py --decay head --heads 30 --dk 96 \
        --dv 192 --slots 24 --rows 1024 --runs 2x512,1x1024 --call both

`--decay head` times Gated DeltaNet's kernels (`gdn_seg`, and with `--call
step|both` the decode step `gdn_step` at `--slots` rows beside its jnp form)
at any `--dk` x `--dv`: the last line above is Olmo-Hybrid's cell.

One line of JSON a reading (also appended to chiprun_out/bench_kda.jsonl).
`ms` is the host's clock over `--calls` programs chained through the donated
state pool, each program `--layers` calls of the layer's one after another
(every call's v takes the call before's output, as a stack's layers do, so
nothing is shared between them but the run table), divided by calls x
layers: the time a layer's call adds to a mixed step, the host's dispatch
a tenth of what one call a program would read; `us_per_chunk` that over
the chunks of 64 the runs hold, a head.  `AxB` = A
runs of B rows in the 192 (the third starts where the second ended: no run
starts at a multiple of 64 unless B is one); `max_abs_diff` compares the
two forms' outputs and states.  Fails off a TPU: a CPU time is no device
number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

S, P, D = 128, 192, 128


def operands(H, rows, seed, beta_scale=1.0, slots=S, dk=D, dv=D,
             per_head=False):
    """The layer's own ranges: unit-norm q and k, decays with a memory of
    tens to thousands of tokens (one a head with `per_head`), beta in
    (0, beta_scale)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[0], (slots + 1, H, dk, dv), jnp.float32)
    q = kda.l2norm(jax.random.normal(ks[1], (rows, H, dk)))
    k = kda.l2norm(jax.random.normal(ks[2], (rows, H, dk)))
    v = jax.random.normal(ks[3], (rows, H, dv), jnp.float32)
    g = -jnp.exp(jax.random.uniform(
        ks[4], (rows, H) if per_head else (rows, H, dk), minval=-7,
        maxval=-1))
    beta = beta_scale * jax.nn.sigmoid(jax.random.normal(ks[5], (rows, H)))
    return state, (q, k, v, g, beta)


def run_table(spec, rows=P, slots=S):
    """`AxB` -> (seg_slot, seg_pos) of A runs of B rows, packed from row 0,
    each continuing a state (position 128 on)."""
    import numpy as np
    n_runs, length = (int(v) for v in spec.split("x"))
    assert n_runs * length <= rows, spec
    seg_slot = np.full(rows, slots, np.int32)
    seg_pos = np.zeros(rows, np.int32)
    for i in range(n_runs):
        seg_slot[i * length:(i + 1) * length] = (7 * i + 3) % slots
        seg_pos[i * length:(i + 1) * length] = 128 + np.arange(length)
    return seg_slot, seg_pos, n_runs * -(-length // 64)


def _ms(fn, state, rest, calls):
    import jax
    import numpy as np
    o0, state = fn(state, *rest)                   # compiled; the result
    first = np.asarray(state)                      # that is compared
    t = time.perf_counter()
    for _ in range(calls):
        o, state = fn(state, *rest)
    jax.block_until_ready((o, state))
    return (time.perf_counter() - t) / calls * 1e3, o0, first


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", default="32,64")
    ap.add_argument("--runs", default="1x192,2x96,3x64,3x50,12x16")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--layers", type=int, default=10)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--decay", choices=("channel", "head"), default="channel")
    ap.add_argument("--dk", type=int, default=D)
    ap.add_argument("--dv", type=int, default=D)
    ap.add_argument("--slots", type=int, default=S)
    ap.add_argument("--rows", type=int, default=P)
    ap.add_argument("--call", choices=("seg", "step", "both"), default="seg")
    a = ap.parse_args()
    per_head = a.decay == "head"

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import kda
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench_kda measures on a TPU, found {dev.platform}")
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/bench_kda.jsonl", "a")

    def say(**row):
        line = json.dumps(dict(row, device=dev.device_kind))
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for H in (int(h) for h in a.heads.split(",")):
        state, rest = operands(
            H, a.rows, a.seed, 2.0 if H == 64 or per_head else 1.0, a.slots,
            a.dk, a.dv, per_head)
        if a.call != "seg":
            step_rows(a, H, state, rest, say)
        for spec in a.runs.split(",") if a.call != "step" else ():
            seg_slot, seg_pos, chunks = run_table(spec, a.rows, a.slots)
            sl, sp = jnp.asarray(seg_slot), jnp.asarray(seg_pos)
            got = {}
            for impl in ("kernel", "jnp"):
                def stack(st, q, k, v, g, beta, impl=impl):
                    o = jnp.zeros_like(v)
                    for _ in range(a.layers):
                        o, st, _ = kda.segment_rows(
                            st, sl, sp, q, k, v + 1e-3 * o, g, beta,
                            use_kernel=impl == "kernel")
                    return o, st

                fn = jax.jit(stack, donate_argnums=(0,))
                calls = a.calls if impl == "kernel" else max(2, a.calls // 5)
                ms, o, st = _ms(fn, jnp.array(state), rest, calls)
                ms /= a.layers
                got[impl] = (np.asarray(o), st)
                say(call="seg", impl=impl, decay=a.decay, heads=H, runs=spec,
                    chunks=chunks,
                    layers=a.layers, ms=ms,
                    us_per_chunk=ms * 1e3 / chunks / H)
            say(call="seg", heads=H, runs=spec, max_abs_diff=[
                float(np.abs(x - y).max())
                for x, y in zip(got["kernel"], got["jnp"])])


def step_rows(a, H, state, rest, say):
    """The decode step at `--slots` live rows: `kda_step` / `gdn_step`
    beside the jnp step, `--layers` calls a program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import kda
    rows = tuple(x[:a.slots] for x in rest)
    live = jnp.ones((a.slots,), bool)
    got = {}
    for impl in ("kernel", "jnp"):
        def stack(st, q, k, v, g, beta, impl=impl):
            o = jnp.zeros_like(v)
            for _ in range(a.layers):
                o, st = kda.step_rows(st, None, live, q, k, v + 1e-3 * o, g,
                                      beta, use_kernel=impl == "kernel")
            return o, st

        fn = jax.jit(stack, donate_argnums=(0,))
        ms, o, st = _ms(fn, jnp.array(state), rows, a.calls)
        ms /= a.layers
        got[impl] = (np.asarray(o), st)
        moved = 2 * a.slots * H * a.dk * a.dv * 4
        say(call="step", impl=impl, decay=a.decay, heads=H, rows=a.slots,
            dk=a.dk, dv=a.dv, layers=a.layers, ms=ms,
            gb_per_s=moved / ms / 1e6)
    say(call="step", heads=H, max_abs_diff=[
        float(np.abs(x - y).max())
        for x, y in zip(got["kernel"], got["jnp"])])


if __name__ == "__main__":
    main()
