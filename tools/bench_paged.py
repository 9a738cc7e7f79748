"""Time the paged decode kernel alone on the chip at a serve cell's shapes:
where `ops/pallas_paged.py`'s cost a block and a page come from.

    chiprun -- python3 tools/bench_paged.py                   # sc2 decode + mixed
    chiprun -- python3 tools/bench_paged.py --shapes lfm2-decode,nemotron-decode
    chiprun -- python3 tools/bench_paged.py --budgets 512,1024  # VMEM budget, KiB
    chiprun -- python3 tools/bench_paged.py --fills            # rows of 1 / 256 / 512 / ... tokens

One line of JSON a reading (also appended to chiprun_out/bench_paged.jsonl):
`ms` is the DEVICE time of one `paged_attn` call, the mean of the profiler's
`tpu_custom_call` events over `--calls` calls.  `blocks` and `pages`
are what the call's rows walk (a dead row walks one block), so two fills
give the cost a block and a page (`--fills` fits them).  Fails off a TPU: a
CPU time is no device number.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: rows, slots, query heads, KV heads, head size, live-token range of a
#: decode row, rows of one prompt chunk (bf16, page 16, context 4,096): the
#: serve cells' decode and mixed steps at the fill their windows hold
SHAPES = {
    "sc2-decode": (64, 64, 24, 2, 128, (300, 1060), 0),
    "sc2-mixed": (128, 64, 24, 2, 128, (300, 1060), 64),
    "sc2-chat": (64, 64, 24, 2, 128, (0, 0), 0),     # dead rows but two
    "lfm2-decode": (256, 256, 32, 8, 64, (600, 2000), 0),
    "nemotron-decode": (256, 256, 32, 2, 128, (600, 2000), 0),
}
PAGE, MAXP = 16, 256


def _operands(name, seed, tokens=None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas_paged import kv_row_shape
    R, S, H, h_kv, D, (lo, hi), chunk = SHAPES[name]
    rng = np.random.default_rng(seed)
    P = S * MAXP + 1                                # + the trash page 0
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    kp, vp = (jax.random.normal(k, (P, PAGE) + kv_row_shape(h_kv, D),
                                jnp.bfloat16) for k in keys[:2])
    q = jax.random.normal(keys[2], (R, H, D), jnp.bfloat16)
    # every slot owns its pages, scattered over the pool; row S is the
    # all-zero row the padding rows read
    table = np.zeros((S + 1, MAXP), np.int32)
    table[:S] = (rng.permutation(S * MAXP) + 1).reshape(S, MAXP)
    lengths = np.zeros(R, np.int32)
    row_slot = np.full(R, S, np.int32)
    n_dec = R - chunk if hi else 2
    lengths[:n_dec] = tokens if tokens is not None else \
        rng.integers(lo, hi + 1, n_dec) if hi else (40, 300)
    row_slot[:n_dec] = np.arange(n_dec)
    if chunk:       # one prompt chunk: consecutive rows of the last slot
        lengths[n_dec:] = 192 + 1 + np.arange(chunk)
        row_slot[n_dec:] = S - 1
    return (q, kp, vp, jnp.asarray(table), jnp.asarray(lengths),
            jnp.asarray(row_slot)), lengths


def _walked(lengths, bt):
    """(blocks, pages fetched) of one call: every row folds at least one."""
    blocks = int(sum(max(1, -(-int(n) // bt)) for n in lengths))
    return blocks, blocks * (bt // PAGE)


def _device_ms(fn, args, calls):
    """Mean device time of the Pallas call, ms, over `calls` calls."""
    import jax
    jax.block_until_ready(fn(*args))
    d = tempfile.mkdtemp()
    with jax.profiler.trace(d):
        for _ in range(calls):
            y = fn(*args)
        jax.block_until_ready(y)
    path = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    ns = [ev.duration_ns for plane in data.planes
          if plane.name.startswith("/device:TPU:0")
          for line in plane.lines if line.name == "XLA Ops"
          for ev in line.events if "custom-call" in ev.name]
    assert len(ns) == calls, (len(ns), calls)
    return sum(ns) / calls / 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="sc2-decode,sc2-mixed,sc2-chat")
    ap.add_argument("--budgets", default="",
                    help="VMEM budgets to try, KiB (default: the kernel's)")
    ap.add_argument("--fills", action="store_true",
                    help="every live row at 1, 256, 512, 1024, 2048 tokens: "
                         "the cost a block and a page by least squares")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=42)
    a = ap.parse_args()

    import jax
    import numpy as np
    from paddle_tpu.ops import pallas_paged
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench_paged measures on a TPU, found {dev.platform}")
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/bench_paged.jsonl", "a")

    def say(**row):
        line = json.dumps(dict(row, device=dev.device_kind))
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    was = pallas_paged._KV_VMEM_BUDGET
    budgets = [int(b) << 10 for b in a.budgets.split(",") if b] or [was]
    for name in a.shapes.split(","):
        h_kv, D = SHAPES[name][3:5]
        row = pallas_paged.kv_row_shape(h_kv, D)
        for budget in budgets:
            pallas_paged._KV_VMEM_BUDGET = budget
            bt = pallas_paged.block_tokens(PAGE, row[0], row[1], 2, MAXP)
            # a function of its own a budget: jit's cache is keyed by it
            fn = jax.jit(lambda q, kp, vp, table, lengths, row_slot:
                         pallas_paged.paged_attention(
                             q, kp, vp, table, lengths, row_slot=row_slot))
            points = []
            for tokens in (1, 256, 512, 1024, 2048) if a.fills else (None,):
                args, lengths = _operands(name, a.seed, tokens)
                blocks, pages = _walked(lengths, bt)
                ms = _device_ms(fn, args, a.calls)
                points.append((blocks, pages, ms))
                say(shape=name, budget_kib=budget >> 10, block_tokens=bt,
                    rows=len(lengths), live_tokens=int(lengths.sum()),
                    blocks=blocks, pages=pages, ms=round(ms, 4),
                    hbm_ms=round(int(lengths.sum()) * 2 * row[0] * row[1] * 2
                                 / 819e9 * 1e3, 4))
            if a.fills:
                # ms = a call's fixed cost + rows' + blocks' + pages'; the
                # rows are constant here, so the fit is over blocks alone
                # (pages = blocks x pages a block at one budget)
                b, _, t = map(np.asarray, zip(*points))
                slope, fixed = np.polyfit(b, t, 1)
                say(shape=name, budget_kib=budget >> 10, block_tokens=bt,
                    us_a_block=round(slope * 1e3, 4),
                    ms_fixed_a_call=round(float(fixed), 4))
    pallas_paged._KV_VMEM_BUDGET = was


if __name__ == "__main__":
    main()
