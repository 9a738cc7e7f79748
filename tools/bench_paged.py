"""Time the paged decode kernel alone on the chip at a serve cell's shapes:
where `ops/pallas_paged.py`'s cost a block and a page come from.

    chiprun -- python3 tools/bench_paged.py                   # sc2 decode + mixed
    chiprun -- python3 tools/bench_paged.py --shapes lfm2-decode,nemotron-decode
    chiprun -- python3 tools/bench_paged.py --budgets 512,1024  # VMEM budget, KiB
    chiprun -- python3 tools/bench_paged.py --fills            # rows of 1 / 256 / 512 / ... tokens
    chiprun -- python3 tools/bench_paged.py --shapes laguna-mixed --tile-rows 1,8  # every row alone / tiles of 8
    chiprun -- python3 tools/bench_paged.py --shapes xing-mixed,xing-decode   # the latent kernel

One line of JSON a reading (also appended to chiprun_out/bench_paged.jsonl):
`ms` is the DEVICE time of one `paged_attn` call, the mean of the profiler's
events of that name over `--calls` calls (`other_ops_ms`: the call's other
device ops — q's and the output's layouts, the tiles' runs).  `blocks` and `pages`
are what the call fetches (`pallas_paged.walked_blocks`: a run of one
slot's rows in a tile walks its blocks once, a dead row walks one), so two fills
give the cost a block and a page (`--fills` fits them); `shared_rows` are
the rows on such a walk.  Fails off a TPU: a CPU time is no device number.
"""

from __future__ import annotations

import argparse
import glob
import itertools
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: rows, slots, query heads, KV heads, head size, live-token range of a
#: decode row, the prompt chunks' runs as (rows, first position) — each the
#: consecutive rows of a slot of its own, the last slots — and the pages a
#: table row maps (bf16, page 16): the serve cells' decode and mixed steps
#: at the fill their windows hold
SHAPES = {
    "sc2-decode": (64, 64, 24, 2, 128, (300, 1060), (), 256),
    "sc2-mixed": (128, 64, 24, 2, 128, (300, 1060), ((64, 192),), 256),
    "sc2-chat": (64, 64, 24, 2, 128, (0, 0), (), 256),  # dead rows but two
    "lfm2-decode": (256, 256, 32, 8, 64, (600, 2000), (), 256),
    "nemotron-decode": (256, 256, 32, 2, 128, (600, 2000), (), 256),
    # laguna-xs2-33b-serve.long-context-64's full layer: 64 decode rows at
    # 4,000 tokens beside two chunks deep in 8k prompts
    "laguna-mixed": (320, 66, 48, 8, 128, (4000, 4000),
                     ((128, 1000), (128, 5000)), 512),
    "laguna-decode": (64, 64, 48, 8, 128, (4000, 4000), (), 512),
    # olmo-hybrid-7b-serve.long-context-24's full layer (30 heads on 30,
    # stored as 32): 24 decode rows at 4-8 k tokens beside 256 chunk rows in
    # two runs deep in 8k prompts
    "olmo-mixed": (280, 26, 30, 30, 128, (4096, 8192),
                   ((128, 3000), (128, 6500)), 576),
    "olmo-decode": (24, 24, 30, 30, 128, (4096, 8192), (), 576),
    # 2 and 4 stored rows under prompt chunks: Nemotron's and LFM2's mixed
    # steps (256 decode rows beside two chunks of 128)
    "nemotron-mixed": (512, 258, 32, 2, 128, (600, 2000),
                       ((128, 300), (128, 600)), 256),
    "lfm2-mixed": (512, 258, 32, 8, 64, (600, 2000),
                   ((128, 300), (128, 600)), 256),
    # KV heads 0: the LATENT kernel (`mla_paged_attn`), query heads against
    # one 640-lane row whose first 512 are the value —
    # xing4.0-29b-serve.long-prompt-48's mixed step (48 decode rows beside
    # two chunks of 520) and its decode step
    "xing-mixed": (1088, 50, 32, 0, 640, (2048, 7168),
                   ((520, 1000), (520, 4000)), 512),
    "xing-decode": (48, 48, 32, 0, 640, (2048, 7168), (), 512),
}
LATENT_VALUE = 512
PAGE = 16


def _operands(name, seed, tokens=None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas_paged import kv_row_shape
    R, S, H, h_kv, D, (lo, hi), runs, maxp = SHAPES[name]
    rng = np.random.default_rng(seed)
    P = S * maxp + 1                                # + the trash page 0
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    pools = [jax.random.normal(k, (P, PAGE) + (
        kv_row_shape(h_kv, D) if h_kv else (D,)), jnp.bfloat16)
        for k in keys[:2 if h_kv else 1]]
    q = jax.random.normal(keys[2], (R, H, D), jnp.bfloat16)
    # every slot owns its pages, scattered over the pool; row S is the
    # all-zero row the padding rows read
    table = np.zeros((S + 1, maxp), np.int32)
    table[:S] = (rng.permutation(S * maxp) + 1).reshape(S, maxp)
    lengths = np.zeros(R, np.int32)
    row_slot = np.full(R, S, np.int32)
    n_dec = R - sum(n for n, _ in runs) if hi else 2
    lengths[:n_dec] = tokens if tokens is not None else \
        rng.integers(lo, hi + 1, n_dec) if hi else (40, 300)
    row_slot[:n_dec] = np.arange(n_dec)
    r = n_dec
    for i, (n, pos) in enumerate(runs):
        lengths[r:r + n] = pos + 1 + np.arange(n)
        row_slot[r:r + n] = S - len(runs) + i
        r += n
    return (q, *pools, jnp.asarray(table), jnp.asarray(lengths),
            jnp.asarray(row_slot)), lengths, row_slot


def _device_ms(fn, args, calls):
    """(mean device time of the Pallas call, of the call's other device ops
    — q's and the output's layouts, the tiles' runs), ms, over `calls`
    calls."""
    import jax
    jax.block_until_ready(fn(*args))
    d = tempfile.mkdtemp()
    with jax.profiler.trace(d):
        for _ in range(calls):
            y = fn(*args)
        jax.block_until_ready(y)
    path = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    ops = [(ev.name, ev.duration_ns) for plane in data.planes
           if plane.name.startswith("/device:TPU:0")
           for line in plane.lines if line.name == "XLA Ops"
           for ev in line.events]
    # the call itself (`%paged_attn.1 = ...`, `%mla_paged_attn.1`), not the
    # ops that read its result
    ns = [t for name, t in ops
          if name.lstrip("%").startswith(("paged_attn", "mla_paged_attn"))]
    assert len(ns) == calls, (len(ns), calls, sorted({n for n, _ in ops}))
    return sum(ns) / calls / 1e6, \
        (sum(t for _, t in ops) - sum(ns)) / calls / 1e6


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
    ap.add_argument("--tile-rows", default="",
                    help="ceilings of a tile's rows to try (default: the "
                         "kernel's; 1 = every row walks alone)")
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

    was = pallas_paged._KV_VMEM_BUDGET, pallas_paged._TILE_ROWS
    budgets = [int(b) << 10 for b in a.budgets.split(",") if b] or was[:1]
    ceilings = [int(n) for n in a.tile_rows.split(",") if n] or was[1:]
    dot_rows = getattr(pallas_paged, "_DOT_ROWS", None)
    if a.tile_rows:     # the ceiling asked for, whatever fills a dot's rows
        pallas_paged._DOT_ROWS = 0
    for name in a.shapes.split(","):
        R, _, H, h_kv, D, _, _, maxp = SHAPES[name]
        row = pallas_paged.kv_row_shape(h_kv, D) if h_kv else (1, D)
        for budget, ceiling in itertools.product(budgets, ceilings):
            pallas_paged._KV_VMEM_BUDGET = budget
            pallas_paged._TILE_ROWS = ceiling
            bt = pallas_paged.block_tokens(PAGE, row[0], row[1], 2, maxp)
            # a function of its own a setting: jit's cache is keyed by it
            if h_kv:
                bq = pallas_paged.tile_rows(R, *pallas_paged.query_tile(
                    H, h_kv, row, bt, "bfloat16"))
                fn = jax.jit(lambda q, kp, vp, table, lengths, row_slot:
                             pallas_paged.paged_attention(
                                 q, kp, vp, table, lengths,
                                 row_slot=row_slot, kv_heads=h_kv))
            else:       # the latent kernel: this much runs on PR 59's too
                bq = pallas_paged.tile_rows(R, H, bt, D, "bfloat16")
                fn = jax.jit(lambda q, pool, table, lengths, row_slot:
                             pallas_paged.latent_paged_attention(
                                 q, pool, table, lengths, D ** -0.5,
                                 row_slot=row_slot, v_width=LATENT_VALUE))
            points = []
            for tokens in (1, 256, 512, 1024, 2048) if a.fills else (None,):
                args, lengths, row_slot = _operands(name, a.seed, tokens)
                blocks, shared = pallas_paged.walked_blocks(
                    lengths, row_slot, bq, bt)
                pages = blocks * (bt // PAGE)
                ms, other_ms = _device_ms(fn, args, a.calls)
                points.append((blocks, pages, ms))
                say(shape=name, budget_kib=budget >> 10, block_tokens=bt,
                    tile_rows=bq, shared_rows=shared,
                    rows=len(lengths), live_tokens=int(lengths.sum()),
                    blocks=blocks, pages=pages, ms=round(ms, 4),
                    other_ops_ms=round(other_ms, 4),
                    hbm_ms=round(int(lengths.sum()) * (2 if h_kv else 1)
                                 * row[0] * row[1] * 2 / 819e9 * 1e3, 4))
            if a.fills:
                # ms = a call's fixed cost + rows' + blocks' + pages'; the
                # rows are constant here, so the fit is over blocks alone
                # (pages = blocks x pages a block at one budget)
                b, _, t = map(np.asarray, zip(*points))
                slope, fixed = np.polyfit(b, t, 1)
                say(shape=name, budget_kib=budget >> 10, block_tokens=bt,
                    tile_rows=bq, us_a_block=round(slope * 1e3, 4),
                    ms_fixed_a_call=round(float(fixed), 4))
    pallas_paged._KV_VMEM_BUDGET, pallas_paged._TILE_ROWS = was
    pallas_paged._DOT_ROWS = dot_rows


if __name__ == "__main__":
    main()
