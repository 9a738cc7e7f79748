"""Time the expert block's two formulations (parallel/moe.py) on the chip at
a cell's own shapes: where `_GROUPED_OVER_RIDGE` and `_GROUP_SLOTS` come from.

    chiprun -- python3 tools/moe_forms.py                 # the three shapes
    chiprun -- python3 tools/moe_forms.py --sweep         # + slots a round
    chiprun -- python3 tools/moe_forms.py --ops lfm2-mixed  # + device ops

One line of JSON a reading (also appended to chiprun_out/moe_forms.jsonl):
milliseconds a layer call, median of `--reps` timed batches of 10 calls
each ending in `block_until_ready`.  Fails off a TPU: a CPU time is no
device number.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: rows, experts held, experts scored, top_k, hidden, expert hidden (bf16,
#: gated experts): the MoE serve cells' decode and mixed steps
SHAPES = {
    "lfm2-decode": (256, 64, 64, 4, 2048, 1536),
    "lfm2-mixed": (512, 64, 64, 4, 2048, 1536),
    "kimi-decode": (128, 16, 256, 8, 2304, 1024),
    "kimi-mixed": (320, 16, 256, 8, 2304, 1024),
    "gigachat-mixed": (128, 8, 256, 8, 7168, 2048),
    "lfm2-128": (128, 64, 64, 4, 2048, 1536),
    "lfm2-768": (768, 64, 64, 4, 2048, 1536),
}


def _time(fn, args, reps):
    import jax
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(10):
            y = fn(*args)
        jax.block_until_ready(y)
        out.append((time.perf_counter() - t0) / 10 * 1e3)
    return statistics.median(out)


def _device_ops(fn, args, calls=5):
    """Device time by op name over `calls` calls, ms a call (profiler)."""
    import glob
    import tempfile
    import jax
    jax.block_until_ready(fn(*args))
    d = tempfile.mkdtemp()
    with jax.profiler.trace(d):
        for _ in range(calls):
            y = fn(*args)
        jax.block_until_ready(y)
    path = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    ops = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                # "%gmm.1 = f32[2048,2048]{...} custom-call(...)": the
                # instruction's name and its result
                name = " ".join(ev.name.split("{")[0].split(" = "))
                ops[name] = ops.get(name, 0.0) + ev.duration_ns
    return {k: round(v / calls / 1e6, 4)
            for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:14]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="lfm2-decode,lfm2-mixed,kimi-mixed")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--sweep", action="store_true",
                    help="the grouped form at 64, 96, 128 and 192 slots an "
                         "expert a round")
    ap.add_argument("--ops", default="",
                    help="shapes whose grouped and dense calls are traced")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"moe_forms measures on a TPU, found {dev.platform}")
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/moe_forms.jsonl", "a")

    def say(**row):
        line = json.dumps(dict(row, device=dev.device_kind))
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for name in a.shapes.split(","):
        B, h, E, k, D, H = SHAPES[name]
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 5)
        x = jax.random.normal(keys[0], (B, D), jnp.bfloat16)
        w_r = jax.random.normal(keys[1], (D, E), jnp.float32) * 0.02
        experts = tuple(
            (jax.random.normal(kk, s, jnp.float32) * 0.02
             ).astype(jnp.bfloat16)
            for kk, s in zip(keys[2:], [(h, D, H), (h, D, H), (h, H, D)]))
        read_ms = sum(w.size * 2 for w in experts) / 819e9 * 1e3

        def layer(form):
            return jax.jit(lambda x, w_r, ex: moe.moe_ffn(
                x, w_r, ex, top_k=k, scoring="sigmoid", form=form)[0])

        args = (x, w_r, experts)
        base = dict(shape=name, rows=B, held=h, pairs=B * k,
                    weights_read_ms=round(read_ms, 3),
                    rule=moe.expert_form(B, k, E, 2))
        say(**base, form="dense", ms=round(_time(layer("dense"), args,
                                                 a.reps), 4))
        was = moe._GROUP_SLOTS
        for slots in (64, 96, 128, 192) if a.sweep else (was,):
            moe._GROUP_SLOTS = slots
            say(**base, form="grouped", slots=slots,
                ms=round(_time(layer("grouped"), args, a.reps), 4))
        moe._GROUP_SLOTS = was
        if name in a.ops.split(","):
            for form in ("grouped", "dense"):
                say(**base, form=form, ops=_device_ops(layer(form), args))


if __name__ == "__main__":
    main()
