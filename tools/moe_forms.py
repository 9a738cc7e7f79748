"""Time the expert block's two formulations (parallel/moe.py) on the chip at
a cell's own shapes: where `_GROUPED_OVER_RIDGE`, `_GROUP_SLOTS` and
`_FIRST_ROUND_OVER_MEAN` come from.

    chiprun -- python3 tools/moe_forms.py                 # the three shapes
    chiprun -- python3 tools/moe_forms.py --sweep [128,192]  # first-round slots
    chiprun -- python3 tools/moe_forms.py --ops lfm2-mixed  # + device ops
    chiprun -- python3 tools/moe_forms.py --shapes xing-mixed --skew 1.0,2.6

One line of JSON a reading (also appended to chiprun_out/moe_forms.jsonl):
milliseconds a layer call, median of `--reps` timed batches of 10 calls
each ending in `block_until_ready`, beside the busiest expert's pairs, the
first round's slots and the overflow tiles the grouped call ran (counted
from the pairs the device routed).  `--skew M` biases the router's selection
until the busiest expert holds M mean loads (the Xing cell's mixed steps:
about four in their busiest layer).  Fails off a TPU: a CPU time is no
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
#: gated experts; a seventh entry 2: plain experts without biases, relu2):
#: the MoE serve cells' decode and mixed steps
SHAPES = {
    "lfm2-decode": (256, 64, 64, 4, 2048, 1536),
    "lfm2-mixed": (512, 64, 64, 4, 2048, 1536),
    "kimi-decode": (128, 16, 256, 8, 2304, 1024),
    "kimi-mixed": (320, 16, 256, 8, 2304, 1024),
    "gigachat-mixed": (128, 8, 256, 8, 7168, 2048),
    "lfm2-128": (128, 64, 64, 4, 2048, 1536),
    "lfm2-768": (768, 64, 64, 4, 2048, 1536),
    "xing-mixed": (1088, 64, 64, 4, 3584, 1024),
    "laguna-mixed": (320, 256, 256, 8, 2048, 512),
    "solar-mixed": (320, 40, 320, 8, 4096, 1280),
    "nemotron-mixed": (512, 32, 128, 6, 2688, 1856, 2),
}


def _skew_bias(x, w_r, k, times):
    """A selection bias [E] under which the busiest expert draws `times`
    mean loads: one fixed draw an expert, scaled by bisection on the host's
    own routing of `x` (sigmoid scores, top-k of score + bias)."""
    import numpy as np
    x, w_r = np.asarray(x, np.float32), np.asarray(w_r, np.float32)
    scores = 1.0 / (1.0 + np.exp(-(x @ w_r)))
    draw = np.random.default_rng(0).normal(size=scores.shape[1])

    def busiest(scale):
        pick = np.argsort(-(scores + scale * draw), axis=1)[:, :k]
        return np.bincount(pick.reshape(-1), minlength=len(draw)).max()

    want = times * x.shape[0] * k / len(draw)
    lo, hi = 0.0, 1.0
    if busiest(lo) >= want:
        return np.zeros_like(draw, np.float32)
    for _ in range(40):
        mid = (lo + hi) / 2
        lo, hi = (lo, mid) if busiest(mid) >= want else (mid, hi)
    return (hi * draw).astype(np.float32)


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
    ap.add_argument("--sweep", nargs="?", const="64,96,128,192", default="",
                    help="the grouped form at these slots an expert in the "
                         "first round (64,96,128,192 without a value)")
    ap.add_argument("--skew", default="",
                    help="mean loads the busiest expert holds, a reading "
                         "each (1.0,2.6); without it the router's own draw")
    ap.add_argument("--ops", default="",
                    help="shapes whose grouped and dense calls are traced")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
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

    rule = moe.first_round_slots

    for name in a.shapes.split(","):
        B, h, E, k, D, H, *mats = SHAPES[name]
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 5)
        x = jax.random.normal(keys[0], (B, D), jnp.bfloat16)
        w_r = jax.random.normal(keys[1], (D, E), jnp.float32) * 0.02
        shapes = [(h, D, H), (h, D, H), (h, H, D)] if mats != [2] \
            else [(h, D, H), (h, H, D)]
        experts = tuple(
            (jax.random.normal(kk, s, jnp.float32) * 0.02
             ).astype(jnp.bfloat16)
            for kk, s in zip(keys[2:], shapes))
        read_ms = sum(w.size * 2 for w in experts) / 819e9 * 1e3

        def layer(form):
            return jax.jit(lambda x, w_r, ex, bias: moe.moe_ffn(
                x, w_r, ex, top_k=k, scoring="sigmoid", form=form,
                select_bias=bias,
                activation=moe.expert_activation("relu2")))

        for times in [float(t) for t in a.skew.split(",") if t] or [None]:
            bias = jnp.zeros((E,), jnp.float32) if times is None \
                else jnp.asarray(_skew_bias(x, w_r, k, times))
            args = (x, w_r, experts, bias)
            sizes = np.asarray(layer("dense")(*args)[2]).sum(axis=0)
            base = dict(shape=name, rows=B, held=h, pairs=int(sizes.sum()),
                        busiest=int(sizes.max()),
                        over_mean=round(float(sizes.max()) * E / (B * k), 3),
                        weights_read_ms=round(read_ms, 3),
                        rule=moe.expert_form(B, k, E, 2))

            def ms(form):
                fn = layer(form)
                return round(_time(lambda *xs: fn(*xs)[0], args, a.reps), 4)

            say(**base, form="dense", ms=ms("dense"))
            for slots in [int(n) for n in a.sweep.split(",") if n] or [None]:
                moe.first_round_slots = rule if slots is None \
                    else lambda *_, slots=slots: slots
                first = moe.first_round_slots(B, k, E)
                say(**base, form="grouped", slots=first, tiles=int(
                    np.asarray(moe.overflow_tiles(sizes, first)).sum()),
                    ms=ms("grouped"))
            moe.first_round_slots = rule
            if name in a.ops.split(","):
                for form in ("grouped", "dense"):
                    fn = layer(form)
                    say(**base, form=form, ops=_device_ops(
                        lambda *xs: fn(*xs)[0], args))


if __name__ == "__main__":
    main()
