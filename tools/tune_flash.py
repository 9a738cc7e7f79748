"""Sweep the pallas flash-attention block sizes on device, per kernel.

For each (block_q, block_k) pair — passed to `flash_attention` as
arguments — one causal forward + backward is compiled, warmed up and then
profiled for `--iters` iterations; the device time of `flash_fwd`,
`flash_bwd_dq` and `flash_bwd_dkv` is summed from the profiler trace by
kernel name, so each kernel's best pair shows by itself.  Prints one JSON
row a pair, then per sequence length each kernel's best pair beside the
pick of `ops/pallas_attention.py:derive_blocks` (the rule the code uses when
no block is given) and the rule's own row.  The rule is what ships: a
per-layer `block_q` / `block_k` attr overrides it for one layer.

`--operands float32` times the kernels with fp32 matmul operands at default
precision (what they did before bf16 inputs fed the MXU as they came in):
the operand type's effect alone.

Usage: python tools/tune_flash.py [--lens 4096] [--blocks 128,256,512,1024]
       [--batch 2] [--heads 24] [--kv-heads 2] [--dim 128] [--dtype bfloat16]
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

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.pallas_attention import KERNELS  # noqa: E402


def kernel_ms(trace_dir: str, iters: int) -> dict:
    """ms a call of each flash kernel on the first device: summed duration
    of the trace's device ops that carry the kernel's name."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    total = dict.fromkeys(KERNELS, 0.0)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                head = ev.name.split(" = ", 1)[0]
                # flash_bwd_dq / flash_bwd_dkv do not contain one another
                for kern in KERNELS:
                    if kern in head:
                        total[kern] += ev.duration_ns / 1e6
    return {k: v / iters for k, v in total.items()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lens", default="4096")
    ap.add_argument("--blocks", default="128,256,512,1024",
                    help="block sizes swept on both axes; or explicit "
                         "pairs as 512x1024,256x512")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--heads", type=int, default=24)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--operands", default="input",
                    choices=("input", "float32"))
    args = ap.parse_args()

    from paddle_tpu.ops import pallas_attention

    if not pallas_attention.supported():
        print(json.dumps({"error": "pallas flash unsupported on this "
                          "backend (set PADDLE_TPU_PALLAS_INTERPRET=1 to "
                          "rehearse)"}))
        return 1
    if args.operands == "float32":
        # fp32 operands, default (not HIGHEST) precision
        pallas_attention._in_kernel_precision = \
            lambda *a: jax.lax.Precision.DEFAULT

    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if "x" in args.blocks:
        pairs = [tuple(int(b) for b in p.split("x"))
                 for p in args.blocks.split(",")]
    else:
        blocks = [int(b) for b in args.blocks.split(",")]
        pairs = list(itertools.product(blocks, blocks))
    rng = np.random.default_rng(0)
    ok = True
    for T in [int(x) for x in args.lens.split(",")]:
        q = jnp.asarray(rng.normal(size=(args.batch, T, args.heads,
                                         args.dim)), dt)
        k, v = (jnp.asarray(rng.normal(size=(args.batch, T, args.kv_heads,
                                             args.dim)), dt)
                for _ in range(2))

        def measure(**blocks):
            def loss(q, k, v):
                return jnp.sum(pallas_attention.flash_attention(
                    q, k, v, causal=True, **blocks).astype(jnp.float32))
            step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
            jax.block_until_ready(step(q, k, v))         # compile + warm up
            with tempfile.TemporaryDirectory() as d:
                jax.profiler.start_trace(d)
                for _ in range(args.iters):
                    out = step(q, k, v)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                ms = kernel_ms(d, args.iters)
            return {k: round(v, 4) for k, v in ms.items()}

        best: dict = {}
        for bq, bk in pairs:
            if bq > T or bk > T:
                continue
            row = {"seq_len": T, "block_q": bq, "block_k": bk}
            try:
                ms = measure(block_q=bq, block_k=bk)
                print(json.dumps({**row, **ms}), flush=True)
                for kern, t in ms.items():
                    if kern not in best or t < best[kern][0]:
                        best[kern] = (t, bq, bk)
            except Exception as e:      # noqa: BLE001 — a pair Mosaic refuses
                ok = False
                print(json.dumps({**row, "error": f"{type(e).__name__}: "
                                  f"{str(e)[:200]}"}), flush=True)
        rule = pallas_attention.derive_blocks(T, T, args.dim, dt)
        print(json.dumps({"rule": True, "seq_len": T, **measure()}),
              flush=True)
        for kern in KERNELS:
            if kern in best:
                print(json.dumps({
                    "best": True, "seq_len": T, "kernel": kern,
                    "block_q": best[kern][1], "block_k": best[kern][2],
                    "ms": best[kern][0],
                    "rule_block_q": rule[kern][0],
                    "rule_block_k": rule[kern][1]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
