"""Tune the pallas flash-attention block sizes on device (VERDICT r4
item 7: "tune flash block sizes").

Sweeps (block_q, block_k) over the flash kernel at transformer-LM-ish
shapes with the shared dispatch-proof harness (tools/_scan_bench) and
prints one JSON row per point plus a `best` row per sequence length.
Apply a winner globally via the env defaults the attention layer reads
(PADDLE_TPU_FLASH_BLOCK_Q / PADDLE_TPU_FLASH_BLOCK_K,
graph/layers_attn.py) or per layer via the block_q/block_k attrs.

Usage: python tools/tune_flash.py [--lens 1024,4096] [--blocks 128,256,512]
       [--batch 8] [--heads 8] [--dim 64] [--dtype bfloat16]
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lens", default="1024,4096")
    ap.add_argument("--blocks", default="128,256,512")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--target-ms", type=float, default=250.0)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()

    from _scan_bench import attn_step_flops, fold, scan_length, timed_chain
    from paddle_tpu.ops import pallas_attention

    if not pallas_attention.supported():
        print(json.dumps({"error": "pallas flash unsupported on this "
                          "backend (set PADDLE_TPU_PALLAS_INTERPRET=1 to "
                          "rehearse)"}))
        return 1

    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    blocks = [int(b) for b in args.blocks.split(",")]
    rng = np.random.default_rng(0)
    ok = True
    for T in [int(x) for x in args.lens.split(",")]:
        shape = (args.batch, T, args.heads, args.dim)
        q = jnp.asarray(rng.normal(size=shape), dt)
        k = jnp.asarray(rng.normal(size=shape), dt)
        v = jnp.asarray(rng.normal(size=shape), dt)
        est = attn_step_flops(args.batch, T, args.heads, args.dim)
        n_steps = scan_length(est, target_ms=args.target_ms)
        best = None
        for bq, bk in itertools.product(blocks, blocks):
            if bq > T or bk > T:
                continue

            def step(carry, bq=bq, bk=bk):
                q, k, v = carry

                def loss(q, k, v):
                    return jnp.sum(pallas_attention.flash_attention(
                        q, k, v, causal=True, block_q=bq, block_k=bk)
                        .astype(jnp.float32))
                l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
                return fold(carry, g), l

            try:
                sec = timed_chain(step, (q, k, v), n_steps, args.reps)
                row = {"seq_len": T, "block_q": bq, "block_k": bk,
                       "n_steps": n_steps,
                       "ms_per_step": round(sec * 1e3, 3)}
                print(json.dumps(row), flush=True)
                if best is None or sec < best[0]:
                    best = (sec, bq, bk)
            except Exception as e:
                ok = False
                print(json.dumps({"seq_len": T, "block_q": bq,
                                  "block_k": bk,
                                  "error": f"{type(e).__name__}: "
                                           f"{str(e)[:200]}"}), flush=True)
        if best is not None:
            print(json.dumps({"best": True, "seq_len": T,
                              "block_q": best[1], "block_k": best[2],
                              "ms_per_step": round(best[0] * 1e3, 3)}),
                  flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
