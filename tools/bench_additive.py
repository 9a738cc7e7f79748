"""Micro-benchmark: fused additive-attention step — pallas kernel vs the
single-expression jnp formulation, inside a scan like the real decoder.

Decides whether graph/layers_attn.py should route simple_attention's
additive_attention_step layer to ops/pallas_additive.py (current default
on TPU) or let XLA fuse the jnp expression.  Mirrors the seq2seq training
shape: the step runs T_dec times inside lax.scan with a dummy carry, fwd
+ bwd, bf16 by default.

Usage: python tools/bench_additive.py [--batch 64] [--enc-len 30]
       [--dec-len 30] [--dim 512] [--reps 3] [--dtype bfloat16]
Prints one JSON line per implementation.
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


def bench_impl(name, step_fn, args, dec_len, reps):
    """Times the full fwd+bwd decoder-scan step with the dispatch-proof
    chained-scan harness (tools/_scan_bench) — the r4 numbers from the
    old block_until_ready loop were physically impossible (0.028 ms for
    ~10 GFLOP of work) and are superseded."""
    from _scan_bench import fold, scan_length, timed_chain

    dec0, w, v, proj, seq, mask = args
    B, T, D = proj.shape

    def train_step(carry):
        w, v, proj, seq = carry

        # grads w.r.t. proj/seq too: in real training the encoder states
        # are computed from trained params, and their per-step [B, T, D]
        # cotangent accumulation is the bandwidth-heavy half of backward —
        # eliding it would bias the kernel-routing decision
        def loss(w, v, proj, seq):
            def body(c, _):
                ctxv = step_fn(c, w, v, proj, seq, mask)
                # small mixing matmul stands in for the GRU: the carry must
                # depend on the context so the scan is sequential like the
                # real decoder
                new = jnp.tanh(ctxv @ w[: ctxv.shape[-1], : c.shape[-1]]
                               + c)
                return new, jnp.sum(ctxv.astype(jnp.float32))
            _, outs = jax.lax.scan(body, dec0, None, length=dec_len)
            return jnp.sum(outs)
        l, g = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(w, v, proj, seq)
        return fold(carry, g), l

    # fwd ~ dec_len * (two [B,D]x[D,D] matmuls + score/context reads);
    # bwd ~2.5x — coarse, only sizes the scan
    est = 3.5 * dec_len * (4 * B * D * D + 6 * B * T * D)
    n_steps = scan_length(est)
    dt = timed_chain(train_step, (w, v, proj, seq), n_steps, reps)
    return {"impl": name, "n_steps": n_steps,
            "ms_per_step": round(dt * 1e3, 3),
            "samples_per_sec": round(dec0.shape[0] / dt, 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--enc-len", type=int, default=30)
    ap.add_argument("--dec-len", type=int, default=30)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()

    from paddle_tpu.ops import pallas_additive
    from paddle_tpu.ops.attention import additive_attention_step as jnp_step

    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    rng = np.random.default_rng(0)
    B, T, D = args.batch, args.enc_len, args.dim
    dec0 = jnp.asarray(rng.normal(size=(B, D)), dt)
    w = jnp.asarray(rng.normal(size=(D, D)) * 0.05, dt)
    v = jnp.asarray(rng.normal(size=(D,)), dt)
    proj = jnp.asarray(rng.normal(size=(B, T, D)), dt)
    seq = jnp.asarray(rng.normal(size=(B, T, D)), dt)
    lens = rng.integers(T // 2, T + 1, B).astype(np.int32)
    mask = jnp.arange(T)[None, :] < jnp.asarray(lens)[:, None]

    impls = {"jnp_fused": jnp_step}
    if pallas_additive.supported():
        impls["pallas"] = pallas_additive.additive_attention_step

    for name, fn in impls.items():
        try:
            res = bench_impl(name, fn, (dec0, w, v, proj, seq, mask),
                             args.dec_len, args.reps)
            print(json.dumps(res))
        except Exception as e:
            print(json.dumps({"impl": name,
                              "error": f"{type(e).__name__}: {e}"}))


if __name__ == "__main__":
    main()
