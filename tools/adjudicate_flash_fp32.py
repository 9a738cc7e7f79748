"""Adjudicate the round-4 fp32 flash parity failure with an f64 oracle.

The round-4 parity run on a v5e: `flash B2_T512_H4_D64_float32` had
46/262144 elements outside rtol/atol=2e-3 against an fp32 dense reference
(max abs diff 5e-3, max REL diff 0.49 — i.e. tiny-magnitude outputs).
Question (VERDICT r4 item 2): kernel bug (masking/accumulation) or
tolerance artifact of the MXU's fp32 emulation?

Method: compute the same case three ways on CPU (true-fp32 matmuls,
no MXU) — f64 dense oracle, f32 dense, interpret-mode pallas kernel —
and compare each f32 path's error against the f64 truth.  If the kernel's
error distribution matches dense-f32's, the kernel math is sound and the
on-device miss was MXU precision (adjudication: tolerance); a kernel bug
would show as outliers far beyond dense-f32's rounding envelope.

Run: PYTHONPATH= JAX_PLATFORMS=cpu python tools/adjudicate_flash_fp32.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops import pallas_attention  # noqa: E402
from paddle_tpu.ops.attention import dot_product_attention  # noqa: E402


def main() -> int:
    B, T, H, D, causal = 2, 512, 4, 64, True
    rng = np.random.default_rng(102)  # the failing case's seed
    q64 = rng.normal(size=(B, T, H, D))
    k64 = rng.normal(size=(B, T, H, D))
    v64 = rng.normal(size=(B, T, H, D))

    with jax.default_matmul_precision("highest"):
        want64 = np.asarray(dot_product_attention(
            jnp.asarray(q64), jnp.asarray(k64), jnp.asarray(v64),
            causal=causal))

    q = jnp.asarray(q64, jnp.float32)
    k = jnp.asarray(k64, jnp.float32)
    v = jnp.asarray(v64, jnp.float32)
    with jax.default_matmul_precision("highest"):
        dense32 = np.asarray(dot_product_attention(q, k, v, causal=causal),
                             np.float64)
    flash32 = np.asarray(pallas_attention.flash_attention(q, k, v,
                                                          causal=causal),
                         np.float64)

    def stats(name, got):
        err = np.abs(got - want64)
        rel = err / np.maximum(np.abs(want64), 1e-30)
        bad = np.sum((err > 2e-3) & (rel > 2e-3))
        out = {"path": name, "max_abs_err": float(err.max()),
               "max_rel_err": float(rel.max()),
               "p99.9_abs_err": float(np.quantile(err, 0.999)),
               "n_beyond_2e-3": int(bad)}
        print(json.dumps(out), flush=True)
        return err.max()

    e_dense = stats("dense_f32_vs_f64", dense32)
    e_flash = stats("flash_interpret_f32_vs_f64", flash32)
    # also: flash-vs-dense in f32 (what the on-device parity actually bars)
    d = np.abs(flash32 - dense32)
    print(json.dumps({"path": "flash_vs_dense_f32",
                      "max_abs_diff": float(d.max())}), flush=True)

    # kernel math is sound iff its f64-truth error is within a small factor
    # of dense-f32's own rounding (both are f32 pipelines of ~T=512 sums)
    verdict = "tolerance" if e_flash < 10 * max(e_dense, 1e-7) else "bug"
    print(json.dumps({"verdict": verdict,
                      "dense_f32_err": float(e_dense),
                      "flash_f32_err": float(e_flash)}), flush=True)
    return 0 if verdict == "tolerance" else 1


if __name__ == "__main__":
    sys.exit(main())
