"""Dispatch-proof micro-bench timing shared by the tools/ benches.

The r4 sweeps timed a Python loop of jitted calls with one
`block_until_ready` at the end; on the backend of the day that reported
times far beyond the chip's peak FLOP rate (tools/bench_attention.py
docstring has the numbers) — the loop measured dispatch, not compute.
The fix, shared here: run N iterations inside ONE jitted `lax.scan`
whose carry feeds iteration i+1 from iteration i's outputs (gradients
folded back with an eps-scaled add), so a single dispatch covers all N
and XLA cannot elide, dedup, or memoize the repeats; completion is
forced by a host read (float()) of a scalar reduced from the final
carry — a device->host fetch is a completion barrier on any backend.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp


def fold(carry, grads, eps: float = 1e-30):
    """carry' = carry + eps*grads, leafwise — the dependency chain that
    keeps every scan iteration live without changing the measured math
    (eps is representable in bf16; the add is elementwise noise)."""
    return jax.tree.map(
        lambda c, g: c + jnp.asarray(eps, c.dtype) * g.astype(c.dtype),
        carry, grads)


def timed_chain(step, carry0, n_steps: int, reps: int = 3) -> float:
    """step: carry -> (carry', scalar).  Returns min seconds per step over
    `reps` single-dispatch runs of an n_steps-long scan (compile excluded:
    the warmup dispatch uses the same static n_steps program)."""
    @functools.partial(jax.jit, static_argnums=1)
    def many(carry, n):
        cf, ss = jax.lax.scan(lambda c, _: step(c), carry, None, length=n)
        leaves = [jnp.sum(x.astype(jnp.float32)) for x in jax.tree.leaves(cf)]
        return jnp.sum(ss) + sum(leaves)

    float(many(carry0, n_steps))           # compile + warmup, same program
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(many(carry0, n_steps))
        times.append(time.perf_counter() - t0)
    return min(times) / n_steps


def attn_step_flops(B: int, T: int, H: int, D: int) -> float:
    """fwd (QK^T + PV = 4*B*H*T^2*D) + bwd (~2.5x fwd) — shared by the
    attention bench and the flash tuner so their scan regions are sized
    identically; coarse on purpose (it only sizes the region)."""
    return 3.5 * 4 * B * H * T * T * D


def scan_length(est_step_flops: float, target_ms: float = 250.0,
                assumed_flops: float = 80e12,
                lo: int = 4, hi: int = 1024) -> int:
    """Size the scan so one timed region is >= ~target_ms of device work
    (assumed_flops only sizes the region; it is not reported)."""
    n = int(target_ms / 1e3 * assumed_flops / max(est_step_flops, 1.0))
    return max(lo, min(hi, n))
