"""Device-memory accounting: HBM gauges with a CPU-safe fallback.

The KV page pools and the parameter arrays (with the copies a serving
engine derives from them in the compute dtype, `hbm_step_weight_bytes`)
are the deliberate HBM tenants of a serving replica; everything else
(prefill activations, a leaked buffer from a bug) shows up as the gap
between them and the device's own accounting.  The production failure mode this makes visible
is HBM exhaustion of the page pools (the headroom signal the TPU serving
literature treats as first-class, arXiv:2605.25645): when
`hbm_bytes_in_use` approaches `hbm_bytes_limit` while `hbm_kv_pool_bytes`
is flat, the leak is NOT the pool — and vice versa.

Three sources, each degrading independently (CPU test runs must keep the
metrics frame renderable with zero of them available):

  * `device_memory_stats()` — the backend's own accounting
    (`Device.memory_stats()`: TPU/GPU report bytes_in_use/limit; the CPU
    backend returns None or raises, and the gauges are simply absent);
  * `live_array_bytes()` — `jax.live_arrays()` walked for nbytes: every
    on-device buffer the process still references, whatever allocated it;
  * `tree_bytes()` / `kv_pool_bytes()` — duck-typed nbytes sums over the
    params pytree and the paged-KV pools (always available, no jax
    import needed at module load).

`hbm_collector()` adapts them into the obs.metrics registries (the
server's `metrics` frame and the trainer's `metrics.jsonl`) at render
time — scrape cadence, never the token hot path.
"""

from __future__ import annotations

from typing import Callable, Optional


def device_memory_stats() -> Optional[dict]:
    """The first addressable device's memory_stats(), or None when the
    backend does not report (CPU) or jax is absent entirely."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
        return dict(stats) if stats else None
    except Exception:                      # noqa: BLE001 — no backend = no gauge
        return None


def live_array_bytes() -> Optional[tuple[int, int]]:
    """(total_nbytes, count) over jax.live_arrays(), or None when the
    probe is unavailable (old jax / no jax)."""
    try:
        import jax

        arrays = jax.live_arrays()
    except Exception:                      # noqa: BLE001
        return None
    total = count = 0
    for a in arrays:
        try:
            total += int(a.nbytes)
            count += 1
        except Exception:                  # noqa: BLE001 — deleted buffer race
            continue
    return total, count


def tree_bytes(tree) -> int:
    """nbytes summed over array-ish leaves of a nested dict/list/tuple —
    duck-typed so it works on np arrays, jax arrays, and mixed pytrees."""
    total = 0
    stack = [tree]
    while stack:
        x = stack.pop()
        if isinstance(x, dict):
            stack.extend(x.values())
        elif isinstance(x, (list, tuple)):
            stack.extend(x)
        elif hasattr(x, "nbytes"):
            try:
                total += int(x.nbytes)
            except Exception:              # noqa: BLE001
                continue
    return total


def kv_pool_bytes(kv) -> int:
    """Bytes held by a PagedKVCache's per-layer page pools."""
    return tree_bytes(kv.pools)


def hbm_collector(params_fn: Optional[Callable] = None,
                  kv_fn: Optional[Callable] = None,
                  step_weight_bytes_fn: Optional[Callable] = None):
    """obs.metrics collector for the hbm_* gauges.

    `params_fn()` -> the live params pytree (a callable, not a snapshot —
    donated buffers rebind every step); `kv_fn()` -> the PagedKVCache;
    `step_weight_bytes_fn()` -> the bytes of the copies a serving engine
    derived from its params for the compiled steps (0 where the steps
    take the params themselves).  Each may be None (the trainer has no KV
    pool and no derived weights; a bare tool has no params).  Backend
    gauges are EMITTED ONLY WHEN THE PROBE ANSWERS — an absent
    `hbm_bytes_in_use` means "backend does not report", a zero would
    lie."""

    def collect():
        out = []
        stats = device_memory_stats()
        if stats is not None:
            if "bytes_in_use" in stats:
                out.append(("hbm_bytes_in_use", "gauge", None,
                            float(stats["bytes_in_use"])))
            if "bytes_limit" in stats:
                out.append(("hbm_bytes_limit", "gauge", None,
                            float(stats["bytes_limit"])))
        live = live_array_bytes()
        if live is not None:
            out.append(("hbm_live_array_bytes", "gauge", None,
                        float(live[0])))
            out.append(("hbm_live_arrays", "gauge", None, float(live[1])))
        if params_fn is not None:
            out.append(("hbm_param_bytes", "gauge", None,
                        float(tree_bytes(params_fn()))))
        if step_weight_bytes_fn is not None:
            out.append(("hbm_step_weight_bytes", "gauge", None,
                        float(step_weight_bytes_fn())))
        if kv_fn is not None:
            out.append(("hbm_kv_pool_bytes", "gauge", None,
                        float(kv_pool_bytes(kv_fn()))))
        return out

    return collect


def hbm_snapshot(params=None, kv=None,
                 step_weight_bytes: Optional[int] = None) -> dict:
    """One-shot dict of everything measurable — the postmortem-bundle
    shape (and a convenient REPL probe)."""
    out: dict = {}
    stats = device_memory_stats()
    if stats is not None:
        out["device_memory_stats"] = stats
    live = live_array_bytes()
    if live is not None:
        out["live_array_bytes"], out["live_arrays"] = live
    if params is not None:
        out["param_bytes"] = tree_bytes(params)
    if step_weight_bytes is not None:
        out["step_weight_bytes"] = int(step_weight_bytes)
    if kv is not None:
        out["kv_pool_bytes"] = kv_pool_bytes(kv)
    return out
