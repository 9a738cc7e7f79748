"""Roofline share of the WINDOWED paged-attention kernel (`window_attn`,
ops/pallas_paged.py: the sliding-window layers' decode and chunk rows): the
least time the chip could take to read what a call's rows can see —
min(context, window) tokens a row, K and V, the queries in and the result
out (benchmark/lib/window_moe.py:window_cost) — over the kernel's summed
device time in the traced slice, one call a window layer a step.  The rows
of a call are the engine's own count over the slice, decode rows and chunk
rows, padding left out (`serving_window_rows_total` over
`serving_window_steps_total`: window_moe.rows_per_window_call); the context
is the mean of the requests the client saw in flight, so a chunk row inside
its prompt's first window is counted at more than it sees (about a
twentieth of this mix's rows at half too much).  The pattern is the
kernel's OWN name, which `paged_attn_named_roofline.serve`'s pattern for
the full layers' calls does not match.  A trace without the kernel, or a
program without the counters, has nothing to read."""
from benchmark.lib import arith, window_moe
from benchmark.lib.common import log
from benchmark.lib.trace import TraceError

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
PATTERN = r"window_attn.*\[tpu_custom_call\]"


def read(ctx):
    if ctx.trace_data is None:
        return None
    span = ctx.counters.get("trace_span") or {}
    live = [(c, n) for t, c, n in ctx.counters.get("live_samples", [])
            if span.get("t0", 0) <= t <= span.get("t1", 0) and n]
    if not live:
        return None
    try:
        k = ctx.trace_data.kernel(PATTERN)
    except TraceError as e:
        log(f"KERNEL window_attn: {str(e)[:200]}")
        return None
    rows = window_moe.rows_per_window_call(ctx)
    if not rows:
        return None
    context = sum(c for c, _ in live) / sum(n for _, n in live)
    cost = window_moe.window_cost(ctx.cfg, rows, context)
    r = arith.roofline_share(cost["flops"] * k["calls"],
                             cost["bytes"] * k["calls"], k["seconds"],
                             ctx.peaks)
    log(f"KERNEL window_attn: {k['calls']:.0f} calls, {k['seconds']:.4f}s, "
        f"{rows:.1f} rows of mean context {context:.0f} a call, "
        f"{r['bound']}-bound")
    return arith.check_share("window_paged_attn_roofline.serve",
                             r["share_pct"])
