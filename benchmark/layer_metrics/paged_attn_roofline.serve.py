"""Roofline share of the paged-attention kernel (decode and mixed rows): the
least time the chip could take to read the LIVE K/V of the requests in
flight (what the client saw in flight during the traced slice; prompts still
in prefill are left out, so the share errs low) over the kernel's summed
device time in that slice.  Every Pallas custom call of the decode and
mixed steps is the paged kernel (one call a layer a step)."""
from benchmark.lib import arith
from benchmark.lib.common import log

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
PATTERN = r"\[tpu_custom_call\]"


def read(ctx):
    if ctx.trace_data is None:
        return None
    span = ctx.counters.get("trace_span") or {}
    live = [(c, n) for t, c, n in ctx.counters.get("live_samples", [])
            if span.get("t0", 0) <= t <= span.get("t1", 0)]
    if not live:
        return None
    tokens = sum(c for c, _ in live) / len(live)
    rows = sum(n for _, n in live) / len(live)
    k = ctx.trace_data.kernel(PATTERN)
    cost = arith.paged_decode_cost(ctx.cfg, tokens, rows,
                                   ctx.cfg.get("kv_dtype_bytes", 2))
    r = arith.roofline_share(cost["flops"] * k["calls"],
                             cost["bytes"] * k["calls"], k["seconds"],
                             ctx.peaks)
    log(f"KERNEL paged: {k['calls']:.0f} calls, {k['seconds']:.4f}s, mean "
        f"live context {tokens:.0f} tokens over {rows:.1f} rows, "
        f"{r['bound']}-bound")
    return arith.check_share("paged_attn_roofline.serve", r["share_pct"])
