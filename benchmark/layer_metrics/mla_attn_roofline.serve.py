"""Roofline share of the latent paged-attention kernel (`mla_paged_attn`,
ops/pallas_paged.py: decode and mixed rows): the least time the chip could
take for the LIVE latent rows of the requests in flight (what the client
saw in flight during the traced slice: 1,152 B a token a layer read once,
2 x 64 x (576 + 512) operations a live token a call;
benchmark/lib/latent_moe.py) over the kernel's summed device time in that
slice.  Prompts still in prefill are left out, so the share errs low.  The
pattern is the kernel's own name, not every custom call.  A trace without
the kernel has nothing to read."""
from benchmark.lib import arith, latent_moe
from benchmark.lib.common import log
from benchmark.lib.trace import TraceError

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
PATTERN = r"mla_paged_attn.*\[tpu_custom_call\]"


def read(ctx):
    if ctx.trace_data is None:
        return None
    span = ctx.counters.get("trace_span") or {}
    live = [(c, n) for t, c, n in ctx.counters.get("live_samples", [])
            if span.get("t0", 0) <= t <= span.get("t1", 0)]
    if not live:
        return None
    try:
        k = ctx.trace_data.kernel(PATTERN)
    except TraceError as e:
        log(f"KERNEL mla_paged_attn: {str(e)[:200]}")
        return None
    tokens = sum(c for c, _ in live) / len(live)
    rows = sum(n for _, n in live) / len(live)
    cost = latent_moe.latent_attention_cost(ctx.cfg, tokens, rows)
    r = arith.roofline_share(cost["flops"] * k["calls"],
                             cost["bytes"] * k["calls"], k["seconds"],
                             ctx.peaks)
    log(f"KERNEL mla_paged_attn: {k['calls']:.0f} calls, {k['seconds']:.4f}s,"
        f" mean live context {tokens:.0f} tokens over {rows:.1f} rows, "
        f"{r['bound']}-bound")
    return arith.check_share("mla_attn_roofline.serve", r["share_pct"])
