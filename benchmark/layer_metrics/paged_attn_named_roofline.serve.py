"""Roofline share of the paged-attention kernel in a step that holds other
Pallas calls too (`paged_attn`, ops/pallas_paged.py, beside `kda_step`):
the least time the chip could take to read the LIVE K/V of the requests in
flight (what the client saw in flight during the traced slice; prompts
still in prefill are left out, so the share errs low) over the kernel's
summed device time in that slice, one call a GQA layer a step.  The pattern
is the kernel's OWN name — `paged_attn_roofline.serve` sums every custom
call of a step and would take `kda_step`'s time for this kernel's — and the
cost reads the configuration's `head_dim` (benchmark/lib/kda_gqa_moe.py:
the heads are 128 wide beside hidden_size / heads = 64).  A trace without
the kernel has nothing to read."""
from benchmark.lib import arith, kda_gqa_moe
from benchmark.lib.common import log
from benchmark.lib.trace import TraceError

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
PATTERN = r"(?<!mla_)paged_attn.*\[tpu_custom_call\]"


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
        log(f"KERNEL paged_attn: {str(e)[:200]}")
        return None
    tokens = sum(c for c, _ in live) / len(live)
    rows = sum(n for _, n in live) / len(live)
    cost = kda_gqa_moe.paged_cost(ctx.cfg, tokens, rows)
    r = arith.roofline_share(cost["flops"] * k["calls"],
                             cost["bytes"] * k["calls"], k["seconds"],
                             ctx.peaks)
    log(f"KERNEL paged_attn: {k['calls']:.0f} calls, {k['seconds']:.4f}s, "
        f"mean live context {tokens:.0f} tokens over {rows:.1f} rows, "
        f"{r['bound']}-bound")
    return arith.check_share("paged_attn_named_roofline.serve",
                             r["share_pct"])
