"""Roofline share of the Gated DeltaNet decode-step kernel (`gdn_step`,
ops/pallas_kda.py: the delta rule with a decay a head on a [96, 192] state,
the decode step and the decode rows of a mixed step): the least time the
chip could take to read and write the published states of the rows that
really advanced (2 x 30 x 96 x 192 x 4 B = 2 x 2,211,840 B a live row a
call, 6 operations a state element; benchmark/lib/gdn_mha_dense.py) over
the kernel's summed device time in the traced slice.  The live rows a call
are the engine's own count over the slice's stretch
(`serving_recurrent_tokens_total{kind="step"}` over
`serving_recurrent_steps_total`).  The chip holds a row of 192 float32 as
two lane tiles, so the kernel moves 256 / 192 of the published bytes: that
third is the program's, and the share says so by staying under 75%.  The
pattern is the kernel's own name.  A trace without the kernel, or a program
without the counters, has nothing to read."""
from benchmark.lib import arith, gdn_mha_dense
from benchmark.lib.common import log
from benchmark.lib.trace import TraceError

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
PATTERN = r"gdn_step.*\[tpu_custom_call\]"


def read(ctx):
    if ctx.trace_data is None:
        return None
    rows = gdn_mha_dense.updates_per_step(ctx)
    if rows is None:
        return None
    try:
        k = ctx.trace_data.kernel(PATTERN)
    except TraceError as e:
        log(f"KERNEL gdn_step: {str(e)[:200]}")
        return None
    cost = gdn_mha_dense.gdn_step_cost(ctx.cfg, rows)
    r = arith.roofline_share(cost["flops"] * k["calls"],
                             cost["bytes"] * k["calls"], k["seconds"],
                             ctx.peaks)
    log(f"KERNEL gdn_step: {k['calls']:.0f} calls, {k['seconds']:.4f}s, "
        f"{rows:.1f} live rows a call, {r['bound']}-bound")
    return arith.check_share("gdn_step_roofline.serve", r["share_pct"])
