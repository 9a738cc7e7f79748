"""Roofline share of the KDA decode-step kernel (`kda_step`,
ops/pallas_kda.py: the decode step and the decode rows of a mixed step): the
least time the chip could take to read and write the states of the rows that
really advanced (2 x 32 x 128 x 128 x 4 B a live row a call, 6 operations a
state element; benchmark/lib/hybrid_linear.py) over the kernel's summed
device time in the traced slice.  The live rows a call are the program's own
count (serving_recurrent_slot_updates_total / steps / KDA layers, cumulative
over the process: warm-up and ramp, where fewer slots run, are in it, so the
share errs low).  The pattern is the kernel's own name, not every custom
call.  A trace without the kernel, or a program without the counters, has
nothing to read."""
from benchmark.lib import arith, hybrid_linear
from benchmark.lib.common import log
from benchmark.lib.trace import TraceError

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
PATTERN = r"kda_step.*\[tpu_custom_call\]"


def read(ctx):
    if ctx.trace_data is None:
        return None
    rows = hybrid_linear.updates_per_step(ctx.cfg)
    if rows is None:
        return None
    try:
        k = ctx.trace_data.kernel(PATTERN)
    except TraceError as e:
        log(f"KERNEL kda_step: {str(e)[:200]}")
        return None
    cost = hybrid_linear.kda_step_cost(ctx.cfg, rows)
    r = arith.roofline_share(cost["flops"] * k["calls"],
                             cost["bytes"] * k["calls"], k["seconds"],
                             ctx.peaks)
    log(f"KERNEL kda_step: {k['calls']:.0f} calls, {k['seconds']:.4f}s, "
        f"{rows:.1f} live rows a call, {r['bound']}-bound")
    return arith.check_share("kda_step_roofline.serve", r["share_pct"])
