"""Roofline share of the Mamba-2 decode-step kernel (`ssd_step`,
ops/pallas_kda.py: the decode step and the decode rows of a mixed step): the
least time the chip could take to read and write the states of the rows that
really advanced (2 x 64 x 64 x 128 x 4 B = 2 x 2 MiB a live row a call, 5
operations a state element; benchmark/lib/ssm_moe.py) over the kernel's
summed device time in the traced slice.  The live rows a call are the
program's own count (serving_recurrent_slot_updates_total / steps / Mamba-2
layers, cumulative over the process: warm-up and ramp, where fewer slots
run, are in it, so the share errs low).  The pattern is the kernel's own
name — it shares its body with `kda_step`, and the trace shows each under
its own name — never every custom call.  A trace without the kernel, or a
program without the counters, has nothing to read."""
from benchmark.lib import arith, ssm_moe
from benchmark.lib.common import log
from benchmark.lib.trace import TraceError

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
PATTERN = r"ssd_step.*\[tpu_custom_call\]"


def read(ctx):
    if ctx.trace_data is None:
        return None
    rows = ssm_moe.updates_per_step(ctx.cfg)
    if rows is None:
        return None
    try:
        k = ctx.trace_data.kernel(PATTERN)
    except TraceError as e:
        log(f"KERNEL ssd_step: {str(e)[:200]}")
        return None
    cost = ssm_moe.ssd_step_cost(ctx.cfg, rows)
    r = arith.roofline_share(cost["flops"] * k["calls"],
                             cost["bytes"] * k["calls"], k["seconds"],
                             ctx.peaks)
    log(f"KERNEL ssd_step: {k['calls']:.0f} calls, {k['seconds']:.4f}s, "
        f"{rows:.1f} live rows a call, {r['bound']}-bound")
    return arith.check_share("ssd_step_roofline.serve", r["share_pct"])
