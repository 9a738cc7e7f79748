"""Roofline share of the flash forward + backward kernels: the least time
the chip could take for their operations and bytes (from shapes,
benchmark/lib/arith.py:flash_train_cost) over their summed device time in
the traced steps.  Every Pallas custom call of the train step is one of the
flash kernels (forward, dq, dkv: three calls a layer a step); the kernels
carry no name of their own yet (PERF.md, for the tracing issue)."""
from benchmark.lib import arith
from benchmark.lib.common import log

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
PATTERN = r"\[tpu_custom_call\]"


def read(ctx):
    if ctx.trace_data is None:
        return None
    k = ctx.trace_data.kernel(PATTERN)
    tf = ctx.traffic
    per_chip = tf["sequences_per_step"] // ctx.chips
    cost = arith.flash_train_cost(ctx.cfg, per_chip, tf["seq_len"])
    n = k["calls"] / 3.0            # forward, dq and dkv calls of one layer
    r = arith.roofline_share(cost["flops"] * n, cost["bytes"] * n,
                             k["seconds"], ctx.peaks)
    log(f"KERNEL flash: {k['calls']:.0f} calls, {k['seconds']:.4f}s, "
        f"{r['bound']}-bound")
    return arith.check_share("flash_attn_roofline.train", r["share_pct"])
