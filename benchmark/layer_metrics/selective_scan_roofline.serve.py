"""Roofline share of the Mamba-1 selective-scan kernel
(ops/pallas_selective_scan.py), both of its calls under their own names:
`selective_scan_step` (one token a row: the decode step and the decode rows
of a mixed step) and `selective_scan_seg` (a mixed step's prompt-chunk runs,
the time loop inside the kernel).  The least time the chip could take over
the calls' summed device time in the traced slice: each live run's state in
and out (2 x 16 x 5,120 x 4 B = 2 x 327,680 B) plus each token's operands
and result (x', dt in and y out a channel, B and C a state row, float32) at
the chip's HBM bandwidth, 8 operations a state element a token against its
peak (benchmark/lib/ssm_dense.py: the same work whatever implements a
call).  Runs and tokens a step are the program's own counts as they grew
over the measured window (serving_recurrent_slot_updates_total,
serving_recurrent_tokens_total by kind, over serving_recurrent_steps_total;
benchmark/lib/ssm_dense.py:window_growth — warm-up and ramp are not in
them); the steps in the slice are the step
call's count over the Mamba layers (every step makes one a layer), or the
engine's step spans where the decode rows run no kernel.  The scan is
float32 work on the vector unit,
which benchmark/peaks.json has no peak for: against the MXU's the segments'
call reads memory-bound and low — its log line gives the time a token,
which is the number to size it by.  The patterns are the kernel's own
names, never every custom call.  A trace without either call, or a program
without the counters, has nothing to read."""
from benchmark.lib import arith, ssm_dense
from benchmark.lib.common import log
from benchmark.lib.trace import TraceError

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
PATTERNS = {"step": r"selective_scan_step.*\[tpu_custom_call\]",
            "segment": r"selective_scan_seg.*\[tpu_custom_call\]"}


def read(ctx):
    if ctx.trace_data is None:
        return None
    work = ssm_dense.scan_work_per_step(ctx)
    if work is None:
        return None
    found = {}
    for kind, pattern in PATTERNS.items():
        try:
            found[kind] = ctx.trace_data.kernel(pattern)
        except TraceError as e:
            log(f"KERNEL selective_scan {kind}: {str(e)[:160]}")
    if not found:
        return None
    layers = ssm_dense.layer_counts(ctx.cfg)["mamba"]
    steps = found["step"]["calls"] / layers if "step" in found \
        else ssm_dense.steps_in_slice(ctx)
    if not steps:
        return None
    flops = nbytes = seconds = 0.0
    for kind, k in found.items():
        w = work[kind]
        flops += w["flops"] * steps * layers
        nbytes += w["bytes"] * steps * layers
        seconds += k["seconds"]
        tokens = w["tokens"] * steps * layers
        log(f"KERNEL selective_scan {kind}: {k['calls']:.0f} calls, "
            f"{k['seconds']:.4f}s, {1e6 * k['seconds'] / k['calls']:.1f} us "
            f"a call, {w['runs']:.1f} runs and {w['tokens']:.1f} tokens a "
            f"layer a step, "
            f"{1e6 * k['seconds'] / tokens if tokens else 0.0:.3f} us a "
            f"token, least "
            f"{1e3 * w['bytes'] / ctx.peaks['hbm_bytes_per_s']:.4f} ms a "
            f"layer a step")
    r = arith.roofline_share(flops, nbytes, seconds, ctx.peaks)
    log(f"KERNEL selective_scan: {steps:.1f} steps x {layers} layers, "
        f"{seconds:.4f}s, {r['bound']}-bound")
    return arith.check_share("selective_scan_roofline.serve", r["share_pct"])
