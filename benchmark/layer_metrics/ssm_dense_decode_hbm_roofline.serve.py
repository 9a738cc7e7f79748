"""Share of the HBM roofline a step of the Mamba-1 / multi-query attention /
dense-MLP model reaches: the least time the chip could take to move what
ONE decode step must (benchmark/lib/ssm_dense.py, by the layer rule: the
state and the tail of the rows that advanced, read and written; the Mamba
and attention mixers' matrices and the live K/V rows; every block's MLP;
the head) over the device's busy time a step in the traced slice (busy time
of the first device over the `pt.step.decode` and `pt.step.mixed` spans in
it; a mixed step moves at least what a decode step does, so the share errs
low where chunks ride along).  This cell's share of the whole step, as each
serve configuration has one."""
from benchmark.lib import arith, ssm_dense
from benchmark.lib.common import log

LAYER = "graph and ops"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    steps = ssm_dense.steps_in_slice(ctx)
    span = ctx.counters.get("trace_span") or {}
    live = [(c, n) for t, c, n in ctx.counters.get("live_samples", [])
            if span.get("t0", 0) <= t <= span.get("t1", 0)]
    state_rows = ssm_dense.updates_per_step(ctx)
    if not steps or not live or state_rows is None:
        return None
    tokens = sum(c for c, _ in live) / len(live)
    rows = sum(n for _, n in live) / len(live)
    parts = ssm_dense.decode_step_bytes(ctx.cfg, rows, tokens, state_rows)
    busy = ctx.trace_data.busy_s() / steps
    least = parts["total"] / ctx.peaks["hbm_bytes_per_s"]
    flops = ssm_dense.step_matmul_flops(ctx.cfg, rows)
    log(f"SSM DENSE DECODE STEP bytes "
        f"{({k: round(v / 1e6, 1) for k, v in parts.items()})} MB, least "
        f"{1e3 * least:.3f} ms, busy {1e3 * busy:.3f} ms a step over {steps} "
        f"steps, live context {tokens:.0f} tokens over {rows:.1f} rows, "
        f"{state_rows:.1f} states moved a layer a step; the matrices' "
        f"products at these rows {flops / 1e12:.3f} TFLOP = "
        f"{1e3 * flops / ctx.peaks['bf16_flops']:.2f} ms at the peak")
    return arith.check_share("ssm_dense_decode_hbm_roofline.serve",
                             100.0 * least / busy)
