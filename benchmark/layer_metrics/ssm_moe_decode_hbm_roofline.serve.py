"""Share of the HBM roofline a step of the Mamba-2 / attention / MoE model
reaches: the least time the chip could take to move what ONE decode step
must (benchmark/lib/ssm_moe.py: the state and the tail of the rows that
advanced, read and written; the Mamba-2 and attention mixers' matrices and
the live K/V rows; routers, shared experts and the held experts that drew a
pair; the head) over the device's busy time a step in the traced slice
(busy time of the first device over the `pt.step.decode` and
`pt.step.mixed` spans in it; a mixed step moves at least what a decode step
does, so the share errs low where chunks ride along).  The bytes are what
the step MUST move: where every row is multiplied by every held expert at
the ridge the step is bound by those products as much as by these bytes,
and the share says how far."""
from benchmark.lib import arith, latent_moe, ssm_moe
from benchmark.lib.common import log
from benchmark.lib.phases import Phases

LAYER = "graph and ops"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    ph = Phases.of(ctx, "serve")
    if ph is None:
        return None
    steps = sum(len(ph.durations(n)) for n in ("pt.step.decode",
                                               "pt.step.mixed")
                if n in ph.names)
    span = ctx.counters.get("trace_span") or {}
    live = [(c, n) for t, c, n in ctx.counters.get("live_samples", [])
            if span.get("t0", 0) <= t <= span.get("t1", 0)]
    pairs = latent_moe.pairs_per_expert(ctx.cfg)
    state_rows = ssm_moe.updates_per_step(ctx.cfg)
    if not steps or not live or pairs is None or state_rows is None:
        return None
    tokens = sum(c for c, _ in live) / len(live)
    rows = sum(n for _, n in live) / len(live)
    parts = ssm_moe.decode_step_bytes(ctx.cfg, rows, tokens, pairs,
                                      state_rows)
    busy = ctx.trace_data.busy_s() / steps
    least = parts["total"] / ctx.peaks["hbm_bytes_per_s"]
    flops = ssm_moe.expert_flops(ctx.cfg, rows)
    log(f"SSM MOE DECODE STEP bytes "
        f"{({k: round(v / 1e6, 1) for k, v in parts.items()})} MB, least "
        f"{1e3 * least:.3f} ms, busy {1e3 * busy:.3f} ms a step over {steps} "
        f"steps, live context {tokens:.0f} tokens over {rows:.1f} rows, "
        f"{state_rows:.1f} states moved a layer a step, {pairs:.2f} pairs an "
        f"expert; the experts' products at these rows: routed "
        f"{flops['routed_pairs'] / 1e12:.3f} TFLOP, rows x held "
        f"{flops['rows_x_held'] / 1e12:.3f} TFLOP = "
        f"{1e3 * flops['rows_x_held'] / ctx.peaks['bf16_flops']:.2f} ms at "
        f"the peak")
    return arith.check_share("ssm_moe_decode_hbm_roofline.serve",
                             100.0 * least / busy)
