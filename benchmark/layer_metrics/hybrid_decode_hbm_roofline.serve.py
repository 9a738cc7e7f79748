"""Share of the HBM roofline a step of the hybrid model reaches: the least
time the chip could take to move what ONE decode step must
(benchmark/lib/hybrid_linear.py: the KDA layers' matrices and the state of
the rows that advanced, read and written; the MLA layers' matrices and live
latent rows; the dense MLP, routers and shared experts, the held experts
that drew a pair, the head) over the device's busy time a step in the
traced slice (busy time of the first device over the `pt.step.decode` and
`pt.step.mixed` spans in it; a mixed step moves at least what a decode step
does, so the share errs low where chunks ride along).  Memory-bound by
construction: at 128 rows a step the matmuls' operations are far under
their bytes' time."""
from benchmark.lib import arith, hybrid_linear, latent_moe
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
    state_rows = hybrid_linear.updates_per_step(ctx.cfg)
    if not steps or not live or pairs is None or state_rows is None:
        return None
    tokens = sum(c for c, _ in live) / len(live)
    rows = sum(n for _, n in live) / len(live)
    parts = hybrid_linear.decode_step_bytes(ctx.cfg, rows, tokens, pairs,
                                            state_rows)
    busy = ctx.trace_data.busy_s() / steps
    least = parts["total"] / ctx.peaks["hbm_bytes_per_s"]
    log(f"HYBRID DECODE STEP bytes "
        f"{({k: round(v / 1e6, 1) for k, v in parts.items()})} MB, least "
        f"{1e3 * least:.3f} ms, busy {1e3 * busy:.3f} ms a step over {steps} "
        f"steps, live context {tokens:.0f} tokens over {rows:.1f} rows, "
        f"{state_rows:.1f} states moved a layer a step")
    return arith.check_share("hybrid_decode_hbm_roofline.serve",
                             100.0 * least / busy)
