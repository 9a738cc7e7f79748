"""Share of the HBM roofline a step of the window / full attention MoE
model reaches: the least time the chip could take to read what ONE decode
step must (benchmark/lib/window_moe.py: the attention layers' matrices,
the dense MLP, every sparse layer's router and shared expert and the
experts that drew a pair — from the engine's counters —, the head, the full
layers' pages to each row's context, the window layers' to the window) over
the device's busy time a step in the traced slice (busy time of the first
device over the `pt.step.decode` and `pt.step.mixed` spans in it; a mixed
step moves at least what a decode step does, so the share errs low where
chunks ride along).  A program without the expert counters has nothing to
read."""
from benchmark.lib import arith, window_moe
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
            if span.get("t0", 0) <= t <= span.get("t1", 0) and n]
    pairs = window_moe.pairs_per_expert(ctx.cfg)
    if not steps or not live or pairs is None:
        return None
    tokens = sum(c for c, _ in live) / len(live)
    rows = sum(n for _, n in live) / len(live)
    parts = window_moe.decode_step_bytes(ctx.cfg, rows, tokens, pairs)
    busy = ctx.trace_data.busy_s() / steps
    least = parts["total"] / ctx.peaks["hbm_bytes_per_s"]
    log(f"WINDOW MOE DECODE STEP bytes "
        f"{({k: round(v / 1e6, 1) for k, v in parts.items()})} MB, least "
        f"{1e3 * least:.3f} ms, busy {1e3 * busy:.3f} ms a step over {steps} "
        f"steps, live context {tokens:.0f} tokens over {rows:.1f} rows, "
        f"{pairs:.2f} pairs an expert")
    return arith.check_share("window_moe_decode_hbm_roofline.serve",
                             100.0 * least / busy)
