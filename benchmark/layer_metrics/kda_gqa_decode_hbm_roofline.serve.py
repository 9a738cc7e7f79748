"""Share of the HBM roofline a step of the KDA / gated-GQA / MoE model
reaches: the least time the chip could take to move what ONE decode step
must (benchmark/lib/kda_gqa_moe.py: the KDA layers' matrices and the state
of the rows that advanced, read and written; the GQA layers' matrices, the
gate's among them, and the live K/V rows; every layer's router and shared
expert and the held experts that drew a pair; the head) over the device's
busy time a step in the traced slice (busy time of the first device over
the `pt.step.decode` and `pt.step.mixed` spans in it; a mixed step moves at
least what a decode step does, so the share errs low where chunks ride
along).  Memory-bound by construction: at 128 rows a step the matmuls'
operations are far under their bytes' time.  A program without the
recurrent or the expert counters has nothing to read.

The log also gives `kda_step`'s own share of its roofline at this
configuration's heads (the kernel's summed time in the slice against each
moved state read once and written once): `kda_step_roofline.serve` would
read it, but its `hybrid_linear.mixer_layers` wants
`linear_attn_config.full_attn_layers`, which this family's published group
does not have (PERF.md section 7 row 20)."""
from benchmark.lib import arith, kda_gqa_moe, latent_moe
from benchmark.lib.common import log
from benchmark.lib.phases import Phases
from benchmark.lib.trace import TraceError

LAYER = "graph and ops"
UNIT = "%"
MOVES = "itl_p95_ms"


def log_kda_step_share(ctx, state_rows):
    try:
        k = ctx.trace_data.kernel(r"kda_step.*\[tpu_custom_call\]")
    except TraceError as e:
        log(f"KERNEL kda_step: {str(e)[:200]}")
        return
    cost = kda_gqa_moe.kda_step_cost(ctx.cfg, state_rows)
    r = arith.roofline_share(cost["flops"] * k["calls"],
                             cost["bytes"] * k["calls"], k["seconds"],
                             ctx.peaks)
    log(f"KERNEL kda_step: {k['calls']:.0f} calls, {k['seconds']:.4f}s, "
        f"{state_rows:.1f} live rows a call, {r['bound']}-bound, "
        f"{r['share_pct']:.2f}% of its roofline")


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
    state_rows = kda_gqa_moe.updates_per_step(ctx.cfg)
    if not steps or not live or pairs is None or state_rows is None:
        return None
    tokens = sum(c for c, _ in live) / len(live)
    rows = sum(n for _, n in live) / len(live)
    parts = kda_gqa_moe.decode_step_bytes(ctx.cfg, rows, tokens, pairs,
                                          state_rows)
    busy = ctx.trace_data.busy_s() / steps
    least = parts["total"] / ctx.peaks["hbm_bytes_per_s"]
    log(f"KDA GQA DECODE STEP bytes "
        f"{({k: round(v / 1e6, 1) for k, v in parts.items()})} MB, least "
        f"{1e3 * least:.3f} ms, busy {1e3 * busy:.3f} ms a step over {steps} "
        f"steps, live context {tokens:.0f} tokens over {rows:.1f} rows, "
        f"{state_rows:.1f} states moved a layer a step, {pairs:.2f} pairs an "
        f"expert")
    log_kda_step_share(ctx, state_rows)
    return arith.check_share("kda_gqa_decode_hbm_roofline.serve",
                             100.0 * least / busy)
