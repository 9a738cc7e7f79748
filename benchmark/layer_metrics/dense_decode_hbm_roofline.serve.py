"""Share of the HBM roofline a step of a dense model reaches: the least time
the chip could take to read what ONE decode step must AT THE STATED COMPUTE
DTYPE — every matrix of the blocks and the head once
(`arith.lm_matmul_params`; the embedding is a gather of the step's rows) and
each layer's live K/V rows (`arith.paged_decode_cost`) — over the device's
busy time a step in the traced slice (busy time of the first device over the
`pt.step.decode` and `pt.step.mixed` spans in it; a mixed step reads at
least what a decode step does, so the share errs low where chunks ride
along).  The bytes are the model's, not the program's: a program that holds
its weights wider than it computes with, and casts them every step, reads
more than this and shows it as a lower share.  Memory-bound by construction:
at 64 rows a step the matmuls' operations are far under their bytes' time."""
from benchmark.lib import arith
from benchmark.lib.common import log
from benchmark.lib.phases import Phases

LAYER = "graph and ops"
UNIT = "%"
MOVES = "itl_p95_ms"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def decode_step_bytes(cfg: dict, rows: float, tokens: float) -> dict:
    """Bytes ONE decode step must read: the matrices in the compute dtype,
    and every layer's live K/V (`tokens` summed over the `rows` in flight)."""
    weights = arith.lm_matmul_params(cfg)["total"] \
        * DTYPE_BYTES[cfg["compute_dtype"]]
    kv = cfg["num_hidden_layers"] * arith.paged_decode_cost(
        cfg, tokens, rows, cfg.get("kv_dtype_bytes", 2))["bytes"]
    return {"weights": float(weights), "kv": kv, "total": weights + kv}


def share(step_bytes: float, busy_s_a_step: float, peaks: dict) -> float:
    """The least time the chip could take to read `step_bytes` over the busy
    time a step, in percent; above what the chip can give it raises."""
    least = step_bytes / peaks["hbm_bytes_per_s"]
    return arith.check_share("dense_decode_hbm_roofline.serve",
                             100.0 * least / busy_s_a_step)


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
    if not steps or not live:
        return None
    tokens = sum(c for c, _ in live) / len(live)
    rows = sum(n for _, n in live) / len(live)
    parts = decode_step_bytes(ctx.cfg, rows, tokens)
    busy = ctx.trace_data.busy_s() / steps
    log(f"DENSE DECODE STEP bytes "
        f"{({k: round(v / 1e6, 1) for k, v in parts.items()})} MB at "
        f"{ctx.cfg['compute_dtype']}, least "
        f"{1e3 * parts['total'] / ctx.peaks['hbm_bytes_per_s']:.3f} ms, busy "
        f"{1e3 * busy:.3f} ms a step over {steps} steps, live context "
        f"{tokens:.0f} tokens over {rows:.1f} rows")
    return share(parts["total"], busy, ctx.peaks)
