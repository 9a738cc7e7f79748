"""Operations and bytes of a Mamba-1 / multi-query attention / dense-MLP
model's decode step and of its selective-scan calls, from shapes alone and
by the layer rule (the functions a roofline share is computed from are kept
with the benchmark).  `cfg` is a configuration file of the jamba family as
benchmark/configs/ holds it: HF-named keys plus `head_dim`.  Layer i (from
0) is attention where i % attn_layer_period == attn_layer_offset, Mamba
elsewhere; every block carries the dense SwiGLU MLP and two norms."""

from __future__ import annotations

from benchmark.lib import step_clock
from benchmark.lib.common import log
# one attention mixer's parameters and one token's K and V: the sibling's
# functions, the same keys
from benchmark.lib.ssm_moe import attention_params, kv_row_bytes  # noqa: F401

TOKENS = 'serving_recurrent_tokens_total{kind="%s"}'


def layer_counts(cfg: dict) -> dict:
    """{"mamba": Mamba layers, "attention": attention layers}."""
    n = cfg["num_hidden_layers"]
    attn = sum(1 for i in range(n)
               if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"])
    return {"mamba": n - attn, "attention": attn}


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def mamba_params(cfg: dict) -> int:
    """One Mamba-1 mixer: W_in (x, z), the taps and their bias, W_x (r, B,
    C), W_dt and its bias, A_log, D, the three inner norms' scales, W_out."""
    d, d_in = cfg["hidden_size"], d_inner(cfg)
    N, R = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return d * 2 * d_in + (cfg["mamba_d_conv"] + 1) * d_in \
        + d_in * (R + 2 * N) + (R + 1) * d_in + N * d_in + d_in \
        + (R + 2 * N) + d_in * d


def mlp_params(cfg: dict) -> int:
    """One block's SwiGLU MLP and its two RMSNorm scales."""
    d = cfg["hidden_size"]
    return 3 * d * cfg["intermediate_size"] + 2 * d


def weight_params(cfg: dict) -> dict:
    """Parameters held, by part (the configuration file's table); the head
    is untied: the embedding's shape twice."""
    n = layer_counts(cfg)
    d = cfg["hidden_size"]
    parts = {
        "mamba": n["mamba"] * mamba_params(cfg),
        "attention": n["attention"] * attention_params(cfg),
        "mlp_and_norms": cfg["num_hidden_layers"] * mlp_params(cfg) + d,
        "embedding_and_head": 2 * d * cfg["vocab_size"],
    }
    parts["total"] = sum(parts.values())
    return parts


def state_bytes(cfg: dict) -> int:
    """One slot's recurrent state in one Mamba layer: N x d_in float32."""
    return cfg["mamba_d_state"] * d_inner(cfg) * 4


def conv_tail_bytes(cfg: dict) -> int:
    """One slot's tail in one Mamba layer: taps - 1 rows of x."""
    return (cfg["mamba_d_conv"] - 1) * d_inner(cfg) * \
        int(cfg.get("kv_dtype_bytes", 2))


def resident_bytes(cfg: dict, weight_bytes: int = 2) -> dict:
    """What the served model holds on the chip, by part: the weights, the
    slot pools (slots + the trash row) and the K/V pool (every slot's worst
    case + the trash page)."""
    f = cfg["server_flags"]
    n = layer_counts(cfg)
    rows = f["slots"] + 1
    pages = f["slots"] * -(-f["max_context"] // f["page_size"]) + 1
    parts = {
        "weights": weight_params(cfg)["total"] * weight_bytes,
        "state_pool": n["mamba"] * rows * state_bytes(cfg),
        "conv_tails": n["mamba"] * rows * conv_tail_bytes(cfg),
        "kv_pool": n["attention"] * pages * f["page_size"]
        * kv_row_bytes(cfg),
    }
    parts["total"] = sum(parts.values())
    return parts


# -- the selective scan's calls ------------------------------------------------

SCAN_OPS_PER_ELEMENT = 8    # a state element a token: dt A, exp, decay x h,
                            # dt x B (the product dt x counted a channel),
                            # the add, h C, the readout's add: 8 with the
                            # channel's own two spread over N


def scan_token_bytes(d_in: int, N: int) -> int:
    """What a token brings to and takes from a scan call, float32: x', dt
    in and y out a channel, B and C a state row."""
    return (3 * d_in + 2 * N) * 4


def scan_call_bytes(d_in: int, N: int, runs: float, tokens: float) -> float:
    """Bytes a scan call (one layer) must move: each live run's state in
    and out, each token's operands and result — the same work whatever
    implements the call."""
    return 2.0 * N * d_in * 4 * runs + scan_token_bytes(d_in, N) * tokens


def scan_call_flops(d_in: int, N: int, tokens: float) -> float:
    return float(SCAN_OPS_PER_ELEMENT) * N * d_in * tokens


def window_growth(ctx) -> dict:
    """The process counters' growth over the measured window (the pump's
    checkpoints: benchmark/lib/step_clock.py), warm-up and ramp left out.
    The whole window where the checkpoints cover it — a count a step is as
    true under the profiler as beside it —, else the stretch outside the
    profiler's slice that the step clock's readers use, else {} (a parent
    commit keeps no checkpoints)."""
    pc = step_clock._counters()
    if pc is None:
        return {}
    t0, t1, _ = step_clock.stretch(ctx)
    try:
        return pc.between(t0, t1)[0]
    except LookupError as e:
        log(f"SSM DENSE counters: the whole window is not covered ({e}); "
            f"reading outside the profiler's slice")
    try:
        w = step_clock.window(ctx)
    except LookupError as e:
        log(f"SSM DENSE counters: nothing to read: {e}")
        return {}
    return w.growth if w is not None else {}


def scan_counters(ctx) -> dict:
    """The engine's counters behind the scan AS THEY GREW OVER THE MEASURED
    WINDOW (`window_growth`): steps counted, slot states moved (all Mamba
    layers), tokens the recurrent layers ran as decode rows and as chunk
    runs (one layer's worth) — or {} where the program keeps none (a parent
    commit) or the window counted nothing."""
    if "ssm_dense_growth" not in ctx.spans:         # read once a run
        ctx.spans["ssm_dense_growth"] = window_growth(ctx)
    g = ctx.spans["ssm_dense_growth"]
    steps = g.get("serving_recurrent_steps_total")
    tok = {k: g.get(TOKENS % k) for k in ("step", "segment")}
    if not steps or tok["step"] is None or tok["segment"] is None:
        return {}
    return {"steps": steps,
            "updates": g["serving_recurrent_slot_updates_total"],
            "step_tokens": tok["step"], "segment_tokens": tok["segment"]}


def steps_in_slice(ctx) -> int:
    """Compiled steps the traced slice holds, by the engine's own
    `pt.step.decode` / `pt.step.mixed` spans (0 without a trace)."""
    from benchmark.lib.phases import Phases
    ph = Phases.of(ctx, "serve")
    if ph is None:
        return 0
    return sum(len(ph.durations(n)) for n in ("pt.step.decode",
                                              "pt.step.mixed")
               if n in ph.names)


def updates_per_step(ctx):
    """Slot states ONE Mamba layer read and wrote in ONE compiled step, on
    average (decode rows that really advanced, plus a prompt chunk's run
    each) over the measured window, or None with nothing counted."""
    c = scan_counters(ctx)
    n = layer_counts(ctx.cfg)["mamba"]
    if not c or n <= 0:
        return None
    return c["updates"] / c["steps"] / n


def scan_work_per_step(ctx):
    """What ONE Mamba layer's scan calls must do in ONE compiled step, on
    average over the measured window, by call kind: {"step": {runs,
    tokens, bytes, flops}, "segment": {...}} — the per-row call's live rows
    (a run of one token each) and the chunk runs (the states moved less the
    rows' own) with their tokens — or None with nothing counted."""
    c = scan_counters(ctx)
    cfg = ctx.cfg
    n = layer_counts(cfg)["mamba"]
    if not c or n <= 0:
        return None
    d_in, N = d_inner(cfg), cfg["mamba_d_state"]
    step = c["step_tokens"] / c["steps"]
    kinds = {"step": (step, step),
             "segment": (max(c["updates"] / c["steps"] / n - step, 0.0),
                         c["segment_tokens"] / c["steps"])}
    return {k: {"runs": runs, "tokens": tokens,
                "bytes": scan_call_bytes(d_in, N, runs, tokens),
                "flops": scan_call_flops(d_in, N, tokens)}
            for k, (runs, tokens) in kinds.items()}


def decode_step_bytes(cfg: dict, rows: float, live_tokens: float,
                      state_rows: float, weight_bytes: int = 2) -> dict:
    """What ONE decode step must move through HBM, by part: the Mamba
    mixers' matrices, the state and the tail of the rows that advanced
    (read + write), the attention mixers' matrices and live K/V rows, the
    MLPs, the head, the rows' embedding vectors."""
    d = cfg["hidden_size"]
    n = layer_counts(cfg)
    parts = {
        "ssm_state": n["mamba"] * 2.0 * state_bytes(cfg) * state_rows,
        "conv_tails": n["mamba"] * 2.0 * conv_tail_bytes(cfg) * state_rows,
        "mamba_matrices": n["mamba"] * mamba_params(cfg) * weight_bytes,
        "attention_matrices": n["attention"] * attention_params(cfg)
        * weight_bytes,
        "kv_rows": n["attention"] * live_tokens * kv_row_bytes(cfg),
        "mlp_and_norms": (cfg["num_hidden_layers"] * mlp_params(cfg) + d)
        * weight_bytes,
        "head": d * cfg["vocab_size"] * weight_bytes,
        "embedding_rows": rows * d * weight_bytes,
    }
    parts["total"] = float(sum(parts.values()))
    return parts


def step_matmul_flops(cfg: dict, rows: float) -> float:
    """Operations of the matrices of one step of `rows` rows (2 a
    parameter a row; the embedding is a gather)."""
    w = weight_params(cfg)
    return 2.0 * rows * (w["total"] - cfg["hidden_size"] * cfg["vocab_size"])
