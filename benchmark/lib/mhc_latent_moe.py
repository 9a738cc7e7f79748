"""Parameters, bytes and operations of the hyper-connected latent-attention
/ mixture-of-experts model (Xing4.0: the DeepSeek-V3 block on `hc_mult`
residual streams), from shapes alone (the functions a roofline share is
computed from are kept with the benchmark).  `cfg` is a configuration file
of the xing4_0 family as benchmark/configs/ holds it: HF-named keys,
`server_flags`, and `published` for the uncut depth.  Latent attention's
own counts are benchmark/lib/latent_moe.py's."""

from __future__ import annotations

import time

from benchmark.lib import latent_moe, step_clock
from benchmark.lib.window_moe import _process_counters


def map_width(cfg: dict) -> int:
    n = cfg["hc_mult"]
    return 2 * n + n * n


def maps_params(cfg: dict) -> int:
    """ONE sublayer's maps: phi [n C, 2 n + n^2], its bias row, 3 gates."""
    m = map_width(cfg)
    return cfg["hc_mult"] * cfg["hidden_size"] * m + m + 3


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: dict, sparse: bool) -> dict:
    """One block by part: attention, its MLP (dense SwiGLU, or router +
    routed + shared experts), the two sublayers' maps (norm scales are
    noise)."""
    d = cfg["hidden_size"]
    parts = {"attention": latent_moe.attention_params(cfg),
             "maps": 2 * maps_params(cfg)}
    if sparse:
        parts.update(
            router=d * cfg["n_routed_experts"],
            routed_experts=cfg["n_routed_experts"] * expert_params(cfg),
            shared_experts=cfg["n_shared_experts"] * expert_params(cfg))
    else:
        parts["dense_mlp"] = 3 * d * cfg["intermediate_size"]
    return parts


def stack_params(cfg: dict, layers: int, dense: int) -> dict:
    """A stack of `layers` blocks of which the first `dense` are dense,
    with the embedding and the head, by part."""
    total: dict = {}
    for i in range(layers):
        for k, v in layer_params(cfg, i >= dense).items():
            total[k] = total.get(k, 0) + v
    total["embedding_head"] = 2 * cfg["vocab_size"] * cfg["hidden_size"]
    return total


def stage_params(cfg: dict) -> dict:
    """The weights this stage holds, by part, and their `total`."""
    parts = stack_params(cfg, cfg["num_hidden_layers"],
                         min(cfg["first_k_dense_replace"],
                             cfg["num_hidden_layers"]))
    parts["total"] = sum(parts.values())
    return parts


def published_params(cfg: dict) -> dict:
    """The uncut model's parameters and those active a token, the
    multi-token-prediction module left out (`published` holds the depth)."""
    pub = cfg["published"]
    layers, dense = pub["num_hidden_layers"], pub["first_k_dense_replace"]
    parts = stack_params(cfg, layers, dense)
    total = sum(parts.values())
    idle = (layers - dense) * expert_params(cfg) * \
        (cfg["n_routed_experts"] - cfg["num_experts_per_tok"])
    # of the embedding's rows a token reads one
    return {"total": total,
            "active": total - idle - cfg["vocab_size"] * cfg["hidden_size"]}


def cache_bytes(cfg: dict) -> int:
    """The latent pool: every layer's [c_kv | k_pe] row for slots x
    max_context tokens and the trash page."""
    f = cfg["server_flags"]
    tokens = f["slots"] * f["max_context"] + f["page_size"]
    return cfg["num_hidden_layers"] * tokens * latent_moe.latent_row_bytes(cfg)


def resident_bytes(cfg: dict, weight_bytes: int = 2) -> dict:
    """What the chip holds once start-up is over: the stage's weights by
    part and the latent pool."""
    out = {k: v * weight_bytes for k, v in stage_params(cfg).items()
           if k != "total"}
    out["latent_pool"] = cache_bytes(cfg)
    out["total"] = sum(out.values())
    return out


# -- the stream pass -------------------------------------------------------------

def mix_call_bytes(cfg: dict, rows: float, act_bytes: int = 2) -> float:
    """What ONE `mhc_mix` call must move: a row's n streams and the
    sublayer's output in, the n new streams out ((2 n + 1) C values; ISSUE
    57 wrote 2 n + 2, one C more than a pass that does not write the next
    sublayer's read moves), and the row's float32 maps beside them."""
    n = cfg["hc_mult"]
    return rows * ((2 * n + 1) * cfg["hidden_size"] * act_bytes
                   + map_width(cfg) * 4)


def mix_call_flops(cfg: dict, rows: float) -> float:
    """n (n + 1) multiply-adds a column (VPU work: no MXU shape)."""
    n = cfg["hc_mult"]
    return 2.0 * rows * n * (n + 1) * cfg["hidden_size"]


def _between(t0: float, t1: float, edge: float):
    """The process's counters' growth between the pump's checkpoints
    nearest inside [t0, t1] (`time.perf_counter()`; ProcessCounters.between),
    each at most `edge` of the stretch from its end; or None where the
    program keeps no checkpoints or none cover the stretch."""
    pc = _process_counters()
    if not hasattr(pc, "between"):
        return None
    try:
        return pc.between(t0, t1, max_edge=edge * (t1 - t0))[0]
    except LookupError:
        return None


def _growth(ctx):
    """The counters' growth inside `trace_span` (the traced slice and the
    profiler's stop behind it), or None."""
    span = ctx.counters.get("trace_span") or {}
    if "t1" not in span:
        return None
    offset = time.perf_counter() - time.time()      # the checkpoints' clock
    return _between(span["t0"] + offset, span["t1"] + offset, 0.25)


def rows_per_mix_call(ctx):
    """Rows ONE stream pass carried in the traced slice, padding included:
    `serving_mhc_rows_total` over `serving_mhc_calls_total`."""
    g = _growth(ctx)
    if not g or not g.get("serving_mhc_calls_total"):
        return None
    return g["serving_mhc_rows_total"] / g["serving_mhc_calls_total"]


def chunk_rows_per_mixed_step(ctx):
    """Prompt rows a mixed step of the MEASURED WINDOW carried (its rows
    less padding less decode rows; warm-up and ramp left out):
    `serving_chunk_rows_total` over `serving_mixed_steps_total` as they
    grew between the pump's checkpoints inside the window
    (step_clock.stretch), or None with no mixed step counted.  A ratio of
    two counts of one steady window: a checkpoint may lie up to half the
    window from its end (a loaded host's pump checkpoints late)."""
    if not hasattr(_process_counters(), "between"):
        return None                 # a parent commit keeps no checkpoints
    t0, t1, _ = step_clock.stretch(ctx)
    g = _between(t0, t1, 0.5)
    if not g or not g.get("serving_mixed_steps_total"):
        return None
    return g.get("serving_chunk_rows_total", 0) / g["serving_mixed_steps_total"]


# -- the whole step ---------------------------------------------------------------

def step_cost(cfg: dict, rows: float, attended: float, read: float,
              pairs: float, weight_bytes: int = 2) -> dict:
    """Bytes and operations ONE compiled step must move and do, from
    shapes: `rows` token rows (decode and chunk rows alike), `attended` the
    sum of the contexts its rows attend, `read` the cached tokens it must
    fetch (a decode row its context; a chunk's rows share ONE walk of their
    slot's pages, so a chunk its context once), `pairs` the routed pairs an
    expert draws in a layer's call.  Bytes: the weights read once (the
    routed experts that drew a pair, latent_moe.experts_hit), the
    embedding's rows, the latent rows of every layer.  Operations: two a
    weight a row (4 of 64 experts a row; the head on the `slots` rows a
    step samples at most, whatever the program computes), latent
    attention's absorbed form.  The stream passes are in neither: their
    n (n + 1) multiply-adds a column are VPU work, and their bytes (0.84 GB
    a step of 1,088 rows) moved faster than the HBM's rate in every run of
    this cell (PERF.md section 6, PR 57), so they are no part of what the
    HBM had to give; without them the count stays a lower bound wherever
    the streams live.  Linear in `rows`, `attended` and `read` but for the
    experts hit and the sampled rows."""
    n = cfg["num_hidden_layers"]
    p = stage_params(cfg)
    sparse = n - min(cfg["first_k_dense_replace"], n)
    held = p.get("routed_experts", 0)
    head = p["embedding_head"] // 2
    static = p["total"] - held - head                 # the embedding: rows
    bytes_ = (static + held * latent_moe.experts_hit(pairs)) * weight_bytes \
        + rows * cfg["hidden_size"] * weight_bytes \
        + n * latent_moe.latent_attention_cost(cfg, read, rows)["bytes"]
    blocks = static - head \
        + sparse * cfg["num_experts_per_tok"] * expert_params(cfg)
    sampled = min(rows, cfg["server_flags"]["slots"])
    flops = 2.0 * blocks * rows + 2.0 * head * sampled \
        + n * latent_moe.latent_attention_cost(cfg, attended, rows)["flops"]
    return {"bytes": float(bytes_), "flops": float(flops)}


SLICE_COUNTERS = (
    "serving_mhc_calls_total", "serving_kv_rows_total",
    "serving_kv_tokens_attended_total", "serving_kv_tokens_fetched_total")


def slice_cost(ctx, steps_traced: float):
    """Bytes and operations the `steps_traced` steps of the traced slice
    must move and do, from the engine's counters and nothing assumed: the
    steps (`serving_mhc_calls_total` over two passes a layer), of which
    mixed (`serving_mixed_steps_total`); the rows that carried a token
    (`serving_kv_rows_total` less `serving_step_pad_rows_total`; a decode
    step's idle slots stay in, under 1% at a closed loop's occupancy), of
    which prompt rows (`serving_chunk_rows_total`); the contexts the rows
    attended and the cached tokens the kernel fetched for them, a tile's
    shared walk once (`serving_kv_tokens_attended_total` less a token a
    padding row, `serving_kv_tokens_fetched_total`: what THIS kernel's
    tiles walk — a wider tile fetches less, and the least falls with it).
    A step's weights and products are `step_cost`'s at each kind's rows —
    decode rows spread evenly over the steps, the prompt rows over the
    mixed ones — and the contexts' share is added as a whole (`step_cost`
    is linear in them: the counters do not split contexts by kind, and
    need not).  **The counters' stretch is `trace_span`, which the harness
    closes AFTER the profiler has stopped and written its trace (tens of
    seconds past the 12 s the trace holds: 647 steps counted where the
    trace held 256, my chip run, PR 57)**, so every total is scaled by
    `steps_traced` over the steps counted: the slice's steps at the
    stretch's mean step, all of one steady window.  None where the program
    counted none of this."""
    g = _growth(ctx)
    if not g or not steps_traced or not all(g.get(k) for k in SLICE_COUNTERS):
        return None
    cfg = ctx.cfg
    layers = cfg["num_hidden_layers"]
    steps = g["serving_mhc_calls_total"] / (2 * layers)
    mixed = g.get("serving_mixed_steps_total", 0)
    pad = g.get("serving_step_pad_rows_total", 0)
    chunk = g.get("serving_chunk_rows_total", 0)
    decode_rows = (g["serving_kv_rows_total"] - pad - chunk) / steps
    per_row = cfg["num_experts_per_tok"] / cfg["n_routed_experts"]
    kinds = {"decode": (steps - mixed, decode_rows)}
    if mixed:
        kinds["mixed"] = (mixed, decode_rows + chunk / mixed)
    total = {"bytes": 0.0, "flops": 0.0}
    for count, rows in kinds.values():
        c = step_cost(cfg, rows, 0.0, 0.0, rows * per_row)
        for k in total:
            total[k] += count * c[k]
    attended = g["serving_kv_tokens_attended_total"] - pad
    fetched = g["serving_kv_tokens_fetched_total"]
    total["flops"] += layers * \
        latent_moe.latent_attention_cost(cfg, attended, 0)["flops"]
    total["bytes"] += layers * \
        latent_moe.latent_attention_cost(cfg, fetched, 0)["bytes"]
    scale = steps_traced / steps
    return {"bytes": scale * total["bytes"], "flops": scale * total["flops"],
            "steps_counted": steps, "mixed_share": mixed / steps,
            "decode_rows": decode_rows,
            "chunk_rows": chunk / mixed if mixed else 0.0,
            "attended": attended / steps, "fetched": fetched / steps}
