"""Parameters, residency, operations and bytes of a model whose attention
layers are of two kinds — full layers that hold every token of a slot, and
sliding-window layers that hold a ring of window + step pages — beside a
mixture of experts all of whom are held, from shapes alone (the functions a
roofline share is computed from are kept with the benchmark).  `cfg` is a
configuration file of the laguna family as benchmark/configs/ holds it:
HF-named keys, the three per-layer lists whole and read up to the depth,
and `server_flags`.  A layer's heads are `head_dim` wide whatever
hidden_size / heads says, and their count is the layer's own
(`num_attention_heads_per_layer`)."""

from __future__ import annotations

import time

from benchmark.lib import latent_moe


def layers(cfg: dict) -> list:
    """[(kind, query heads, mlp kind)] of the layers held, from 0."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n],
                    cfg["mlp_layer_types"][:n]))


def kind_layers(cfg: dict) -> tuple[int, int]:
    """(full layers, window layers) at the configuration's depth."""
    kinds = [k for k, _, _ in layers(cfg)]
    return kinds.count("full_attention"), kinds.count("sliding_attention")


def sparse_layers(cfg: dict) -> int:
    return sum(1 for _, _, m in layers(cfg) if m == "sparse")


def attention_params(cfg: dict, heads: int) -> int:
    """One attention layer of `heads` query heads: q and o [d, heads x
    head_dim], k and v [d, H_kv x head_dim], the gate a head [d, heads]."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    gate = d * heads if cfg["gating"] else 0
    return 2 * d * heads * dh + 2 * d * cfg["num_key_value_heads"] * dh + gate


def expert_params(cfg: dict) -> int:
    """One routed SwiGLU expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_router_params(cfg: dict) -> int:
    """A sparse layer's shared expert and router matrix."""
    d = cfg["hidden_size"]
    return 3 * d * cfg["shared_expert_intermediate_size"] + \
        d * cfg["num_experts"]


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def weight_params(cfg: dict) -> dict:
    """Parameters held, by part (the configuration file's table)."""
    d = cfg["hidden_size"]
    ls = layers(cfg)
    n_sparse = sparse_layers(cfg)
    parts = {
        "full_attention": sum(attention_params(cfg, h) for k, h, _ in ls
                              if k == "full_attention"),
        "window_attention": sum(attention_params(cfg, h) for k, h, _ in ls
                                if k == "sliding_attention"),
        "routed_experts": n_sparse * cfg["num_experts"] * expert_params(cfg),
        "shared_router": n_sparse * shared_router_params(cfg),
        "dense_mlp": (len(ls) - n_sparse) * dense_mlp_params(cfg),
        "norms": (2 * len(ls) + 1) * d,
        "embedding_head": 2 * d * cfg["vocab_size"],
    }
    parts["total"] = sum(parts.values())
    return parts


def published_params(cfg: dict) -> dict:
    """The published model's parameters, all and active a token, at the
    file's `published` depth — what the catalog's "33.4B-A3B" is held to."""
    pub = dict(cfg, num_hidden_layers=cfg["published"]["num_hidden_layers"])
    total = weight_params(pub)["total"]
    idle = sparse_layers(pub) * expert_params(pub) * (
        pub["num_experts"] - pub["num_experts_per_tok"])
    return {"total": total, "active": total - idle}


def kv_row_bytes(cfg: dict) -> int:
    """One token's K and V in one attention layer, of either kind."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        int(cfg.get("kv_dtype_bytes", 2))


def ring_pages(cfg: dict) -> int:
    """Pages a slot's ring holds in a window layer: window + the most rows
    a slot can get in a step, and a page more (neither end of that span
    sits on a page boundary) — serving/paged_kv.py:ring_pages_for."""
    f = cfg["server_flags"]
    return min(-(-(cfg["sliding_window"] + f["max_step_tokens"])
                 // f["page_size"]) + 1,
               -(-f["max_context"] // f["page_size"]))


def pool_bytes(cfg: dict) -> dict:
    """The K/V pools by kind: a full layer a whole context a slot and the
    trash page, a window layer a ring a slot and the trash page."""
    f = cfg["server_flags"]
    n_full, n_win = kind_layers(cfg)
    page = f["page_size"] * kv_row_bytes(cfg)
    full = 1 + f["slots"] * -(-f["max_context"] // f["page_size"])
    ring = 1 + f["slots"] * ring_pages(cfg)
    return {"full": n_full * full * page, "window": n_win * ring * page}


def one_table_pool_bytes(cfg: dict) -> int:
    """What the pools would take with every layer under one logical page
    table: a whole context a slot in the window layers too."""
    n_full, n_win = kind_layers(cfg)
    return pool_bytes(cfg)["full"] // n_full * (n_full + n_win)


def resident_bytes(cfg: dict, weight_bytes: int = 2) -> dict:
    """What the chip holds, by part: the weights in `param_dtype`, the
    full layers' pools, the window layers' rings."""
    pools = pool_bytes(cfg)
    parts = {"weights": weight_params(cfg)["total"] * weight_bytes,
             "full_pools": pools["full"], "window_pools": pools["window"]}
    parts["total"] = sum(parts.values())
    return parts


def _call_cost(cfg: dict, kind: str, tokens: float, rows: float) -> dict:
    """Operations and bytes of ONE paged-attention call of a layer of
    `kind` that reads `tokens` K and V rows for `rows` query rows: each
    read once; q in and the result out at that kind's heads."""
    heads = max(h for k, h, _ in layers(cfg) if k == kind)
    dh = cfg["head_dim"]
    return {"flops": 4.0 * heads * dh * tokens,
            "bytes": float(tokens * kv_row_bytes(cfg)
                           + rows * heads * dh * 2 * 2)}


def window_cost(cfg: dict, rows: float, mean_context: float) -> dict:
    """ONE windowed call (one window layer, one engine step):
    min(context, window) tokens a row."""
    return _call_cost(cfg, "sliding_attention",
                      rows * min(mean_context, cfg["sliding_window"]), rows)


def full_cost(cfg: dict, live_tokens: float, rows: float) -> dict:
    """ONE full layer's call: every live K and V row (`live_tokens`: the
    sum of the rows' contexts)."""
    return _call_cost(cfg, "full_attention", live_tokens, rows)


def _process_counters():
    """The program's ProcessCounters (paddle_tpu/obs/metrics.py), or None
    where it has none (a parent commit)."""
    try:
        from paddle_tpu.obs.metrics import process_counters
    except ImportError:
        return None
    return process_counters()


def window_counters() -> dict:
    """The engine's process-wide window-layer counters (cumulative over the
    process, warm-up and ramp included), or {} where the program has none
    or counted nothing."""
    pc = _process_counters()
    c = pc.snapshot() if pc is not None else {}
    return c if c.get("serving_window_steps_total") else {}


def rows_per_window_call(ctx):
    """Rows ONE windowed call carried in the traced slice, decode rows and
    chunk rows, padding left out: the growth of `serving_window_rows_total`
    (summed over the window layers) over `serving_window_steps_total` x
    window layers between the pump's checkpoints inside `trace_span`
    (ProcessCounters.between, the step clock's way).  None where the
    program keeps no such counters or no checkpoints cover the slice."""
    pc = _process_counters()
    span = ctx.counters.get("trace_span") or {}
    _, n_win = kind_layers(ctx.cfg)
    if not hasattr(pc, "between") or "t1" not in span or n_win <= 0:
        return None
    offset = time.perf_counter() - time.time()      # the checkpoints' clock
    try:
        growth, _ = pc.between(span["t0"] + offset, span["t1"] + offset,
                               max_edge=(span["t1"] - span["t0"]) / 4)
    except LookupError:
        return None
    steps = growth.get("serving_window_steps_total")
    if not steps:
        return None
    return growth.get("serving_window_rows_total", 0) / steps / n_win


def pages_recycled_per_step(cfg: dict):
    """Ring pages ONE window layer wrote over in ONE compiled step, on
    average, or None with nothing counted: with every slot decoding, a
    slot past its ring's first lap recycles a page every page_size steps."""
    c = window_counters()
    _, n_win = kind_layers(cfg)
    if not c or n_win <= 0:
        return None
    return c.get("serving_window_pages_recycled_total", 0) / \
        c["serving_window_steps_total"] / n_win


def pairs_per_expert(cfg: dict):
    """Mean routed pairs ONE expert draws in ONE sparse layer's call, from
    the engine's MoE counters, or None with nothing counted."""
    c = latent_moe.moe_counters()
    n = sparse_layers(cfg)
    if not c or n <= 0:
        return None
    return c["serving_moe_pairs_total"] / c["serving_moe_steps_total"] \
        / cfg["num_experts"] / n


def decode_step_bytes(cfg: dict, rows: float, live_tokens: float,
                      pairs: float, weight_bytes: int = 2) -> dict:
    """What ONE decode step must read from HBM, by part: the attention
    layers' matrices, the dense MLP, each sparse layer's router and shared
    expert, the experts that drew a pair, the head, the rows' embedding
    vectors, the full layers' pages to each row's context and the window
    layers' to the window."""
    d = cfg["hidden_size"]
    w = weight_params(cfg)
    n_full, n_win = kind_layers(cfg)
    mean = live_tokens / rows if rows else 0.0
    parts = {
        "attention": (w["full_attention"] + w["window_attention"])
        * weight_bytes,
        "dense_mlp": w["dense_mlp"] * weight_bytes,
        "shared_router": w["shared_router"] * weight_bytes,
        "routed_experts": w["routed_experts"] * weight_bytes
        * latent_moe.experts_hit(pairs),
        "head": d * cfg["vocab_size"] * weight_bytes,
        "embedding_rows": rows * d * weight_bytes,
        "full_pages": n_full * live_tokens * kv_row_bytes(cfg),
        "window_pages": n_win * rows * min(mean, cfg["sliding_window"])
        * kv_row_bytes(cfg),
    }
    parts["total"] = float(sum(parts.values()))
    return parts
