"""Operations and bytes of the latent-attention / mixture-of-experts decode
step, from shapes alone (the functions a roofline share is computed from
are kept with the benchmark).  `cfg` is a configuration file of the
deepseek_v3 family as benchmark/configs/ holds it: HF-named keys plus
`experts_held`."""

from __future__ import annotations

import math


def attention_params(cfg: dict) -> int:
    """One MLA layer's matrices: q_a, q_b, kv_a, kv_b, o (norm scales are
    noise)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * qr + qr * h * (nope + rope) + d * (kr + rope)
            + kr * h * (nope + vd) + h * vd * d)


def latent_row_bytes(cfg: dict) -> int:
    """One token's cache row in one layer: [c_kv, k_pe]."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * \
        int(cfg.get("kv_dtype_bytes", 2))


def experts_hit(pairs_per_expert: float) -> float:
    """Expected share of the held experts that drew at least one pair in a
    layer's call, from the MEASURED mean pairs an expert a call, taking the
    draws as independent (Poisson): 1 - exp(-mean)."""
    return 1.0 - math.exp(-max(0.0, float(pairs_per_expert)))


def moe_counters() -> dict:
    """The engine's process-wide MoE counters (paddle_tpu/obs/metrics.py
    process_counters: cumulative over the process, warm-up and ramp
    included, all drawn from the cell's one mix), or {} where the program
    has none (a parent commit) or counted nothing."""
    try:
        from paddle_tpu.obs.metrics import process_counters
    except ImportError:
        return {}
    c = process_counters().snapshot()
    return c if c.get("serving_moe_steps_total") else {}


def pairs_per_expert(cfg: dict):
    """Mean routed pairs ONE held expert draws in ONE MoE layer's call, or
    None with nothing counted."""
    c = moe_counters()
    layers = cfg["num_hidden_layers"] - min(cfg["first_k_dense_replace"],
                                            cfg["num_hidden_layers"])
    if not c or layers <= 0:
        return None
    return c["serving_moe_pairs_total"] / c["serving_moe_steps_total"] \
        / cfg["experts_held"] / layers


def decode_step_bytes(cfg: dict, rows: float, live_tokens: float,
                      pairs_per_expert: float, weight_bytes: int = 2) -> dict:
    """What ONE decode step must read from HBM, by part: every layer's
    attention matrices, the dense layers' MLP, each expert layer's router
    and shared expert, the held experts that drew a pair, the head, the
    rows' embedding vectors, and the live latent rows of every layer."""
    d = cfg["hidden_size"]
    n = cfg["num_hidden_layers"]
    n_dense = min(cfg["first_k_dense_replace"], n)
    n_moe = n - n_dense
    fm = cfg["moe_intermediate_size"]
    expert = 3 * d * fm
    parts = {
        "attention": n * attention_params(cfg) * weight_bytes,
        "dense_mlp": n_dense * 3 * d * cfg["intermediate_size"] * weight_bytes,
        "router": n_moe * d * cfg["n_routed_experts"] * weight_bytes,
        "shared_experts": n_moe * cfg["n_shared_experts"] * expert
        * weight_bytes,
        "routed_experts": n_moe * cfg["experts_held"] * expert * weight_bytes
        * experts_hit(pairs_per_expert),
        "head": d * cfg["vocab_size"] * weight_bytes,
        "embedding_rows": rows * d * weight_bytes,
        "latent_rows": n * live_tokens * latent_row_bytes(cfg),
    }
    parts["total"] = float(sum(parts.values()))
    return parts


def latent_attention_cost(cfg: dict, live_tokens: float, rows: float) -> dict:
    """Operations and bytes ONE latent paged-attention call (one layer, one
    engine step) needs: every live latent row read once; each of the H
    query heads scores it over its full width and weighs its first
    kv_lora_rank columns (`live_tokens` is the sum of the rows' contexts)."""
    h = cfg["num_attention_heads"]
    kr, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    io = rows * h * ((kr + rope) + kr) * 2          # q in, weighted rows out
    return {"flops": 2.0 * h * ((kr + rope) + kr) * live_tokens,
            "bytes": float(live_tokens * latent_row_bytes(cfg) + io)}
