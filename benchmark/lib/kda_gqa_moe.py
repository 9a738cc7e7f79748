"""Parameters, residency, operations and bytes of a KDA / gated
grouped-query attention / mixture-of-experts model's decode step, from
shapes alone (the functions a roofline share is computed from are kept with
the benchmark).  `cfg` is a configuration file of the solar_open2 family as
benchmark/configs/ holds it: HF-named keys plus `experts_held` and
`server_flags`.  The attention's heads are `head_dim` wide whatever
hidden_size / heads says (64 x 128 = 8,192 beside a hidden size of 4,096),
so nothing here divides the hidden size by the heads."""

from __future__ import annotations

from benchmark.lib import hybrid_linear, latent_moe

kda_heads = hybrid_linear.kda_heads
kda_state_bytes = hybrid_linear.kda_state_bytes
kda_step_cost = hybrid_linear.kda_step_cost


def mixer_layers(cfg: dict) -> tuple[int, int]:
    """(KDA layers, gated GQA layers) at the configuration's depth: layer
    i, from 0, is GQA where `gqa_layers` lists it."""
    n = cfg["num_hidden_layers"]
    n_gqa = sum(1 for i in cfg["gqa_layers"] if i < n)
    return n - n_gqa, n_gqa


def kda_params(cfg: dict) -> int:
    """One KDA layer: hybrid_linear's matrices and taps, and the vectors it
    leaves out as noise (A_log, dt_bias, the head norm's scale) — the
    configuration file's table is exact."""
    la = cfg["linear_attn_config"]
    h = kda_heads(cfg)
    return hybrid_linear.kda_params(cfg) + h + h * la["head_dim"] + \
        la["head_dim"]


def gqa_params(cfg: dict) -> int:
    """One gated GQA layer: q, o and the gate [d, H head_dim]; k and v
    [d, H_kv head_dim]; no bias, no head norms."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    wide = cfg["num_attention_heads"] * dh
    gate = d * wide if cfg["use_gqa_gate"] else 0
    return 2 * d * wide + gate + 2 * d * cfg["num_key_value_heads"] * dh


def kv_row_bytes(cfg: dict) -> int:
    """One token's K and V in one GQA layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        int(cfg.get("kv_dtype_bytes", 2))


def conv_tail_bytes(cfg: dict) -> int:
    """One slot's tail in one KDA layer: taps - 1 inputs of q, k and v."""
    la = cfg["linear_attn_config"]
    return (la["short_conv_kernel_size"] - 1) * 3 * kda_heads(cfg) * \
        la["head_dim"] * int(cfg.get("kv_dtype_bytes", 2))


def expert_params(cfg: dict) -> int:
    """One SwiGLU expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router's matrix and its selection bias."""
    return (cfg["hidden_size"] + 1) * cfg["n_routed_experts"]


def weight_params(cfg: dict) -> dict:
    """Parameters held, by part (the configuration file's table)."""
    n = cfg["num_hidden_layers"]
    n_kda, n_gqa = mixer_layers(cfg)
    d = cfg["hidden_size"]
    parts = {
        "kda": n_kda * kda_params(cfg),
        "gqa": n_gqa * gqa_params(cfg),
        "routed_experts": n * cfg["experts_held"] * expert_params(cfg),
        "router_shared_norms": n * (
            router_params(cfg)
            + cfg["n_shared_experts"] * expert_params(cfg) + 2 * d) + d,
        "embedding_head": 2 * d * cfg["vocab_size"],
    }
    parts["total"] = sum(parts.values())
    return parts


def published_params(cfg: dict) -> dict:
    """The published model's parameters, all and active a token, from the
    file's `published` depth, experts and vocabulary — what the catalog's
    "250B-A15B" is held against."""
    pub = dict(cfg, experts_held=cfg["n_routed_experts"],
               **{k: cfg["published"][k]
                  for k in ("num_hidden_layers", "vocab_size")})
    total = weight_params(pub)["total"]
    idle = pub["num_hidden_layers"] * expert_params(pub) * (
        pub["n_routed_experts"] - pub["num_experts_per_tok"])
    return {"total": total, "active": total - idle}


def pool_tokens(cfg: dict) -> int:
    """Tokens the K/V pool holds: a full context a slot, and the trash
    page (serving/paged_kv.py)."""
    f = cfg["server_flags"]
    return f["slots"] * f["max_context"] + f["page_size"]


def resident_bytes(cfg: dict, weight_bytes: int = 2) -> dict:
    """What the chip holds, by part: the weights in `param_dtype`, the KDA
    state (float32) and tails of slots + 1 rows (the trash row), the K/V
    pool."""
    n_kda, n_gqa = mixer_layers(cfg)
    rows = cfg["server_flags"]["slots"] + 1
    parts = {
        "weights": weight_params(cfg)["total"] * weight_bytes,
        "kda_state": n_kda * rows * kda_state_bytes(cfg),
        "conv_tails": n_kda * rows * conv_tail_bytes(cfg),
        "kv_pool": n_gqa * kv_row_bytes(cfg) * pool_tokens(cfg),
    }
    parts["total"] = sum(parts.values())
    return parts


def paged_cost(cfg: dict, live_tokens: float, rows: float) -> dict:
    """Operations and bytes ONE paged-attention call (one GQA layer, one
    engine step) needs: every live K and V row read once, each query row
    against its own context (`live_tokens` is the sum of the rows'
    contexts); q in and the result out, `head_dim` a head."""
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    return {"flops": 4.0 * h * dh * live_tokens,
            "bytes": float(live_tokens * kv_row_bytes(cfg)
                           + rows * h * dh * 2 * 2)}


def updates_per_step(cfg: dict):
    """Slot states ONE KDA layer read and wrote in ONE compiled step, on
    average, from the engine's recurrent counters, or None with nothing
    counted."""
    c = hybrid_linear.recurrent_counters()
    n_kda, _ = mixer_layers(cfg)
    if not c or n_kda <= 0:
        return None
    return c["serving_recurrent_slot_updates_total"] / \
        c["serving_recurrent_steps_total"] / n_kda


def deployment_pairs_per_expert(cfg: dict, rows: float) -> dict:
    """Routed pairs an expert a step at `rows` rows a chip: here, where one
    chip's rows meet every scored expert's odds, and in the deployment,
    where an expert draws from every chip's rows."""
    here = rows * cfg["num_experts_per_tok"] / cfg["n_routed_experts"]
    return {"here": here,
            "deployment": here * cfg["deployment"]["chips_sharing_a_layer"]}


def decode_step_bytes(cfg: dict, rows: float, live_tokens: float,
                      pairs_per_expert: float, state_rows: float,
                      weight_bytes: int = 2) -> dict:
    """What ONE decode step must move through HBM, by part: the KDA layers'
    matrices and the state of the rows that advanced (read + write), the
    GQA layers' matrices and live K/V rows, each layer's router and shared
    expert, the held experts that drew a pair, the head, the rows'
    embedding vectors."""
    d = cfg["hidden_size"]
    n = cfg["num_hidden_layers"]
    n_kda, n_gqa = mixer_layers(cfg)
    parts = {
        "kda_state": n_kda * 2.0 * kda_state_bytes(cfg) * state_rows,
        "kda_matrices": n_kda * kda_params(cfg) * weight_bytes,
        "gqa_matrices": n_gqa * gqa_params(cfg) * weight_bytes,
        "kv_rows": n_gqa * live_tokens * kv_row_bytes(cfg),
        "router": n * router_params(cfg) * weight_bytes,
        "shared_experts": n * cfg["n_shared_experts"] * expert_params(cfg)
        * weight_bytes,
        "routed_experts": n * cfg["experts_held"] * expert_params(cfg)
        * weight_bytes * latent_moe.experts_hit(pairs_per_expert),
        "head": d * cfg["vocab_size"] * weight_bytes,
        "embedding_rows": rows * d * weight_bytes,
    }
    parts["total"] = float(sum(parts.values()))
    return parts
