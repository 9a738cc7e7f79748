"""Operations and bytes of a Mamba-2 / grouped-query attention /
mixture-of-experts decode step, from shapes alone and by the letters of the
pattern (the functions a roofline share is computed from are kept with the
benchmark).  `cfg` is a configuration file of the nemotron_h family as
benchmark/configs/ holds it: HF-named keys plus `first_layer`,
`experts_held` and the aliases the shared readers read.  Every block is ONE
mixer: M (Mamba-2), E (experts) or * (attention)."""

from __future__ import annotations

from benchmark.lib import hybrid_linear, latent_moe


def layer_letters(cfg: dict) -> str:
    """The letters of the layers held: the published pattern from
    `first_layer` (counting from 1) on."""
    first = int(cfg.get("first_layer", 1)) - 1
    return cfg["hybrid_override_pattern"][
        first:first + cfg["num_hidden_layers"]]


def layer_counts(cfg: dict) -> dict:
    """{"M": Mamba-2 layers, "E": expert layers, "*": attention layers}."""
    letters = layer_letters(cfg)
    return {k: letters.count(k) for k in "ME*"}


def mamba_sizes(cfg: dict) -> dict:
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return {"d_in": H * P, "conv": H * P + 2 * gn,
            "in": 2 * H * P + 2 * gn + H}


def mamba_params(cfg: dict) -> int:
    """One Mamba-2 mixer: the in matrix (z, xBC, dt), the out matrix, the
    taps and their bias, A_log, D, dt_bias, the gated norm's scale."""
    d, z = cfg["hidden_size"], mamba_sizes(cfg)
    return d * z["in"] + z["d_in"] * d + (cfg["conv_kernel"] + 1) * z["conv"] \
        + 3 * cfg["mamba_num_heads"] + z["d_in"]


def ssm_state_bytes(cfg: dict) -> int:
    """One slot's recurrent state in one Mamba-2 layer: H x P x N float32."""
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * \
        cfg["ssm_state_size"] * 4


def conv_tail_bytes(cfg: dict) -> int:
    """One slot's tail in one Mamba-2 layer: taps - 1 rows of x, B, C."""
    return (cfg["conv_kernel"] - 1) * mamba_sizes(cfg)["conv"] * \
        int(cfg.get("kv_dtype_bytes", 2))


def attention_params(cfg: dict) -> int:
    """One attention mixer: q, k, v, o (no bias, no head norms)."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * h * dh + 2 * d * hkv * dh


def kv_row_bytes(cfg: dict) -> int:
    """One token's K and V in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        int(cfg.get("kv_dtype_bytes", 2))


def expert_params(cfg: dict) -> int:
    """One routed expert: up and down, no gate, no bias."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router's matrix and its selection bias."""
    return (cfg["hidden_size"] + 1) * cfg["n_routed_experts"]


def weight_params(cfg: dict) -> dict:
    """Parameters held, by part (the configuration file's table)."""
    n = layer_counts(cfg)
    d = cfg["hidden_size"]
    parts = {
        "mamba": n["M"] * mamba_params(cfg),
        "attention": n["*"] * attention_params(cfg),
        "routed_experts": n["E"] * cfg["experts_held"] * expert_params(cfg),
        "shared_and_router": n["E"] * (shared_expert_params(cfg)
                                       + router_params(cfg)),
        "embedding_head_norms": 2 * d * cfg["vocab_size"]
        + (cfg["num_hidden_layers"] + 1) * d,
    }
    parts["total"] = sum(parts.values())
    return parts


def expert_flops(cfg: dict, rows: float) -> dict:
    """Operations of the expert layers' ROUTED part in one step of `rows`
    rows: what the routed pairs need, and what a formulation that
    multiplies every row by every held expert does (parallel/moe.py's dense
    combine)."""
    per = 2.0 * expert_params(cfg) * layer_counts(cfg)["E"]
    return {"routed_pairs": per * rows * cfg["num_experts_per_tok"]
            * cfg["experts_held"] / cfg["n_routed_experts"],
            "rows_x_held": per * rows * cfg["experts_held"]}


def ssd_step_cost(cfg: dict, live_rows: float) -> dict:
    """Operations and bytes ONE `ssd_step` call (one layer, one engine
    step) needs: each live row's state read once and written once; per
    state element a decay, a product and an add for the update, a product
    and an add for S C — 5 (the per-row vectors, under 1% of the state's
    bytes, are left out: errs low)."""
    elems = ssm_state_bytes(cfg) // 4
    return {"flops": 5.0 * elems * live_rows,
            "bytes": 2.0 * ssm_state_bytes(cfg) * live_rows}


def updates_per_step(cfg: dict):
    """Slot states ONE Mamba-2 layer read and wrote in ONE compiled step,
    on average (decode rows that really advanced, plus a prompt chunk's
    segment each), from the engine's recurrent counters, or None with
    nothing counted."""
    c = hybrid_linear.recurrent_counters()
    n = layer_counts(cfg)["M"]
    if not c or n <= 0:
        return None
    return c["serving_recurrent_slot_updates_total"] / \
        c["serving_recurrent_steps_total"] / n


def decode_step_bytes(cfg: dict, rows: float, live_tokens: float,
                      pairs_per_expert: float, state_rows: float,
                      weight_bytes: int = 2) -> dict:
    """What ONE decode step must move through HBM, by part: the Mamba-2
    mixers' matrices, the state and the tail of the rows that advanced
    (read + write), the attention mixers' matrices and live K/V rows, each
    expert layer's router and shared expert and the held experts that drew
    a pair, the head, the rows' embedding vectors."""
    d = cfg["hidden_size"]
    n = layer_counts(cfg)
    parts = {
        "ssm_state": n["M"] * 2.0 * ssm_state_bytes(cfg) * state_rows,
        "conv_tails": n["M"] * 2.0 * conv_tail_bytes(cfg) * state_rows,
        "mamba_matrices": n["M"] * mamba_params(cfg) * weight_bytes,
        "attention_matrices": n["*"] * attention_params(cfg) * weight_bytes,
        "kv_rows": n["*"] * live_tokens * kv_row_bytes(cfg),
        "router": n["E"] * router_params(cfg) * weight_bytes,
        "shared_experts": n["E"] * shared_expert_params(cfg) * weight_bytes,
        "routed_experts": n["E"] * cfg["experts_held"] * expert_params(cfg)
        * weight_bytes * latent_moe.experts_hit(pairs_per_expert),
        "head": d * cfg["vocab_size"] * weight_bytes,
        "embedding_rows": rows * d * weight_bytes,
    }
    parts["total"] = float(sum(parts.values()))
    return parts
