"""Operations and bytes of a gated short-convolution / grouped-query
attention / mixture-of-experts decode step, from shapes alone (the
functions a roofline share is computed from are kept with the benchmark).
`cfg` is a configuration file of the lfm2_moe family as benchmark/configs/
holds it: HF-named keys plus `first_layer`, `experts_held` and the aliases
the shared readers read."""

from __future__ import annotations

from benchmark.lib import hybrid_linear, latent_moe


def mixer_layers(cfg: dict) -> tuple[int, int]:
    """(conv layers, attention layers) among the layers held: the
    published `layer_types` from `first_layer` (counting from 1) on."""
    first = int(cfg.get("first_layer", 1)) - 1
    kinds = cfg["layer_types"][first:first + cfg["num_hidden_layers"]]
    n_attn = sum(k == "full_attention" for k in kinds)
    return len(kinds) - n_attn, n_attn


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def conv_params(cfg: dict) -> int:
    """One conv mixer: the in matrix [d, 3d], the taps, the out matrix."""
    d = cfg["hidden_size"]
    return 3 * d * d + cfg["conv_L_cache"] * d + d * d


def conv_tail_bytes(cfg: dict) -> int:
    """One slot's tail in one conv layer: taps - 1 rows of d."""
    return (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * \
        int(cfg.get("kv_dtype_bytes", 2))


def attention_params(cfg: dict) -> int:
    """One attention mixer: q, k, v, o and the two head norms."""
    d, dh = cfg["hidden_size"], head_dim(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * dh + 2 * d * hkv * dh + h * dh * d + 2 * dh


def kv_row_bytes(cfg: dict) -> int:
    """One token's K and V in one attention layer at the pool's stored
    width: narrow heads are packed whole lane tiles, so the stored row is
    the heads' own h_kv x head_dim."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * \
        int(cfg.get("kv_dtype_bytes", 2))


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_layers(cfg: dict) -> int:
    n = cfg["num_hidden_layers"]
    return n - min(cfg["num_dense_layers"], n)


def expert_flops(cfg: dict, rows: float) -> dict:
    """Operations of the expert layers in one step of `rows` rows: what the
    routed pairs need, and what a formulation that multiplies every row by
    every held expert does (parallel/moe.py's dense combine)."""
    per = 2.0 * expert_params(cfg) * moe_layers(cfg)
    return {"routed_pairs": per * rows * cfg["num_experts_per_tok"]
            * cfg["experts_held"] / cfg["num_experts"],
            "rows_x_held": per * rows * cfg["experts_held"]}


def updates_per_step(cfg: dict):
    """Conv tails ONE conv layer wrote in ONE compiled step, on average
    (decode rows that really advanced, plus a prompt chunk's segment each),
    from the engine's recurrent counters, or None with nothing counted."""
    c = hybrid_linear.recurrent_counters()
    n_conv, _ = mixer_layers(cfg)
    if not c or n_conv <= 0:
        return None
    return c["serving_recurrent_slot_updates_total"] / \
        c["serving_recurrent_steps_total"] / n_conv


def decode_step_bytes(cfg: dict, rows: float, live_tokens: float,
                      pairs_per_expert: float, tail_rows: float,
                      weight_bytes: int = 2) -> dict:
    """What ONE decode step must move through HBM, by part: the conv
    mixers' matrices and the tails of the rows that advanced (read +
    write), the attention mixers' matrices and live K/V rows, the dense
    layers' MLP, each expert layer's router and the held experts that drew
    a pair, the head, the rows' embedding vectors."""
    d = cfg["hidden_size"]
    n = cfg["num_hidden_layers"]
    n_conv, n_attn = mixer_layers(cfg)
    n_moe = moe_layers(cfg)
    parts = {
        "conv_matrices": n_conv * conv_params(cfg) * weight_bytes,
        "conv_tails": n_conv * 2.0 * conv_tail_bytes(cfg) * tail_rows,
        "attention_matrices": n_attn * attention_params(cfg) * weight_bytes,
        "kv_rows": n_attn * live_tokens * kv_row_bytes(cfg),
        "dense_mlp": (n - n_moe) * 3 * d * cfg["intermediate_size"]
        * weight_bytes,
        "router": n_moe * d * cfg["num_experts"] * weight_bytes,
        "routed_experts": n_moe * cfg["experts_held"] * expert_params(cfg)
        * weight_bytes * latent_moe.experts_hit(pairs_per_expert),
        "head": d * cfg["vocab_size"] * weight_bytes,
        "embedding_rows": rows * d * weight_bytes,
    }
    parts["total"] = float(sum(parts.values()))
    return parts
