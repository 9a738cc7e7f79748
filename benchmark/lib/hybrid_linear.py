"""Operations and bytes of a hybrid linear-attention / latent-attention /
mixture-of-experts decode step, from shapes alone (the functions a roofline
share is computed from are kept with the benchmark).  `cfg` is a
configuration file of the kimi_linear family as benchmark/configs/ holds
it: HF-named keys plus `experts_held`."""

from __future__ import annotations

from benchmark.lib import latent_moe


def mixer_layers(cfg: dict) -> tuple[int, int]:
    """(KDA layers, full-attention layers) at the configuration's depth:
    the published list cut to the depth, the last layer where it holds
    none."""
    n = cfg["num_hidden_layers"]
    full = [i for i in cfg["linear_attn_config"]["full_attn_layers"] if i <= n]
    n_full = len(full) or 1
    return n - n_full, n_full


def kda_heads(cfg: dict) -> int:
    return min(cfg["linear_attn_config"]["num_heads"],
               cfg["num_attention_heads"])


def kda_state_bytes(cfg: dict) -> int:
    """One slot's recurrent state in one KDA layer: H x dk x dk float32."""
    dk = cfg["linear_attn_config"]["head_dim"]
    return kda_heads(cfg) * dk * dk * 4


def kda_params(cfg: dict) -> int:
    """One KDA layer's matrices: q, k, v, o; the decay's and the gate's
    low-rank pairs; beta; the three depthwise convolutions."""
    d, la = cfg["hidden_size"], cfg["linear_attn_config"]
    w = kda_heads(cfg) * la["head_dim"]
    r = la["head_dim"]
    return 4 * d * w + 2 * (d * r + r * w) + d * kda_heads(cfg) + \
        3 * la["short_conv_kernel_size"] * w


def mla_params(cfg: dict) -> int:
    """One NoPE MLA layer's matrices: q (one matrix), kv_a, kv_b, o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd, kr = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"], cfg["kv_lora_rank"])
    return d * h * (nope + rope) + d * (kr + rope) + kr * h * (nope + vd) + \
        h * vd * d


def kda_step_cost(cfg: dict, live_rows: float) -> dict:
    """Operations and bytes ONE `kda_step` call (one layer, one engine
    step) needs: each live row's state read once and written once; per
    state element a decay, a product and an add for S'^T k, a product and
    an add for the update, a product and an add for S^T q — 6 (the
    per-row vectors, 2% of the state's bytes, are left out: errs low)."""
    dk = cfg["linear_attn_config"]["head_dim"]
    elems = kda_heads(cfg) * dk * dk
    return {"flops": 6.0 * elems * live_rows,
            "bytes": 2.0 * kda_state_bytes(cfg) * live_rows}


def recurrent_counters() -> dict:
    """The engine's process-wide recurrent-state counters
    (paddle_tpu/obs/metrics.py process_counters: cumulative over the
    process, warm-up and ramp included), or {} where the program has none
    (a parent commit) or counted nothing."""
    try:
        from paddle_tpu.obs.metrics import process_counters
    except ImportError:
        return {}
    c = process_counters().snapshot()
    return c if c.get("serving_recurrent_steps_total") else {}


def updates_per_step(cfg: dict):
    """Slot states ONE KDA layer read and wrote in ONE compiled step, on
    average (decode rows that really advanced, plus a prompt chunk's
    segment each), or None with nothing counted."""
    c = recurrent_counters()
    n_kda, _ = mixer_layers(cfg)
    if not c or n_kda <= 0:
        return None
    return c["serving_recurrent_slot_updates_total"] / \
        c["serving_recurrent_steps_total"] / n_kda


def decode_step_bytes(cfg: dict, rows: float, live_tokens: float,
                      pairs_per_expert: float, state_rows: float,
                      weight_bytes: int = 2) -> dict:
    """What ONE decode step must move through HBM, by part: the KDA
    layers' matrices and their live rows' state (read + write), the MLA
    layers' matrices and live latent rows, the dense layer's MLP, each
    expert layer's router and shared expert, the held experts that drew a
    pair, the head, the rows' embedding vectors."""
    d = cfg["hidden_size"]
    n = cfg["num_hidden_layers"]
    n_kda, n_full = mixer_layers(cfg)
    n_dense = min(cfg["first_k_dense_replace"], n)
    n_moe = n - n_dense
    expert = 3 * d * cfg["moe_intermediate_size"]
    parts = {
        "kda_state": n_kda * 2.0 * kda_state_bytes(cfg) * state_rows,
        "kda_matrices": n_kda * kda_params(cfg) * weight_bytes,
        "mla_matrices": n_full * mla_params(cfg) * weight_bytes,
        "latent_rows": n_full * live_tokens * latent_moe.latent_row_bytes(cfg),
        "dense_mlp": n_dense * 3 * d * cfg["intermediate_size"] * weight_bytes,
        "router": n_moe * d * cfg["num_experts"] * weight_bytes,
        "shared_experts": n_moe * cfg["num_shared_experts"] * expert
        * weight_bytes,
        "routed_experts": n_moe * cfg["experts_held"] * expert * weight_bytes
        * latent_moe.experts_hit(pairs_per_expert),
        "head": d * cfg["vocab_size"] * weight_bytes,
        "embedding_rows": rows * d * weight_bytes,
    }
    parts["total"] = float(sum(parts.values()))
    return parts
