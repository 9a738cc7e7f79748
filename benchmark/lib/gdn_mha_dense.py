"""Parameters, residency, operations and bytes of a Gated DeltaNet /
full-attention / dense-MLP model's steps, from shapes alone (the functions a
roofline share is computed from are kept with the benchmark).  `cfg` is a
configuration file of the olmo_hybrid family as benchmark/configs/ holds it:
HF-named keys plus `server_flags`.  The attention's heads are hidden_size /
heads wide (30 x 128 = 3,840) on as many KV heads; the linear layers' heads
are `linear_key_head_dim` x `linear_value_head_dim` (96 x 192), one decay a
head."""

from __future__ import annotations

from benchmark.lib import mhc_latent_moe

CHUNK = 64      # rows a chunk of the chunkwise form (ops/kda.py)


def mixer_layers(cfg: dict) -> tuple[int, int]:
    """(linear layers, full layers) at the configuration's depth."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    n_full = sum(1 for t in kinds if t == "full_attention")
    return len(kinds) - n_full, n_full


def gdn_dims(cfg: dict) -> tuple[int, int, int]:
    """(heads, dk, dv) of a linear layer's state."""
    return (min(cfg["linear_num_key_heads"], cfg["num_attention_heads"]),
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def gdn_params(cfg: dict) -> int:
    """One Gated DeltaNet mixer: q, k [d, H dk]; v, the gate z [d, H dv]; o
    [H dv, d]; the decay's and beta's projections [d, H]; the taps over
    H (2 dk + dv) channels; A_log, dt_bias [H]; the head norm's scale."""
    d = cfg["hidden_size"]
    h, dk, dv = gdn_dims(cfg)
    return 2 * d * h * dk + 3 * d * h * dv + 2 * d * h + \
        cfg["linear_conv_kernel_dim"] * h * (2 * dk + dv) + 2 * h + dv


def attn_params(cfg: dict) -> int:
    """One full-attention mixer: q, o [d, d]; k, v [d, H_kv head]; the
    QK-norm's two scales over the whole projections."""
    d = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    norms = (d + kv) if cfg["use_qk_norm"] and cfg["qk_norm_whole"] else \
        2 * (d // cfg["num_attention_heads"]) if cfg["use_qk_norm"] else 0
    return 2 * d * d + 2 * d * kv + norms


def mlp_params(cfg: dict) -> int:
    """The dense SwiGLU and the block's two norms."""
    d = cfg["hidden_size"]
    return 3 * d * cfg["intermediate_size"] + 2 * d


def weight_params(cfg: dict) -> dict:
    """Parameters held, by part (the configuration file's table)."""
    n_gdn, n_full = mixer_layers(cfg)
    d = cfg["hidden_size"]
    parts = {
        "gdn_layers": n_gdn * (gdn_params(cfg) + mlp_params(cfg)),
        "full_layers": n_full * (attn_params(cfg) + mlp_params(cfg)),
        "embedding_head": 2 * d * cfg["vocab_size"] + d,
    }
    parts["total"] = sum(parts.values())
    return parts


def published_params(cfg: dict) -> dict:
    """The published model's parameters at the file's `published` depth —
    what the catalog's "7B" is held against (dense: all are active)."""
    pub = dict(cfg,
               num_hidden_layers=cfg["published"]["num_hidden_layers"])
    total = weight_params(pub)["total"]
    return {"total": total, "active": total}


def state_bytes(cfg: dict) -> int:
    """One slot's float32 state in one linear layer, as published:
    [H, dk, dv] — 2,211,840 B at 30 x 96 x 192.  (The TPU lays a row of 192
    float32 out as two lane tiles: the pool HOLDS 256 / 192 of this.)"""
    h, dk, dv = gdn_dims(cfg)
    return h * dk * dv * 4


def state_bytes_held(cfg: dict) -> int:
    """`state_bytes` as the chip's (8, 128) tiles hold it."""
    h, dk, dv = gdn_dims(cfg)
    return h * -(-dk // 8) * 8 * -(-dv // 128) * 128 * 4


def conv_tail_bytes(cfg: dict) -> int:
    """One slot's tail in one linear layer: taps - 1 inputs of q, k and v."""
    h, dk, dv = gdn_dims(cfg)
    return (cfg["linear_conv_kernel_dim"] - 1) * h * (2 * dk + dv) * \
        int(cfg.get("kv_dtype_bytes", 2))


def kv_row_bytes(cfg: dict) -> int:
    """One token's K and V in one full layer."""
    return 2 * cfg["num_key_value_heads"] * \
        (cfg["hidden_size"] // cfg["num_attention_heads"]) * \
        int(cfg.get("kv_dtype_bytes", 2))


def kv_row_bytes_held(cfg: dict) -> int:
    """`kv_row_bytes` as the pool stores a token: more than 8 KV heads of
    128 lanes in whole tiles of 8 heads (30 as 32: the chip's bf16 tiles
    hold them so whatever the shape says; ops/pallas_paged.py
    `kv_row_shape`)."""
    h = cfg["num_key_value_heads"]
    held = -(-h // 8) * 8 if h > 8 else h
    return kv_row_bytes(cfg) // h * held


def pool_tokens(cfg: dict) -> int:
    """Tokens the K/V pool holds: a full context a slot, and the trash
    page (serving/paged_kv.py)."""
    f = cfg["server_flags"]
    return f["slots"] * f["max_context"] + f["page_size"]


def resident_bytes(cfg: dict, weight_bytes: int = 2) -> dict:
    """What the chip holds, by part: the weights in `param_dtype`, the
    linear layers' state (float32, as published) and tails of slots + 1
    rows (the trash row), the K/V pool — `total` as PUBLISHED shapes count
    them; `state_tile_padding` and `kv_tile_padding` are what the chip's
    tiles add (`state_bytes_held`, `kv_row_bytes_held`), `held` the sum the
    device reports."""
    n_gdn, n_full = mixer_layers(cfg)
    rows = cfg["server_flags"]["slots"] + 1
    parts = {
        "weights": weight_params(cfg)["total"] * weight_bytes,
        "gdn_state": n_gdn * rows * state_bytes(cfg),
        "conv_tails": n_gdn * rows * conv_tail_bytes(cfg),
        "kv_pool": n_full * kv_row_bytes(cfg) * pool_tokens(cfg),
    }
    parts["total"] = sum(parts.values())
    parts["state_tile_padding"] = n_gdn * rows * (
        state_bytes_held(cfg) - state_bytes(cfg))
    parts["kv_tile_padding"] = n_full * pool_tokens(cfg) * (
        kv_row_bytes_held(cfg) - kv_row_bytes(cfg))
    parts["held"] = parts["total"] + parts["state_tile_padding"] + \
        parts["kv_tile_padding"]
    return parts


# -- the kernels --------------------------------------------------------------------

def gdn_step_cost(cfg: dict, live_rows: float) -> dict:
    """Operations and bytes ONE `gdn_step` call (one layer, one engine
    step) needs: each live row's state read once and written once (the
    published bytes: tile padding is the program's, not the algorithm's);
    per state element a decay, a product and an add for S'^T k, a product
    and an add for the update, a product and an add for S^T q — 6 (the
    per-row vectors, 1% of the state's bytes, are left out: errs low)."""
    h, dk, dv = gdn_dims(cfg)
    return {"flops": 6.0 * h * dk * dv * live_rows,
            "bytes": 2.0 * state_bytes(cfg) * live_rows}


def gdn_seg_cost(cfg: dict, chunks: float, runs: float) -> dict:
    """Operations and bytes the `gdn_seg` calls of ONE layer need for
    `chunks` folded chunks in `runs` runs.  A chunk of C = 64 rows of one
    head: the pairwise exponents' product 2 C^3; (b k e^G | q e^G) S 4 C dk
    dv; (b k | q) k^T 4 C^2 dk; the solve's C^2 dv; (qk) u 2 C^2 dv; the
    state's 2 C dk dv — products at ONE pass each (the kernel runs them at
    full float32 precision, six bf16 passes: the program's choice).  Bytes:
    a chunk's q, k, v, g, beta in and o out, float32; a run's state read
    once and written once."""
    h, dk, dv = gdn_dims(cfg)
    c = CHUNK
    per_chunk = 2 * c ** 3 + 6 * c * dk * dv + 4 * c * c * dk + 3 * c * c * dv
    rows_bytes = 4 * c * (2 * dk + 2 * dv + 2)
    return {"flops": float(h * per_chunk * chunks),
            "bytes": float(h * rows_bytes * chunks
                           + 2 * state_bytes(cfg) * runs)}


def _step_tokens(g: dict) -> float:
    """Decode rows that advanced a state, a layer's worth."""
    return sum(v for k, v in g.items()
               if k.startswith("serving_recurrent_tokens_total")
               and 'kind="step"' in k)


def seg_counts(ctx):
    """{chunks `gdn_seg` folded, runs it ran, mixed steps} — a LAYER's
    worth each — in the traced slice's stretch of the engine's counters:
    `serving_recurrent_segment_chunks_total`, `serving_mixed_steps_total`,
    and for the runs the states moved that no decode row moved
    (`serving_recurrent_slot_updates_total` a layer less
    `serving_recurrent_tokens_total{kind="step"}`); or None where the
    program counted no chunk."""
    g = mhc_latent_moe._growth(ctx)
    if not g or not g.get("serving_recurrent_segment_chunks_total") \
            or not g.get("serving_mixed_steps_total"):
        return None
    n_gdn, _ = mixer_layers(ctx.cfg)
    runs = g.get("serving_recurrent_slot_updates_total", 0) / max(n_gdn, 1) \
        - _step_tokens(g)
    return {"chunks": g["serving_recurrent_segment_chunks_total"],
            "runs": max(runs, 0.0), "mixed": g["serving_mixed_steps_total"]}


def updates_per_step(ctx):
    """Slot states ONE linear layer read and wrote through `gdn_step` in
    ONE compiled step of the traced slice's stretch: the decode rows that
    advanced (`serving_recurrent_tokens_total{kind="step"}` a step), or
    None with nothing counted."""
    g = mhc_latent_moe._growth(ctx)
    steps = (g or {}).get("serving_recurrent_steps_total")
    if not steps:
        return None
    rows = _step_tokens(g)
    return rows / steps if rows else None


# -- the whole step -----------------------------------------------------------------

def step_cost(cfg: dict, rows: float, sampled: float,
              weight_bytes: int = 2) -> dict:
    """Bytes and operations ONE compiled step of `rows` token rows must
    move and do WITHOUT its contexts (they are linear: `slice_cost` adds
    them whole): the layers' and the head's weights read once, the rows'
    embedding vectors; two operations a weight a row, the head on the
    `sampled` rows alone.  The delta rule's own arithmetic is VPU work (and
    full-precision products of 64 rows): no part of what the MXU's bf16
    peak had to give, so the count stays a lower bound."""
    p = weight_params(cfg)
    d = cfg["hidden_size"]
    head = d * cfg["vocab_size"]
    blocks = p["gdn_layers"] + p["full_layers"]
    return {"bytes": float((blocks + head) * weight_bytes
                           + rows * d * weight_bytes),
            "flops": 2.0 * blocks * rows + 2.0 * head * sampled}


def context_cost(cfg: dict, attended: float, fetched: float,
                 state_rows: float) -> dict:
    """What the contexts add, all layers: 4 H head operations a token a
    query row attends and the fetched tokens' K and V in each full layer;
    the states of `state_rows` rows (decode rows and runs) read and written
    in each linear layer."""
    n_gdn, n_full = mixer_layers(cfg)
    return {"flops": n_full * 4.0 * cfg["hidden_size"] * attended,
            "bytes": float(n_full * kv_row_bytes(cfg) * fetched
                           + n_gdn * 2 * state_bytes(cfg) * state_rows)}


SLICE_COUNTERS = ("serving_recurrent_steps_total", "serving_kv_rows_total",
                  "serving_kv_tokens_attended_total",
                  "serving_kv_tokens_fetched_total")


def slice_cost(ctx, steps_traced: float):
    """Bytes and operations the `steps_traced` steps of the traced slice
    must move and do, from the engine's counters' growth around the slice
    scaled to the trace's own steps (benchmark/lib/mhc_latent_moe.py
    `slice_cost` says why: the stretch closes after the profiler has
    written its trace): the steps (`serving_recurrent_steps_total`), of
    which mixed; the rows that carried a token, of which prompt rows; the
    contexts attended and the tokens the paged kernel fetched, a layer's
    worth; the states moved (`serving_recurrent_slot_updates_total`, all
    linear layers').  The head runs on a step's sampled rows: a decode
    step's rows, a mixed step's `slots`.  None where the program counted
    none of this."""
    g = mhc_latent_moe._growth(ctx)
    if not g or not steps_traced or not all(g.get(k) for k in SLICE_COUNTERS):
        return None
    cfg = ctx.cfg
    n_gdn, _ = mixer_layers(cfg)
    steps = g["serving_recurrent_steps_total"]
    mixed = g.get("serving_mixed_steps_total", 0)
    pad = g.get("serving_step_pad_rows_total", 0)
    chunk = g.get("serving_chunk_rows_total", 0)
    decode_rows = (g["serving_kv_rows_total"] - pad - chunk) / steps
    slots = cfg["server_flags"]["slots"]
    kinds = {"decode": (steps - mixed, decode_rows, decode_rows)}
    if mixed:
        kinds["mixed"] = (mixed, decode_rows + chunk / mixed, slots)
    total = {"bytes": 0.0, "flops": 0.0}
    for count, rows, sampled in kinds.values():
        c = step_cost(cfg, rows, sampled)
        for k in total:
            total[k] += count * c[k]
    attended = g["serving_kv_tokens_attended_total"] - pad
    fetched = g["serving_kv_tokens_fetched_total"]
    updates = g.get("serving_recurrent_slot_updates_total", 0) / max(n_gdn, 1)
    c = context_cost(cfg, attended, fetched, updates)
    scale = steps_traced / steps
    return {"bytes": scale * (total["bytes"] + c["bytes"]),
            "flops": scale * (total["flops"] + c["flops"]),
            "steps_counted": steps, "mixed_share": mixed / steps,
            "decode_rows": decode_rows,
            "chunk_rows": chunk / mixed if mixed else 0.0,
            "attended": attended / steps, "fetched": fetched / steps,
            "state_rows": updates / steps}
