"""Multi-head latent attention (MLA, DeepSeek-V2/V3: arXiv:2405.04434,
arXiv:2412.19437) and its YaRN rotary positions (arXiv:2309.00071).

Keys and values are never stored per head: a token leaves ONE latent row
`[c_kv (kv_lora_rank), k_pe (qk_rope_head_dim)]` — the RMS-normed
compressed KV and the rotated shared position key — and every head's key
and value are linear in it:

    k_h = [c_kv W_kvb,k_h , k_pe]        v_h = c_kv W_kvb,v_h

Two algebraically equal forms are used:

  * EXPANDED (whole sequences: training forward, lm_generate, legacy
    prefill): materialize k_h, v_h and run plain multi-head attention at
    head width nope+rope — any of the repo's attention impls applies.
  * ABSORBED (decode and mixed steps over the cache): fold W_kvb,k into the
    query, `q_lat = q_nope W_kvb,k^T`, score against the latent row itself,
    `q_lat . c_kv + q_pe . k_pe`, weigh the latent rows, and expand the
    result through W_kvb,v.  The cache row is read once for all heads: one
    KV "head" serves every query head, and V is the row's first
    kv_lora_rank columns.

Rotation is the repo's half-split convention (feature i pairs with
i + D/2).  The published checkpoints interleave the pairs (2i, 2i+1): a
fixed column permutation of W_qb's and W_kva's rope columns maps one onto
the other, so q_pe . k_pe is the same number.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_NEG_INF = -1e30


# -- YaRN -------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * float(mscale) * math.log(float(factor)) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[dict]) -> np.ndarray:
    """The dim/2 rotary frequencies.  Without `scaling`: theta^(-2i/dim).
    With a YaRN `scaling` dict (factor, original_max_position_embeddings,
    beta_fast, beta_slow): high-frequency pairs (more than beta_fast turns
    over the original context) keep their frequency, low-frequency pairs
    (fewer than beta_slow turns) are divided by `factor`, and a linear ramp
    over the pair index blends the two between the correction dims."""
    idx = np.arange(0, dim, 2, dtype=np.float64)
    extra = theta ** (-idx / dim)
    if not scaling or float(scaling.get("factor", 1.0)) <= 1.0:
        return extra.astype(np.float32)
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(turns: float) -> float:
        return dim * math.log(orig / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))),
               dim - 1)
    span = max(high - low, 1e-3)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / span, 0, 1)
    inter = extra / factor
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def softmax_scale(qk_head_dim: int, scaling: Optional[dict]) -> float:
    """qk_head_dim^-0.5, times YaRN's mscale(factor, mscale_all_dim)^2."""
    s = float(qk_head_dim) ** -0.5
    if scaling and float(scaling.get("mscale_all_dim", 0) or 0):
        m = yarn_mscale(float(scaling["factor"]),
                        float(scaling["mscale_all_dim"]))
        s *= m * m
    return s


def rope_amplitude(scaling: Optional[dict]) -> float:
    """What YaRN multiplies cos and sin by: mscale / mscale_all_dim's."""
    if not scaling or float(scaling.get("factor", 1.0)) <= 1.0:
        return 1.0
    return yarn_mscale(float(scaling["factor"]),
                       float(scaling.get("mscale", 1) or 1)) / \
        yarn_mscale(float(scaling["factor"]),
                    float(scaling.get("mscale_all_dim", 0) or 0))


def rotate(x: Array, positions: Array, inv_freq, amplitude: float = 1.0
           ) -> Array:
    """Half-split rotation of x [..., D] at `positions` (broadcast against
    x's leading dims: [T], [B, T], ...; head axes, if any, sit between the
    position axes and D and are passed with size-1 position dims)."""
    half = x.shape[-1] // 2
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * amplitude, jnp.sin(ang) * amplitude
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


# -- the absorbed form over a cache ---------------------------------------------

def lane_width(width: int) -> int:
    """The width a cache stores the latent row at: the next multiple of
    128 lanes (576 -> 640, zeros behind the latent).  HBM tiles pad the row
    to that anyway, and Mosaic copies whole tiles only — the same bytes,
    made addressable (serving/paged_kv.py, graph/lm_decode.py)."""
    return -(-int(width) // 128) * 128


def pad_lanes(x: Array, width: int) -> Array:
    """Zeros behind x's last dimension up to `width`."""
    pad = width - x.shape[-1]
    return x if pad == 0 else \
        jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def absorb_query(q_nope: Array, q_pe: Array, w_kvb: Array, nope: int) -> Array:
    """[..., H, nope] and [..., H, rope] -> the latent-space query
    [..., H, kv_lora + rope]: q_lat = q_nope W_kvb,k^T per head, beside
    q_pe.  `w_kvb` is [kv_lora, H * (nope + v)], each head's columns its
    k_nope then its v."""
    H = q_nope.shape[-2]
    wk = w_kvb.reshape(w_kvb.shape[0], H, -1)[:, :, :nope]     # [c, H, n]
    q_lat = jnp.einsum("...hn,chn->...hc", q_nope, wk)
    return jnp.concatenate([q_lat.astype(q_pe.dtype), q_pe], axis=-1)


def expand_value(o_lat: Array, w_kvb: Array, nope: int) -> Array:
    """Weighted latent rows [..., H, kv_lora] -> head values [..., H, v]."""
    H = o_lat.shape[-2]
    wv = w_kvb.reshape(w_kvb.shape[0], H, -1)[:, :, nope:]     # [c, H, v]
    return jnp.einsum("...hc,chv->...hv", o_lat.astype(w_kvb.dtype), wv)


def latent_attend(q_full: Array, rows: Array, allowed: Array, scale: float,
                  kv_rank: int) -> Array:
    """q_full [R, H, W] against each query row's own latent context
    rows [R, T, W] under `allowed` [R, T] -> weighted latents [R, H, kv_rank].
    Scores and softmax in float32, the weights cast to the rows' dtype (the
    discipline of ops/attention.py's paged fallback)."""
    from paddle_tpu.utils.dtypes import promote_compute

    s = promote_compute(jnp.einsum("rhw,rtw->rht", q_full, rows)) * scale
    s = jnp.where(allowed[:, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
    return jnp.einsum("rht,rtc->rhc", p, rows[..., :kv_rank])


def paged_latent_step(
    q_full: Array,          # [R, H, W] absorbed queries, one token a row
    new_rows: Array,        # [R, W] the rows' own latents (normed, rotated)
    kv_pages: Array,        # [P, page_size, W] the latent pool
    page_table: Array,      # [S, max_pages]
    row_slot: Array,        # [R] table row of each query row
    row_pos: Array,         # [R] global position of each query row
    scale: float,
    kv_rank: int,
    use_kernel: bool = False,
) -> tuple[Array, Array]:
    """One decode or mixed step against the LATENT paged pool — the
    contract of ops/attention.py:ragged_paged_attention_step (rows scatter
    before the read; row r attends its slot's positions 0..row_pos[r];
    padding rows aim at the trash page) with one latent row a token in
    place of per-head K and V.  Returns (weighted latents [R, H, kv_rank],
    new pool).  The decode step is the case row_slot = arange(S)."""
    page_size = kv_pages.shape[1]
    max_pages = page_table.shape[1]
    phys = page_table[row_slot, row_pos // page_size]
    pool = kv_pages.at[phys, row_pos % page_size].set(
        new_rows.astype(kv_pages.dtype))
    if use_kernel:
        from paddle_tpu.ops import pallas_paged

        out = pallas_paged.latent_paged_attention(
            q_full, pool, page_table, row_pos + 1, scale=scale,
            row_slot=row_slot, v_width=kv_rank)
        return out, pool
    T_ctx = max_pages * page_size
    rows = pool[page_table[row_slot]].reshape(q_full.shape[0], T_ctx, -1)
    allowed = jnp.arange(T_ctx)[None, :] <= row_pos[:, None]
    return latent_attend(q_full, rows, allowed, scale, kv_rank), pool


def cached_latent_step(q_full: Array, new_rows: Array, cache: Array,
                       pos: Array, n_new: Array, scale: float, kv_rank: int
                       ) -> tuple[Array, Array]:
    """The dense-cache analog (lm_generate's use_cache path and a
    prefix-hit continuation): q_full [B, Tn, H, W], new_rows [B, Tn, W],
    cache [B, Tmax, W], pos [B] tokens resident, n_new [B] valid new tokens.
    Token i of row b lands at pos[b] + i and attends 0..pos[b] + i.
    Returns (weighted latents [B, Tn, H, kv_rank], new cache)."""
    B, Tn, H, W = q_full.shape
    Tmax = cache.shape[1]
    qpos = pos[:, None] + jnp.arange(Tn)[None, :]                  # [B, Tn]
    live = jnp.arange(Tn)[None, :] < n_new[:, None]
    # rows past n_new write where they already are (no-op positions clip)
    wpos = jnp.where(live, qpos, Tmax)                    # out of range: drop
    new = cache.at[jnp.arange(B)[:, None], wpos].set(
        new_rows.astype(cache.dtype), mode="drop")
    allowed = jnp.arange(Tmax)[None, None, :] <= qpos[:, :, None]  # [B,Tn,T]
    from paddle_tpu.utils.dtypes import promote_compute

    s = promote_compute(jnp.einsum("bqhw,btw->bhqt", q_full, new)) * scale
    s = jnp.where(allowed[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(new.dtype)
    return jnp.einsum("bhqt,btc->bqhc", p, new[..., :kv_rank]), new
