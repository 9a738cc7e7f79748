"""Scaled-dot-product attention ops: dense, blockwise (online-softmax), and
ring attention for sequence/context parallelism.

This is a NEW capability beyond the reference (which predates transformer
attention — its closest analog is the additive-attention composite
`simple_attention`, ref: python/paddle/trainer_config_helpers/networks.py:1257,
and the zero-padding sequence machinery of SURVEY.md §5 "long-context").
The TPU framework makes long-context first-class:

  * `dot_product_attention` — one fused XLA einsum-softmax-einsum; masking by
    per-sequence lengths and/or causality.
  * `blockwise_attention` — O(T) memory online-softmax accumulation over
    key/value blocks (the flash-attention recurrence), written with
    `lax.scan` so XLA keeps the running (m, l, o) accumulators in registers
    /VMEM instead of materializing the [T, T] score matrix.
  * `ring_attention` — context parallelism over a mesh axis: each device
    holds a sequence shard; key/value shards rotate around the ring via
    `lax.ppermute` while every device folds each incoming block into its
    online-softmax accumulator.  One step of compute overlaps with the next
    ppermute.  Equivalent math to the single-device versions, differentiable
    end-to-end (ppermute has a transpose rule, so jax.grad produces the
    reverse ring automatically).

Layouts follow TPU conventions: q/k/v are [B, T, H, Dh] (batch, time, heads,
head_dim); scores are [B, H, Tq, Tk] so the contractions are MXU-friendly
einsums.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

_NEG_INF = -1e30


def _score_mask(
    q_pos: Array,            # [Tq] global positions of the query rows
    k_pos: Array,            # [Tk] global positions of the key rows
    q_valid: Optional[Array],   # [B, Tq] or None
    k_valid: Optional[Array],   # [B, Tk] or None
    causal: bool,
    window: Optional[int] = None,
) -> Optional[Array]:
    """Combined validity mask broadcastable to [B, 1, Tq, Tk]; None = all valid.

    `window` keeps only keys with |q_pos - k_pos| < window (sliding-window /
    local attention; one-sided when combined with causal)."""
    mask = None
    if causal:
        mask = (k_pos[None, :] <= q_pos[:, None])[None, None]    # [1,1,Tq,Tk]
    if window is not None:
        d = q_pos[:, None] - k_pos[None, :]
        w = (jnp.abs(d) < window)[None, None]
        mask = w if mask is None else jnp.logical_and(mask, w)
    if k_valid is not None:
        kv = k_valid[:, None, None, :]                           # [B,1,1,Tk]
        mask = kv if mask is None else jnp.logical_and(mask, kv)
    if q_valid is not None:
        qv = q_valid[:, None, :, None]                           # [B,1,Tq,1]
        mask = qv if mask is None else jnp.logical_and(mask, qv)
    return mask


def rope(x: Array, positions: Array, theta: float = 10000.0,
         rotary_dim: Optional[int] = None, rope_scaling: Optional[dict] = None,
         attention_factor: float = 1.0) -> Array:
    """Rotary position embedding (RoPE, Su et al. 2021) — NEW capability
    beyond the reference.  x [B, T, H, D] with D even, positions [T] (or
    [B, T]) absolute token positions; rotate-half convention (feature i
    pairs with i + D/2, the GPT-NeoX/llama layout — NOT the interleaved
    consecutive-pair GPT-J layout) with position-dependent angles, so q·k
    depends only on relative offsets.
    Applied to q/k BEFORE attention, it composes with every implementation
    (dense/blockwise/flash/ring) — for ring/context-parallel shards pass the
    shard's global positions.

    `rotary_dim` rotates the FIRST rotary_dim columns of each head only (the
    pairing is then i with i + rotary_dim/2; the other columns pass through:
    partial rotation, `partial_rotary_factor` of the published configs);
    `rope_scaling` (a YaRN dict: factor, original_max_position_embeddings,
    beta_fast, beta_slow) blends the frequencies as ops/mla.py:yarn_inv_freq
    does, and `attention_factor` multiplies cos and sin — of the rotated
    columns only, so a partly rotated head is scaled in that part alone."""
    D = x.shape[-1] if rotary_dim is None else int(rotary_dim)
    assert D % 2 == 0 and D <= x.shape[-1], \
        f"rope needs an even rotated width within the head, got {D}"
    half = D // 2
    if rope_scaling:
        from paddle_tpu.ops.mla import yarn_inv_freq
        freqs = jnp.asarray(yarn_inv_freq(D, float(theta), rope_scaling))
    else:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs     # [..., T, half]
    if ang.ndim == 2:                                          # [T, half]
        ang = ang[None]                                        # [1, T, half]
    cos = jnp.cos(ang)[:, :, None, :]                          # [B|1, T, 1, half]
    sin = jnp.sin(ang)[:, :, None, :]
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    x1, x2 = x[..., :half], x[..., half:D]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos]
                          + ([] if D == x.shape[-1] else [x[..., D:]]),
                          axis=-1)
    return rot.astype(x.dtype)


def _expand_kv_heads(k: Array, v: Array, num_heads: int):
    """Grouped-query attention: k/v carry H_kv <= H heads; repeat each kv
    head over its query-head group so every impl sees matching heads."""
    h_kv = k.shape[2]
    if h_kv == num_heads:
        return k, v
    assert num_heads % h_kv == 0, \
        f"num_heads {num_heads} not divisible by num_kv_heads {h_kv}"
    rep = num_heads // h_kv
    return (jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2))


def dot_product_attention(
    q: Array, k: Array, v: Array,
    q_valid: Optional[Array] = None,
    k_valid: Optional[Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> Array:
    """Dense reference attention. q [B,Tq,H,D], k/v [B,Tk,H_kv,D] (H_kv may
    divide H for grouped-query attention) -> [B,Tq,H,D]."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    k, v = _expand_kv_heads(k, v, q.shape[2])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = _score_mask(jnp.arange(q.shape[1]), jnp.arange(k.shape[1]),
                       q_valid, k_valid, causal, window)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if mask is not None:
        # rows with no valid key (fully masked) must output exactly 0
        any_valid = jnp.any(mask, axis=-1, keepdims=True)
        p = jnp.where(any_valid, p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _online_block(
    acc: tuple[Array, Array, Array],
    q: Array, k_blk: Array, v_blk: Array,
    q_pos: Array, k_pos: Array,
    q_valid: Optional[Array], k_valid_blk: Optional[Array],
    causal: bool, scale: float,
    window: Optional[int] = None,
) -> tuple[Array, Array, Array]:
    """Fold one key/value block into the online-softmax accumulator.

    acc = (o [B,Tq,H,D] f32, m [B,H,Tq] running max, l [B,H,Tq] running sum).
    """
    o, m, l = acc
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale       # [B,H,Tq,Tk]
    mask = _score_mask(q_pos, k_pos, q_valid, k_valid_blk, causal, window)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)                            # kill -inf rows
    corr = jnp.exp(m - m_new)                                  # [B,H,Tq]
    l_new = corr * l + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(p.dtype))
    o_new = o * jnp.moveaxis(corr, 1, 2)[..., None] + pv
    return o_new, m_new, l_new


def _finalize(o: Array, l: Array, dtype) -> Array:
    """o / l with fully-masked rows (l == 0) -> 0."""
    denom = jnp.moveaxis(l, 1, 2)[..., None]                   # [B,Tq,H,1]
    return jnp.where(denom > 0, o / jnp.maximum(denom, 1e-30), 0.0).astype(dtype)


def _init_acc(B: int, Tq: int, H: int, D: int) -> tuple[Array, Array, Array]:
    return (jnp.zeros((B, Tq, H, D), jnp.float32),
            jnp.full((B, H, Tq), _NEG_INF, jnp.float32),
            jnp.zeros((B, H, Tq), jnp.float32))


def blockwise_attention(
    q: Array, k: Array, v: Array,
    q_valid: Optional[Array] = None,
    k_valid: Optional[Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_k: int = 512,
    window: Optional[int] = None,
) -> Array:
    """Online-softmax attention over key blocks — O(Tq * block_k) score memory.

    Same math as `dot_product_attention` (incl. grouped kv heads and sliding
    window); the scan carry holds (o, m, l) so the full [Tq, Tk] score
    matrix never exists.
    """
    B, Tq, H, D = q.shape
    k, v = _expand_kv_heads(k, v, H)
    Tk = k.shape[1]
    if scale is None:
        scale = D ** -0.5
    block_k = min(block_k, Tk)
    n_blocks = -(-Tk // block_k)
    pad = n_blocks * block_k - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pad = (jnp.arange(n_blocks * block_k) < Tk)[None, :]
        k_valid = kv_pad if k_valid is None else \
            jnp.logical_and(jnp.pad(k_valid, ((0, 0), (0, pad))), kv_pad)
    q_pos = jnp.arange(Tq)
    kb = jnp.moveaxis(k.reshape(B, n_blocks, block_k, H, D), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, n_blocks, block_k, H, D), 1, 0)
    kvalb = (None if k_valid is None else
             jnp.moveaxis(jnp.broadcast_to(
                 k_valid, (B, n_blocks * block_k)).reshape(B, n_blocks, block_k), 1, 0))

    # remat: without it, scan's backward saves every block's score tile —
    # n_blocks x [B, H, Tq, block_k] fp32 residuals, 32 GB at T=16384 (from
    # a sweep since withdrawn, see ROADMAP S5) where the whole point of
    # blockwise is O(T) memory.  Recomputing the tile in backward is the
    # standard flash-attention trade and keeps train-mode long context
    # viable on the portable (non-pallas) path too.
    # prevent_cse=False: CSE prevention is unnecessary for a scan body
    # (the scan barrier already keeps fwd/bwd apart) and only blocks XLA
    # optimizations
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(acc, xs):
        i = xs["i"]
        k_pos = i * block_k + jnp.arange(block_k)
        acc = _online_block(acc, q, xs["k"], xs["v"], q_pos, k_pos,
                            q_valid, xs.get("kv"), causal, scale, window)
        return acc, None

    xs = {"i": jnp.arange(n_blocks), "k": kb, "v": vb}
    if kvalb is not None:
        xs["kv"] = kvalb
    (o, m, l), _ = lax.scan(body, _init_acc(B, Tq, H, D), xs)
    return _finalize(o, l, q.dtype)


def ring_attention(
    q: Array, k: Array, v: Array,
    axis_name: str,
    q_valid: Optional[Array] = None,
    k_valid: Optional[Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
    window: Optional[int] = None,
) -> Array:
    """Context-parallel attention for use INSIDE `shard_map` over `axis_name`.

    Every device holds its local sequence shard q/k/v [B, T_local, H, D]
    (shard d covers global positions [d*T_local, (d+1)*T_local)).  K/V shards
    rotate one hop per step via `lax.ppermute` while each device folds the
    incoming block into its online-softmax accumulator; after axis_size steps
    every query row has attended to every key.  The python loop is unrolled
    (axis_size is static) so XLA can overlap each ppermute with the previous
    block's einsums — the collective rides ICI behind the MXU work.

    On TPU each per-hop block runs the fused pallas flash kernel
    (ring flash attention): the kernel returns the block's normalized output
    + log-sum-exp, and blocks combine with exp(lse_b - m) weights — the same
    online-softmax math, score tiles never leaving VMEM.  `use_flash=False`
    forces the portable jnp fold (and is the oracle in tests).
    """
    B, Tl, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    from paddle_tpu.utils.jax_compat import axis_size
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    if use_flash is None:
        from paddle_tpu.ops import pallas_attention
        use_flash = pallas_attention.supported()

    if use_flash:
        return _ring_flash(q, k, v, axis_name, idx, n, perm,
                           q_valid, k_valid, causal, scale, window)

    q_pos = idx * Tl + jnp.arange(Tl)
    acc = _init_acc(B, Tl, H, D)
    k_blk, v_blk, kv_blk = k, v, k_valid
    for step in range(n):
        src = (idx - step) % n                      # owner of the current block
        k_pos = src * k_blk.shape[1] + jnp.arange(k_blk.shape[1])
        # grouped kv heads expand AFTER the rotation, so the ring moves the
        # small H_kv tensors over ICI
        k_use, v_use = _expand_kv_heads(k_blk, v_blk, H)
        acc = _online_block(acc, q, k_use, v_use, q_pos, k_pos,
                            q_valid, kv_blk, causal, scale, window)
        if step + 1 < n:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
            if kv_blk is not None:
                kv_blk = lax.ppermute(kv_blk, axis_name, perm)
    o, m, l = acc
    return _finalize(o, l, q.dtype)


def _ring_flash(q, k, v, axis_name, idx, n, perm,
                q_valid, k_valid, causal, scale, window=None):
    """Ring attention with the pallas flash kernel per hop: each block call
    yields (o_b normalized, lse_b); blocks fold into a running
    (num, den, max) — o = num/den at the end.  Differentiable end-to-end
    (the kernel's custom VJP accepts the lse cotangent; ppermute has a
    transpose rule, so jax.grad produces the reverse ring automatically)."""
    from paddle_tpu.ops.pallas_attention import flash_attention

    B, Tl, H, D = q.shape
    m_run = jnp.full((B, H, Tl), -jnp.inf, jnp.float32)
    num = jnp.zeros((B, Tl, H, D), jnp.float32)
    den = jnp.zeros((B, H, Tl), jnp.float32)

    k_blk, v_blk, kv_blk = k, v, k_valid
    for step in range(n):
        src = (idx - step) % n                      # owner of the current block
        o_b, lse_b = flash_attention(
            q, k_blk, v_blk, q_valid=q_valid, k_valid=kv_blk, causal=causal,
            scale=scale, q_offset=idx * Tl, k_offset=src * k_blk.shape[1],
            return_lse=True, window=window)
        m_new = jnp.maximum(m_run, lse_b)
        alive = m_new > -jnp.inf
        # sanitize BEFORE exp: -inf - -inf would be NaN, and a NaN in the
        # untaken where-branch still poisons gradients (0 * NaN)
        m_safe = jnp.where(alive, m_new, 0.0)
        corr = jnp.where(alive & (m_run > -jnp.inf),
                         jnp.exp(jnp.where(m_run > -jnp.inf, m_run, 0.0)
                                 - m_safe), 0.0)
        w = jnp.where(alive & (lse_b > -jnp.inf),
                      jnp.exp(jnp.where(lse_b > -jnp.inf, lse_b, 0.0)
                              - m_safe), 0.0)
        num = num * jnp.moveaxis(corr, 1, 2)[..., None] \
            + o_b.astype(jnp.float32) * jnp.moveaxis(w, 1, 2)[..., None]
        den = den * corr + w
        m_run = m_new
        if step + 1 < n:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
            if kv_blk is not None:
                kv_blk = lax.ppermute(kv_blk, axis_name, perm)
    return _finalize(num, den, q.dtype)


def project_qkv(query: Array, key: Array, value: Array,
                w_q: Array, w_k: Array, w_v: Array,
                num_heads: int, num_kv_heads: int,
                q_pos: Array, k_pos: Array,
                use_rope: bool = False, rope_theta: float = 10000.0,
                qk_norm: Optional[tuple] = None, **rope_kw):
    """The projections every attention path starts with: q [..., H, D], k
    and v [..., H_kv, D] from inputs [..., T, d].  `qk_norm` (q scale [D],
    k scale [D], eps) RMS-norms each head of q and k — statistics in
    float32, times the learned scale — BEFORE the rotation (QK-norm as the
    LFM2 / Qwen3 families apply it); scales as wide as the WHOLE projection
    (q [H D], k [H_kv D]) norm over all of it before the heads are split
    (OLMo 2's); `use_rope` then rotates q at
    `q_pos` and k at `k_pos` (`rope_kw`: `rope`'s rotary_dim, rope_scaling,
    attention_factor).  What is written to a KV cache is this k."""
    Dh = w_q.shape[1] // num_heads
    q = (query @ w_q).reshape(query.shape[:-1] + (num_heads, Dh))
    k = (key @ w_k).reshape(key.shape[:-1] + (num_kv_heads, Dh))
    v = (value @ w_v).reshape(value.shape[:-1] + (num_kv_heads, Dh))
    if qk_norm is not None:
        q_scale, k_scale, eps = qk_norm

        def norm(x, scale):
            x32 = x.astype(jnp.float32)
            if scale.size != x.shape[-1]:       # over the whole projection
                x32 = x32.reshape(x.shape[:-2] + (-1,))
            x32 = x32 * jax.lax.rsqrt(
                jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
            return (x32 * scale.astype(jnp.float32).reshape(-1)).reshape(
                x.shape).astype(x.dtype)

        q, k = norm(q, q_scale), norm(k, k_scale)
    if use_rope:
        q = rope(q, q_pos, rope_theta, **rope_kw)
        k = rope(k, k_pos, rope_theta, **rope_kw)
    return q, k, v


def project_out(o: Array, x: Array, w_o: Array,
                bias_o: Optional[Array] = None,
                w_g: Optional[Array] = None) -> Array:
    """What every attention path ends with: the heads' results o [..., H, D]
    of inputs x [..., d] through the output projection.  With `w_g` [d, H*D]
    an elementwise sigmoid gate from the layer's input comes first, y = (o *
    sigmoid(x w_g)) w_o (gated attention, arXiv:2505.06708): it multiplies
    whatever computed o, so no kernel knows of it.  A `w_g` of [d, H] is the
    gate PER HEAD (one value a head, the dsl's out_gate="head"): the same
    product with the gate broadcast over the head's D columns."""
    o = o.reshape(x.shape[:-1] + (w_o.shape[0],))
    if w_g is not None:
        g = jax.nn.sigmoid(x @ w_g).astype(o.dtype)
        if w_g.shape[1] != w_o.shape[0]:                   # one value a head
            g = jnp.repeat(g, w_o.shape[0] // w_g.shape[1], axis=-1)
        o = o * g
    out = o @ w_o
    return out if bias_o is None else out + bias_o


def multi_head_attention(
    query: Array,                     # [B, Tq, Dq]
    key: Array,                       # [B, Tk, Dk]
    value: Array,                     # [B, Tk, Dv]
    w_q: Array, w_k: Array, w_v: Array, w_o: Array,
    num_heads: int,
    q_valid: Optional[Array] = None,
    k_valid: Optional[Array] = None,
    causal: bool = False,
    bias_o: Optional[Array] = None,
    attn_fn=dot_product_attention,
    num_kv_heads: Optional[int] = None,
    window: Optional[int] = None,
    use_rope: bool = False,
    rope_theta: float = 10000.0,
    qk_norm: Optional[tuple] = None,
    w_g: Optional[Array] = None,
    **rope_kw,
) -> Array:
    """Projected multi-head attention; attn_fn pluggable (dense / blockwise /
    flash / a ring closure from parallel/context.py).

    num_kv_heads < num_heads gives grouped-query attention (w_k/w_v project
    to num_kv_heads * head_dim); window gives sliding-window attention;
    use_rope applies rotary position embeddings to q/k; qk_norm RMS-norms
    each head of q and k first (`project_qkv`); w_g gates the result in
    front of the output projection (`project_out`)."""
    Tq = query.shape[1]
    q, k, v = project_qkv(query, key, value, w_q, w_k, w_v, num_heads,
                          num_kv_heads or num_heads, jnp.arange(Tq),
                          jnp.arange(key.shape[1]), use_rope, rope_theta,
                          qk_norm, **rope_kw)
    kw = {} if window is None else {"window": window}
    o = attn_fn(q, k, v, q_valid=q_valid, k_valid=k_valid, causal=causal,
                **kw)
    return project_out(o, query, w_o, bias_o, w_g)


def cached_attention_step(
    q_new: Array,          # [B, Tn, H, D] new-token queries
    k_new: Array,          # [B, Tn, H_kv, D]
    v_new: Array,          # [B, Tn, H_kv, D]
    cache_k: Array,        # [B, Tmax, H_kv, D]
    cache_v: Array,        # [B, Tmax, H_kv, D]
    pos: Array,            # [B] int32 — tokens already resident per row
    n_new: Array,          # [B] int32 — valid new tokens this call (<= Tn)
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> tuple[Array, Array, Array, Array]:
    """Incremental causal attention against a fixed-size KV cache — the
    O(T)-per-token decode step (the reference's closest analog is the
    recurrent generator's carried state, RecurrentGradientMachine
    generation; transformers have no recurrence, so the cache IS the
    carried state).

    Row b's new tokens land at cache positions pos[b]..pos[b]+Tn-1 (rows
    advance independently — prompts have ragged lengths).  Writes use a
    one-hot batched matmul rather than per-row dynamic slices: static
    shapes, MXU-friendly, and scan/jit-stable.  Slots past pos+n_new hold
    garbage from padded prefill calls; causality (k_pos <= q_pos) already
    excludes them for every valid query, and the next call overwrites
    them.  Returns (out [B,Tn,H,D], new_cache_k, new_cache_v, new_pos).
    """
    B, Tn, H, D = q_new.shape
    Tmax = cache_k.shape[1]
    if scale is None:
        scale = D ** -0.5
    t = jnp.arange(Tmax)
    i = jnp.arange(Tn)
    # [B, Tmax, Tn] one-hot: slot t receives new token i of row b
    sel = (t[None, :, None] ==
           (pos[:, None, None] + i[None, None, :])).astype(cache_k.dtype)
    keep = 1.0 - jnp.max(sel, axis=2)                       # [B, Tmax]

    def scatter(cache, new):
        upd = jnp.einsum("bti,bihd->bthd", sel, new.astype(cache.dtype))
        return cache * keep[:, :, None, None] + upd

    ck, cv = scatter(cache_k, k_new), scatter(cache_v, v_new)

    qpos = pos[:, None] + i[None, :]                        # [B, Tn] global
    mask = t[None, None, :] <= qpos[:, :, None]             # causal, global
    if window is not None:
        mask = jnp.logical_and(mask,
                               t[None, None, :] > qpos[:, :, None] - window)

    k_full, v_full = _expand_kv_heads(ck, cv, H)
    s = jnp.einsum("bqhd,bkhd->bhqk", q_new, k_full) * scale
    from paddle_tpu.utils.dtypes import promote_compute
    s = promote_compute(s)
    s = jnp.where(mask[:, None, :, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_full.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v_full)
    return out, ck, cv, pos + n_new


def _tp_shards(mesh) -> int:
    """Size of a mesh's `model` axis (1 = no tensor parallelism); reads
    the mesh's own shape map — no parallel.mesh import, keeping this
    module cycle-free."""
    if mesh is None:
        return 1
    try:
        return int(dict(mesh.shape).get("model", 1))
    except (AttributeError, TypeError):
        return 1


def _tp_paged_call(mesh, body, head_args, pool_args, repl_args,
                   head_axis: int):
    """Run a paged-attention body under shard_map over the mesh `model`
    axis: query/key/value shard on their head axis, the page pools on
    their kv-head axis (axis 2), tables/positions replicate.  Each device
    reads and writes ONLY its own head shard of the pools — the pools are
    never all-gathered (tools/hlo_shard_check.py asserts it on the
    lowered HLO), and since no reduction ever crosses heads inside
    attention, the sharded math is the single-device math per head.
    Returns (out [head-sharded], k_pages', v_pages' [pool-sharded])."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.utils.jax_compat import shard_map

    head = P(*([None] * head_axis + ["model", None]))
    pool = P(None, None, "model", None)
    in_specs = tuple([head] * len(head_args) + [pool] * len(pool_args)
                     + [P()] * len(repl_args))
    fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=(head, pool, pool), check_vma=False)
    return fn(*head_args, *pool_args, *repl_args)


def _stored_rows(new: Array, pages: Array) -> Array:
    """New K or V rows [N, H_kv, D] in the shape and dtype the pool stores
    a token's row in: as they are, or several narrow heads a 128-lane tile
    (ops/pallas_paged.py:kv_row_shape) — the same values in the same
    order, so the gather path below reads a pool of either layout by
    reshaping what it gathered back to [H_kv, D]."""
    row = pages.shape[3 if _tokens_a_row(new, pages) > 1 else 2:]
    if _whole_tiles_of_heads(new, pages):
        # the heads past the model's own are zeros no query reads
        new = jnp.pad(new, ((0, 0), (0, row[0] - new.shape[-2]), (0, 0)))
    return new.reshape(new.shape[:1] + row).astype(pages.dtype)


def _whole_tiles_of_heads(new: Array, pages: Array) -> bool:
    """Whether the pool stores the rows' MORE THAN 8 KV heads in whole
    tiles of 8 heads (30 as 32: ops/pallas_paged.py:kv_row_shape)."""
    from paddle_tpu.ops.pallas_paged import heads_padded
    return heads_padded(new.shape[-2], new.shape[-1], pages.shape)


def _tokens_a_row(new: Array, pages: Array) -> int:
    """Tokens one stored row of the pool holds: 1, or 2 where a lone KV
    head of 128 lanes is stored two tokens a sublane row
    (ops/pallas_paged.py:kv_page_shape)."""
    return pages.shape[2] * pages.shape[3] // (new.shape[-2] * new.shape[-1])


def _page_size(new: Array, pages: Array) -> int:
    """Tokens a page of the pool holds, whatever shape it is stored in."""
    return pages.shape[1] * _tokens_a_row(new, pages)


def _write_rows(pages: Array, phys: Array, off: Array, new: Array) -> Array:
    """Scatter the rows `new` [N, H_kv, D] to token `off` of page `phys`."""
    tpr = _tokens_a_row(new, pages)
    rows = _stored_rows(new, pages)
    if tpr > 1:
        return pages.at[phys, off // tpr, off % tpr].set(rows)
    return pages.at[phys, off].set(rows)


def window_pages(window: int, page_size: int) -> int:
    """The most pages `window` consecutive tokens touch, wherever the first
    one sits in its page: what a window layer's read fetches a row — at
    most window + page_size tokens, whatever the context."""
    return (window + page_size - 2) // page_size + 1


def _window_view(page_table: Array, slot: Array, pos: Array, window: int,
                 page_size: int, ring: bool):
    """What a query row of a WINDOW layer reads: the pages that hold keys
    pos - window + 1 .. pos of its slot, as a table of that row's own —
    `view` [T, window_pages] physical pages, `base` [T], the position of
    the first token of view[:, 0], and `lo` [T], the window's first key
    counted from there.  Logical page j of a slot sits in
    column j of its table row, or with `ring` in column j % R of a ring of
    R pages that the slot recycles in place as it advances
    (serving/paged_kv.py "WINDOW LAYERS"); a column past the row's
    position re-reads the last one, and is masked by position."""
    R = page_table.shape[1]
    oldest = jnp.maximum(pos - (window - 1), 0)                      # [T]
    base = oldest // page_size * page_size
    logical = (oldest // page_size)[:, None] + jnp.arange(
        window_pages(window, page_size))
    logical = jnp.minimum(logical, (pos // page_size)[:, None])
    col = logical % R if ring else jnp.minimum(logical, R - 1)
    return page_table[slot[:, None], col], base, oldest - base


def _paged_read(q: Array, ck: Array, cv: Array, k_new: Array,
                page_table: Array, slot: Array, pos: Array, scale: float,
                window: Optional[int], ring: bool,
                use_kernel: Optional[bool]) -> Array:
    """The read of both paged steps, AFTER their writes: query rows q
    [T, H, D], row r at position pos[r] of table row slot[r], causally over
    that slot's pages — all of them, or with `window` the last `window`
    keys through `_window_view`.  The Pallas kernels (ops/pallas_paged.py)
    when supported, else — and as their oracle — a gather of the pages into
    a contiguous view and a masked softmax."""
    from paddle_tpu.ops import pallas_paged
    T, H, D = q.shape
    page_size = _page_size(k_new, ck)
    if use_kernel is None:
        use_kernel = pallas_paged.supported()
    if window is not None:
        table, base, lo = _window_view(page_table, slot, pos, window,
                                       page_size, ring)
        rows = jnp.arange(T, dtype=jnp.int32)
    else:
        assert not ring, "a ring of pages holds a window layer's keys only"
        table, rows, base, lo = page_table, slot, 0, None
    if use_kernel:
        return pallas_paged.paged_attention(
            q, ck, cv, table, pos + 1 - base, scale=scale, row_slot=rows,
            kv_heads=k_new.shape[-2], first=lo)
    # -- per-row page gather -> [T, T_ctx] contiguous view -----------------
    T_ctx = table.shape[1] * page_size
    if _whole_tiles_of_heads(k_new, ck):
        ck, cv = (p[..., :k_new.shape[-2], :] for p in (ck, cv))   # padding
    kc = ck[table[rows]].reshape(T, T_ctx, *k_new.shape[-2:])
    vc = cv[table[rows]].reshape(T, T_ctx, *k_new.shape[-2:])
    k_full, v_full = _expand_kv_heads(kc, vc, H)
    t = jnp.arange(T_ctx)[None, :] + jnp.reshape(base, (-1, 1))
    mask = t <= pos[:, None]                                     # causal
    if window is not None:
        mask = jnp.logical_and(mask, t > pos[:, None] - window)
    s = jnp.einsum("qhd,qkhd->qhk", q, k_full) * scale
    from paddle_tpu.utils.dtypes import promote_compute
    s = promote_compute(s)
    s = jnp.where(mask[:, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_full.dtype)
    return jnp.einsum("qhk,qkhd->qhd", p, v_full)


def paged_attention_step(
    q_new: Array,          # [S, 1, H, D] one new-token query per slot
    k_new: Array,          # [S, 1, H_kv, D]
    v_new: Array,          # [S, 1, H_kv, D]
    k_pages: Array,        # [P, page_size, H_kv, D] shared page pool
    v_pages: Array,        # [P, page_size, H_kv, D]
    page_table: Array,     # [S, max_pages] int32 physical page per logical
                           # page of each slot (0 = unmapped -> trash page)
    pos: Array,            # [S] int32 tokens already resident per slot
    scale: Optional[float] = None,
    window: Optional[int] = None,
    use_kernel: Optional[bool] = None,
    mesh=None,
    ring: bool = False,
) -> tuple[Array, Array, Array]:
    """One continuous-batching decode micro-step against a PAGED KV cache —
    the serving analog of `cached_attention_step`: instead of one dense
    [B, Tmax, H_kv, D] cache per request batch, every slot's context lives
    in fixed-size pages of a shared pool, mapped by a per-slot page table,
    so cache HBM is proportional to tokens actually held and ONE compiled
    step serves an ever-changing request mix.

    Contract (mirrors cached_attention_step with Tn == 1): slot s's new
    token lands at logical position pos[s] — physical page
    page_table[s, pos[s] // page_size], offset pos[s] % page_size — and
    attends causally over logical positions 0..pos[s].  Physical page 0 is
    the TRASH page: unmapped logical pages (inactive slots, a paused slot
    whose next page is not yet allocated) write there and their reads are
    causally masked or discarded by the scheduler, so the one compiled
    program needs no per-slot branching.  Gathered positions past pos[s]
    carry finite garbage; the -1e30 mask makes their softmax weight exactly
    0.0, so they cannot perturb live slots (same discipline as the dense
    cache's padded-prefill slots).

    Returns (out [S, 1, H, D], new_k_pages, new_v_pages).  `use_kernel`
    routes the read through the Pallas ragged-paged kernel
    (ops/pallas_paged.py) — default: auto (the kernel when supported, with
    or without a window); False forces the jnp gather fallback (the oracle
    in tests and the exactness anchor of the serving engine).

    `window` reads the last `window` keys of each slot (key j for the query
    at i iff 0 <= i - j < window) and fetches only the pages that hold them
    (`_window_view`); with `ring`, `page_table` is [S, R] and logical page j
    of a slot is its column j % R — the slot's ring, written and read in
    place (serving/paged_kv.py "WINDOW LAYERS").

    SCAN-BODY SAFE: the write+read core is pure in its operands (no
    host callback, no per-call state — including the shard_map TP path,
    whose collective set is fixed per call), so a caller may trace it
    inside a `lax.scan` body with `pos`/`page_table`-addressed writes
    riding the scan carry — body i+1 reads exactly the pool state body
    i's scatter produced (tests/test_chunked_prefill.py:
    test_paged_kernel_inside_scan_matches_fallback).
    """
    S, Tn, H, D = q_new.shape
    assert Tn == 1, "paged decode feeds exactly one new token per slot"
    out, ck, cv = ragged_paged_attention_step(
        q_new[:, 0], k_new[:, 0], v_new[:, 0], k_pages, v_pages, page_table,
        jnp.arange(S, dtype=jnp.int32), pos, scale=scale, window=window,
        use_kernel=use_kernel, mesh=mesh, ring=ring)
    return out[:, None], ck, cv


def ragged_paged_attention_step(
    q_new: Array,          # [T, H, D] packed query rows — ONE token each
    k_new: Array,          # [T, H_kv, D]
    v_new: Array,          # [T, H_kv, D]
    k_pages: Array,        # [P, page_size, H_kv, D] shared page pool
    v_pages: Array,        # [P, page_size, H_kv, D]
    page_table: Array,     # [S, max_pages] int32 physical page per logical
                           # page of each table row (0 = unmapped -> trash)
    row_slot: Array,       # [T] int32 page-table row each query row reads
    row_pos: Array,        # [T] int32 global position of each query row
    scale: Optional[float] = None,
    window: Optional[int] = None,
    use_kernel: Optional[bool] = None,
    mesh=None,
    ring: bool = False,
) -> tuple[Array, Array, Array]:
    """RAGGED paged attention — the mixed prefill/decode step of the
    serving engine (the full Ragged Paged Attention shape of
    arXiv:2604.15464, generalizing `paged_attention_step`'s one-token-per-
    slot contract): query tokens are PACKED into a flat [T] row dimension
    where row r is one token of slot `row_slot[r]` at global position
    `row_pos[r]`.  A decode slot contributes one row; a prompt being
    chunk-prefilled contributes up to `chunk` consecutive rows — both
    shapes share this ONE dispatch, so a long cold prompt can no longer
    stall every decoding slot's inter-token latency behind its own
    prefill program.

    Contract per row r: its k/v land at logical position row_pos[r] of
    table row row_slot[r] (physical page page_table[row_slot[r],
    row_pos[r] // page_size], offset row_pos[r] % page_size), and it
    attends causally over that slot's logical positions 0..row_pos[r].
    All writes scatter BEFORE the read, so chunk rows of the same slot
    see each other's K/V under the causal mask (token i of a chunk
    attends tokens 0..i — exactly the dense prefill semantics).  Padding
    rows point `row_slot` at an all-zero table row (every logical page
    unmapped -> trash page 0) with row_pos 0: their writes land in the
    trash page and their outputs are garbage the scheduler discards.

    The SPECULATIVE verify step (serving/engine.py `_spec_impl`) rides
    this same contract with a third row flavor: a decoding slot's
    draft CHAIN — its committed last token at row_pos = pos plus k
    drafted tokens at pos+1..pos+k — so draft row i attends the
    committed context plus drafts 1..i-1, exactly the context a
    sequential engine would have if the drafts were true.  The scatter
    is ROLLBACK-SAFE by construction: a rejected draft's K/V sits at
    positions beyond the slot's committed length, where the causal
    mask excludes it from every live query, and the next step's rows
    overwrite those positions before the slot's pos can ever reach
    them — so the device state needs no undo, and the host merely
    returns the unjustified tail pages (paged_kv.uncommit_tail).

    Returns (out [T, H, D], new_k_pages, new_v_pages).  `use_kernel`
    routes the read through the Pallas ragged-paged kernel with the
    row->slot indirection (ops/pallas_paged.py), with a `window` the
    kernel's windowed form over the pages `_window_view` names (`ring`: as
    for `paged_attention_step`; a chunk's rows are written before any is
    read, so a ring holds window + chunk tokens and a page more); the jnp
    gather fallback is the exactness oracle of both."""
    T, H, D = q_new.shape
    page_size = _page_size(k_new, k_pages)
    if scale is None:
        scale = D ** -0.5

    if _tp_shards(mesh) > 1:
        # tensor parallelism: heads partition over the mesh `model` axis —
        # the whole write+read core runs per head shard under shard_map
        # (each device's local H/h_kv keep the same grouped-query ratio;
        # the engine validated divisibility), row indirection replicated
        def body(q, k, v, kp, vp, tbl, rs, rp):
            return ragged_paged_attention_step(q, k, v, kp, vp, tbl, rs,
                                               rp, scale=scale,
                                               window=window,
                                               use_kernel=use_kernel,
                                               mesh=None, ring=ring)

        return _tp_paged_call(mesh, body, (q_new, k_new, v_new),
                              (k_pages, v_pages),
                              (page_table, row_slot, row_pos), head_axis=1)

    # -- write: scatter every row's k/v into its slot's current page -----
    col = row_pos // page_size
    phys = page_table[row_slot, col % page_table.shape[1] if ring else col]
    off = row_pos % page_size
    ck = _write_rows(k_pages, phys, off, k_new)
    cv = _write_rows(v_pages, phys, off, v_new)
    out = _paged_read(q_new, ck, cv, k_new, page_table, row_slot, row_pos,
                      scale, window, ring, use_kernel)
    return out, ck, cv


def additive_attention_step(
    dec_state: Array,      # [B, Ds] decoder state for THIS timestep
    w: Array,              # [Ds, D] state transform
    v: Array,              # [D] scoring vector
    enc_proj: Array,       # [B, T, D] pre-projected encoder states
    enc_seq: Array,        # [B, T, Dv] encoder values
    mask: Optional[Array] = None,   # [B, T] validity
) -> Array:
    """One Bahdanau additive-attention step, fused (ref: the reference's
    simple_attention 5-layer composite — networks.py:1257: fc + expand +
    addto/tanh + sequence-softmax + scaling + seq-pool).

    Single expression so XLA fuses score computation, masking, softmax and
    the context reduction into one pass over [B, T, D] instead of
    materializing each composite layer's [B, T, D] intermediate — inside
    the decoder scan this is the bandwidth-bound hot path (PERF.md: seq2seq
    gains need fewer bytes/step, not fewer flops).  Returns [B, Dv].
    """
    from paddle_tpu.utils.dtypes import promote_compute

    s = jnp.einsum("btd,d->bt",
                   jnp.tanh(enc_proj + (dec_state @ w)[:, None, :]), v)
    s = promote_compute(s)                      # fp32 softmax
    if mask is not None:
        s = jnp.where(mask, s, jnp.asarray(-1e30, s.dtype))
    alpha = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bt,btd->bd", alpha.astype(enc_seq.dtype), enc_seq)
