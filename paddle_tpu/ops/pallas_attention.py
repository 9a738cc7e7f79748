"""Flash attention in Pallas (TPU) — fused online-softmax attention.

TPU-native "hot op" for the long-context path (NEW capability beyond the
reference, whose closest analog is the additive simple_attention composite,
ref: python/paddle/trainer_config_helpers/networks.py:1257).  The scan-based
`ops/attention.py:blockwise_attention` stays as the portable fallback; this
kernel computes the same math with the score tile resident in VMEM:

  forward   — grid (B*H, Tq/Bq, Tk/Bk): for one query tile, fold key/value
              tiles into the running (max, sum, acc) online-softmax state
              held in VMEM scratch across the sequential innermost grid
              axis; one [Bq,D]x[D,Bk] + one [Bq,Bk]x[Bk,D] MXU matmul per
              tile, no [Tq,Tk] score matrix in HBM.
  backward  — custom_vjp (FlashAttention-2 style): the forward saves only
              the per-row log-sum-exp; two kernels recompute the score
              tiles and produce dq (grid over q tiles) and dk/dv (grid
              over k tiles).  delta = rowsum(do * o) is precomputed, and an
              lse cotangent (from a ring combine) folds into it as
              delta - dlse, since dlse/ds_j = p_j.

Masking matches `dot_product_attention`: per-sequence key validity +
causality, fully-masked rows output exactly 0 with lse = -inf (so a ring
combine weighs them out naturally; the backward kernels' validity mask
already zeroes their p).  Query-row validity is applied OUTSIDE the kernel
(out *= q_mask): the zeroed cotangent then kills all gradient contributions
of invalid rows.

`q_offset` / `k_offset` (SMEM scalars, may be traced) globalize the causal
positions so a ring/context-parallel caller can run the kernel on one
(q-shard, k-shard) pair of a longer sequence — see
`ops/attention.py:ring_attention`'s flash path.

Head dim and sequence lengths are zero-padded to tile multiples (lane dim
128); zero k/v padding columns are inert in the dot products and padded key
rows are masked invalid.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_NEG_INF = -1e30


def supported(backend: Optional[str] = None) -> bool:
    """Whether the pallas flash kernel may be used."""
    if os.environ.get("PADDLE_TPU_PALLAS", "1") == "0":
        return False
    backend = backend or jax.default_backend()
    if backend == "tpu":
        return True
    # off-TPU the kernel only runs in (slow) interpret mode — opt-in for tests
    return os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _tile_mask(kv_row, q_off, k_off, iq, ik, Bq, Bk, causal, window):
    """[Bq, Bk] validity of one score tile: key validity x causality x
    sliding window, on GLOBAL positions (offsets cover ring/context-parallel
    shards)."""
    mask = jnp.broadcast_to((kv_row > 0.0)[None, :], (Bq, Bk))
    if causal or window is not None:
        qpos = q_off + iq * Bq + jax.lax.broadcasted_iota(
            jnp.int32, (Bq, Bk), 0)
        kpos = k_off + ik * Bk + jax.lax.broadcasted_iota(
            jnp.int32, (Bq, Bk), 1)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, jnp.abs(qpos - kpos) < window)
    return mask


def _tile_live(q_off, k_off, iq, ik, Bq, Bk, causal, window):
    """False iff causality/window masks the ENTIRE tile — those tiles skip
    both matmuls (halves long-causal work; makes sliding-window cost
    O(T * window) instead of O(T^2))."""
    q_lo = q_off + iq * Bq
    q_hi = q_lo + Bq - 1
    k_lo = k_off + ik * Bk
    k_hi = k_lo + Bk - 1
    live = True
    if causal:
        live = jnp.logical_and(live, k_lo <= q_hi)
    if window is not None:
        # tile intersects the |q - k| < window band
        live = jnp.logical_and(live, k_hi > q_lo - window)
        if not causal:
            live = jnp.logical_and(live, k_lo < q_hi + window)
    return live


# ===========================================================================
# forward
# ===========================================================================

def _fwd_kernel(H, Bq, Bk, scale, causal, window, prec,
                qoff_ref, koff_ref, q_ref, k_ref, v_ref, kv_ref,
                o_ref, lse_ref, m_s, l_s, acc_s):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)
    # m/l live in the first lane of a [Bq, 128] scratch (TPU tiles are
    # 128-lane; a [Bq, 1] buffer would violate the minimum tile)

    q_off, k_off = qoff_ref[0], koff_ref[0]

    @pl.when(_tile_live(q_off, k_off, iq, ik, Bq, Bk, causal, window))
    def _():
        q = q_ref[0].astype(jnp.float32)                 # [Bq, D]
        k = k_ref[0].astype(jnp.float32)                 # [Bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=prec) * scale
        mask = _tile_mask(kv_ref[0, 0], q_off, k_off, iq, ik, Bq, Bk, causal,
                          window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev, l_prev = m_s[:, :1], l_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)                      # kill -inf rows
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v_ref[0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=prec)
        acc_s[:] = acc_s[:] * corr + pv
        m_s[:, :1] = m_new
        l_s[:, :1] = l_new

    @pl.when(ik == nk - 1)
    def _():
        l = l_s[:, :1]
        o_ref[0] = jnp.where(l > 0, acc_s[:] / jnp.maximum(l, 1e-30),
                             0.0).astype(o_ref.dtype)
        # -inf for fully-masked rows: a ring combine weighs them out with
        # exp(lse - total) = 0, and the backward mask already zeroes p
        lse_ref[0, 0] = jnp.where(l[:, 0] > 0, m_s[:, 0] + jnp.log(l[:, 0]),
                                  -jnp.inf)


def _scalar_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _kv_index(H, H_kv):
    """Map the query-head grid index bh in [0, B*H) to its kv row in
    [0, B*H_kv) — grouped-query attention reads kv straight from the small
    [B*H_kv, Tk, D] array, never materializing the repeat in HBM."""
    rep = H // H_kv
    return lambda bh: (bh // H) * H_kv + (bh % H) // rep


def _in_kernel_precision(*arrays):
    """fp32 inputs get 3-pass (HIGHEST) in-kernel matmuls — the MXU's
    default single-bf16-pass fp32 visibly diverges from a true-fp32
    reference (0.02% of elements out at 2e-3 in an on-chip parity run
    since withdrawn, see ROADMAP S5); bf16 inputs keep the fast default,
    their tolerance
    already absorbs one bf16 rounding."""
    if any(a.dtype == jnp.float32 for a in arrays):
        return jax.lax.Precision.HIGHEST
    return None


def _fwd_call(q, k, v, kv_mask, q_off, k_off, H, scale, causal, window,
              Bq, Bk):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    H_kv = k.shape[0] // (BH // H)
    kvi = _kv_index(H, H_kv)
    nq, nk = Tq // Bq, Tk // Bk
    kernel = functools.partial(_fwd_kernel, H, Bq, Bk, scale, causal, window,
                               _in_kernel_precision(q, k, v))
    return pl.pallas_call(
        kernel,
        name="flash_fwd",       # the device op's name in a profiler trace
        grid=(BH, nq, nk),
        in_specs=[
            _scalar_spec(),
            _scalar_spec(),
            pl.BlockSpec((1, Bq, D), lambda bh, iq, ik: (bh, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Bk, D), lambda bh, iq, ik: (kvi(bh), ik, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Bk, D), lambda bh, iq, ik: (kvi(bh), ik, 0),
                         memory_space=pltpu.VMEM),
            # 2-D arrays ride with a singleton middle dim: mosaic requires
            # the block's last-two dims be (8k, 128k) or equal the array's —
            # a (1, Bk) block on [B, Tk] has sublane dim 1 != B and is
            # rejected on hardware (interpret mode never checks)
            pl.BlockSpec((1, 1, Bk), lambda bh, iq, ik: (bh // H, 0, ik),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, Bq, D), lambda bh, iq, ik: (bh, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, Bq), lambda bh, iq, ik: (bh, 0, iq),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Bq, 128), jnp.float32),   # running max (lane 0)
            pltpu.VMEM((Bq, 128), jnp.float32),   # running sum (lane 0)
            pltpu.VMEM((Bq, D), jnp.float32),     # output accumulator
        ],
        interpret=_interpret(),
    )(q_off, k_off, q, k, v, kv_mask)


# ===========================================================================
# backward
# ===========================================================================

def _bwd_dq_kernel(H, Bq, Bk, scale, causal, window, prec,
                   qoff_ref, koff_ref,
                   q_ref, k_ref, v_ref, kv_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_s):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _():
        dq_s[:] = jnp.zeros_like(dq_s)

    q_off, k_off = qoff_ref[0], koff_ref[0]

    @pl.when(_tile_live(q_off, k_off, iq, ik, Bq, Bk, causal, window))
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=prec) * scale
        mask = _tile_mask(kv_ref[0, 0], q_off, k_off, iq, ik, Bq, Bk, causal,
                          window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0][:, None]), 0.0)  # [Bq, Bk]

        do = do_ref[0].astype(jnp.float32)                          # [Bq, D]
        dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=prec)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dq_s[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32,
                                       precision=prec)

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(H, nq, Bq, Bk, scale, causal, window, prec,
                    qoff_ref, koff_ref,
                    q_ref, k_ref, v_ref, kv_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_s, dv_s):
    # grid (B*H_kv, nk, rep*nq): the sequential inner axis walks every
    # (query head of the group) x (q tile) pair, so one program owns each
    # dk/dv block and grouped-query heads accumulate without HBM expansion
    ik, inner = pl.program_id(1), pl.program_id(2)
    n_inner = pl.num_programs(2)
    iq = inner % nq

    @pl.when(inner == 0)
    def _():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    q_off, k_off = qoff_ref[0], koff_ref[0]

    @pl.when(_tile_live(q_off, k_off, iq, ik, Bq, Bk, causal, window))
    def _():
        q = q_ref[0].astype(jnp.float32)                          # [Bq, D]
        k = k_ref[0].astype(jnp.float32)                          # [Bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=prec) * scale
        mask = _tile_mask(kv_ref[0, 0], q_off, k_off, iq, ik, Bq, Bk, causal,
                          window)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0][:, None]), 0.0)  # [Bq, Bk]

        do = do_ref[0].astype(jnp.float32)                          # [Bq, D]
        # dv += p^T @ do
        dv_s[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32,
                                       precision=prec)
        dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=prec)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        # dk += ds^T @ q
        dk_s[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32,
                                       precision=prec)

    @pl.when(inner == n_inner - 1)
    def _():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _bwd_call(q, k, v, kv_mask, q_off, k_off, o, lse, do, dlse,
              H, scale, causal, window, Bq, Bk):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    BHkv = k.shape[0]
    H_kv = BHkv // (BH // H)
    rep = H // H_kv
    kvi = _kv_index(H, H_kv)
    nq, nk = Tq // Bq, Tk // Bk
    # d lse/ds_j = p_j, so the lse cotangent folds into the delta term:
    # ds = p (dp - delta + dlse) = p (dp - (delta - dlse))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :] - dlse                 # [BH, 1, Tq]
    delta = jnp.where(jnp.isfinite(delta), delta, 0.0)

    q_spec = pl.BlockSpec((1, Bq, D), lambda bh, iq, ik: (bh, iq, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, Bk, D), lambda bh, iq, ik: (kvi(bh), ik, 0),
                           memory_space=pltpu.VMEM)
    kmask_spec = pl.BlockSpec((1, 1, Bk), lambda bh, iq, ik: (bh // H, 0, ik),
                              memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, Bq), lambda bh, iq, ik: (bh, 0, iq),
                            memory_space=pltpu.VMEM)

    prec = _in_kernel_precision(q, k, v)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, H, Bq, Bk, scale, causal, window,
                          prec),
        name="flash_bwd_dq",
        grid=(BH, nq, nk),
        in_specs=[_scalar_spec(), _scalar_spec(),
                  q_spec, kv_spec, kv_spec, kmask_spec, q_spec,
                  row_spec, row_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((BH, Tq, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((Bq, D), jnp.float32)],
        interpret=_interpret(),
    )(q_off, k_off, q, k, v, kv_mask, do, lse, delta)[0]

    # swapped grid: k tiles outer; the inner axis walks (group head, q tile)
    # pairs so grouped kv heads accumulate their whole group sequentially
    def bh_of(bhkv, inner):
        return (bhkv // H_kv) * H + (bhkv % H_kv) * rep + inner // nq

    q_spec2 = pl.BlockSpec(
        (1, Bq, D), lambda bhkv, ik, inner: (bh_of(bhkv, inner), inner % nq, 0),
        memory_space=pltpu.VMEM)
    kv_spec2 = pl.BlockSpec((1, Bk, D), lambda bhkv, ik, inner: (bhkv, ik, 0),
                            memory_space=pltpu.VMEM)
    kmask_spec2 = pl.BlockSpec(
        (1, 1, Bk), lambda bhkv, ik, inner: (bhkv // H_kv, 0, ik),
        memory_space=pltpu.VMEM)
    row_spec2 = pl.BlockSpec(
        (1, 1, Bq), lambda bhkv, ik, inner: (bh_of(bhkv, inner), 0, inner % nq),
        memory_space=pltpu.VMEM)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, H, nq, Bq, Bk, scale, causal,
                          window, prec),
        name="flash_bwd_dkv",
        grid=(BHkv, nk, rep * nq),
        in_specs=[_scalar_spec(), _scalar_spec(),
                  q_spec2, kv_spec2, kv_spec2, kmask_spec2, q_spec2,
                  row_spec2, row_spec2],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[jax.ShapeDtypeStruct((BHkv, Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((BHkv, Tk, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((Bk, D), jnp.float32),
                        pltpu.VMEM((Bk, D), jnp.float32)],
        interpret=_interpret(),
    )(q_off, k_off, q, k, v, kv_mask, do, lse, delta)
    return dq, dk, dv


# ===========================================================================
# custom-vjp wrapper (padded, [BH, T, D] layout)
# ===========================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash(q, k, v, kv_mask, q_off, k_off, H, scale, causal, window, Bq, Bk):
    return _fwd_call(q, k, v, kv_mask, q_off, k_off, H, scale, causal,
                     window, Bq, Bk)


def _flash_fwd(q, k, v, kv_mask, q_off, k_off, H, scale, causal, window,
               Bq, Bk):
    o, lse = _fwd_call(q, k, v, kv_mask, q_off, k_off, H, scale, causal,
                       window, Bq, Bk)
    return (o, lse), (q, k, v, kv_mask, q_off, k_off, o, lse)


def _flash_bwd(H, scale, causal, window, Bq, Bk, res, cts):
    q, k, v, kv_mask, q_off, k_off, o, lse = res
    do, dlse = cts
    dq, dk, dv = _bwd_call(q, k, v, kv_mask, q_off, k_off, o, lse, do, dlse,
                           H, scale, causal, window, Bq, Bk)
    return dq, dk, dv, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: Array, k: Array, v: Array,
    q_valid: Optional[Array] = None,
    k_valid: Optional[Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    q_offset: Union[int, Array] = 0,
    k_offset: Union[int, Array] = 0,
    return_lse: bool = False,
    window: Optional[int] = None,
):
    """Drop-in for `dot_product_attention`: q [B,Tq,H,D], k/v [B,Tk,H,D]
    -> [B,Tq,H,D], same masking semantics, fused pallas execution.

    With `return_lse`, also returns the per-row log-sum-exp [B, H, Tq]
    (fp32; -inf for fully-masked rows) so a context-parallel caller can
    combine per-shard results; q_offset/k_offset globalize the causal
    positions for such shard calls (scalars, may be traced)."""
    B, Tq, H, D = q.shape
    H_kv = k.shape[2]
    assert H % H_kv == 0, \
        f"num_heads {H} not divisible by num_kv_heads {H_kv}"
    Tk = k.shape[1]
    if scale is None:
        scale = D ** -0.5

    # low-precision (bf16/fp16) minimum TPU tile is (16, 128) vs fp32's
    # (8, 128): both the auto-sized tile for short sequences AND any
    # caller-chosen block must round up to the dtype's sublane minimum or
    # Mosaic rejects the block shapes
    from paddle_tpu.utils.dtypes import sublane_min
    sub = sublane_min(q, k, v)
    Bq = _round_up(min(block_q, _round_up(Tq, sub)), sub)
    Bk = _round_up(min(block_k, _round_up(Tk, sub)), sub)
    Tqp, Tkp = _round_up(Tq, Bq), _round_up(Tk, Bk)
    Dp = _round_up(D, 128)

    def to_bh(x, T, Tp):
        x = jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0), (0, Dp - D)))
        return x.transpose(0, 2, 1, 3).reshape(B * x.shape[2], Tp, -1)

    qp = to_bh(q, Tq, Tqp)                   # [B*H, Tqp, Dp]
    kp = to_bh(k, Tk, Tkp)                   # [B*H_kv, Tkp, Dp] — kv stay
    vp = to_bh(v, Tk, Tkp)                   # at their grouped head count

    kv_mask = jnp.ones((B, Tk), jnp.float32) if k_valid is None \
        else k_valid.astype(jnp.float32)
    # singleton middle dim: see the mosaic block-rule note in _fwd_call
    kv_mask = jnp.pad(kv_mask, ((0, 0), (0, Tkp - Tk)))[:, None, :]

    q_off = jnp.asarray(q_offset, jnp.int32).reshape(1)
    k_off = jnp.asarray(k_offset, jnp.int32).reshape(1)
    o, lse = _flash(qp, kp, vp, kv_mask, q_off, k_off,
                    H, float(scale), bool(causal),
                    None if window is None else int(window), Bq, Bk)
    o = o.reshape(B, H, Tqp, Dp).transpose(0, 2, 1, 3)[:, :Tq, :, :D]
    if q_valid is not None:
        # invalid query rows output exactly 0; the zeroed cotangent also
        # kills their dk/dv contributions in the backward kernels
        o = o * q_valid[:, :, None, None].astype(o.dtype)
    if not return_lse:
        return o
    lse = lse.reshape(B, H, Tqp)[:, :, :Tq]
    if q_valid is not None:
        lse = jnp.where(q_valid[:, None, :], lse, -jnp.inf)
    return o, lse
