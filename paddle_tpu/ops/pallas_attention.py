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
              delta - dlse, since dlse/ds_j = p_j.  dk/dv works on the
              TRANSPOSED tile [Bk, Bq] (k @ q^T), so p^T do and ds^T q are
              plain matmuls and lse / delta broadcast as the rows they are
              stored as.

Masking matches `dot_product_attention`: per-sequence key validity +
causality, fully-masked rows output exactly 0 with lse = -inf (so a ring
combine weighs them out naturally; the backward kernels' validity mask
already zeroes their p).  Query-row validity is applied OUTSIDE the kernel
(out *= q_mask): the zeroed cotangent then kills all gradient contributions
of invalid rows.

`q_offset` / `k_offset` (scalars, may be traced) globalize the causal
positions so a ring/context-parallel caller can run the kernel on one
(q-shard, k-shard) pair of a longer sequence — see
`ops/attention.py:ring_attention`'s flash path.  They ride the scalar-
prefetch channel (`pltpu.PrefetchScalarGridSpec`), so the index maps read
them too — traced offsets included, one path for every caller:

  dead tiles — a tile that causality or the window masks whole
              (`_tile_live` false) skips its matmuls, and the block index
              of what the inner axis walks (k/v and the key mask in
              forward and dq; q, do, lse and delta in dk/dv) is clamped to
              the live range (`_live_k_range` / `_live_q_range`).  Past the
              range the index stays at the last live tile's, so Pallas
              sees an unchanged block and issues no copy; before it
              (window) the first live block is fetched early.  The step is
              still taken: ~0.35 us each, a small share at shape-sized
              blocks.

  live tiles — a tile that lies wholly inside causality and the window
              (`_tile_inside`: most live tiles of a long causal sequence)
              builds no position mask; only a tile their edge crosses pays
              the iota / compare / select chain (on a v5e that chain was
              most of a forward call: 8.2 -> 2.4 ms at 512 x 1,024, T 4,096;
              PERF.md section 6, PR 32).  Key validity rides as an additive
              bias vector, never as a [Bq, Bk] compare.

Blocks come from the shape (`derive_blocks`): per kernel, the largest
(Bq, Bk) whose working set fits an estimate under Mosaic's scoped VMEM
limit.  Operands reach the MXU in the type they came in: bf16 inputs feed
bf16 operands with fp32 accumulation (p and ds are rounded to the input
type only as the second matmuls' operands); max, sum, lse, delta, the
accumulators and every exp stay fp32.  fp32 inputs keep fp32 operands at
`Precision.HIGHEST`.

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


def _pos_mask(q_lo, k_lo, shape, q_axis, causal, window):
    """Causality x sliding window of one score tile on GLOBAL positions
    (offsets cover ring/context-parallel shards).  `shape` is [Bq, Bk] with
    q rows on axis 0 (q_axis=0), or the transposed [Bk, Bq] tile dk/dv
    works on (q_axis=1)."""
    qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = None
    if causal:
        mask = kpos <= qpos
    if window is not None:
        band = jnp.abs(qpos - kpos) < window
        mask = band if mask is None else jnp.logical_and(mask, band)
    return mask


def _tile_edges(q_off, k_off, iq, ik, Bq, Bk):
    """First and last global q and k position of tile (iq, ik)."""
    q_lo = q_off + iq * Bq
    k_lo = k_off + ik * Bk
    return q_lo, q_lo + Bq - 1, k_lo, k_lo + Bk - 1


def _tile_live(q_off, k_off, iq, ik, Bq, Bk, causal, window):
    """False iff causality/window masks the ENTIRE tile — those tiles skip
    both matmuls (halves long-causal work; makes sliding-window cost
    O(T * window) instead of O(T^2))."""
    q_lo, q_hi, k_lo, k_hi = _tile_edges(q_off, k_off, iq, ik, Bq, Bk)
    live = True
    if causal:
        live = jnp.logical_and(live, k_lo <= q_hi)
    if window is not None:
        # tile intersects the |q - k| < window band
        live = jnp.logical_and(live, k_hi > q_lo - window)
        if not causal:
            live = jnp.logical_and(live, k_lo < q_hi + window)
    return live


def _tile_inside(q_off, k_off, iq, ik, Bq, Bk, causal, window):
    """True iff causality/window mask NOTHING of the tile: every (q, k)
    pair is allowed, so the tile runs without building a position mask
    (most live tiles of a long causal sequence lie wholly under the
    diagonal)."""
    q_lo, q_hi, k_lo, k_hi = _tile_edges(q_off, k_off, iq, ik, Bq, Bk)
    inside = True
    if causal:
        inside = jnp.logical_and(inside, k_hi <= q_lo)
    if window is not None:
        inside = jnp.logical_and(inside, q_hi - k_lo < window)
        if not causal:
            inside = jnp.logical_and(inside, k_hi - q_lo < window)
    return inside


def _for_live_tiles(q_off, k_off, iq, ik, Bq, Bk, causal, window, tile):
    """Run `tile(pos_mask: bool)` for a live tile: without a position mask
    where the tile lies wholly inside causality and the window, with one
    where their edge crosses it; not at all for a dead tile."""
    if not causal and window is None:
        tile(False)
        return
    live = _tile_live(q_off, k_off, iq, ik, Bq, Bk, causal, window)
    inside = _tile_inside(q_off, k_off, iq, ik, Bq, Bk, causal, window)
    pl.when(jnp.logical_and(live, inside))(lambda: tile(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(inside)))(
        lambda: tile(True))


def _scores(a, b, key_ok, q_lo, k_lo, q_axis, scale, causal, window,
            pos_mask, prec):
    """Masked scores of one tile, fp32: a @ b^T * scale with invalid keys
    and (if `pos_mask`) disallowed positions at _NEG_INF.  q_axis 0: a = q
    [Bq, D], b = k, key_ok a [1, Bk] row; q_axis 1 (dk/dv's transposed
    tile): a = k, b = q, key_ok a [Bk, 1] column.  Key validity rides as an
    additive bias (one add of a broadcast vector, no [Bq, Bk] compare)."""
    s = _dot(a, b, ((1,), (1,)), prec) * scale
    s = s + jnp.where(key_ok > 0.0, 0.0, _NEG_INF)
    if pos_mask:
        s = jnp.where(_pos_mask(q_lo, k_lo, s.shape, q_axis, causal, window),
                      s, _NEG_INF)
    return s


# ===========================================================================
# dead tiles: the live range of the inner axis, for index maps and counters
# ===========================================================================

def _live_k_range(d, Bq, Bk, causal, window):
    """(lo, hi) such that k tile ik is `_tile_live` for the q tile whose
    first row sits `d` = q_lo - k_off past the k shard's start iff
    lo <= ik <= hi (either bound may be None = unbounded; not yet clipped
    to the grid).  Python ints or traced int32 scalars."""
    lo = hi = None
    if causal:
        hi = (d + Bq - 1) // Bk                  # k_lo <= q_hi
    if window is not None:
        lo = (d - window + 1) // Bk              # k_hi > q_lo - window
        if not causal:
            hi = (d + Bq + window - 2) // Bk     # k_lo < q_hi + window
    return lo, hi


def _live_q_range(e, Bq, Bk, causal, window):
    """The mirror for dk/dv, whose inner axis walks q tiles: q tile iq is
    live for the k tile starting `e` = k_lo - q_off past the q shard's
    start iff lo <= iq <= hi."""
    lo = hi = None
    if causal:
        lo = e // Bq                             # k_lo <= q_hi
    if window is not None:
        hi = (e + Bk + window - 2) // Bq         # k_hi > q_lo - window
        if not causal:
            lo = (e - window + 1) // Bq          # k_lo < q_hi + window
    return lo, hi


def _clamp_tile(i, lo, hi, n):
    """Block index for inner step i: i itself inside the live range, the
    nearest live tile outside it (an unchanged index = no copy)."""
    if lo is None and hi is None:
        return i
    if lo is not None:
        i = jnp.maximum(i, lo)
    if hi is not None:
        i = jnp.minimum(i, hi)
    return jnp.clip(i, 0, n - 1)


def _count_live(ranges, n_inner):
    """Live tiles of a grid whose outer tiles have the given (lo, hi)
    ranges over n_inner inner tiles (Python ints)."""
    live = 0
    for lo, hi in ranges:
        lo = 0 if lo is None else max(lo, 0)
        hi = n_inner - 1 if hi is None else min(hi, n_inner - 1)
        live += max(0, hi - lo + 1)
    return live


# ===========================================================================
# blocks from the shape
# ===========================================================================

#: Mosaic's default scoped VMEM limit: what one kernel may hold without
#: asking for more (16 MiB; the chip's VMEM is larger)
_SCOPED_VMEM_BYTES = 16 * 2 ** 20
#: share of it the estimate may fill; the rest is Mosaic's own (spills,
#: relayouts, the edge tiles' position mask).  The estimate is on the safe
#: side: at T 4,096, D 128, bf16 every pair up to 2,048 x 1,024 compiled
#: and ran on a v5e (tools/tune_flash.py; 2,048 x 2,048 ran out of VMEM)
_VMEM_FILL = 0.9
_BLOCK_STEPS = (128, 256, 512, 1024)
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def vmem_estimate(kernel: str, Bq: int, Bk: int, D: int, itemsize: int) -> int:
    """Bytes of VMEM one grid step of `kernel` holds at blocks (Bq, Bk),
    padded head width D: the double-buffered blocks, the fp32 scratch and
    the [Bq, Bk] intermediates of the body (fp32, and their copies in the
    input type that feed the second matmuls)."""
    tile = Bq * Bk
    # [1, 1, B] fp32 row blocks (key mask, lse, delta) pad to 8 sublanes
    row = 2 * 4 * 8
    if kernel == "flash_fwd":
        blocks = 2 * itemsize * D * (2 * Bq + 2 * Bk)     # q, o; k, v
        rows = row * (Bk + Bq)                            # key mask; lse
        scratch = 4 * Bq * (D + 2 * 128)                  # acc; m, l
        inter = tile * (2 * 4 + itemsize)                 # s, p; p as input
    elif kernel == "flash_bwd_dq":
        blocks = 2 * itemsize * D * (3 * Bq + 2 * Bk)     # q, do, dq; k, v
        rows = row * (Bk + 2 * Bq)
        scratch = 4 * Bq * D
        inter = tile * (3 * 4 + itemsize)                 # p, dp, ds; ds
    else:
        blocks = 2 * itemsize * D * (2 * Bq + 4 * Bk)     # q, do; k, v, dk, dv
        rows = row * (Bk + 2 * Bq)
        scratch = 2 * 4 * Bk * D
        inter = tile * (3 * 4 + 2 * itemsize)             # p, dp, ds; p, ds
    return blocks + rows + scratch + inter


def _axis_blocks(T: int, sub: int) -> list:
    """Candidate blocks along a sequence of T rows: the whole (sublane-
    rounded) sequence when it is shorter than one 128-row tile; else the
    power-of-two multiples of 128 that do not pass the padded sequence and
    pad it by at most an eighth (128 always stays), and the whole sequence
    padded to 128 where one tile can hold it (T 600 is one 640 tile, not
    25 of 128).  Every candidate divides the padding of any larger one."""
    if _round_up(T, sub) <= 128:
        return [_round_up(T, sub)]
    Tp = _round_up(T, 128)
    steps = [b for b in _BLOCK_STEPS
             if b == 128 or (b <= Tp and _round_up(T, b) - T <= T // 8)]
    if Tp <= _BLOCK_STEPS[-1] and Tp not in steps:
        steps.append(Tp)
    return steps


def derive_blocks(Tq: int, Tk: int, D: int, dtype) -> dict:
    """{kernel: (Bq, Bk)} from the shape and the input type alone: per
    kernel the largest tile (by area, the wider k block on a tie — its
    copies are the ones a q tile repeats) whose `vmem_estimate` fits
    under `_VMEM_FILL` of the scoped limit.  Candidates nest
    (`_axis_blocks`), so the wrapper pads each axis once, to the largest
    block chosen.  A shape no candidate places keeps 128 x 128."""
    from paddle_tpu.utils.dtypes import sublane_min
    dtype = jnp.dtype(dtype)
    sub = sublane_min(jax.ShapeDtypeStruct((), dtype))
    Dp = _round_up(D, 128)
    cands = [(bq, bk) for bq in _axis_blocks(Tq, sub)
             for bk in _axis_blocks(Tk, sub)]
    budget = _VMEM_FILL * _SCOPED_VMEM_BYTES
    out = {}
    for kernel in KERNELS:
        fits = [c for c in cands
                if vmem_estimate(kernel, *c, Dp, dtype.itemsize) <= budget]
        out[kernel] = max(fits or cands[:1],
                          key=lambda c: (c[0] * c[1], c[1]))
    return out


_logged_blocks: set = set()


def _log_blocks(Tq, Tk, D, dtype, blocks, derived: bool) -> None:
    """The blocks a call runs at, logged once a signature."""
    sig = (Tq, Tk, D, str(dtype), blocks)
    if sig in _logged_blocks:
        return
    _logged_blocks.add(sig)
    from paddle_tpu.utils import get_logger
    get_logger("flash").info(
        "flash blocks (%s) for Tq=%d Tk=%d D=%d %s: %s",
        "derived" if derived else "given", Tq, Tk, D, dtype,
        ", ".join(f"{k} {bq}x{bk}" for k, (bq, bk) in zip(KERNELS, blocks)))


# ===========================================================================
# forward
# ===========================================================================

def _operand(x, prec):
    """A matmul operand: as it came in (bf16 inputs: one MXU pass, fp32
    accumulation), or fp32 for the `Precision.HIGHEST` path of fp32
    inputs."""
    return x if prec is None else x.astype(jnp.float32)


def _dot(a, b, contract, prec):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=prec)


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

    def tile(pos_mask):
        q = _operand(q_ref[0], prec)                     # [Bq, D]
        k = _operand(k_ref[0], prec)                     # [Bk, D]
        s = _scores(q, k, kv_ref[0], q_off + iq * Bq, k_off + ik * Bk, 0,
                    scale, causal, window, pos_mask, prec)
        m_prev, l_prev = m_s[:, :1], l_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row with no allowed key yet keeps m = _NEG_INF; the exponent's
        # reference stays above it so its masked scores give exactly 0 and
        # not exp(0) — no second [Bq, Bk] select
        p = jnp.exp(s - jnp.maximum(m_new, 0.1 * _NEG_INF))
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = _operand(v_ref[0], prec)
        pv = _dot(p.astype(v.dtype), v, ((1,), (0,)), prec)
        acc_s[:] = acc_s[:] * corr + pv
        m_s[:, :1] = m_new
        l_s[:, :1] = l_new

    _for_live_tiles(q_off, k_off, iq, ik, Bq, Bk, causal, window, tile)

    @pl.when(ik == nk - 1)
    def _():
        l = l_s[:, :1]
        o_ref[0] = jnp.where(l > 0, acc_s[:] / jnp.maximum(l, 1e-30),
                             0.0).astype(o_ref.dtype)
        # -inf for fully-masked rows: a ring combine weighs them out with
        # exp(lse - total) = 0, and the backward mask already zeroes p
        lse_ref[0, 0] = jnp.where(l[:, 0] > 0, m_s[:, 0] + jnp.log(l[:, 0]),
                                  -jnp.inf)


def _kv_index(H, H_kv):
    """Map the query-head grid index bh in [0, B*H) to its kv row in
    [0, B*H_kv) — grouped-query attention reads kv straight from the small
    [B*H_kv, Tk, D] array, never materializing the repeat in HBM."""
    rep = H // H_kv
    return lambda bh: (bh // H) * H_kv + (bh % H) // rep


def _in_kernel_precision(*arrays):
    """fp32 inputs get 3-pass (HIGHEST) in-kernel matmuls on fp32 operands
    — the MXU's default single-bf16-pass fp32 visibly diverges from a
    true-fp32 reference (0.02% of elements out at 2e-3 in an on-chip parity
    run since withdrawn, see ROADMAP S5); bf16 inputs (None) feed the MXU
    as they are, their tolerance already absorbs one bf16 rounding."""
    if any(a.dtype == jnp.float32 for a in arrays):
        return jax.lax.Precision.HIGHEST
    return None


def _qk_grid_specs(H, H_kv, nk, Bq, Bk, D, causal, window):
    """Block specs of the (B*H, nq, nk) grids (forward and dq): q-side
    blocks follow iq, k-side blocks follow ik clamped to the q tile's live
    range.  Index maps take the two prefetched offsets last."""
    kvi = _kv_index(H, H_kv)

    def ik_of(iq, ik, qoff, koff):
        lo, hi = _live_k_range(qoff[0] - koff[0] + iq * Bq, Bq, Bk, causal,
                               window)
        return _clamp_tile(ik, lo, hi, nk)

    q_spec = pl.BlockSpec((1, Bq, D), lambda bh, iq, ik, *_: (bh, iq, 0))
    kv_spec = pl.BlockSpec(
        (1, Bk, D), lambda bh, iq, ik, qo, ko: (kvi(bh), ik_of(iq, ik, qo, ko), 0))
    # 2-D arrays ride with a singleton middle dim: mosaic requires the
    # block's last-two dims be (8k, 128k) or equal the array's — a (1, Bk)
    # block on [B, Tk] has sublane dim 1 != B and is rejected on hardware
    # (interpret mode never checks)
    kmask_spec = pl.BlockSpec(
        (1, 1, Bk), lambda bh, iq, ik, qo, ko: (bh // H, 0, ik_of(iq, ik, qo, ko)))
    row_spec = pl.BlockSpec((1, 1, Bq), lambda bh, iq, ik, *_: (bh, 0, iq))
    return q_spec, kv_spec, kmask_spec, row_spec


def _record_grids(kernels, q, k, causal, window, blocks, static_off):
    """Trace-time counters (obs/metrics.py process counters, labelled by
    kernel): the grid steps each named kernel of this call asks for and how
    many of them are live tiles; `flash_live_tile_share` = live / stepped.
    Shapes as the kernels get them: q [B*H, Tq, D], k [B*H_kv, Tk, D]."""
    if static_off is None:
        return                  # traced offsets: the live count is unknown
    from paddle_tpu.obs.metrics import process_counters
    d = static_off[0] - static_off[1]
    BH, Tq, _ = q.shape
    Tk = k.shape[1]
    for kernel in kernels:
        Bq, Bk = blocks[KERNELS.index(kernel)]
        nq, nk = Tq // Bq, Tk // Bk
        if kernel == "flash_bwd_dkv":       # the swapped grid, k tiles outer
            live = _count_live(
                [_live_q_range(ik * Bk - d, Bq, Bk, causal, window)
                 for ik in range(nk)], nq)
        else:
            live = _count_live(
                [_live_k_range(d + iq * Bq, Bq, Bk, causal, window)
                 for iq in range(nq)], nk)
        for name, n in (("flash_grid_steps_total", nq * nk),
                        ("flash_live_tiles_total", live)):
            process_counters().add('%s{kernel="%s"}' % (name, kernel), BH * n)


# jitted, so that the layers of a model whose calls share a signature share
# ONE trace and ONE lowering of each kernel to Mosaic (a step program's
# jaxpr-to-MLIR conversion otherwise repeats it a layer); XLA inlines the
# calls, the kernels keep their names
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _fwd_call(q, k, v, kv_mask, q_off, k_off, H, scale, causal, window,
              blocks):
    Bq, Bk = blocks[0]
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    H_kv = k.shape[0] // (BH // H)
    nq, nk = Tq // Bq, Tk // Bk
    q_spec, kv_spec, kmask_spec, row_spec = _qk_grid_specs(
        H, H_kv, nk, Bq, Bk, D, causal, window)
    kernel = functools.partial(_fwd_kernel, H, Bq, Bk, scale, causal, window,
                               _in_kernel_precision(q, k, v))
    return pl.pallas_call(
        kernel,
        name="flash_fwd",       # the device op's name in a profiler trace
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                  # q_off, k_off
            grid=(BH, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec, kmask_spec],
            out_specs=[q_spec, row_spec],
            scratch_shapes=[
                pltpu.VMEM((Bq, 128), jnp.float32),   # running max (lane 0)
                pltpu.VMEM((Bq, 128), jnp.float32),   # running sum (lane 0)
                pltpu.VMEM((Bq, D), jnp.float32),     # output accumulator
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Tq), jnp.float32),
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

    def tile(pos_mask):
        q = _operand(q_ref[0], prec)                              # [Bq, D]
        k = _operand(k_ref[0], prec)                              # [Bk, D]
        s = _scores(q, k, kv_ref[0], q_off + iq * Bq, k_off + ik * Bk, 0,
                    scale, causal, window, pos_mask, prec)
        # lse arrives finite (_bwd_call): masked scores give exactly 0
        p = jnp.exp(s - lse_ref[0, 0][:, None])                   # [Bq, Bk]
        dp = _dot(_operand(do_ref[0], prec), _operand(v_ref[0], prec),
                  ((1,), (1,)), prec)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dq_s[:] += _dot(ds.astype(k.dtype), k, ((1,), (0,)), prec)

    _for_live_tiles(q_off, k_off, iq, ik, Bq, Bk, causal, window, tile)

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

    def tile(pos_mask):
        # the tile TRANSPOSED, [Bk, Bq]: dv = p^T do and dk = ds^T q are
        # then plain [Bk, Bq] x [Bq, D] matmuls (no [Bq, Bk] transpose),
        # and lse / delta broadcast as the rows they are stored as
        q = _operand(q_ref[0], prec)                              # [Bq, D]
        k = _operand(k_ref[0], prec)                              # [Bk, D]
        do = _operand(do_ref[0], prec)                            # [Bq, D]
        sT = _scores(k, q, kv_ref[0, 0][:, None], q_off + iq * Bq,
                     k_off + ik * Bk, 1, scale, causal, window, pos_mask,
                     prec)
        pT = jnp.exp(sT - lse_ref[0])                             # [Bk, Bq]
        dv_s[:] += _dot(pT.astype(do.dtype), do, ((1,), (0,)), prec)
        dpT = _dot(_operand(v_ref[0], prec), do, ((1,), (1,)), prec)
        dsT = pT * (dpT - delta_ref[0]) * scale
        dk_s[:] += _dot(dsT.astype(q.dtype), q, ((1,), (0,)), prec)

    _for_live_tiles(q_off, k_off, iq, ik, Bq, Bk, causal, window, tile)

    @pl.when(inner == n_inner - 1)
    def _():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(10, 11, 12, 13, 14))
def _bwd_call(q, k, v, kv_mask, q_off, k_off, o, lse, do, dlse,
              H, scale, causal, window, blocks):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    BHkv = k.shape[0]
    H_kv = BHkv // (BH // H)
    rep = H // H_kv
    # d lse/ds_j = p_j, so the lse cotangent folds into the delta term:
    # ds = p (dp - delta + dlse) = p (dp - (delta - dlse))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :] - dlse                 # [BH, 1, Tq]
    delta = jnp.where(jnp.isfinite(delta), delta, 0.0)
    # a fully-masked row's lse is -inf: the kernels' exp(s - lse) must give
    # 0 there, not exp(+inf)
    lse = jnp.where(jnp.isfinite(lse), lse, -_NEG_INF)
    prec = _in_kernel_precision(q, k, v)

    Bq, Bk = blocks[1]
    nq, nk = Tq // Bq, Tk // Bk
    q_spec, kv_spec, kmask_spec, row_spec = _qk_grid_specs(
        H, H_kv, nk, Bq, Bk, D, causal, window)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, H, Bq, Bk, scale, causal, window,
                          prec),
        name="flash_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec, kmask_spec, q_spec,
                      row_spec, row_spec],
            out_specs=[q_spec],
            scratch_shapes=[pltpu.VMEM((Bq, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((BH, Tq, D), q.dtype)],
        interpret=_interpret(),
    )(q_off, k_off, q, k, v, kv_mask, do, lse, delta)[0]

    # swapped grid: k tiles outer; the inner axis walks (group head, q tile)
    # pairs so grouped kv heads accumulate their whole group sequentially.
    # q-side blocks follow the q tile clamped to the k tile's live range
    Bq, Bk = blocks[2]
    nq, nk = Tq // Bq, Tk // Bk

    def bh_of(bhkv, inner):
        return (bhkv // H_kv) * H + (bhkv % H_kv) * rep + inner // nq

    def iq_of(ik, inner, qoff, koff):
        lo, hi = _live_q_range(koff[0] - qoff[0] + ik * Bk, Bq, Bk, causal,
                               window)
        return _clamp_tile(inner % nq, lo, hi, nq)

    q_spec2 = pl.BlockSpec(
        (1, Bq, D), lambda bhkv, ik, inner, qo, ko:
        (bh_of(bhkv, inner), iq_of(ik, inner, qo, ko), 0))
    kv_spec2 = pl.BlockSpec((1, Bk, D),
                            lambda bhkv, ik, inner, *_: (bhkv, ik, 0))
    kmask_spec2 = pl.BlockSpec(
        (1, 1, Bk), lambda bhkv, ik, inner, *_: (bhkv // H_kv, 0, ik))
    row_spec2 = pl.BlockSpec(
        (1, 1, Bq), lambda bhkv, ik, inner, qo, ko:
        (bh_of(bhkv, inner), 0, iq_of(ik, inner, qo, ko)))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, H, nq, Bq, Bk, scale, causal,
                          window, prec),
        name="flash_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BHkv, nk, rep * nq),
            in_specs=[q_spec2, kv_spec2, kv_spec2, kmask_spec2, q_spec2,
                      row_spec2, row_spec2],
            out_specs=[kv_spec2, kv_spec2],
            scratch_shapes=[pltpu.VMEM((Bk, D), jnp.float32),
                            pltpu.VMEM((Bk, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((BHkv, Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((BHkv, Tk, D), v.dtype)],
        interpret=_interpret(),
    )(q_off, k_off, q, k, v, kv_mask, do, lse, delta)
    return dq, dk, dv


# ===========================================================================
# custom-vjp wrapper (padded, [BH, T, D] layout)
# ===========================================================================
# nondiff: H, scale, causal, window, blocks = ((Bq, Bk) of forward, dq,
# dk/dv), static_off = (q_offset, k_offset) as Python ints or None if traced

def _forward(q, k, v, kv_mask, q_off, k_off, H, scale, causal, window,
             blocks, static_off):
    _record_grids(KERNELS[:1], q, k, causal, window, blocks, static_off)
    return _fwd_call(q, k, v, kv_mask, q_off, k_off, H, scale, causal,
                     window, blocks)


_flash = jax.custom_vjp(_forward, nondiff_argnums=(6, 7, 8, 9, 10, 11))


def _flash_fwd(q, k, v, kv_mask, q_off, k_off, *static):
    o, lse = _forward(q, k, v, kv_mask, q_off, k_off, *static)
    return (o, lse), (q, k, v, kv_mask, q_off, k_off, o, lse)


def _flash_bwd(H, scale, causal, window, blocks, static_off, res, cts):
    q, k, v, kv_mask, q_off, k_off, o, lse = res
    do, dlse = cts
    _record_grids(KERNELS[1:], q, k, causal, window, blocks, static_off)
    dq, dk, dv = _bwd_call(q, k, v, kv_mask, q_off, k_off, o, lse, do, dlse,
                           H, scale, causal, window, blocks)
    return dq, dk, dv, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: Array, k: Array, v: Array,
    q_valid: Optional[Array] = None,
    k_valid: Optional[Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    q_offset: Union[int, Array] = 0,
    k_offset: Union[int, Array] = 0,
    return_lse: bool = False,
    window: Optional[int] = None,
):
    """Drop-in for `dot_product_attention`: q [B,Tq,H,D], k/v [B,Tk,H,D]
    -> [B,Tq,H,D], same masking semantics, fused pallas execution.

    `block_q` / `block_k` left at None take `derive_blocks`' pick for the
    shape (forward, dq and dk/dv each their own); a given value wins on its
    axis, for all three kernels.

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
    # (8, 128): both the whole-sequence tile of a short sequence AND any
    # caller-chosen block must round up to the dtype's sublane minimum or
    # Mosaic rejects the block shapes
    from paddle_tpu.utils.dtypes import sublane_min
    sub = sublane_min(q, k, v)
    derived = derive_blocks(Tq, Tk, D, jnp.result_type(q, k, v))
    blocks = tuple(
        (_round_up(bq if block_q is None
                   else min(block_q, _round_up(Tq, sub)), sub),
         _round_up(bk if block_k is None
                   else min(block_k, _round_up(Tk, sub)), sub))
        for bq, bk in (derived[kern] for kern in KERNELS))
    # derived blocks nest, so the largest pads for all three kernels
    Tqp = _round_up(Tq, max(b[0] for b in blocks))
    Tkp = _round_up(Tk, max(b[1] for b in blocks))
    assert all(Tqp % bq == 0 and Tkp % bk == 0 for bq, bk in blocks), blocks
    Dp = _round_up(D, 128)
    _log_blocks(Tq, Tk, D, q.dtype, blocks,
                derived=block_q is None or block_k is None)

    def to_bh(x, T, Tp):
        x = jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0), (0, Dp - D)))
        return x.transpose(0, 2, 1, 3).reshape(B * x.shape[2], Tp, -1)

    qp = to_bh(q, Tq, Tqp)                   # [B*H, Tqp, Dp]
    kp = to_bh(k, Tk, Tkp)                   # [B*H_kv, Tkp, Dp] — kv stay
    vp = to_bh(v, Tk, Tkp)                   # at their grouped head count

    kv_mask = jnp.ones((B, Tk), jnp.float32) if k_valid is None \
        else k_valid.astype(jnp.float32)
    # singleton middle dim: see the mosaic block-rule note in _qk_grid_specs
    kv_mask = jnp.pad(kv_mask, ((0, 0), (0, Tkp - Tk)))[:, None, :]

    static_off = (q_offset, k_offset) \
        if isinstance(q_offset, int) and isinstance(k_offset, int) else None
    q_off = jnp.asarray(q_offset, jnp.int32).reshape(1)
    k_off = jnp.asarray(k_offset, jnp.int32).reshape(1)
    o, lse = _flash(qp, kp, vp, kv_mask, q_off, k_off,
                    H, float(scale), bool(causal),
                    None if window is None else int(window), blocks,
                    static_off)
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
