"""The gated delta rule, a linear attention whose context is a state a
head: Kimi Delta Attention (KDA, arXiv:2510.26692), whose decay is per
CHANNEL, and Gated DeltaNet (arXiv:2412.06464), whose decay is ONE number a
head — `g` [..., H, dk] or [..., H], read from its rank in every form here,
nothing else differs.  A head keeps a state S [dk, dv] (dk != dv is fine:
Olmo-Hybrid's is 96 x 192):

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T (q_t * dk^-1/2)

with a_t = exp(g_t) in (0, 1)^dk (one number repeated, for a decay a
head) and b_t in (0, 1) — in (0, 2) where the
layer asks for negative eigenvalues (`allow_neg_eigval`: with unit-norm k a
transition I - b k k^T then has an eigenvalue 1 - b in (-1, 1), still a
contraction; every form here takes b as data).  Written as a rank-1
update: S' = Diag(a_t) S_{t-1}, u_t = b_t (v_t - S'^T k_t), S_t = S' + k_t
u_t^T.  Everything here is float32 whatever the inputs' dtype, and the
matmuls of the chunkwise form ask for full float32 precision (the TPU's
default single bf16 pass is not the recurrence's arithmetic).

Four forms, one result:
  * `recurrent`  — the literal per-token scan: the CPU oracle of the tests
    and of both kernels (ops/pallas_kda.py, ops/pallas_kda_seg.py);
  * `chunkwise`  — the WY / UT-transform form, `CHUNK` tokens at a time:
    inside a chunk the pseudo-values u solve (I + A) u = b (v - K~ S_0),
    A strictly lower triangular, and the state moves once a chunk.  Decay
    ratios are taken pairwise, exp(G_t - G_j) with j <= t, so no exponent
    is ever positive.  Rows with g = 0 and b = 0 leave the state as it
    was: that is how padding and the rows of other segments are masked.
    The whole-sequence path (graph/layers_kda.py with no slot state:
    training, any graph run without slots — it has the backward), and the
    body of `segment_rows` where the kernel is not used;
  * `step_rows`  — one token a row against a pool of slot states: the
    decode step, and the decode rows of the ragged mixed step (`kda_step`
    on the TPU, `gdn_step` for a decay a head);
  * `segment_rows` — the chunk rows of the ragged mixed step, each slot's
    run of rows one segment from its slot's state.  On the TPU one
    `kda_seg` call a layer (`gdn_seg` for a decay a head, whose pairwise
    decays are one [CHUNK, CHUNK] matrix; ops/pallas_kda_seg.py: the same chunkwise
    mathematics, the runs' and chunks' loops inside the kernel, the state
    resident in VMEM, the pairwise decays never in HBM; forward only);
    elsewhere `chunkwise` once a run under ops/slot_rows.py
    `advance_segments`.  The two share `CHUNK`, the mathematics and
    nothing else.

The causal depthwise convolution over the last `taps` positions in front
of q, k and v is ops/short_conv.py's, shared with the gated
short-convolution mixer (graph/layers_sconv.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops import slot_rows

_HI = jax.lax.Precision.HIGHEST
# tokens a chunk of the chunkwise form: part of the arithmetic (the order
# the float32 recurrence is summed in), so a constant and no layer's knob
CHUNK = 64


def l2norm(x, eps: float = 1e-6):
    """x / sqrt(sum(x^2) + eps) over the last dim, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def per_head(g, k) -> bool:
    """Whether `g` is one log decay a head ([..., H]) beside k [..., H, dk],
    not one a channel ([..., H, dk])."""
    return g.ndim == k.ndim - 1


def decay(f, a_log, dt_bias):
    """The log decay g = -exp(A_log_h) * softplus(f + dt_bias) <= 0: a_log
    [H]; f [..., H, dk] the gate projection and dt_bias [H, dk] for a decay
    a channel, f [..., H] and dt_bias [H] for one a head."""
    f = f.astype(jnp.float32) + dt_bias.astype(jnp.float32)
    a = -jnp.exp(a_log.astype(jnp.float32))
    return (a if dt_bias.ndim == 1 else a[:, None]) * jax.nn.softplus(f)


# ---------------------------------------------------------------------------
# the recurrence
# ---------------------------------------------------------------------------

def step(S, q, k, v, g, beta, scale: float):
    """One token: S [..., dk, dv], q k [..., dk], g [..., dk] or [...] (a
    decay a head), v [..., dv], beta [...] -> (o [..., dv], S_new).
    Elementwise products and reductions only."""
    a = jnp.exp(g)
    S = S * (a[..., None, None] if per_head(g, k) else a[..., :, None])
    u = beta[..., None] * (v - jnp.sum(S * k[..., :, None], axis=-2))
    S = S + k[..., :, None] * u[..., None, :]
    o = jnp.sum(S * (q * scale)[..., :, None], axis=-2)
    return o, S


def recurrent(q, k, v, g, beta, S0=None):
    """The literal recurrence over T: q k [B, T, H, dk], g [B, T, H, dk] or
    [B, T, H], v [B, T, H, dv], beta [B, T, H] -> (o [B, T, H, dv] float32,
    S [B, H, dk, dv])."""
    f32 = lambda a: a.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    B, T, H, dk = q.shape
    if S0 is None:
        S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    scale = dk ** -0.5

    def body(S, xs):
        o, S = step(S, *xs, scale)
        return S, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    S, o = jax.lax.scan(body, f32(S0), xs)
    return jnp.moveaxis(o, 0, 1), S


def chunkwise(q, k, v, g, beta, S0=None, live=None):
    """The chunkwise form of `recurrent`, same arguments and results.  T
    is padded to whole chunks with rows that leave the state alone.  `live`
    [T] bool (shared by the batch) marks the rows that matter: a chunk with
    no live row is skipped whole — its rows must already be masked (g = 0,
    b = 0), its outputs are zeros — so a short segment of a long row list
    costs its own chunks, not the list's."""
    f32 = lambda a: a.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    scalar = per_head(g, k)
    if scalar:
        g = g[..., None]                # [B, T, H, 1]: broadcasts over dk
    C = min(CHUNK, T)
    N = -(-T // C)
    pad = N * C - T
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) +
                                    ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    # [N, B, H, C, .]: the scan runs over chunks
    cut = lambda a: jnp.moveaxis(
        a.reshape((B, N, C) + a.shape[2:]), (1, 3), (0, 2))
    q, k, v, g = map(cut, (q, k, v, g))
    beta = cut(beta[..., None])                          # [N, B, H, C, 1]
    any_live = jnp.ones((N,), bool) if live is None else \
        jnp.any(jnp.pad(live, (0, pad)).reshape(N, C), axis=1)
    t_idx = jnp.arange(C)
    lower = t_idx[:, None] >= t_idx[None, :]
    strict = lower & ~jnp.eye(C, dtype=bool)
    eye = jnp.eye(C, dtype=jnp.float32)
    scale = dk ** -0.5

    def one_chunk(S, q, k, v, g, beta):
        q = q * scale
        G = jnp.cumsum(g, axis=2)                        # <= 0, falling
        # pairwise decay exp(G_t - G_j) for j <= t, zero above the diagonal
        D = G[..., :, None, :] - G[..., None, :, :]      # [B, H, C, C, dk|1]
        E = jnp.exp(jnp.where(lower[:, :, None], D, -jnp.inf))
        if scalar:
            # a decay a head: ONE [C, C] matrix scales both products
            kk = jnp.einsum("bhtd,bhjd->bhtj", k, k, precision=_HI) * E[..., 0]
            qk = jnp.einsum("bhtd,bhjd->bhtj", q, k, precision=_HI) * E[..., 0]
        else:
            kk = jnp.sum(k[..., :, None, :] * k[..., None, :, :] * E, axis=-1)
            qk = jnp.sum(q[..., :, None, :] * k[..., None, :, :] * E, axis=-1)
        A = jnp.where(strict, beta * kk, 0.0)
        k_in = k * jnp.exp(G)                            # decayed from S_0
        WU = jax.scipy.linalg.solve_triangular(
            eye + A, jnp.concatenate([beta * k_in, beta * v], axis=-1),
            lower=True, unit_diagonal=True)
        W, U = WU[..., :dk], WU[..., dk:]
        G_last = G[..., -1:, :]                          # [B, H, 1, dk]
        u = U - jnp.einsum("bhck,bhkv->bhcv", W, S, precision=_HI)
        o = jnp.einsum("bhck,bhkv->bhcv", q * jnp.exp(G), S,
                       precision=_HI) + \
            jnp.einsum("bhcj,bhjv->bhcv", qk, u, precision=_HI)
        S = jnp.exp(G_last[..., 0, :])[..., None] * S + \
            jnp.einsum("bhck,bhcv->bhkv", k * jnp.exp(G_last - G), u,
                       precision=_HI)                    # decayed to the end
        return S, o

    def body(S, xs):
        some, rest = xs[0], xs[1:]
        return jax.lax.cond(
            some, lambda S: one_chunk(S, *rest),
            lambda S: (S, jnp.zeros((B, H, C, dv), jnp.float32)), S)

    if S0 is None:
        S0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    S, o = jax.lax.scan(body, f32(S0), (any_live, q, k, v, g, beta))
    o = jnp.moveaxis(o, (0, 2), (1, 3))                  # [B, N, C, H, dv]
    return o.reshape(B, N * C, H, dv)[:, :T], S


def step_rows(state, slot, live, q, k, v, g, beta, use_kernel: bool = False):
    """One token a row against the slot states: state [S+1, H, dk, dv]
    float32 (row S is trash), slot [R] int32 the state each row advances
    (None: row r is slot r, the decode step), live [R] bool (a row that is
    paused or padding leaves every state as it was), q k [R, H, dk], g
    [R, H, dk] or [R, H], v [R, H, dv], beta [R, H] -> (o [R, H, dv]
    float32, state).  Each live
    slot's state is read once and written once."""
    f32 = lambda a: a.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    scale = q.shape[-1] ** -0.5
    if use_kernel:
        R, trash = q.shape[0], state.shape[0] - 1
        from paddle_tpu.ops import pallas_kda
        rows = jnp.arange(R, dtype=jnp.int32) if slot is None else slot
        return pallas_kda.kda_step(state, jnp.where(live, rows, trash), live,
                                   q, k, v, g, beta, scale)
    return slot_rows.advance_rows(
        state, slot, live, lambda S: step(S, q, k, v, g, beta, scale))


def segment_rows(state, seg_slot, seg_pos, q, k, v, g, beta,
                 use_kernel: bool = False):
    """The chunk rows of a ragged mixed step: P packed rows holding whole
    runs of slots, contiguous and in order (`seg_slot` [P], trash row S =
    padding; `seg_pos` [P] global positions).  Each run is one segment: it
    starts from its slot's state — from zero where its first row is
    position 0 — goes through the chunkwise form once, chunks of `CHUNK`
    rows from its own first row, and leaves the state it ends in.  On the
    TPU that is one `kda_seg` call (ops/pallas_kda_seg.py: the runs and
    their chunks loops inside the kernel, the state resident); elsewhere
    `chunkwise` over the whole list once a run present with the other rows
    masked (ops/slot_rows.py `advance_segments`), the chunks that hold none
    of its rows skipped.  Returns (o [P, H, dv] float32, state,
    n_segments)."""
    f32 = lambda a: a.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    if use_kernel:
        from paddle_tpu.ops import pallas_kda_seg
        return pallas_kda_seg.kda_segments(state, seg_slot, seg_pos, q, k, v,
                                           g, beta, q.shape[-1] ** -0.5)

    def one_segment(S0, mine):
        m = mine[:, None]
        o_i, S_end = chunkwise(
            q[None], k[None], v[None],
            jnp.where(m if per_head(g, k) else m[..., None], g, 0.0)[None],
            jnp.where(m, beta, 0.0)[None], S0[None], live=mine)
        return o_i[0], S_end[0]

    return slot_rows.advance_segments(
        state, seg_slot, seg_pos,
        jnp.zeros(seg_slot.shape + v.shape[1:], jnp.float32), one_segment)


def gated_out_norm(o, gate, scale, eps: float, act=jax.nn.sigmoid):
    """RMSNorm over each head's dv with a learned scale, times act(gate)
    (KDA's sigmoid, Gated DeltaNet's silu): o gate [..., H, dv], scale [dv]
    -> float32."""
    o = o.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * scale.astype(jnp.float32) * act(gate.astype(jnp.float32))
