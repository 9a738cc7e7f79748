"""The Mamba-2 selective state-space recurrence (SSD, arXiv:2405.21060) with
a SCALAR decay a head.  A head h of group g(h) keeps a state S [P, N]:

    a_t = exp(dt_t * A_h)                         A_h = -exp(A_log_h) < 0
    S_t = a_t S_{t-1} + dt_t x_t (x) B_t[g(h)]    x_t [P], B_t C_t [N]
    y_t = S_t C_t[g(h)] + D_h x_t

with dt_t = softplus(dt_raw + dt_bias) > 0 a head.  The decays, dt and the
state are float32 whatever the inputs' dtype; the matmuls of the chunkwise
form take x, B and C in the dtype they arrive in (the compute dtype) with
float32 accumulation, and the products that read or move the STATE ask for
full float32 precision.

Three forms, one result:
  * `recurrent`  — the literal per-token scan: the CPU oracle of the tests
    and of the step kernel (ops/pallas_kda.py `ssd_step`);
  * `chunkwise`  — `chunk` tokens at a time: inside a chunk the scalar
    decays make the causal part a masked matmul, L * (C B^T) with L_tj =
    exp(G_t - G_j) for j <= t (G the running sum of dt A: no exponent is
    ever positive), and the state moves once a chunk — no triangular solve,
    unlike the delta rule (ops/kda.py).  Rows with dt = 0 leave the state
    as it was: that is how padding and the rows of other segments are
    masked;
  * `step_rows`  — one token a row against a pool of slot states: the
    decode step, and the decode rows of the ragged mixed step.

The pool plumbing (which row reads and writes which slot) is
ops/slot_rows.py's, shared with ops/kda.py; the causal depthwise
convolution in front of x, B and C is ops/short_conv.py's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops import slot_rows

_HI = jax.lax.Precision.HIGHEST


def time_step(dt_raw, dt_bias):
    """dt = softplus(dt_raw + dt_bias) in float32: [..., H], [H]."""
    return jax.nn.softplus(dt_raw.astype(jnp.float32) +
                           dt_bias.astype(jnp.float32))


def _per_head(bc, H: int):
    """[..., G, N] -> [..., H, N]: head h reads group h // (H / G)."""
    return jnp.repeat(bc, H // bc.shape[-2], axis=-2)


# ---------------------------------------------------------------------------
# the recurrence
# ---------------------------------------------------------------------------

def step(S, x, Bm, Cm, dt, A):
    """One token: S [..., H, P, N], x [..., H, P], Bm Cm [..., G, N], dt
    [..., H], A [H] -> (y [..., H, P] WITHOUT the D x term, S_new).
    Elementwise products and reductions only, float32."""
    H = x.shape[-2]
    Bh, Ch = _per_head(Bm, H), _per_head(Cm, H)
    S = S * jnp.exp(dt * A)[..., None, None] + \
        (dt[..., None] * x)[..., :, None] * Bh[..., None, :]
    return jnp.sum(S * Ch[..., None, :], axis=-1), S


def recurrent(x, Bm, Cm, dt, A, S0=None):
    """The literal recurrence over T: x [B, T, H, P], Bm Cm [B, T, G, N],
    dt [B, T, H], A [H] -> (y [B, T, H, P] float32 without D x,
    S [B, H, P, N])."""
    f32 = lambda a: a.astype(jnp.float32)
    x, Bm, Cm, dt, A = map(f32, (x, Bm, Cm, dt, A))
    B, T, H, P = x.shape
    if S0 is None:
        S0 = jnp.zeros((B, H, P, Bm.shape[-1]), jnp.float32)

    def body(S, xs):
        y, S = step(S, *xs, A)
        return S, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt))
    S, y = jax.lax.scan(body, f32(S0), xs)
    return jnp.moveaxis(y, 0, 1), S


def chunkwise(x, Bm, Cm, dt, A, S0=None, live=None, chunk: int = 128):
    """The chunkwise form of `recurrent`, same arguments and results.  T
    is padded to whole chunks with rows of dt = 0, which leave the state
    alone.  `live` [T] bool (shared by the batch) marks the rows that
    matter: a chunk with no live row is skipped whole — its rows must
    already be masked (dt = 0), its outputs are zeros — so a short segment
    of a long row list costs its own chunks, not the list's."""
    dt = dt.astype(jnp.float32)
    A = A.astype(jnp.float32)
    cdt = x.dtype                      # the matmuls' operand dtype
    B, T, H, P = x.shape
    G, N = Bm.shape[-2:]
    Q = min(int(chunk), T)
    n = -(-T // Q)
    pad = n * Q - T
    if pad:
        x, Bm, Cm, dt = (jnp.pad(a, ((0, 0), (0, pad)) +
                                 ((0, 0),) * (a.ndim - 2))
                         for a in (x, Bm, Cm, dt))
    # [n, B, Q, ...]: the scan runs over chunks
    cut = lambda a: jnp.moveaxis(a.reshape((B, n, Q) + a.shape[2:]), 1, 0)
    x, Bm, Cm, dt = map(cut, (x, Bm, Cm, dt))
    any_live = jnp.ones((n,), bool) if live is None else \
        jnp.any(jnp.pad(live, (0, pad)).reshape(n, Q), axis=1)
    t_idx = jnp.arange(Q)
    lower = t_idx[:, None] >= t_idx[None, :]

    def one_chunk(S, x, Bm, Cm, dt):
        # x [B, Q, H, P]; Bm Cm [B, Q, G, N]; dt [B, Q, H]; S [B, H, P, N]
        Gs = jnp.cumsum(dt * A, axis=1)                  # <= 0, falling
        Gh = jnp.moveaxis(Gs, 1, 2)                      # [B, H, Q]
        # pairwise decay exp(G_t - G_j) for j <= t, zero above the diagonal
        L = jnp.exp(jnp.where(lower, Gh[..., :, None] - Gh[..., None, :],
                              -jnp.inf))                 # [B, H, Q, Q]
        cb = jnp.einsum("btgn,bjgn->bgtj", Cm, Bm,
                        preferred_element_type=jnp.float32)
        # head h reads its group's C B^T; dt_j weighs column j
        M = L * jnp.repeat(cb, H // G, axis=1) * \
            jnp.moveaxis(dt, 1, 2)[..., None, :]
        y = jnp.einsum("bhtj,bjhp->bthp", M.astype(cdt), x,
                       preferred_element_type=jnp.float32)
        # what the chunk's start state still gives each row
        Ch = _per_head(Cm, H).astype(jnp.float32)        # [B, Q, H, N]
        y = y + jnp.exp(Gs)[..., None] * jnp.einsum(
            "bthn,bhpn->bthp", Ch, S, precision=_HI)
        # the state at the chunk's end: decayed, plus each row's outer
        # product decayed to the end
        to_end = jnp.exp(Gs[:, -1:] - Gs) * dt           # [B, Q, H]
        Bh = _per_head(Bm, H).astype(jnp.float32)
        S = jnp.exp(Gs[:, -1])[..., None, None] * S + jnp.einsum(
            "bthp,bthn->bhpn", to_end[..., None] * x.astype(jnp.float32),
            Bh, precision=_HI)
        return S, y

    def body(S, xs):
        some, rest = xs[0], xs[1:]
        return jax.lax.cond(
            some, lambda S: one_chunk(S, *rest),
            lambda S: (S, jnp.zeros((B, Q, H, P), jnp.float32)), S)

    if S0 is None:
        S0 = jnp.zeros((B, H, P, N), jnp.float32)
    S, y = jax.lax.scan(body, S0.astype(jnp.float32),
                        (any_live, x, Bm, Cm, dt))
    y = jnp.moveaxis(y, 0, 1)                            # [B, n, Q, H, P]
    return y.reshape(B, n * Q, H, P)[:, :T], S


def step_rows(state, slot, live, x, Bm, Cm, dt, A, use_kernel: bool = False):
    """One token a row against the slot states: state [S+1, H, P, N]
    float32 (row S is trash), slot [R] int32 the state each row advances
    (None: row r is slot r, the decode step), live [R] bool (a row that is
    paused or padding leaves every state as it was), x [R, H, P], Bm Cm
    [R, G, N], dt [R, H], A [H] -> (y [R, H, P] float32 without D x,
    state).  Each live slot's state is read once and written once."""
    f32 = lambda a: a.astype(jnp.float32)
    x, Bm, Cm, dt, A = map(f32, (x, Bm, Cm, dt, A))
    if use_kernel:
        from paddle_tpu.ops import pallas_kda
        R, trash = x.shape[0], state.shape[0] - 1
        rows = jnp.arange(R, dtype=jnp.int32) if slot is None else slot
        return pallas_kda.ssd_step(state, jnp.where(live, rows, trash), live,
                                   x, Bm, Cm, dt, A)
    return slot_rows.advance_rows(
        state, slot, live, lambda S: step(S, x, Bm, Cm, dt, A))


def segment_rows(state, seg_slot, seg_pos, x, Bm, Cm, dt, A, chunk: int):
    """The chunk rows of a ragged mixed step (ops/slot_rows.py
    `advance_segments`): each run of a slot's rows goes through `chunkwise`
    once from its slot's state — from zero where it begins at position 0 —
    with the other rows' dt masked to 0, over the chunks that hold its
    rows.  Returns (y [P, H, P_head] float32 without D x, state,
    n_segments)."""
    dt = dt.astype(jnp.float32)

    def one_segment(S0, mine):
        y_i, S_end = chunkwise(
            x[None], Bm[None], Cm[None],
            jnp.where(mine[:, None], dt, 0.0)[None], A, S0[None],
            live=mine, chunk=chunk)
        return y_i[0], S_end[0]

    return slot_rows.advance_segments(
        state, seg_slot, seg_pos, jnp.zeros(x.shape, jnp.float32),
        one_segment)


def gated_group_norm(y, z, scale, n_groups: int, eps: float):
    """Mamba-2's gated norm: v = y * silu(z), RMS-normed over each of
    `n_groups` contiguous groups of channels, times a learned scale:
    y z [..., d_in], scale [d_in] -> float32."""
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = v.reshape(v.shape[:-1] + (n_groups, v.shape[-1] // n_groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(v.shape) * scale.astype(jnp.float32)
