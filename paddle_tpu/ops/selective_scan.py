"""The Mamba-1 selective scan (arXiv:2312.00752; the Jamba family's Mamba
layers, arXiv:2403.19887): a state-space recurrence with a decay of its own
for EVERY state element.  A channel c of d_in keeps N state elements:

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[n, c] C_t[n]                 (+ D[c] x_t[c], the
                                                        caller's)

with dt_t = softplus(r_t W_dt + b_dt) > 0 a channel and A = -exp(A_log) < 0.
No heads, no groups, and no matmul form: the decay differs along both axes
of the state, so a chunk of tokens is not a masked product (where Mamba-2's
scalar decay a head makes it one, ops/ssd.py) — the scan over tokens IS the
layer.  Everything here is float32 whatever the inputs' dtype.

THE STATE'S ORIENTATION is [N, d_in], not the paper's [d_in, N]: N = 16
rows of d_in lanes is two float32 tiles deep and lane-dense in HBM, where
[d_in, 16] would pad 16 lanes to 128 (the pool eightfold).  The readout's
sum over n is then an add of vregs along sublanes.  `A` comes in the same
orientation.

Three forms, one result:
  * `recurrent`    — the literal per-token `lax.scan`: the whole-sequence
    path, the CPU path of the segments, and the oracle of the kernel
    (ops/pallas_selective_scan.py);
  * `step_rows`    — one token a row against a pool of slot states: the
    decode step, and the decode rows of the ragged mixed step;
  * `segment_rows` — the chunk rows of a ragged mixed step, each slot's run
    of tokens one scan from its slot's state.  On the TPU the kernel runs
    the time loop inside itself, the state block resident in VMEM; elsewhere
    each run is one `recurrent` pass with the other rows' dt masked to 0 —
    dt = 0 is the identity (exp(0) = 1, input 0): that is how padding and
    the rows of other segments are masked, here and in the kernel.

The pool plumbing (which row reads and writes which slot) is
ops/slot_rows.py's, shared with ops/kda.py and ops/ssd.py; the causal
depthwise convolution in front of x is ops/short_conv.py's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops import slot_rows


def time_step(dt_raw, dt_bias):
    """dt = softplus(dt_raw + dt_bias) in float32: [..., d_in], [d_in]."""
    return jax.nn.softplus(dt_raw.astype(jnp.float32) +
                           dt_bias.astype(jnp.float32))


def step(h, x, Bm, Cm, dt, A):
    """One token: h [..., N, d_in], x dt [..., d_in], Bm Cm [..., N], A
    [N, d_in] -> (y [..., d_in] WITHOUT the D x term, h_new).  Elementwise
    products and one reduction over N, float32."""
    h = h * jnp.exp(dt[..., None, :] * A) + \
        (dt * x)[..., None, :] * Bm[..., :, None]
    return jnp.sum(h * Cm[..., :, None], axis=-2), h


def recurrent(x, Bm, Cm, dt, A, h0=None):
    """The literal recurrence over T: x dt [B, T, d_in], Bm Cm [B, T, N],
    A [N, d_in] -> (y [B, T, d_in] float32 without D x, h [B, N, d_in])."""
    f32 = lambda a: a.astype(jnp.float32)
    x, Bm, Cm, dt, A = map(f32, (x, Bm, Cm, dt, A))
    if h0 is None:
        h0 = jnp.zeros((x.shape[0],) + A.shape, jnp.float32)

    def body(h, xs):
        y, h = step(h, *xs, A)
        return h, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt))
    h, y = jax.lax.scan(body, f32(h0), xs)
    return jnp.moveaxis(y, 0, 1), h


def step_rows(state, slot, live, x, Bm, Cm, dt, A, use_kernel: bool = False):
    """One token a row against the slot states: state [S+1, N, d_in]
    float32 (row S is trash), slot [R] int32 the state each row advances
    (None: row r is slot r, the decode step), live [R] bool (a row that is
    paused or padding leaves every state as it was), x dt [R, d_in], Bm Cm
    [R, N], A [N, d_in] -> (y [R, d_in] float32 without D x, state).  Each
    live slot's state is read once and written once."""
    f32 = lambda a: a.astype(jnp.float32)
    x, Bm, Cm, dt, A = map(f32, (x, Bm, Cm, dt, A))
    if use_kernel:
        from paddle_tpu.ops import pallas_selective_scan as kernel
        R, trash = x.shape[0], state.shape[0] - 1
        rows = jnp.arange(R, dtype=jnp.int32) if slot is None else slot
        return kernel.selective_scan_step(
            state, jnp.where(live, rows, trash), live, x, Bm, Cm, dt, A)
    return slot_rows.advance_rows(
        state, slot, live, lambda h: step(h, x, Bm, Cm, dt, A))


def segment_rows(state, seg_slot, seg_pos, x, Bm, Cm, dt, A,
                 use_kernel: bool = False):
    """The chunk rows of a ragged mixed step (ops/slot_rows.py: P packed
    rows holding whole runs of slots, contiguous and in order, padding
    aimed at trash row S): each run is one scan from its slot's state —
    from zero where it begins at position 0 — and leaves the state it ends
    in.  Returns (y [P, d_in] float32 without D x, state, n_segments)."""
    f32 = lambda a: a.astype(jnp.float32)
    x, Bm, Cm, dt, A = map(f32, (x, Bm, Cm, dt, A))
    if use_kernel:
        from paddle_tpu.ops import pallas_selective_scan as kernel
        return kernel.selective_scan_segments(state, seg_slot, seg_pos, x,
                                              Bm, Cm, dt, A)

    def one_segment(h0, mine):
        y_i, h_end = recurrent(x[None], Bm[None], Cm[None],
                               jnp.where(mine[:, None], dt, 0.0)[None], A,
                               h0[None])
        return y_i[0], h_end[0]

    return slot_rows.advance_segments(
        state, seg_slot, seg_pos, jnp.zeros(x.shape, jnp.float32),
        one_segment)


def segment_table(seg_slot, seg_pos, trash: int):
    """The runs of the P packed chunk rows as a table of P entries (a run a
    row at the worst): (start [P], length [P], slot [P], from_zero [P],
    n_segments), run i the i-th of the list; entries past n_segments are
    dead — length 0, aimed at the trash row."""
    P = seg_slot.shape[0]
    idx = jnp.arange(P, dtype=jnp.int32)
    live = seg_slot < trash
    first = live & jnp.concatenate(
        [jnp.ones((1,), bool), seg_slot[1:] != seg_slot[:-1]])
    n_seg = jnp.sum(first.astype(jnp.int32))
    # the i-th run's first row: the rows that start a run, in order
    start = jnp.sort(jnp.where(first, idx, P))
    seg_id = jnp.cumsum(first.astype(jnp.int32)) - 1
    length = jnp.zeros((P,), jnp.int32).at[
        jnp.where(live, seg_id, P)].add(1, mode="drop")
    at = jnp.minimum(start, P - 1)
    alive = idx < n_seg
    return (jnp.where(alive, start, 0), length,
            jnp.where(alive, seg_slot[at], trash),
            alive & (seg_pos[at] == 0), n_seg)
