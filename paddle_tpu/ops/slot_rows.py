"""A recurrence's rows against a pool of slot states: what the KDA layer
(ops/kda.py) and the Mamba-2 layer (ops/ssd.py) share once their own
arithmetic is taken out — which state each row of a slot-state step reads,
which it writes, and what a dead row (paused, empty, padding) leaves alone.
The pool is `[S+1, ...]` float32, row S the trash row
(serving/paged_kv.py, slot-indexed parts); the rows' map onto the slots is
ops/short_conv.py `slot_runs`."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def advance_rows(state, slot, live, one_step):
    """One token a row: `slot` [R] int32 the state each row advances (None:
    row r is slot r, the decode step), `live` [R] bool; `one_step(old
    [R, ...]) -> (o, new [R, ...])` the recurrence's own step.  A row that
    is not live leaves every state as it was; each live slot's state is
    read once and written once.  Returns (o, state)."""
    R, trash = live.shape[0], state.shape[0] - 1
    if slot is None:
        old = state[:R]
        o, new = one_step(old)
        keep = live.reshape((R,) + (1,) * (state.ndim - 1))
        return o, state.at[:R].set(jnp.where(keep, new, old))
    slot = jnp.where(live, slot, trash)
    o, new = one_step(state[slot])
    return o, state.at[slot].set(new)


def advance_segments(state, seg_slot, seg_pos, o0, one_segment):
    """The chunk rows of a ragged mixed step: P packed rows holding whole
    runs of slots, contiguous and in order (`seg_slot` [P], trash row S =
    padding; `seg_pos` [P] global positions).  Each run is one segment: it
    starts from its slot's state — from zero where its first row is
    position 0 —, goes through `one_segment(S0, mine [P] bool) -> (o_i
    [P, ...], S_end)` once (the recurrence's chunkwise form over the P rows
    with the others' masked), and leaves the state it ends in.  One pass a
    segment present (a loop with a dynamic trip count: a step usually holds
    one or two).  `o0` [P, ...]: zeros of the output's shape.  Returns
    (o, state, n_segments)."""
    S = state.shape[0] - 1
    live = seg_slot < S
    first = live & jnp.concatenate(
        [jnp.ones((1,), bool), seg_slot[1:] != seg_slot[:-1]])
    seg_id = jnp.cumsum(first.astype(jnp.int32)) - 1
    n_seg = jnp.sum(first.astype(jnp.int32))

    def body(i, carry):
        state, o = carry
        mine = live & (seg_id == i)
        at = jnp.argmax(mine)                            # its first row
        slot = seg_slot[at]
        S0 = jnp.where(seg_pos[at] == 0, 0.0, state[slot])
        o_i, S_end = one_segment(S0, mine)
        keep = mine.reshape((-1,) + (1,) * (o.ndim - 1))
        return state.at[slot].set(S_end), jnp.where(keep, o_i, o)

    state, o = jax.lax.fori_loop(0, n_seg, body, (state, o0))
    return o, state, n_seg
