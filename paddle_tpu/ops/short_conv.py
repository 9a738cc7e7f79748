"""The causal depthwise short convolution of the recurrent token mixers:
the KDA layer's 4 taps in front of q, k and v (graph/layers_kda.py), the
gated short-convolution mixer's 3 taps (graph/layers_sconv.py) and the
Mamba-2 mixer's 4 taps with a bias over x, B and C together
(graph/layers_ssm.py).  Two forms, one result: a whole sequence from an
empty history, and a packed row list against each slot's tail — the inputs
of the last `taps - 1` positions, which the serving cache manager holds a
slot (serving/paged_kv.py, slot-indexed parts)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def short_conv_whole(x, w, bias=None):
    """Causal depthwise convolution from an empty history: x [B, T, C],
    w [taps, C] (w[-1] multiplies the current position), `bias` [C] or
    None -> [B, T, C]: the sum of `taps` shifted products (plus the
    bias)."""
    taps = w.shape[0]
    T = x.shape[1]
    y = x * w[taps - 1]
    for j in range(1, taps):
        shifted = jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, :T]
        y = y + shifted * w[taps - 1 - j]
    return y if bias is None else y + bias


def short_conv_rows(x, w, tail, seg_off, row_pos, bias=None):
    """The same convolution over a packed row list: x [R, C] in order,
    `tail` [R, taps-1, C] each row's slot history (tail[:, -1] the most
    recent position before the slot's first row of this step), `seg_off`
    [R] the row's offset from that first row, `row_pos` [R] its global
    position (taps reaching before position 0 read zero).  Returns
    (y [R, C], hist [R, taps-1, C]): hist[r] is the history AFTER row r,
    what the slot's tail becomes if r is its last row."""
    taps = w.shape[0]
    R = x.shape[0]
    prev = []                                  # prev[j-1] = input at pos - j
    for j in range(1, taps):
        from_rows = jnp.pad(x, ((j, 0), (0, 0)))[:R]
        # j - seg_off positions before the slot's first row: tail[-(j-off)]
        idx = jnp.clip(taps - 1 - j + seg_off, 0, taps - 2)
        from_tail = jnp.take_along_axis(
            tail, idx[:, None, None], axis=1)[:, 0]
        p = jnp.where((seg_off >= j)[:, None], from_rows, from_tail)
        prev.append(jnp.where((row_pos >= j)[:, None], p, 0).astype(x.dtype))
    y = x * w[taps - 1]
    for j in range(1, taps):
        y = y + prev[j - 1] * w[taps - 1 - j]
    if bias is not None:
        y = y + bias
    hist = jnp.stack(prev[::-1][1:] + [x], axis=1)
    return y, hist


def slot_runs(cache: dict, S: int, R: int):
    """How the R rows of a slot-state step map onto the S slots, from the
    state the executor hands a recurrent layer: (row_slot [R], row_pos
    [R], seg_off [R], last [R], live [R]).  With `row_slot` in the cache it
    is the ragged mixed step under THE PACKING CONTRACT
    (graph/layers_kda.py): a slot's run of rows is contiguous and in
    order, `seg_off` counts from its first row, `last` marks its final
    row, padding aims at trash row S.  Without it, the decode step: row r
    is slot r at `pos`, live where `run` says the slot advances."""
    if "row_slot" in cache:
        row_slot, row_pos = cache["row_slot"], cache["row_pos"]
        assert R > S, f"the mixed step packs its chunk rows from row {S} " \
            f"on (got {R} rows)"
        live = row_slot < S
        idx = jnp.arange(R, dtype=jnp.int32)
        change = row_slot[1:] != row_slot[:-1]
        first = jnp.concatenate([jnp.ones((1,), bool), change])
        last = jnp.concatenate([change, jnp.ones((1,), bool)])
        seg_off = idx - jax.lax.cummax(jnp.where(first, idx, 0))
        return row_slot, row_pos, seg_off, last, live
    return (jnp.arange(S, dtype=jnp.int32), cache["pos"],
            jnp.zeros((S,), jnp.int32), jnp.ones((S,), bool), cache["run"])


def short_conv_slots(x, w, tails, runs, bias=None):
    """`short_conv_rows` against the slot pool `tails` [S+1, taps-1, C]
    (row S is trash) for the rows `runs` describes (`slot_runs`): each
    live slot's tail is read once and written once, by its last row; a
    paused slot's and a padding row's write lands in the trash row.
    Returns (y [R, C], tails)."""
    row_slot, row_pos, seg_off, last, live = runs
    y, hist = short_conv_rows(x, w, tails[row_slot], seg_off, row_pos,
                              bias)
    trash = tails.shape[0] - 1
    return y, tails.at[jnp.where(last & live, row_slot, trash)].set(
        hist.astype(tails.dtype))
