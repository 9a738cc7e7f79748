"""What the recurrent token mixers share in the graph: the KDA layer
(graph/layers_kda.py), the gated short convolution (graph/layers_sconv.py)
and the Mamba-2 mixer (graph/layers_ssm.py) each run THREE PATHS, picked by
the state the executor hands in, as the attention layers do:

  * none — the whole sequence from an empty history (the chunkwise form
    from the zero state, where the layer keeps one);
  * a slot state with `pos` and `run` — the decode step: one token a slot;
    a row whose `run` is false leaves every part of its slot as it was (a
    recurrence recomputed at a frozen position would advance twice, where a
    K/V write is idempotent);
  * a slot state with `row_slot` — the ragged mixed step under THE PACKING
    CONTRACT (graph/layers_kda.py; serving/engine.py `_launch_mixed`).

`slot_step` reads which of them a call is and how its rows map onto the
slots; `conv` is the causal depthwise convolution on whichever path, the
slot's tail read once and written once by its last row (ops/short_conv.py);
`finish` hands the new parts back beside `rows` (rows that advanced a
slot) and `updates` (slot states read and written), the engine's recurrent
counters' source.  The slot parts themselves are declared beside each
layer's registration (graph/registry.py:register_slot_state) and live in
the serving cache manager (serving/paged_kv.py, slot-indexed parts)."""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp

from paddle_tpu.config.schema import LayerConfig
from paddle_tpu.graph.context import ForwardContext
from paddle_tpu.ops import short_conv


class SlotStep(NamedTuple):
    cache: dict       # the state the executor handed in
    slots: int        # S: the parts hold S + 1 rows, row S is trash
    runs: tuple       # short_conv.slot_runs: (row_slot, row_pos, seg_off,
                      # last, live), each [rows]
    ragged: bool      # the mixed step (else the decode step)


def use_step_kernel(cfg: LayerConfig) -> bool:
    """The Pallas step kernel (ops/pallas_kda.py) unless the config pins
    the jnp path (attn_impl dense/blockwise, as for the attention layers)."""
    from paddle_tpu.ops import pallas_kda

    return pallas_kda.supported() and \
        str(cfg.attrs.get("attn_impl", "auto")) not in ("dense", "blockwise")


def slot_step(ctx: ForwardContext, cfg: LayerConfig, x,
              part: str = "conv") -> Optional[SlotStep]:
    """None for the whole-sequence path; else how the rows of `x`
    [B, T, d] map onto the slots of the state handed in for this layer
    (`part`: a slot-indexed part the layer declares)."""
    cache = ctx.state_in.get(cfg.name)
    if not (isinstance(cache, dict) and part in cache):
        return None
    B, T = x.shape[:2]
    ragged = "row_slot" in cache
    assert (B == 1) if ragged else (T == 1), \
        f"layer {cfg.name!r}: a slot-state step feeds one token a slot, " \
        f"or one packed ragged row list (got {x.shape})"
    S = cache[part].shape[0] - 1
    return SlotStep(cache, S, short_conv.slot_runs(cache, S, B * T), ragged)


def conv(step: Optional[SlotStep], x, w, bias=None):
    """The causal depthwise convolution of x [B, T, C] with w [taps, C]
    (and `bias` [C]) on the call's path: (y [B, T, C], the new `conv` part
    — None on the whole-sequence path)."""
    if step is None:
        return short_conv.short_conv_whole(x, w, bias), None
    B, T, C = x.shape
    y, tails = short_conv.short_conv_slots(
        x.reshape(B * T, C), w, step.cache["conv"], step.runs, bias)
    return y.reshape(B, T, C), tails


def finish(ctx: ForwardContext, cfg: LayerConfig, step: SlotStep, updates,
           **parts) -> None:
    """Hand the new parts back to the caller, with the counters."""
    live = step.runs[-1]
    ctx.state_out[cfg.name] = dict(
        step.cache, **parts, rows=jnp.sum(live, dtype=jnp.int32),
        updates=updates)
