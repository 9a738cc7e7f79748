"""The gated short-convolution token mixer (LFM2's `Lfm2ShortConv`): a
causal token mixer whose whole context is the last `conv_size - 1` inputs
of a depthwise convolution — no keys and values a token, no recurrent
matrix state.

    [B_t, C_t, x~_t] = x_t W_in          three d-wide parts, no bias
    u_t = B_t * x~_t
    c_t = sum_j w_j * u_{t-(taps-1)+j}   depthwise, causal, zeros before 0,
                                         no bias, no activation
    y_t = (C_t * c_t) W_out

inputs (all the one data input): w_in [d, 3*d], conv [taps, d], w_out
[d, size].  attrs: conv_size.

Three paths, picked by the state the executor hands in — the dispatch, the
run mask and the tail are graph/slot_steps.py's, shared with the KDA and
Mamba-2 layers (graph/layers_kda.py has THE PACKING CONTRACT of the ragged
one):

  * none — the whole sequence from an empty history;
  * a slot tail with `pos` and `run` — the decode step; a row whose `run`
    is false leaves its tail as it was;
  * a slot tail with `row_slot` — the ragged mixed step: a slot's run of
    rows reads the tail once and its last row writes it; a segment that
    begins at position 0 reads zeros, so admission dispatches nothing.

The convolution over tails and ragged rows is ops/short_conv.py's, the code
the KDA layer's convolutions run.  The slot state lives in the serving
cache manager (serving/paged_kv.py, slot-indexed parts): `conv` [S+1,
taps-1, d] in the compute dtype, u of the last taps-1 positions.  A caller
that hands in a state gets back, beside the new tail, `rows` (rows that
advanced a slot) and `updates` (tails written), as the KDA layer does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.config.schema import LayerConfig
from paddle_tpu.graph.common import finish_layer
from paddle_tpu.graph.context import ForwardContext
from paddle_tpu.graph.registry import register_layer, register_slot_state
from paddle_tpu.graph import slot_steps
from paddle_tpu.parameter.argument import Argument


@register_slot_state("short_conv")
def short_conv_slot_parts(cfg: LayerConfig, compute_dtype) -> dict:
    """The one part: the convolution's tail, in the compute dtype."""
    taps = int(cfg.attrs["conv_size"])
    return {"conv": ((taps - 1, int(cfg.attrs["conv_dim"])), compute_dtype)}


@register_layer("short_conv")
def short_conv_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    x_arg = ctx.get_input(cfg, 0)
    w_in, w_conv, w_out = (ctx.param_of(cfg, i) for i in range(3))
    d = w_conv.shape[1]
    x = x_arg.value                                       # [B, T, d_in]
    step = slot_steps.slot_step(ctx, cfg, x)

    with jax.named_scope("sconv.project"):
        bcx = x @ w_in
        gate_c = bcx[..., d:2 * d]
        u = bcx[..., :d] * bcx[..., 2 * d:]
    with jax.named_scope("sconv.mix"):
        y, tails = slot_steps.conv(step, u, w_conv.astype(u.dtype))
    if step is not None:
        *_, last, live = step.runs
        slot_steps.finish(ctx, cfg, step,
                          jnp.sum(last & live, dtype=jnp.int32), conv=tails)
    with jax.named_scope("sconv.project"):
        out = (gate_c * y) @ w_out
    return finish_layer(ctx, cfg, out, like=x_arg)
