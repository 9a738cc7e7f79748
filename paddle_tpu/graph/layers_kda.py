"""The KDA token mixer (ops/kda.py): a gated delta-rule linear-attention
layer whose context is a recurrent state a sequence, not keys and values a
token.

inputs (all the one data input): w_q [d, H*dk], w_k [d, H*dk], w_v
[d, H*dv], conv_q conv_k conv_v [taps, H*dk | H*dv] (depthwise, no bias),
w_fa [d, r], w_fb [r, H*dk] (the decay's low-rank projection), a_log
[1, H], dt_bias [1, H*dk], w_b [d, H], w_ga [d, r], w_gb [r, H*dv] (the
output gate), o_norm [1, dv], w_o [H*dv, size].
attrs: num_heads, head_dim, conv_size, rms_eps, attn_impl,
allow_neg_eigval (beta = 2 sigmoid(x w_b) in (0, 2), else sigmoid in (0, 1)).

Three paths, picked by the state the executor hands in, as the attention
layers do (the dispatch, the run mask and the convolution's tail are
graph/slot_steps.py's, shared with the short-convolution and Mamba-2
layers):

  * none — the whole sequence in the chunkwise form from the zero state;
  * a slot state with `pos` (and `run`) — the decode step: one rank-1
    update a row; a row whose `run` is false leaves `state` and `conv` as
    they were (a recurrence recomputed at a frozen position would advance
    twice, where a K/V write is idempotent);
  * a slot state with `row_slot` — the ragged mixed step.  THE PACKING
    CONTRACT (serving/engine.py `_launch_mixed`): with S slots, rows
    [0, S) are single rows (decode rows, or padding aimed at trash row S)
    and rows [S, T) hold the prompt chunks, each slot's run contiguous and
    in order.  The first part is one batched rank-1 update; each run of the
    second is one segment through the chunkwise form (on the TPU all of
    them in one `kda_seg` call, ops/pallas_kda_seg.py), starting from its
    slot's state — from zero where it begins at position 0, so admission
    dispatches nothing.  A touched slot's state is read once and written
    once a layer a step.

The slot state lives in the serving cache manager (serving/paged_kv.py,
slot-indexed parts): `state` [S+1, H, dk, dv] float32 and `conv` [S+1,
taps-1, H*(2dk+dv)], the inputs of the last taps-1 positions.  A caller
that hands in a state gets back, beside the new parts, `rows` (rows that
advanced a state) and `updates` (slot states read and written).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.config.schema import LayerConfig
from paddle_tpu.graph.common import finish_layer
from paddle_tpu.graph.context import ForwardContext
from paddle_tpu.graph.registry import register_layer, register_slot_state
from paddle_tpu.graph import slot_steps
from paddle_tpu.ops import kda
from paddle_tpu.parameter.argument import Argument


@register_slot_state("kda_attention")
def kda_slot_parts(cfg: LayerConfig, compute_dtype) -> dict:
    """The recurrent state, float32 whatever the compute dtype (it is what
    the recurrence accumulates in), and the convolution tail (q, k and v
    side by side) in the compute dtype."""
    H, dk = int(cfg.attrs["num_heads"]), int(cfg.attrs["head_dim"])
    taps = int(cfg.attrs.get("conv_size", 4))
    return {"state": ((H, dk, dk), jnp.float32),
            "conv": ((taps - 1, 3 * H * dk), compute_dtype)}


@register_layer("kda_attention")
def kda_attention_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    x_arg = ctx.get_input(cfg, 0)
    (w_q, w_k, w_v, c_q, c_k, c_v, w_fa, w_fb, a_log, dt_bias, w_b, w_ga,
     w_gb, o_norm, w_o) = (ctx.param_of(cfg, i) for i in range(15))
    a = cfg.attrs
    H, dk = int(a["num_heads"]), int(a["head_dim"])
    dv = w_v.shape[1] // H
    eps = float(a.get("rms_eps", 1e-5))
    x = x_arg.value                                       # [B, T, d]
    B, T, _ = x.shape
    step = slot_steps.slot_step(ctx, cfg, x, "state")

    with jax.named_scope("kda.project"):
        xin = jnp.concatenate([x @ w_q, x @ w_k, x @ w_v], axis=-1)
    with jax.named_scope("kda.gate"):
        g = kda.decay(((x @ w_fa) @ w_fb).reshape(B, T, H, dk),
                      a_log.reshape(H), dt_bias.reshape(H, dk))
        beta = jax.nn.sigmoid((x @ w_b).astype(jnp.float32))    # [B, T, H]
        if a.get("allow_neg_eigval"):
            beta = 2.0 * beta
        gate = ((x @ w_ga) @ w_gb).reshape(B, T, H, dv)
    w_conv = jnp.concatenate([c_q, c_k, c_v], axis=-1).astype(xin.dtype)

    def split(y):
        """conv output [..., C] -> q k [.., H, dk] l2-normed, v [.., H, dv]"""
        y = jax.nn.silu(y)
        lead = y.shape[:-1]
        q = kda.l2norm(y[..., :H * dk].reshape(lead + (H, dk)))
        k = kda.l2norm(y[..., H * dk:2 * H * dk].reshape(lead + (H, dk)))
        v = y[..., 2 * H * dk:].reshape(lead + (H, dv)).astype(jnp.float32)
        return q, k, v

    with jax.named_scope("kda.conv"):
        y, conv = slot_steps.conv(step, xin, w_conv)
        q, k, v = split(y)
    if step is None:
        with jax.named_scope("kda.scan"):
            o, _ = kda.chunkwise(q, k, v, g, beta)
    else:
        state, S = step.cache["state"], step.slots
        row_slot, row_pos, _, _, live = step.runs
        rows = lambda a: a.reshape((B * T,) + a.shape[2:])
        q, k, v, g, beta = map(rows, (q, k, v, g, beta))
        kernel = slot_steps.use_step_kernel(cfg)    # kda_step and kda_seg
        with jax.named_scope("kda.step"):
            if step.ragged:
                o_d, state = kda.step_rows(
                    state, row_slot[:S], live[:S], q[:S], k[:S], v[:S],
                    g[:S], beta[:S], use_kernel=kernel)
                o_c, state, n_seg = kda.segment_rows(
                    state, row_slot[S:], row_pos[S:], q[S:], k[S:], v[S:],
                    g[S:], beta[S:], use_kernel=kernel)
                o = jnp.concatenate([o_d, o_c], axis=0)
                updates = jnp.sum(live[:S], dtype=jnp.int32) + n_seg
            else:
                o, state = kda.step_rows(state, None, live, q, k, v, g,
                                         beta, use_kernel=kernel)
                updates = jnp.sum(live, dtype=jnp.int32)
        o = o.reshape(B, T, H, dv)
        slot_steps.finish(ctx, cfg, step, updates, state=state, conv=conv)
    with jax.named_scope("kda.project"):
        o = kda.gated_out_norm(o, gate, o_norm.reshape(dv), eps)
        out = o.reshape(B, T, H * dv).astype(x.dtype) @ w_o
    return finish_layer(ctx, cfg, out, like=x_arg)
