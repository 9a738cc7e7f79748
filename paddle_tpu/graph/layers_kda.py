"""The gated delta-rule token mixer (ops/kda.py): a linear-attention layer
whose context is a recurrent state a sequence, not keys and values a token.
ONE layer type, `kda_attention`, runs both published rules — Kimi Delta
Attention (a decay a CHANNEL, a square state, low-rank decay and gate
projections, a sigmoid output gate) and Gated DeltaNet (`decay="head"`: ONE
decay a head, a state [dk, dv] with dv its own, `full_proj`, a silu gate:
Olmo-Hybrid's 30 heads of 96 x 192) — through the same forms and the same
slot parts; the named scopes (`kda.*` / `gdn.*`) and the kernels' names
(`kda_step` / `gdn_step`, `kda_seg` / `gdn_seg`) tell them apart in a trace.

inputs (all the one data input): w_q [d, H*dk], w_k [d, H*dk], w_v
[d, H*dv], conv_q conv_k conv_v [taps, H*dk | H*dv] (depthwise, no bias),
the decay's projection — w_fa [d, r], w_fb [r, F] low-rank, or w_f [d, F]
with `full_proj`; F = H*dk for a decay a channel, H for one a head —,
a_log [1, H], dt_bias [1, F], w_b [d, H], the output gate's — w_ga [d, r],
w_gb [r, H*dv], or w_g [d, H*dv] with `full_proj` —, o_norm [1, dv], w_o
[H*dv, size].
attrs: num_heads, head_dim (dk), value_dim (dv, head_dim if absent),
conv_size, rms_eps, attn_impl, decay ("channel" | "head"), full_proj,
gate_act ("sigmoid" | "silu"), allow_neg_eigval (beta = 2 sigmoid(x w_b) in
(0, 2), else sigmoid in (0, 1)).

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
    them in one `kda_seg` / `gdn_seg` call, ops/pallas_kda_seg.py), starting from its
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


def _dims(cfg: LayerConfig):
    """(H, dk, dv) of the layer's state."""
    a = cfg.attrs
    dk = int(a["head_dim"])
    return int(a["num_heads"]), dk, int(a.get("value_dim", dk))


@register_slot_state("kda_attention")
def kda_slot_parts(cfg: LayerConfig, compute_dtype) -> dict:
    """The recurrent state [H, dk, dv], float32 whatever the compute dtype
    (it is what the recurrence accumulates in), and the convolution tail (q,
    k and v side by side: H (2 dk + dv) channels) in the compute dtype."""
    H, dk, dv = _dims(cfg)
    taps = int(cfg.attrs.get("conv_size", 4))
    return {"state": ((H, dk, dv), jnp.float32),
            "conv": ((taps - 1, H * (2 * dk + dv)), compute_dtype)}


@register_layer("kda_attention")
def kda_attention_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    x_arg = ctx.get_input(cfg, 0)
    a = cfg.attrs
    H, dk, dv = _dims(cfg)
    per_head = a.get("decay", "channel") == "head"
    full = bool(a.get("full_proj"))
    params = (ctx.param_of(cfg, i) for i in range(len(cfg.inputs)))
    # a projection: one matrix, or a low-rank pair
    proj = (lambda: (next(params),)) if full else \
        (lambda: (next(params), next(params)))
    w_q, w_k, w_v, c_q, c_k, c_v = (next(params) for _ in range(6))
    w_f, a_log, dt_bias, w_b = proj(), next(params), next(params), next(params)
    w_g, o_norm, w_o = proj(), next(params), next(params)
    eps = float(a.get("rms_eps", 1e-5))
    x = x_arg.value                                       # [B, T, d]
    B, T, _ = x.shape
    step = slot_steps.slot_step(ctx, cfg, x, "state")
    scope = lambda what: jax.named_scope(
        ("gdn." if per_head else "kda.") + what)

    def through(ws):
        y = x
        for w in ws:
            y = y @ w
        return y

    with scope("project"):
        xin = jnp.concatenate([x @ w_q, x @ w_k, x @ w_v], axis=-1)
    with scope("gate"):
        f = (H,) if per_head else (H, dk)
        g = kda.decay(through(w_f).reshape((B, T) + f), a_log.reshape(H),
                      dt_bias.reshape(f))
        beta = jax.nn.sigmoid((x @ w_b).astype(jnp.float32))    # [B, T, H]
        if a.get("allow_neg_eigval"):
            beta = 2.0 * beta
        gate = through(w_g).reshape(B, T, H, dv)
    w_conv = jnp.concatenate([c_q, c_k, c_v], axis=-1).astype(xin.dtype)

    def split(y):
        """conv output [..., C] -> q k [.., H, dk] l2-normed, v [.., H, dv]"""
        y = jax.nn.silu(y)
        lead = y.shape[:-1]
        q = kda.l2norm(y[..., :H * dk].reshape(lead + (H, dk)))
        k = kda.l2norm(y[..., H * dk:2 * H * dk].reshape(lead + (H, dk)))
        v = y[..., 2 * H * dk:].reshape(lead + (H, dv)).astype(jnp.float32)
        return q, k, v

    with scope("conv"):
        y, conv = slot_steps.conv(step, xin, w_conv)
        q, k, v = split(y)
    if step is None:
        with scope("scan"):
            o, _ = kda.chunkwise(q, k, v, g, beta)
    else:
        state, S = step.cache["state"], step.slots
        row_slot, row_pos, _, _, live = step.runs
        rows = lambda a: a.reshape((B * T,) + a.shape[2:])
        q, k, v, g, beta = map(rows, (q, k, v, g, beta))
        kernel = slot_steps.use_step_kernel(cfg)    # the step and the seg
        with scope("step"):
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
    with scope("project"):
        act = {"sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu}[
            a.get("gate_act", "sigmoid")]
        o = kda.gated_out_norm(o, gate, o_norm.reshape(dv), eps, act)
        out = o.reshape(B, T, H * dv).astype(x.dtype) @ w_o
    return finish_layer(ctx, cfg, out, like=x_arg)
