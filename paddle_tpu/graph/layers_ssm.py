"""The Mamba-2 token mixer (ops/ssd.py; arXiv:2405.21060, the Nemotron-H
family's `M` layers): a selective state-space layer whose context is one
recurrent state [P, N] a head, moved by a scalar decay a head, and the last
`conv_size - 1` inputs of the convolution in front of it.

    [z_t, xBC_t, dt_t] = u_t W_in        d_in + (d_in + 2 G N) + H columns
    xBC'_t = silu(b + sum_j w_j * xBC_{t-(taps-1)+j})   depthwise, causal
    x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC'_t)
    dt_t = softplus(dt_t + dt_bias);  a_t = exp(dt_t A),  A = -exp(A_log)
    S_t[h] = a_t[h] S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)]
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
    out_t = GroupRMSNorm_G(y_t * silu(z_t)) W_out

with d_in = H P.  inputs (all the one data input): w_in [d, 2 d_in + 2 G N
+ H], conv [taps, d_in + 2 G N], conv_bias [1, d_in + 2 G N], a_log
[1, H], d [1, H], dt_bias [1, H], norm [1, d_in], w_out [d_in, size].
attrs: num_heads, head_dim, state_size, n_groups, conv_size, chunk_size,
rms_eps, attn_impl.

Three paths, picked by the state the executor hands in — the dispatch, the
run mask and the convolution's tail are graph/slot_steps.py's, shared with
the KDA and short-convolution layers (graph/layers_kda.py has THE PACKING
CONTRACT of the ragged one): the whole sequence chunkwise at `chunk_size`
from the zero state; the decode step, one `ssd.step_rows` a row (the Pallas
kernel ops/pallas_kda.py `ssd_step` on the TPU, the jnp step elsewhere —
chosen by platform, as the KDA layer's); the ragged mixed step, its decode
rows one batched step and each chunk run one segment through the chunkwise
form from its slot's state.

The slot state lives in the serving cache manager (serving/paged_kv.py,
slot-indexed parts): `state` [S+1, H, P, N] float32 and `conv` [S+1,
taps-1, d_in + 2 G N] in the compute dtype, xBC of the last taps-1
positions before the activation.  A caller that hands in a state gets
back, beside the new parts, `rows` and `updates`, as the KDA layer does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.config.schema import LayerConfig
from paddle_tpu.graph import slot_steps
from paddle_tpu.graph.common import finish_layer
from paddle_tpu.graph.context import ForwardContext
from paddle_tpu.graph.registry import register_layer, register_slot_state
from paddle_tpu.ops import ssd
from paddle_tpu.parameter.argument import Argument


def _sizes(cfg: LayerConfig) -> tuple[int, int, int, int]:
    a = cfg.attrs
    return (int(a["num_heads"]), int(a["head_dim"]), int(a["state_size"]),
            int(a["n_groups"]))


@register_slot_state("mamba2")
def mamba2_slot_parts(cfg: LayerConfig, compute_dtype) -> dict:
    """The recurrent state, float32 whatever the compute dtype (it is what
    the recurrence accumulates in), and the convolution tail (x, B and C
    side by side) in the compute dtype."""
    H, P, N, G = _sizes(cfg)
    taps = int(cfg.attrs.get("conv_size", 4))
    return {"state": ((H, P, N), jnp.float32),
            "conv": ((taps - 1, H * P + 2 * G * N), compute_dtype)}


@register_layer("mamba2")
def mamba2_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    x_arg = ctx.get_input(cfg, 0)
    w_in, w_conv, b_conv, a_log, d_skip, dt_bias, norm, w_out = (
        ctx.param_of(cfg, i) for i in range(8))
    H, P, N, G = _sizes(cfg)
    d_in, gn = H * P, G * N
    chunk = int(cfg.attrs.get("chunk_size", 128))
    eps = float(cfg.attrs.get("rms_eps", 1e-5))
    x = x_arg.value                                       # [B, T, d]
    B, T, _ = x.shape
    step = slot_steps.slot_step(ctx, cfg, x, "state")

    with jax.named_scope("ssm.project"):
        zxd = x @ w_in
        z, xbc = zxd[..., :d_in], zxd[..., d_in:2 * d_in + 2 * gn]
        dt = ssd.time_step(zxd[..., 2 * d_in + 2 * gn:], dt_bias.reshape(H))
    A = -jnp.exp(a_log.astype(jnp.float32)).reshape(H)
    with jax.named_scope("ssm.conv"):
        y, conv = slot_steps.conv(step, xbc, w_conv.astype(xbc.dtype),
                                  b_conv.reshape(-1).astype(xbc.dtype))
        y = jax.nn.silu(y)
        xs = y[..., :d_in].reshape(B, T, H, P)
        Bm = y[..., d_in:d_in + gn].reshape(B, T, G, N)
        Cm = y[..., d_in + gn:].reshape(B, T, G, N)
    if step is None:
        with jax.named_scope("ssm.scan"):
            o, _ = ssd.chunkwise(xs, Bm, Cm, dt, A, chunk=chunk)
    else:
        state, S = step.cache["state"], step.slots
        row_slot, row_pos, _, _, live = step.runs
        rows = lambda a: a.reshape((B * T,) + a.shape[2:])
        xr, Br, Cr, dtr = map(rows, (xs, Bm, Cm, dt))
        if step.ragged:
            with jax.named_scope("ssm.step"):
                o_d, state = ssd.step_rows(
                    state, row_slot[:S], live[:S], xr[:S], Br[:S], Cr[:S],
                    dtr[:S], A, use_kernel=slot_steps.use_step_kernel(cfg))
            with jax.named_scope("ssm.scan"):
                o_c, state, n_seg = ssd.segment_rows(
                    state, row_slot[S:], row_pos[S:], xr[S:], Br[S:], Cr[S:],
                    dtr[S:], A, chunk)
            o = jnp.concatenate([o_d, o_c], axis=0)
            updates = jnp.sum(live[:S], dtype=jnp.int32) + n_seg
        else:
            with jax.named_scope("ssm.step"):
                o, state = ssd.step_rows(state, None, live, xr, Br, Cr, dtr,
                                         A, use_kernel=slot_steps.use_step_kernel(cfg))
            updates = jnp.sum(live, dtype=jnp.int32)
        o = o.reshape(B, T, H, P)
        slot_steps.finish(ctx, cfg, step, updates, state=state, conv=conv)
    with jax.named_scope("ssm.norm"):
        o = o + d_skip.astype(jnp.float32).reshape(H, 1) * \
            xs.astype(jnp.float32)
        v = ssd.gated_group_norm(o.reshape(B, T, d_in), z, norm.reshape(d_in),
                                 G, eps)
    with jax.named_scope("ssm.project"):
        out = v.astype(x.dtype) @ w_out
    return finish_layer(ctx, cfg, out, like=x_arg)
