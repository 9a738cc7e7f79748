"""The Mamba-1 token mixer (ops/selective_scan.py; arXiv:2312.00752, the
Jamba family's Mamba layers, arXiv:2403.19887): a selective state-space
layer whose context is one recurrent state [N, d_in] a sequence — every
state element with a decay of its own — and the last `conv_size - 1` inputs
of the convolution in front of it.

    [x_t, z_t] = u_t W_in                                 2 d_in columns
    x'_t = silu(b + sum_j w_j * x_{t-(taps-1)+j})         depthwise, causal
    [r_t, B_t, C_t] = x'_t W_x                            R + 2 N columns
    r_t, B_t, C_t = RMSNorm(r_t), RMSNorm(B_t), RMSNorm(C_t)   Jamba's three
                                                    inner norms, a scale each
    dt_t = softplus(r_t W_dt + b_dt)                      float32 from here
    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x'_t[c] B_t[n]
    y_t[c] = sum_n h_t[n, c] C_t[n] + D[c] x'_t[c]        A = -exp(A_log)
    out_t = (y_t * silu(z_t)) W_out

inputs (all the one data input): w_in [d, 2 d_in], conv [taps, d_in],
conv_bias [1, d_in], w_x [d_in, R + 2 N], dt_norm [1, R], b_norm [1, N],
c_norm [1, N], w_dt [R, d_in], dt_bias [1, d_in], a_log [N, d_in] (THE
STATE'S ORIENTATION, ops/selective_scan.py: the paper's [d_in, N]
transposed), d [1, d_in], w_out [d_in, size].
attrs: d_inner, state_size, dt_rank, conv_size, rms_eps, attn_impl.

Three paths, picked by the state the executor hands in — the dispatch, the
run mask and the convolution's tail are graph/slot_steps.py's, shared with
the KDA, short-convolution and Mamba-2 layers (graph/layers_kda.py has THE
PACKING CONTRACT of the ragged one): the whole sequence one literal scan
from the zero state; the decode step, one `selective_scan.step_rows` a row;
the ragged mixed step, its decode rows one batched step and each chunk run
one scan from its slot's state.  On the TPU these are two kernels of
ops/pallas_selective_scan.py: `selective_scan_step`, one token a row, and
`selective_scan_seg`, a chunk run with the time loop inside the kernel;
elsewhere (and under attn_impl dense/blockwise) the jnp forms.

The slot state lives in the serving cache manager (serving/paged_kv.py,
slot-indexed parts): `state` [S+1, N, d_in] float32 and `conv` [S+1,
taps-1, d_in] in the compute dtype, x of the last taps-1 positions before
the activation.  A caller that hands in a state gets back, beside the new
parts, `rows` and `updates`, as the KDA layer does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.config.schema import LayerConfig
from paddle_tpu.graph import slot_steps
from paddle_tpu.graph.common import finish_layer
from paddle_tpu.graph.context import ForwardContext
from paddle_tpu.graph.layers_misc import rms_norm
from paddle_tpu.graph.registry import register_layer, register_slot_state
from paddle_tpu.ops import selective_scan as ss
from paddle_tpu.parameter.argument import Argument


def _sizes(cfg: LayerConfig) -> tuple[int, int, int]:
    a = cfg.attrs
    return int(a["d_inner"]), int(a["state_size"]), int(a["dt_rank"])


@register_slot_state("mamba")
def mamba_slot_parts(cfg: LayerConfig, compute_dtype) -> dict:
    """The recurrent state, float32 whatever the compute dtype (it is what
    the recurrence accumulates in) and [N, d_in] — d_in along lanes — and
    the convolution tail in the compute dtype."""
    d_in, N, _ = _sizes(cfg)
    taps = int(cfg.attrs.get("conv_size", 4))
    return {"state": ((N, d_in), jnp.float32),
            "conv": ((taps - 1, d_in), compute_dtype)}


@register_layer("mamba")
def mamba_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    x_arg = ctx.get_input(cfg, 0)
    (w_in, w_conv, b_conv, w_x, dt_norm, b_norm, c_norm, w_dt, dt_bias,
     a_log, d_skip, w_out) = (ctx.param_of(cfg, i) for i in range(12))
    d_in, N, R = _sizes(cfg)
    eps = float(cfg.attrs.get("rms_eps", 1e-6))
    u = x_arg.value                                       # [B, T, d]
    B, T, _ = u.shape
    step = slot_steps.slot_step(ctx, cfg, u, "state")

    with jax.named_scope("mamba.project"):
        xz = u @ w_in
        x, z = xz[..., :d_in], xz[..., d_in:]
    with jax.named_scope("mamba.conv"):
        x, conv = slot_steps.conv(step, x, w_conv.astype(x.dtype),
                                  b_conv.reshape(-1).astype(x.dtype))
        x = jax.nn.silu(x)
    with jax.named_scope("mamba.project"):
        rbc = x @ w_x
        r, Bm, Cm = rbc[..., :R], rbc[..., R:R + N], rbc[..., R + N:]
    with jax.named_scope("mamba.norm"):
        r = rms_norm(r, dt_norm, eps)
        Bm = rms_norm(Bm.astype(jnp.float32), b_norm, eps)
        Cm = rms_norm(Cm.astype(jnp.float32), c_norm, eps)
    with jax.named_scope("mamba.project"):
        dt = ss.time_step(
            jnp.matmul(r, w_dt, preferred_element_type=jnp.float32),
            dt_bias.reshape(d_in))
    A = -jnp.exp(a_log.astype(jnp.float32))               # [N, d_in]
    if step is None:
        with jax.named_scope("mamba.scan"):
            y, _ = ss.recurrent(x, Bm, Cm, dt, A)
    else:
        state, S = step.cache["state"], step.slots
        row_slot, row_pos, _, _, live = step.runs
        rows = lambda a: a.reshape((B * T,) + a.shape[2:])
        xr, Br, Cr, dtr = map(rows, (x, Bm, Cm, dt))
        kernel = slot_steps.use_step_kernel(cfg)
        if step.ragged:
            with jax.named_scope("mamba.step"):
                y_d, state = ss.step_rows(
                    state, row_slot[:S], live[:S], xr[:S], Br[:S], Cr[:S],
                    dtr[:S], A, use_kernel=kernel)
            with jax.named_scope("mamba.scan"):
                y_c, state, n_seg = ss.segment_rows(
                    state, row_slot[S:], row_pos[S:], xr[S:], Br[S:], Cr[S:],
                    dtr[S:], A, use_kernel=kernel)
            y = jnp.concatenate([y_d, y_c], axis=0)
            updates = jnp.sum(live[:S], dtype=jnp.int32) + n_seg
        else:
            with jax.named_scope("mamba.step"):
                y, state = ss.step_rows(state, None, live, xr, Br, Cr, dtr,
                                        A, use_kernel=kernel)
            updates = jnp.sum(live, dtype=jnp.int32)
        y = y.reshape(B, T, d_in)
        slot_steps.finish(ctx, cfg, step, updates, state=state, conv=conv)
    with jax.named_scope("mamba.project"):
        y = y + d_skip.astype(jnp.float32).reshape(d_in) * \
            x.astype(jnp.float32)
        v = y * jax.nn.silu(z.astype(jnp.float32))
        out = v.astype(u.dtype) @ w_out
    return finish_layer(ctx, cfg, out, like=x_arg)
