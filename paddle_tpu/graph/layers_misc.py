"""Elementwise / combination layer zoo.

Covers the reference's small-but-numerous combination layers (ref:
paddle/gserver/layers/{ScalingLayer,SlopeInterceptLayer,InterpolationLayer,
PowerLayer,ConvexCombinationLayer,CosSimLayer,CosSimVecMatLayer,
OuterProdLayer,TensorLayer,MultiplexLayer,TransLayer,ResizeLayer,
FeatureMapExpandLayer,ParameterReluLayer,PrintLayer,SelectiveFullyConnectedLayer}.cpp).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.config.schema import LayerConfig
from paddle_tpu.graph.common import finish_layer
from paddle_tpu.graph.context import ForwardContext
from paddle_tpu.graph.registry import register_layer
from paddle_tpu.parameter.argument import Argument

Array = jax.Array


@register_layer("scaling")
def scaling_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Row-wise scale: out[i] = w[i] * x[i]; input0 = weights [B,1], input1 = x
    (ref: ScalingLayer.cpp)."""
    w, x = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    return finish_layer(ctx, cfg, x.value * w.value, like=x)


@register_layer("slope_intercept")
def slope_intercept_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """out = slope * x + intercept (ref: SlopeInterceptLayer.cpp)."""
    x = ctx.get_input(cfg, 0)
    slope = cfg.attrs.get("slope", 1.0)
    intercept = cfg.attrs.get("intercept", 0.0)
    return finish_layer(ctx, cfg, slope * x.value + intercept, like=x)


@register_layer("interpolation")
def interpolation_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """out = w*x1 + (1-w)*x2, w per-row [B,1] (ref: InterpolationLayer.cpp)."""
    w, a, b = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1), ctx.get_input(cfg, 2)
    out = w.value * a.value + (1.0 - w.value) * b.value
    return finish_layer(ctx, cfg, out, like=a)


@register_layer("power")
def power_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """out = x ** w, w per-row [B,1] (ref: PowerLayer.cpp)."""
    w, x = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    return finish_layer(ctx, cfg, jnp.power(x.value, w.value), like=x)


@register_layer("convex_comb", "linear_comb")
def linear_comb_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """out = weights-row-matrix @ x-matrix per sample: input0 [B, M] weights,
    input1 [B, M*D] values -> [B, D] (ref: ConvexCombinationLayer.cpp)."""
    w, x = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    B, M = w.value.shape
    D = cfg.size
    xv = x.value.reshape(B, M, D)
    out = jnp.einsum("bm,bmd->bd", w.value, xv)
    return finish_layer(ctx, cfg, out)


@register_layer("cos")
def cos_sim_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Cosine similarity * scale (ref: CosSimLayer.cpp, hl_cossim)."""
    a, b = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    scale = cfg.attrs.get("cos_scale", 1.0)
    eps = 1e-8
    num = jnp.sum(a.value * b.value, axis=-1)
    den = jnp.sqrt(jnp.sum(jnp.square(a.value), axis=-1) *
                   jnp.sum(jnp.square(b.value), axis=-1))
    out = scale * num / jnp.maximum(den, eps)
    return finish_layer(ctx, cfg, out[..., None], like=a)


@register_layer("cos_vm")
def cos_sim_vecmat_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Cosine of a vector against each row of a per-sample matrix:
    input0 [B, D], input1 [B, M*D] -> [B, M] (ref: CosSimVecMatLayer.cpp)."""
    v, m = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    scale = cfg.attrs.get("cos_scale", 1.0)
    B, D = v.value.shape
    M = cfg.size
    mv = m.value.reshape(B, M, D)
    eps = 1e-8
    num = jnp.einsum("bmd,bd->bm", mv, v.value)
    den = jnp.sqrt(jnp.sum(jnp.square(mv), axis=-1) *
                   jnp.sum(jnp.square(v.value), axis=-1, keepdims=True))
    out = scale * num / jnp.maximum(den, eps)
    return finish_layer(ctx, cfg, out)


@register_layer("out_prod")
def outer_prod_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Flattened outer product of two vectors (ref: OuterProdLayer.cpp)."""
    a, b = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    out = jnp.einsum("bi,bj->bij", a.value, b.value)
    return finish_layer(ctx, cfg, out.reshape(out.shape[0], -1))


@register_layer("tensor")
def tensor_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Bilinear tensor product: out_k = x1 W_k x2^T
    (ref: TensorLayer.cpp; parameter [D1, K*D2])."""
    a, b = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    w = ctx.param_of(cfg, 0)
    K = cfg.size
    D1 = a.value.shape[-1]
    D2 = b.value.shape[-1]
    w3 = w.reshape(D1, K, D2)
    out = jnp.einsum("bi,ikj,bj->bk", a.value, w3, b.value)
    bb = ctx.bias_of(cfg)
    if bb is not None:
        out = out + bb
    return finish_layer(ctx, cfg, out)


@register_layer("multiplex")
def multiplex_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Row-wise select among inputs 1..N by index input 0
    (ref: MultiplexLayer.cpp)."""
    sel = ctx.get_input(cfg, 0)
    options = [ctx.get_input(cfg, i).value for i in range(1, len(cfg.inputs))]
    stacked = jnp.stack(options, axis=1)          # [B, N, D]
    idx = sel.ids
    out = jnp.take_along_axis(stacked, idx[:, None, None].astype(jnp.int32)
                              .repeat(stacked.shape[-1], -1), axis=1)[:, 0]
    return finish_layer(ctx, cfg, out)


@register_layer("trans")
def trans_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Transpose the (batch x dim) matrix (ref: TransLayer.cpp)."""
    x = ctx.get_input(cfg, 0)
    return finish_layer(ctx, cfg, x.value.T)


@register_layer("resize")
def resize_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Reinterpret the batch as rows of `size` (ref: ResizeLayer.cpp)."""
    x = ctx.get_input(cfg, 0)
    return finish_layer(ctx, cfg, x.value.reshape(-1, cfg.size))


@register_layer("featmap_expand")
def featmap_expand_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Tile features num_filters times (ref: FeatureMapExpandLayer.cpp)."""
    x = ctx.get_input(cfg, 0)
    out = jnp.repeat(x.value[:, None, :], cfg.num_filters, axis=1)
    return finish_layer(ctx, cfg, out.reshape(x.value.shape[0], -1), like=x)


@register_layer("prelu")
def parameter_relu_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Parametric ReLU with partition sharing (ref: ParameterReluLayer.cpp)."""
    x = ctx.get_input(cfg, 0)
    w = ctx.param_of(cfg, 0)
    D = x.value.shape[-1]
    # each slope is shared across partial_sum consecutive dims (w.size = D/partial_sum)
    slopes = jnp.repeat(w.reshape(-1), D // w.size)
    out = jnp.where(x.value > 0, x.value, x.value * slopes)
    return finish_layer(ctx, cfg, out, like=x)


@register_layer("conv_shift")
def conv_shift_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Circular correlation of each row of a with kernel b (odd length M):
    out[i] = sum_j b[j] * a[(i + j - M//2) mod D] (ref: ConvShiftLayer.cpp,
    used for NTM-style shift attention)."""
    a, b = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    D = a.value.shape[-1]
    M = b.value.shape[-1]
    half = M // 2
    out = jnp.zeros_like(a.value)
    for j in range(M):
        out = out + b.value[:, j:j + 1] * jnp.roll(a.value, half - j, axis=-1)
    return finish_layer(ctx, cfg, out, like=a)


@register_layer("print")
def print_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Debug-print inputs at trace time (ref: PrintLayer.cpp); identity."""
    x = ctx.get_input(cfg, 0)
    jax.debug.print("print layer {}: {}", cfg.name, x.data)
    return x


@register_layer("selective_fc")
def selective_fc_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Selective FC (ref: SelectiveFullyConnectedLayer.cpp): full output here —
    the selection mask is an inference-time sparsity optimization that XLA's
    dense matmul makes unnecessary.  With a selection input, non-selected
    logits are pushed to a large negative value BEFORE the (softmax)
    activation so unselected classes get ~zero probability — the reference
    computes softmax over only the selected columns."""
    inputs = ctx.get_inputs(cfg)
    has_sel = cfg.attrs.get("has_selected_colums", False)
    feat_inputs = inputs[:-1] if has_sel else inputs
    acc = None
    for i, arg in enumerate(feat_inputs):
        w = ctx.param_of(cfg, i)
        y = jnp.matmul(arg.value, w.T if w.shape[0] == cfg.size else w)
        acc = y if acc is None else acc + y
    b = ctx.bias_of(cfg)
    if b is not None:
        acc = acc + b
    if has_sel:
        sel = inputs[-1]
        if cfg.active_type == "softmax":
            acc = jnp.where(sel.value > 0, acc, -1e9)
        else:
            acc = acc * sel.value
    return finish_layer(ctx, cfg, acc, like=feat_inputs[0])


@register_layer("layer_norm")
def layer_norm_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Last-dim layer normalization with learned scale/bias — beyond the
    reference's zoo (its only norms are cross-map response norms,
    NormLayer.cpp); the transformer-era block needs it.  Statistics in
    fp32 under mixed precision."""
    x = ctx.get_input(cfg, 0)
    from paddle_tpu.utils.dtypes import promote_compute
    v32 = promote_compute(x.value)
    mean = jnp.mean(v32, axis=-1, keepdims=True)
    var = jnp.var(v32, axis=-1, keepdims=True)
    normed = (v32 - mean) * jax.lax.rsqrt(var + 1e-6)
    scale = ctx.param_of(cfg, 0)
    if scale is not None:
        normed = normed * promote_compute(scale).reshape(-1)
    b = ctx.bias_of(cfg)
    if b is not None:
        normed = normed + promote_compute(b).reshape(-1)
    return finish_layer(ctx, cfg, normed.astype(x.value.dtype), like=x)


def rms_norm(x, scale, eps: float):
    """x / sqrt(mean(x^2) + eps) * scale over the last dim; statistics in
    fp32 under mixed precision, the result in x's dtype."""
    from paddle_tpu.utils.dtypes import promote_compute
    v32 = promote_compute(x)
    normed = v32 * jax.lax.rsqrt(
        jnp.mean(v32 * v32, axis=-1, keepdims=True) + eps)
    if scale is not None:
        normed = normed * promote_compute(scale).reshape(-1)
    return normed.astype(x.dtype)


@register_layer("rms_norm")
def rms_norm_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Root-mean-square normalization with a learned scale and no bias
    (Zhang & Sennrich 2019) — the norm of the llama/deepseek-era blocks;
    attrs['eps'] (default 1e-6)."""
    x = ctx.get_input(cfg, 0)
    out = rms_norm(x.value, ctx.param_of(cfg, 0),
                   float(cfg.attrs.get("eps", 1e-6)))
    return finish_layer(ctx, cfg, out, like=x)


def gated_ffn(x, w_gate, w_up, w_down):
    """SwiGLU: (silu(x W_gate) * (x W_up)) W_down, bias-free."""
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


@register_layer("gated_ffn")
def gated_ffn_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """The gated feed-forward block (Shazeer 2020, "GLU variants"): three
    bias-free matrices gate [d, f], up [d, f], down [f, size], all carried
    by the one data input."""
    x = ctx.get_input(cfg, 0)
    w_gate, w_up, w_down = (ctx.param_of(cfg, i) for i in range(3))
    return finish_layer(ctx, cfg, gated_ffn(x.value, w_gate, w_up, w_down),
                        like=x)
