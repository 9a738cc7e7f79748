"""Hyper-connections in the graph (ops/hyper_conn.py; mHC, arXiv:2512.24880):
a block's residual path as `streams` copies of the hidden size in ONE flat
layer of size streams x C (vec(X) IS the flat row the DSL carries), and
five layer kinds around a sublayer F:

  hyper_expand    h [.., C] -> X: the embedding copied into every stream
  hyper_maps      X -> [.., 2 n + n^2] float32: H_pre | H_post | H_res of
                  this sublayer, from its own parameters phi, b, alpha
  hyper_read      (X, maps) -> u = sum_i H_pre[i] X[i]: what F's norm reads
  hyper_write     (X, y, maps) -> X' = H_res X + H_post^T y: the stream pass
  hyper_collapse  X -> sum_i X[i]: what the final norm reads

Every one is a function of the row alone, so one implementation serves the
whole sequence, the dense cache, the paged decode step and the ragged mixed
step: a value [B, T, D] is taken as B T rows.  The maps are float32
whatever the compute dtype; the streams stay in it.

The stream pass goes through the Pallas kernel `mhc_mix`
(ops/pallas_hyper_conn.py) where `supported()` says so and the graph is not
being differentiated (the kernel is forward only); no flag selects the
form.  Device scopes: `mhc.map` (maps and read), `mhc.mix` (the write).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.config.schema import LayerConfig
from paddle_tpu.graph.common import finish_layer
from paddle_tpu.graph.context import ForwardContext
from paddle_tpu.graph.registry import register_layer
from paddle_tpu.ops import hyper_conn
from paddle_tpu.parameter.argument import Argument


def _rows(v):
    return v.reshape(-1, v.shape[-1])


def _shaped(rows, like_value):
    return rows.reshape(like_value.shape[:-1] + (rows.shape[-1],))


def use_mix_kernel(ctx: ForwardContext) -> bool:
    """`mhc_mix` unless the graph is being trained (no backward) or the
    kernel is not supported here."""
    if ctx.is_training:
        return False
    from paddle_tpu.ops import pallas_hyper_conn
    return pallas_hyper_conn.supported()


@register_layer("hyper_expand")
def hyper_expand_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    x = ctx.get_input(cfg, 0)
    n = int(cfg.attrs["streams"])
    reps = (1,) * (x.value.ndim - 1) + (n,)
    return finish_layer(ctx, cfg, jnp.tile(x.value, reps), like=x)


@register_layer("hyper_collapse")
def hyper_collapse_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    x = ctx.get_input(cfg, 0)
    n = int(cfg.attrs["streams"])
    v = x.value
    s = jnp.sum(v.reshape(v.shape[:-1] + (n, -1)).astype(jnp.float32),
                axis=-2)
    return finish_layer(ctx, cfg, s.astype(v.dtype), like=x)


@register_layer("hyper_maps")
def hyper_maps_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    x = ctx.get_input(cfg, 0)
    a = cfg.attrs
    phi, bias, alpha = (ctx.param_of(cfg, i) for i in range(3))
    with jax.named_scope("mhc.map"):
        m = hyper_conn.maps(
            _rows(x.value), phi, bias, alpha, n=int(a["streams"]),
            iters=int(a["sinkhorn_iters"]), eps=float(a["eps"]),
            clamp=tuple(a["res_clamp"]))
    return finish_layer(ctx, cfg, _shaped(m, x.value), like=x)


@register_layer("hyper_read")
def hyper_read_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    x, m = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    with jax.named_scope("mhc.map"):
        u = hyper_conn.read(_rows(x.value), _rows(m.value),
                            int(cfg.attrs["streams"]))
    return finish_layer(ctx, cfg, _shaped(u, x.value), like=x)


@register_layer("hyper_write")
def hyper_write_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    x, y, m = (ctx.get_input(cfg, i) for i in range(3))
    with jax.named_scope("mhc.mix"):
        out = hyper_conn.write(
            _rows(x.value), _rows(y.value), _rows(m.value),
            int(cfg.attrs["streams"]), kernel=use_mix_kernel(ctx))
    return finish_layer(ctx, cfg, _shaped(out, x.value), like=x)
