"""Mixture-of-experts layer — routed (and optionally shared) expert FFNs.

NEW capability beyond the reference (see parallel/moe.py).  The layer's
parameters ride the standard input-parameter mechanism: LayerInputs all
referencing the single data input.  THREE EXPERT FORMS (parallel/moe.py
`_expert_products`).  Plain experts with biases (the default): router, w1,
b1, w2, b2, their nonlinearity attrs['expert_act'] (`relu`, `relu2`).
Bias-free plain experts (attrs['expert_bias'] false): router, w_up, w_down.
Gated experts (attrs['gated']): router, w_gate, w_up, w_down.  After the
two bias-free forms come the selection bias [1, E] if attrs['select_bias']
and, if attrs['shared_hidden'] > 0, the shared expert IN THE EXPERTS' OWN
FORM: gate, up, down beside gated experts, up, down (the same nonlinearity)
beside plain ones.

attrs['first_expert'] says which experts the stacked weights are: the layer
routes over all attrs['num_experts'] and computes the held block's part
(parallel/moe.py); the shared expert, which every chip computes alike, is
added whole.  The aux load-balancing loss registers into ctx.costs like a
cost layer, scaled by attrs['aux_weight'].

A caller that hands in a state entry for the layer (the serving engine)
gets back `pairs` [rows, E_held]: which held experts each row was routed
to — the load counters' source — and `overflow_tiles`, the tiles the
grouped form's overflow loop ran in this call (0 under the dense form).
The entry may say which rows are `live` ([rows] bool; a mixed step's
padding rows are not): the grouped form routes only those — the padding
rows of a step are all alike, so they would all fill the same experts'
slots.

Which of the expert block's two formulations a program runs is
`expert_form_of`: parallel/moe.py's rule given this layer's shapes, its
mesh and its mode — grouped from `_GROUPED_OVER_RIDGE` times the chip's
ridge on, the first round's slots (`first_round_slots`) a function of the
same shapes.  The layer asks it when it is traced; the serving engine asks
the same function what its step programs were traced to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.config.schema import LayerConfig
from paddle_tpu.graph.common import finish_layer
from paddle_tpu.graph.context import ForwardContext
from paddle_tpu.graph.registry import register_layer
from paddle_tpu.parallel.mesh import MODEL_AXIS, axis_size
from paddle_tpu.parallel.moe import (expert_activation, expert_form,
                                     first_round_slots, moe_ffn,
                                     overflow_tiles)
from paddle_tpu.parameter.argument import Argument


def expert_form_of(cfg: LayerConfig, params: dict, rows: int, mesh=None,
                   training: bool = False) -> str:
    """"dense" or "grouped": what this layer's expert block runs at `rows`
    token rows (`params`: name -> array, or anything with its shape and
    dtype)."""
    w_router = params[cfg.inputs[0].input_parameter_name]
    held = params[cfg.inputs[1].input_parameter_name]
    return expert_form(rows, int(cfg.attrs.get("top_k", 2)),
                       w_router.shape[-1], jnp.dtype(held.dtype).itemsize,
                       partitioned=axis_size(mesh, MODEL_AXIS) > 1,
                       training=training)


@register_layer("moe")
def moe_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    x = ctx.get_input(cfg, 0)
    a = cfg.attrs
    params = [ctx.param_of(cfg, i) for i in range(len(cfg.inputs))]
    w_router = params[0]
    n_mats = 3 if a.get("gated") else 4 if a.get("expert_bias", True) else 2
    experts = tuple(params[1:1 + n_mats])
    rest = params[1 + n_mats:]
    select_bias = rest.pop(0).reshape(-1) if a.get("select_bias") else None
    # the shared expert's matrices, in the experts' own (bias-free) form
    shared = tuple(rest[:n_mats]) \
        if int(a.get("shared_hidden", 0) or 0) else None
    activation = expert_activation(str(a.get("expert_act", "relu")))
    aux_w = float(a.get("aux_weight", 0.01))

    v = x.value
    seq_shape = None
    valid = None
    if v.ndim == 3:                      # [B, T, D] -> route per token
        seq_shape = v.shape[:2]
        v = v.reshape(-1, v.shape[-1])
        mask = x.mask()                  # padding never routed (cf. attention)
        if mask is not None:
            valid = mask.reshape(-1)
    form = expert_form_of(cfg, ctx.params, v.shape[0], ctx.mesh,
                          ctx.is_training)
    live = (ctx.state_in.get(cfg.name) or {}).get("live")
    if form == "grouped" and live is not None:
        live = live.reshape(-1)
        valid = live if valid is None else jnp.logical_and(valid, live)
    y, aux, pairs = moe_ffn(
        v, w_router, experts, top_k=int(a.get("top_k", 2)),
        first_expert=int(a.get("first_expert", 0)), activation=activation,
        valid=valid, form=form,
        scoring=str(a.get("scoring", "softmax")),
        n_group=int(a.get("n_group", 1)),
        topk_group=int(a.get("topk_group", 1)), select_bias=select_bias,
        norm_topk=bool(a.get("norm_topk", True)),
        scale=float(a.get("routed_scale", 1.0)))
    if shared is not None:
        with jax.named_scope("moe.shared"):
            if len(shared) == 3:
                from paddle_tpu.graph.layers_misc import gated_ffn
                y = y + gated_ffn(v, *shared)
            else:
                y = y + activation(v @ shared[0]) @ shared[1]
    if ctx.state_in.get(cfg.name) is not None:
        # the grouped form's `pairs` are of the rows it routed (`valid`)
        tiles = jnp.int32(0) if form == "dense" else jnp.sum(overflow_tiles(
            jnp.sum(pairs, axis=0, dtype=jnp.int32), first_round_slots(
                v.shape[0], int(a.get("top_k", 2)), w_router.shape[-1])))
        ctx.state_out[cfg.name] = {"pairs": pairs, "overflow_tiles": tiles}
    if seq_shape is not None:
        y = y.reshape(seq_shape + (y.shape[-1],))
    if aux_w > 0 and ctx.is_training:
        # per-sample broadcast so the executor's mean() leaves aux_w * aux
        ctx.costs[f"{cfg.name}.aux"] = jnp.broadcast_to(
            aux_w * aux, (x.batch_size,))
    return finish_layer(ctx, cfg, y, like=x)
