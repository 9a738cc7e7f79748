"""Cost layers.

The reference's cost zoo (ref: paddle/gserver/layers/CostLayer.cpp: multi-class
cross-entropy, self-normalized CE, soft binary CE, sum-of-squares, rank cost,
lambda rank, huber two-class, multi-binary-label CE) as per-sample cost
functions.  Each registers its [B] cost vector into ctx.costs; the executor
sums coeff-weighted costs into the scalar loss that jax.grad differentiates
(ref: Argument::sumCosts + hand-written backwardImp per cost — all replaced by
autodiff).
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

from paddle_tpu.config.schema import LayerConfig
from paddle_tpu.graph.context import ForwardContext
from paddle_tpu.graph.registry import register_layer
from paddle_tpu.parameter.argument import Argument

Array = jax.Array
_EPS = 1e-10
log = logging.getLogger("paddle_tpu.graph")


def _record(ctx: ForwardContext, cfg: LayerConfig, cost: Array) -> Argument:
    """Register per-sample cost; optional weight input is the 3rd input
    (ref: CostLayer weights handling in forward)."""
    if len(cfg.inputs) > 2:
        w = ctx.get_input(cfg, 2)
        cost = cost * (w.value.reshape(cost.shape) if w.value is not None else w.ids)
    ctx.costs[cfg.name] = cfg.coeff * cost
    return Argument(value=cost[:, None])


def _flatten_seq(out: Argument, lbl: Argument):
    """Sequence-shaped costs reduce over valid timesteps — the reference's flat
    token matrix sums per-token costs; on padded tensors we mask."""
    if out.is_sequence:
        mask = out.mask(jnp.float32)
        return out.value, lbl, mask
    return out.value, lbl, None


@register_layer("multi-class-cross-entropy", cost=True)
def multi_class_cross_entropy(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """-log p[label]; input is a probability distribution (softmax already
    applied as the previous layer's activation, matching the reference's
    classification_cost composition) (ref: MultiClassCrossEntropy::forwardImp).

    This is the path of every graph in which something else reads the
    probabilities, and of `forward`; where a softmax `fc` feeds only this
    cost, `loss` runs the pair as `fused_softmax_cost` instead."""
    out, lbl = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    probs = out.value
    labels = lbl.ids
    # gather THEN log: log∘gather == gather∘log elementwise, but this keeps
    # the work (and the materialized fp32 array) at O(B*T) instead of
    # O(B*T*vocab) — at vocab 30k the full-array log was 7% of the whole
    # seq2seq train step
    picked_p = jnp.take_along_axis(probs, labels[..., None], axis=-1)[..., 0]
    picked = jnp.log(jnp.maximum(picked_p, _EPS))
    if out.is_sequence:
        cost = -jnp.sum(picked * out.mask(probs.dtype), axis=-1)
    else:
        cost = -picked
    return _record(ctx, cfg, cost)


def fused_softmax_cost(ctx: ForwardContext, fc: LayerConfig,
                       cfg: LayerConfig) -> None:
    """`fc(act=softmax)` and its `multi-class-cross-entropy` as one op
    (ops/softmax_ce.py): the cost from the fc's input and weight by a
    float32 log-sum-exp, no probabilities built.  The executor calls this
    in `loss` for the pairs its graph walk found
    (GraphExecutor._fusable_softmax_costs); it publishes the cost as
    `multi_class_cross_entropy` does and, under the fc's name, the rows'
    argmax as an ids-only Argument for `classification_error`."""
    from paddle_tpu.obs.metrics import process_counters
    from paddle_tpu.ops.softmax_ce import linear_softmax_ce, time_chunks
    from paddle_tpu.parallel.mesh import DATA_AXIS, axis_size

    src = ctx.get_input(fc, 0)
    chunks = 1
    if src.value.ndim == 3:
        B, T, _ = src.value.shape
        chunks = time_chunks(-(-B // axis_size(ctx.mesh, DATA_AXIS)), T,
                             fc.size)
    nll, pred = linear_softmax_ce(src.value, ctx.param_of(fc, 0),
                                  ctx.bias_of(fc), ctx.get_input(cfg, 1).ids,
                                  chunks)
    out = Argument(ids=pred, lengths=src.lengths if pred.ndim >= 2 else None)
    cost = jnp.sum(nll * out.mask(nll.dtype), axis=-1) \
        if out.is_sequence else nll
    ctx.outputs[fc.name] = out
    ctx.outputs[cfg.name] = _record(ctx, cfg, cost)
    process_counters().add("graph_fused_softmax_cost_total", 1)
    log.info("fused softmax cost: fc %r + %r as one op (rows %s in %d "
             "piece(s), %d classes, %s)", fc.name, cfg.name, list(pred.shape),
             chunks, fc.size, src.value.dtype.name)


@register_layer("multi_class_cross_entropy_with_selfnorm", cost=True)
def selfnorm_cross_entropy(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """CE + alpha * log(Z)^2 self-normalization penalty
    (ref: MultiClassCrossEntropyWithSelfNorm::forwardImp)."""
    out, lbl = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    probs = out.value
    z = jnp.sum(probs, axis=-1)
    probs_n = probs / jnp.maximum(z[..., None], _EPS)
    picked = jnp.take_along_axis(
        jnp.log(jnp.maximum(probs_n, _EPS)), lbl.ids[..., None], axis=-1)[..., 0]
    cost = -picked + cfg.softmax_selfnorm_alpha * jnp.square(jnp.log(jnp.maximum(z, _EPS)))
    return _record(ctx, cfg, cost)


@register_layer("soft_binary_class_cross_entropy", cost=True)
def soft_binary_cross_entropy(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """-sum t*log(p) + (1-t)*log(1-p) with soft targets
    (ref: SoftBinaryClassCrossEntropy::forwardImp)."""
    out, lbl = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    p = jnp.clip(out.value, _EPS, 1.0 - _EPS)
    t = lbl.value
    cost = -jnp.sum(t * jnp.log(p) + (1.0 - t) * jnp.log1p(-p), axis=-1)
    return _record(ctx, cfg, cost)


@register_layer("multi_binary_label_cross_entropy", cost=True)
def multi_binary_label_cross_entropy(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Binary CE against a set of positive label ids
    (ref: MultiBinaryLabelCrossEntropy::forwardImp; label is a sparse binary
    vector — here a dense 0/1 matrix [B, C])."""
    out, lbl = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    p = jnp.clip(out.value, _EPS, 1.0 - _EPS)
    t = lbl.value
    cost = -jnp.sum(t * jnp.log(p) + (1.0 - t) * jnp.log1p(-p), axis=-1)
    return _record(ctx, cfg, cost)


@register_layer("square_error", cost=True)
def square_error(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """0.5 * ||out - label||^2 (ref: SumOfSquaresCostLayer::forwardImp)."""
    out, lbl = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    d = out.value - lbl.value
    if out.is_sequence:
        cost = 0.5 * jnp.sum(jnp.sum(jnp.square(d), axis=-1) * out.mask(d.dtype), axis=-1)
    else:
        cost = 0.5 * jnp.sum(jnp.square(d), axis=-1)
    return _record(ctx, cfg, cost)


@register_layer("rank-cost", cost=True)
def rank_cost(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Pairwise ranking: -t*o + log(1 + exp(o)), o = s_a - s_b
    (ref: RankingCost::forwardImp)."""
    a, b, lbl = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1), ctx.get_input(cfg, 2)
    o = (a.value - b.value)[..., 0]
    t = lbl.value[..., 0] if lbl.value is not None else lbl.ids.astype(o.dtype)
    cost = -t * o + jax.nn.softplus(o)
    ctx.costs[cfg.name] = cfg.coeff * cost
    return Argument(value=cost[:, None])


@register_layer("huber_classification", "huber", cost=True)
def huber_two_class(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Two-class huber cost on a scalar score with labels {0,1} -> y in {-1,1}
    (ref: HuberTwoClass::forwardImp)."""
    out, lbl = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    score = out.value[..., 0]
    y = 2.0 * lbl.ids.astype(score.dtype) - 1.0
    a = y * score
    cost = jnp.where(a < -1.0, -4.0 * a, jnp.where(a < 1.0, jnp.square(1.0 - a), 0.0))
    return _record(ctx, cfg, cost)


@register_layer("sum_cost", cost=True)
def sum_cost(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Sum input values as cost (ref: SumCostLayer)."""
    out = ctx.get_input(cfg, 0)
    if out.is_sequence:
        cost = jnp.sum(jnp.sum(out.value, axis=-1) * out.mask(out.value.dtype), axis=-1)
    else:
        cost = jnp.sum(out.value, axis=-1)
    ctx.costs[cfg.name] = cfg.coeff * cost
    return Argument(value=cost[:, None])


@register_layer("lambda_cost", cost=True)
def lambda_cost(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """LambdaRank NDCG cost over each list (sequence) (ref: LambdaCost).

    Differentiable surrogate: for each pair (i,j) in a list, logistic pairwise
    loss weighted by |ΔNDCG|.  The reference computes hand-crafted lambdas in
    backward; here the pairwise-weighted loss's autodiff gradient plays that
    role.
    """
    out, lbl = ctx.get_input(cfg, 0), ctx.get_input(cfg, 1)
    s = out.value[..., 0]                      # [B, T] scores
    r = lbl.value[..., 0]                      # [B, T] relevance
    mask = out.mask(s.dtype)
    pair_valid = mask[:, :, None] * mask[:, None, :]
    sdiff = s[:, :, None] - s[:, None, :]
    rdiff = r[:, :, None] - r[:, None, :]
    better = (rdiff > 0).astype(s.dtype)
    gain_w = jnp.abs(rdiff)
    pair_cost = jax.nn.softplus(-sdiff) * better * gain_w * pair_valid
    cost = jnp.sum(pair_cost, axis=(1, 2))
    return _record(ctx, cfg, cost)


# -- in-graph validation layers ---------------------------------------------

@register_layer("auc-validation", "pnpair-validation", validation=True)
def validation_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Evaluation inside the graph during training (ref:
    paddle/gserver/layers/ValidationLayer.cpp; created at Layer.cpp:116-119;
    DSL side config_parser.py:1961-1962).

    The reference's AucValidation/PnpairValidation wrap an Evaluator
    ('last-column-auc' / 'pnpair') fed every forward, with a no-op
    backward.  Here the layer itself is a stop-gradient pass-through of
    its score input; the evaluator wiring is synthesized from the layer
    config by EvaluatorSet (trainer/evaluators.py), which already owns
    the start/eval/finish accumulation protocol.
    """
    out = ctx.get_input(cfg, 0)
    return jax.tree.map(jax.lax.stop_gradient, out)
