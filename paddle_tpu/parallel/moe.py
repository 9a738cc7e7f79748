"""Mixture-of-experts: top-k routing and a DROPLESS expert block that is
told which experts it holds.

NEW capability beyond the reference (2016-era PaddlePaddle predates MoE; its
closest relative is per-layer device placement, ref: paddle/gserver/
gradientmachines/ParallelNeuralNetwork.h:35-70).  Completes the framework's
parallelism portfolio (dp/tp/sp/pp + ep).

Routing (`moe_route`) covers the published families with one function:

  * softmax scores, plain top-k, weights renormalized over the picks
    (Shazeer et al. 2017, GShard) — the defaults;
  * sigmoid scores with GROUP-LIMITED selection and a selection-only bias
    (DeepSeek-V3, arXiv:2412.19437 "noaux_tc"): experts sit in `n_group`
    contiguous groups, a group scores the sum of its two best
    `score + bias`, only the `topk_group` best groups stay eligible, the
    top-k of `score + bias` among them are selected — and the combine
    weights come from the UNBIASED scores, renormalized over the picks and
    multiplied by `scale`.

The expert block (`moe_ffn`) is dropless: every routed (token, expert) pair
is computed, there is no capacity and nothing is dropped.  It computes the
experts `[first_expert, first_expert + E_held)` — the stacked weights it is
handed — and adds up only what THOSE experts give; pairs routed to experts
held elsewhere contribute nothing here (their chips add them: expert
parallelism without the exchange, which a one-chip share never runs).  With
every expert held it is the whole layer.

Formulation: each held expert multiplies every row and a dense combine
matrix `[B, E_held]` (zero off the routed pairs) weighs the results.  Work
is B x E_held expert products, not the routed pairs: right where rows x
held experts is small — a step reads each expert's weights once whatever
the rows, and while the products take less time than that read the MXU is
idle beside the HBM (8 held x 64 rows and 16 held x 128-320 rows, the
GigaChat and Kimi-Linear cells: 2 and 4 routed pairs an expert).  THE
BOUND: with 64 held experts of 3 x 2048 x 1536 and 256-512 rows
(`lfm2-24b-serve.long-output-256`, 16 pairs an expert) the products are
1.24-2.47 TFLOP a step in four layers, 6.3-12.6 ms at the v5e's peak,
against 5.9 ms to read the experts' 4.83 GB: the MXU sets the pace, at 16
times the routed work (PERF.md section 5 has the measured cost).  Past
that, and for long whole-sequence calls, a sort by expert and a ragged
product belongs — to be claimed in that cell (ROADMAP D12, S14).  Stacked
expert weights shard over the `model` mesh axis as before; XLA partitions
the einsums.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


def moe_route(
    gate_logits: Array,        # [B, E]
    top_k: int,
    *,
    scoring: str = "softmax",  # | "sigmoid"
    n_group: int = 1,
    topk_group: int = 1,
    select_bias: Optional[Array] = None,   # [E]; selection only
    norm_topk: bool = True,
    scale: float = 1.0,
    valid: Optional[Array] = None,   # [B] bool; padding tokens never routed
) -> tuple[Array, Array, Array]:
    """(expert ids [B, k] int32, combine weights [B, k] float32, aux loss).

    aux loss is the load-balancing loss of Shazeer et al.: E * sum_e
    (fraction of valid tokens whose FIRST choice is e) * (mean score of e).
    Padding tokens (valid False) get weight 0 on every pick.  With
    top_k == 1 the raw score is the output scale (Switch Transformer:
    normalizing would cancel it and starve the router of gradient)."""
    B, E = gate_logits.shape
    logits = gate_logits.astype(jnp.float32)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router scoring {scoring!r} "
                         f"(softmax or sigmoid)")
    choice = scores if select_bias is None else \
        scores + select_bias.astype(jnp.float32).reshape(1, E)
    if n_group > 1:
        assert E % n_group == 0, f"{E} experts do not split in {n_group} groups"
        per = choice.reshape(B, n_group, E // n_group)
        group_score = jnp.sum(jax.lax.top_k(per, min(2, E // n_group))[0], -1)
        _, keep = jax.lax.top_k(group_score, topk_group)          # [B, g]
        in_kept = jnp.any(keep[:, :, None] == jnp.arange(n_group)[None, None],
                          axis=1)                                 # [B, G]
        choice = jnp.where(jnp.repeat(in_kept, E // n_group, axis=1),
                           choice, -jnp.inf)
    _, idx = jax.lax.top_k(choice, top_k)                         # [B, k]
    weight = jnp.take_along_axis(scores, idx, axis=1)
    if norm_topk and top_k > 1:
        weight = weight / jnp.maximum(
            jnp.sum(weight, axis=-1, keepdims=True), 1e-20)
    weight = weight * scale
    vmask = jnp.ones((B,), jnp.float32) if valid is None \
        else valid.astype(jnp.float32)
    weight = weight * vmask[:, None]

    n_valid = jnp.maximum(jnp.sum(vmask), 1.0)
    first = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32) * vmask[:, None]
    aux = E * jnp.sum((jnp.sum(first, 0) / n_valid) *
                      (jnp.sum(scores * vmask[:, None], 0) / n_valid))
    return idx.astype(jnp.int32), weight, aux


def held_hits(idx: Array, first_expert: int, n_held: int) -> Array:
    """[B, k, n_held] bool: pick j of token b is held expert
    first_expert + h."""
    held = first_expert + jnp.arange(n_held, dtype=idx.dtype)
    return idx[:, :, None] == held[None, None, :]


def combine_weights(idx: Array, weight: Array, first_expert: int,
                    n_held: int) -> Array:
    """The dense combine matrix [B, n_held] of the experts
    [first_expert, first_expert + n_held): entry (b, j) is token b's weight
    on expert first_expert + j, 0 where that pair was not routed."""
    hit = held_hits(idx, first_expert, n_held)
    return jnp.sum(jnp.where(hit, weight[:, :, None], 0.0), axis=1)


def moe_ffn(
    x: Array,                  # [B, D] tokens
    w_router: Array,           # [D, E]  E = ALL experts the router scores
    experts: tuple,            # (w1 [h,D,H], b1 [h,H], w2 [h,H,Do], b2 [h,Do])
                               # plain, or (w_gate [h,D,H], w_up [h,D,H],
                               # w_down [h,H,Do]) gated; h = experts held
    top_k: int = 2,
    *,
    first_expert: int = 0,     # the held experts are [first, first + h)
    activation=jax.nn.relu,    # plain experts' nonlinearity
    valid: Optional[Array] = None,
    **routing,                 # moe_route's keywords
) -> tuple[Array, Array, Array]:
    """The routed experts' part of the layer over the held experts; returns
    (y [B, D_out], aux loss, pairs [B, h] bool — which held experts each
    token was routed to).  Stacked expert weights shard on the model axis
    (['model', None, ...])."""
    with jax.named_scope("moe.route"):
        logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
        idx, weight, aux = moe_route(logits, top_k, valid=valid, **routing)
        n_held = experts[0].shape[0]
        comb = combine_weights(idx, weight, first_expert, n_held)
        pairs = jnp.any(held_hits(idx, first_expert, n_held), axis=1)
        if valid is not None:
            pairs = jnp.logical_and(pairs, valid[:, None])
    with jax.named_scope("moe.experts"):
        if len(experts) == 3:
            w_gate, w_up, w_down = experts
            h = jax.nn.silu(jnp.einsum("bd,edh->ebh", x, w_gate)) * \
                jnp.einsum("bd,edh->ebh", x, w_up)
            out = jnp.einsum("ebh,ehd->ebd", h, w_down)
        else:
            w1, b1, w2, b2 = experts
            h = activation(jnp.einsum("bd,edh->ebh", x, w1) + b1[:, None, :])
            out = jnp.einsum("ebh,ehd->ebd", h, w2) + b2[:, None, :]
        y = jnp.einsum("ebd,be->bd", out, comb.astype(out.dtype))
    return y, aux, pairs


def expert_partition_specs(n_leading_dims: int = 3) -> list:
    """Partition spec stubs for stacked expert params: expert dim over the
    `model` axis (['model', None, ...])."""
    return ["model"] + [None] * (n_leading_dims - 1)
