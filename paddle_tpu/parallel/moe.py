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

THREE EXPERT FORMS, told apart by the stacked operands (`_expert_products`):
gated SwiGLU without biases (w_gate, w_up, w_down); plain with two biases
(w1, b1, w2, b2) and a nonlinearity by name (`expert_activation`: relu,
relu2 = relu(x)^2); plain WITHOUT biases (w_up, w_down), the Nemotron-H
experts — no zero biases stored or read.  A layer's shared expert follows
its experts' form (graph/layers_moe.py).

The expert block (`moe_ffn`) is dropless: every routed (token, expert) pair
is computed, there is no capacity and nothing is dropped.  It computes the
experts `[first_expert, first_expert + E_held)` — the stacked weights it is
handed — and adds up only what THOSE experts give; pairs routed to experts
held elsewhere contribute nothing here (their chips add them: expert
parallelism without the exchange, which a one-chip share never runs).  With
every expert held it is the whole layer.

TWO FORMULATIONS of the same block, chosen from the operands' shapes when
a program is traced (`expert_form`; nothing selects one from outside):

  * DENSE: each held expert multiplies every row and a dense combine matrix
    `[B, E_held]` (zero off the routed pairs) weighs the results.  Work is
    B x E_held expert products, not the routed pairs.  A step reads each
    expert's weights once whatever the rows and does 2 x B flops a weight,
    so under the chip's ridge — `ridge_rows`: about 240 rows of 2-byte
    weights on the v5e, 197 TFLOP/s over 819 GB/s — the MXU is idle beside
    the HBM and the extra products cost nothing (8 held x 64-128 rows and
    16 held x 128 rows: the GigaChat cell and Kimi-Linear's decode steps,
    2 and 4 routed pairs an expert).  XLA partitions its einsums where the
    stacked weights shard over the `model` mesh axis.
  * GROUPED: each routed pair of a held expert takes a slot of that
    expert (its rank among the expert's pairs, by a stable sort).  THE FIRST
    ROUND is whole and unconditional: every held expert's first
    `first_round_slots` slots (twice the mean load in half tiles of 64 and
    at least 128 — 128 in every cell but Xing's mixed step, 192: a function
    of the call's rows, top_k and experts scored), the slots' rows of `x`
    gathered `[E_held, slots, D]`, each expert multiplying its own slots —
    batched einsums against the stacked weights exactly as they are stored
    —, each pair's output weighed by its routing weight and the top_k
    results of a row added in float32.  Under the ridge the round takes
    the weights' read, whatever the rows.  THE OVERFLOW — the pairs an expert draws
    beyond its first-round slots — goes expert by expert: a loop over
    (expert, tile of `_GROUP_SLOTS` slots) of the experts that have such
    pairs alone, each tile reading that ONE expert's weights (a dynamic
    slice of the stack) for its at most 128 rows and adding its weighted
    results to their rows in float32.  Nothing is dropped whatever the
    skew, and an overflowing expert costs its own weights' read a tile,
    not every held expert's.

THE RULE (`expert_form`): past the ridge the dense form is compute-bound
on products of which E/top_k - 1 parts in E/top_k are multiplied by zero
— with 64 held experts of 3 x 2048 x 1536 and 512 rows (`lfm2-24b-serve.
long-output-256`'s mixed step, 32 pairs an expert) 3.7 ms a layer, 14.8
of a step's 21.2, against 5.9 ms to read the experts' 4.83 GB — so the grouped form
runs from `_GROUPED_OVER_RIDGE` times the ridge on (the measurement beside
the constant; a first round wider than the ridge counts as the reads it
is worth), the dense form below it, under a `model`-axis mesh (XLA
partitions the dense einsums; the grouped form's gathers have not been
tried there) and in training (its loop over the overflow has no reverse
mode).
"""

from __future__ import annotations

import math
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


#: rows at which the dense form's 2 x rows flops a weight take as long as the
#: weight's read, for each byte of a weight: 197 TFLOP/s over 2 x 819 GB/s,
#: the v5e (the chip this repo is measured on; benchmark/peaks.json)
_RIDGE_ROWS_PER_BYTE = 120

# The grouped form runs where the rows are at least `_GROUPED_OVER_RIDGE`
# times the ridge (`expert_form`).  One layer's call on
# the v5e, bf16 gated experts, ms dense -> grouped, beside the weights' read
# at 819 GB/s (my chip runs, PR 38: tools/moe_forms.py, medians of 5 x 10
# calls, the same to 0.01 ms in four calls of the tool):
#   256 rows x 64 held of 3 x 2048 x 1536 (LFM2 decode, 1.07 x the ridge)
#       1.926 -> 1.877   read 1.475   a tie: stays dense
#   320 rows x 16 held of 3 x 2304 x 1024 (Kimi mixed, 1.33 x)
#       0.589 -> 0.559   read 0.277   grouped, 5%
#   512 rows x 64 held of 3 x 2048 x 1536 (LFM2 mixed, 2.13 x)
#       3.707 -> 1.961   read 1.475   grouped, 47%: the two product fusions
#       1.60 ms (755 GB/s of weights), the slots' gather 0.11, the weighted
#       add 0.05, scatter, sorts and the rest 0.2
#   768 rows x 64 held 5.880 -> 2.17;  128 rows x 64 held 1.677 -> 1.99
# What else was tried in the products' place, same three shapes: the Pallas
# grouped matmul (jax.experimental.pallas.ops.tpu.megablox.gmm, the whole
# contraction a block, 64-row tile) 1.892 / 0.508 / 2.083 — no faster, and
# the benchmark's paged-kernel reader counts every Pallas call of a serve
# step as the paged kernel; `jax.lax.ragged_dot` 4.00 / 1.08 / 4.20 —
# slower than the dense form.
_GROUPED_OVER_RIDGE = 1.25

#: slots of one tile of the grouped form: one MXU tile's height.  The first
#: round's slots are whole and half tiles of it (`first_round_slots`), an
#: overflow tile is one.  Under the ridge the first round is bound by the
#: weights' read whatever its slots hold (ms a layer at 512 x 64 held: 64
#: slots 1.91, 96 1.95, 128 1.96, 192 2.14; my chip runs, PR 38); an
#: overflow tile reads ONE expert's weights for its at most 128 rows.
_GROUP_SLOTS = 128

# The first round holds `_FIRST_ROUND_OVER_MEAN` mean loads (rows x top_k /
# experts scored) an expert, in half tiles and at least one tile: slots the
# busiest expert leaves empty are paid in the round's gathers and products,
# pairs beyond them in overflow tiles.  One layer's call at the Xing cell's
# mixed step — 1,088 rows x 64 held of 3 x 3584 x 1024, 68 pairs an expert
# on the mean, the weights' read 1.72 ms — on the v5e (my chip runs, PR 65:
# tools/moe_forms.py --shapes xing-mixed --skew ... --sweep 128,192, medians
# of 3-5 x 10 calls), ms at a first round of 128 / of 192 slots:
#   busiest 87-119 pairs (1.3-1.75 x the mean), no tile:   2.45 / 2.67
#       (rounds of 128 for all, before PR 65: 2.53-2.54)
#   busiest 150 (2.2 x):  2 tiles 2.56 / no tile 2.67
#       (two rounds of 128, before PR 65: 4.79)
#   busiest 176 (2.6 x):  10 tiles 3.10 / no tile 2.67
#   busiest 236 (3.5 x):  11 tiles 3.18 / 3 tiles 2.87
#   busiest 305 (4.5 x):  15 tiles 3.44 / 9 tiles 3.26
# A tile is 66 us — its products 50 (an expert's 22 MB at 819 GB/s are 27),
# the float32 scatter-add of its 128 rows 16 (the same as a one-hot product
# at the highest precision; told that its rows ascend and are unique, XLA's
# scatter took 74) — and 64 slots more in the first round cost 0.22 ms,
# three tiles' worth, in every call.  The cell decides: its busiest expert
# of a layer holds FOUR mean loads (257-273 pairs on the mean of a mixed
# step's busiest layer, p95 329-372), so 128 slots run 34-36 tiles a step
# over the five layers and 192 slots 5.7-7.7 — `itl_p95_ms` 59.75 / 59.36
# at 128, 59.33 / 58.76 at 192, mixed step p50 46.0 / 47.6 against 45.4 /
# 46.7 ms (two seeds a side).  Twice the mean it is: 192 slots for the Xing
# step, and one tile for every other cell's grouped call (8-32 pairs an
# expert on the mean: LFM2's busiest holds 116-126 of 128).
_FIRST_ROUND_OVER_MEAN = 2.0


def ridge_rows(itemsize: int) -> int:
    """Rows from which the dense form is bound by the MXU and no longer by
    the weights' read (weights of `itemsize` bytes)."""
    return _RIDGE_ROWS_PER_BYTE * itemsize


def first_round_slots(rows: int, top_k: int, n_experts: int) -> int:
    """Slots an expert has in the grouped form's first round, for `rows`
    token rows routed top_k of `n_experts`: a pure function of what a trace
    sees, as `expert_form` is.  `_GROUP_SLOTS` (128) wherever
    `_FIRST_ROUND_OVER_MEAN` mean loads fit in it — every grouped call of
    every cell but the Xing cell's mixed step, 192 —, else the next half
    tile that holds them."""
    half = max(_GROUP_SLOTS // 2, 1)
    want = math.ceil(_FIRST_ROUND_OVER_MEAN * rows * top_k / n_experts)
    return max(_GROUP_SLOTS, half * -(-want // half))


def overflow_tiles(sizes: Array, slots: int) -> Array:
    """Tiles of `_GROUP_SLOTS` slots that each expert's pairs beyond its
    first round's `slots` fill (`sizes`: pairs an expert, int32): what the
    grouped form's overflow loop runs, expert by expert."""
    return -(-jnp.maximum(sizes - slots, 0) // _GROUP_SLOTS)


def expert_form(rows: int, top_k: int, n_experts: int, itemsize: int, *,
                partitioned: bool = False, training: bool = False) -> str:
    """"dense" or "grouped": the formulation `moe_ffn` runs for `rows`
    token rows routed top_k of `n_experts`, weights of `itemsize` bytes — a
    pure function of what a trace sees (the experts held do not enter: both
    forms' work is the same multiple of them).  `partitioned`: the stacked
    weights shard over a `model` mesh axis; `training`: the program is
    differentiated (the grouped form's loop has no reverse mode)."""
    ridge = ridge_rows(itemsize)
    if partitioned or training or _GROUP_SLOTS > ridge:
        return "dense"
    # the first round reads the weights once, as `ridge` rows of the dense
    # form do, for as long as its slots stay under the ridge themselves (in
    # every cell); the overflow reads an expert's weights a tile, not a round
    reads = -(-first_round_slots(rows, top_k, n_experts) // ridge)
    return "grouped" if rows >= _GROUPED_OVER_RIDGE * ridge * reads \
        else "dense"


def expert_activation(name: str):
    """A plain expert's nonlinearity by the name a config gives it."""
    try:
        return {"relu": jax.nn.relu,
                "relu2": lambda x: jnp.square(jax.nn.relu(x))}[name]
    except KeyError:
        raise ValueError(f"unknown expert activation {name!r} "
                         f"(relu or relu2)") from None


def _expert_products(xs, experts, activation):
    """out[e] = expert e applied to xs[e] (`[h, rows, D]`; `[rows, D]`: the
    same rows for every expert), `[h, rows, D_out]`: operands as stored,
    float32 accumulation, `h` and `out` rounded to the einsums' result
    type.  The experts' form is the number of stacked operands: 3 gated
    (SwiGLU), 2 plain without biases, 4 plain with two biases."""
    lhs = "bd" if xs.ndim == 2 else "ebd"
    if len(experts) == 3:
        w_gate, w_up, w_down = experts
        h = jax.nn.silu(jnp.einsum(f"{lhs},edh->ebh", xs, w_gate)) * \
            jnp.einsum(f"{lhs},edh->ebh", xs, w_up)
        return jnp.einsum("ebh,ehd->ebd", h, w_down)
    if len(experts) == 2:
        w_up, w_down = experts
        h = activation(jnp.einsum(f"{lhs},edh->ebh", xs, w_up))
        return jnp.einsum("ebh,ehd->ebd", h, w_down)
    w1, b1, w2, b2 = experts
    h = activation(jnp.einsum(f"{lhs},edh->ebh", xs, w1) + b1[:, None, :])
    return jnp.einsum("ebh,ehd->ebd", h, w2) + b2[:, None, :]


def _experts_grouped(x, experts, idx, weight, first_expert, activation,
                     valid, n_experts):
    """The held experts' part over the routed pairs alone: each held pair
    takes a slot of its expert — its rank among that expert's pairs, by a
    stable sort.  The first round is whole: every held expert's first
    `first_round_slots` slots, their rows of `x` gathered `[h, slots, D]`,
    the experts multiplying their own slots, each pair's result weighed and
    added to its row in float32; an empty slot computes row 0 and is read
    by no pair.  The pairs of rank beyond it go expert by expert, a tile of
    `_GROUP_SLOTS` slots at a time over the experts that have such pairs
    alone, each tile against that one expert's weights: nothing is
    dropped."""
    B, k = idx.shape
    T = _GROUP_SLOTS
    C = first_round_slots(B, k, n_experts)
    n_held = experts[0].shape[0]
    e = idx - first_expert
    held = jnp.logical_and(e >= 0, e < n_held)             # [B, k]
    if valid is not None:
        held = jnp.logical_and(held, valid[:, None])
    # pairs of experts held elsewhere and padding rows sort last
    key = jnp.where(held, e, n_held).reshape(-1)
    sizes = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :], axis=0,
                    dtype=jnp.int32)
    e = jnp.clip(e, 0, n_held - 1)
    order = jnp.argsort(key, stable=True)          # sorted position -> pair
    place = jnp.argsort(order)                     # pair -> sorted position
    first = jnp.cumsum(sizes) - sizes              # an expert's first position
    rank = place.reshape(B, k) - jnp.take(first, e)
    row_of_pair = jnp.arange(B * k, dtype=jnp.int32) // k

    mine = jnp.logical_and(held, rank < C)
    at = jnp.where(mine, e * C + rank, n_held * C).reshape(-1)
    rows = jnp.zeros((n_held * C,), jnp.int32).at[at].set(
        row_of_pair, mode="drop")
    xs = jnp.take(x, rows, axis=0).reshape(n_held, C, -1)
    out = _expert_products(xs, experts, activation)
    out = jnp.take(out.reshape(n_held * C, -1), at, axis=0, mode="clip")
    out = jnp.where(mine[:, :, None], out.reshape(B, k, -1).astype(
        jnp.float32) * weight[:, :, None], 0.0)
    y = jnp.sum(out, axis=1)

    tiles = overflow_tiles(sizes, C)                       # [h]
    ends = jnp.cumsum(tiles)
    lane = jnp.arange(T, dtype=jnp.int32)
    weight = weight.reshape(-1)

    def one_tile(carry):
        i, y = carry
        h = jnp.sum(ends <= i, dtype=jnp.int32)    # the tile's expert
        slot = C + (i - ends[h] + tiles[h]) * T + lane
        pair = jnp.take(order, first[h] + slot, mode="clip")
        # an empty slot computes some row and adds it nowhere
        row = jnp.where(slot < sizes[h], jnp.take(row_of_pair, pair), B)
        one = tuple(jax.lax.dynamic_index_in_dim(w, h, 0) for w in experts)
        out = _expert_products(jnp.take(x, row, axis=0, mode="clip")[None],
                               one, activation)[0]
        out = out.astype(jnp.float32) * jnp.take(weight, pair)[:, None]
        return i + 1, y.at[row].add(out, mode="drop")

    _, y = jax.lax.while_loop(lambda carry: carry[0] < ends[-1], one_tile,
                              (jnp.int32(0), y))
    return y.astype(jnp.result_type(x.dtype, experts[0].dtype))


def moe_ffn(
    x: Array,                  # [B, D] tokens
    w_router: Array,           # [D, E]  E = ALL experts the router scores
    experts: tuple,            # (w1 [h,D,H], b1 [h,H], w2 [h,H,Do], b2 [h,Do])
                               # plain, (w_up [h,D,H], w_down [h,H,Do])
                               # plain without biases, or (w_gate [h,D,H],
                               # w_up, w_down [h,H,Do]) gated; h = held
    top_k: int = 2,
    *,
    first_expert: int = 0,     # the held experts are [first, first + h)
    activation=jax.nn.relu,    # plain experts' nonlinearity
    valid: Optional[Array] = None,
    form: Optional[str] = None,   # None: `expert_form` of the shapes
    **routing,                 # moe_route's keywords
) -> tuple[Array, Array, Array]:
    """The routed experts' part of the layer over the held experts; returns
    (y [B, D_out], aux loss, pairs [B, h] bool — which held experts each
    token was routed to).  Stacked expert weights shard on the model axis
    (['model', None, ...]).  `form` is the rule's answer where the caller
    knows more than the shapes (the layer: its mesh, its mode)."""
    n_held = experts[0].shape[0]
    if form is None:
        form = expert_form(x.shape[0], top_k, w_router.shape[-1],
                           experts[0].dtype.itemsize)
    with jax.named_scope("moe.route"):
        logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
        idx, weight, aux = moe_route(logits, top_k, valid=valid, **routing)
        if form == "dense":
            comb = combine_weights(idx, weight, first_expert, n_held)
        pairs = jnp.any(held_hits(idx, first_expert, n_held), axis=1)
        if valid is not None:
            pairs = jnp.logical_and(pairs, valid[:, None])
    with jax.named_scope("moe.experts"):
        if form == "dense":
            out = _expert_products(x, experts, activation)
            y = jnp.einsum("ebd,be->bd", out, comb.astype(out.dtype))
        else:
            y = _experts_grouped(x, experts, idx, weight, first_expert,
                                 activation, valid, w_router.shape[-1])
    return y, aux, pairs


def expert_partition_specs(n_leading_dims: int = 3) -> list:
    """Partition spec stubs for stacked expert params: expert dim over the
    `model` axis (['model', None, ...])."""
    return ["model"] + [None] * (n_leading_dims - 1)
