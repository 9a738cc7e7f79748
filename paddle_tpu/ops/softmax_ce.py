"""Softmax cross-entropy straight from a linear layer's input and weight.

`fc(act=softmax)` + `multi-class-cross-entropy` as ONE op under
`jax.custom_vjp`: per row, `z = x @ w (+ b)` accumulated in float32,
`nll = logsumexp(z) - z[label]`, and the row's prediction `argmax z` from
the same pass.  The `[rows, classes]` table of probabilities is never built
and the backward has no reduction over the classes and no scatter:
`dz = (softmax(z) - onehot(label)) * g` feeds the two matmuls directly.

The executor takes this path where the graph shows that nothing but the
cost (and `classification_error`) reads the layer's output
(graph/builder.py: GraphExecutor._fusable_softmax_costs); every other
reader keeps `ops/activations.py:softmax` and the cost layer's gather.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

# the composition's `-log(max(p, 1e-10))`: a row's cost stops here, and its
# gradient is zero where the ceiling binds
NLL_CEILING = -math.log(1e-10)

# bytes of one piece's float32 logits when a sequence batch is walked along
# its time axis: 512 rows of 49,152 classes.  Measured on a v5e at
# [2, 4096, 3072] x [3072, 49152] (PERF.md, PR 49): pieces of 512 rows run
# the head in 45.0 ms where one piece of 8,192 runs it in 49.6 (a piece's
# reductions no longer go through HBM) and pieces of 256 in 46.3 (the
# matmuls get small)
_BLOCK_BYTES = 512 * 49152 * 4


def _forward(x: Array, w: Array, b: Optional[Array], labels: Array):
    """One block of rows: (nll, pred, dz_unit, keep).  `dz_unit` is
    softmax(z) - onehot(label) in the operands' dtype, what the backward's
    matmuls read; `keep` is 0 where the ceiling binds."""
    z = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    if b is not None:
        z = z + b.astype(jnp.float32)
    m = jnp.max(z, axis=-1, keepdims=True)
    pred = jnp.argmax(z, axis=-1).astype(jnp.int32)
    lse = m + jnp.log(jnp.sum(jnp.exp(z - m), axis=-1, keepdims=True))
    hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, z.ndim - 1) \
        == labels[..., None]
    raw = lse[..., 0] - jnp.sum(jnp.where(hit, z, 0.0), axis=-1)
    keep = raw < NLL_CEILING
    p = jnp.exp(z - lse)
    dz_unit = jnp.where(hit, p - 1.0, p).astype(x.dtype)
    return jnp.minimum(raw, NLL_CEILING), pred, dz_unit, keep


def _backward(x: Array, w: Array, b: Optional[Array], dz_unit: Array,
              g: Array):
    """dx, dw, db from a block's `dz_unit` and the rows' cotangents `g`
    (float32, already zero where the ceiling bound).  A row's scale
    commutes with both matmuls, so it is applied to `[rows, d]` arrays and
    the `[rows, classes]` block is read by the matmuls alone."""
    gx = g[..., None]
    dx = (jnp.matmul(dz_unit, w.T, preferred_element_type=jnp.float32)
          * gx).astype(x.dtype)
    xg = (x.astype(jnp.float32) * gx).astype(x.dtype)
    rows = math.prod(x.shape[:-1])
    # dw leaves the matmul in the weight's own type, as autodiff's does:
    # under a `data` mesh the all-reduce then moves those bytes and not
    # float32's (measured on dp4, PERF.md PR 49: 10.5 against 5.3 ms a step)
    dw = jnp.matmul(xg.reshape(rows, -1).T, dz_unit.reshape(rows, -1),
                    preferred_element_type=w.dtype)
    db = None
    if b is not None:
        db = jnp.matmul(g.reshape(rows).astype(x.dtype),
                        dz_unit.reshape(rows, -1),
                        preferred_element_type=jnp.float32
                        ).astype(b.dtype).reshape(b.shape)
    return dx, dw, db


def time_chunks(batch: int, steps: int, classes: int) -> int:
    """How many pieces the time axis of a [batch, steps] block of rows is
    walked in: the fewest that divide `steps` and keep a piece's float32
    logits at `_BLOCK_BYTES` or under (1 when it fits whole).  `batch` is
    what ONE device holds of the batch axis, which stays whole."""
    n = -(-batch * steps * classes * 4 // _BLOCK_BYTES)
    while n < steps and steps % n:
        n += 1
    return min(n, steps)


def _split_time(a: Array, n: int) -> Array:
    """[B, T, ...] -> [n, B, T/n, ...]"""
    B, T = a.shape[:2]
    return jnp.moveaxis(a.reshape((B, n, T // n) + a.shape[2:]), 1, 0)


def _join_time(a: Array) -> Array:
    """[n, B, Tc, ...] -> [B, n*Tc, ...]"""
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape((a.shape[0], a.shape[1] * a.shape[2]) + a.shape[3:])


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _softmax_ce(x, w, b, labels, chunks):
    nll, pred, _, _ = _fwd_blocks(x, w, b, labels, chunks)
    return nll, pred


def _fwd_blocks(x, w, b, labels, chunks):
    """(nll, pred, dz_unit, keep); with `chunks` > 1 `dz_unit` stays in the
    pieces' own layout [chunks, B, T/chunks, C] (joining it would copy the
    one large array), the per-row results are joined."""
    if chunks == 1:
        return _forward(x, w, b, labels)
    nll, pred, dz_unit, keep = jax.lax.map(
        lambda xl: _forward(xl[0], w, b, xl[1]),
        (_split_time(x, chunks), _split_time(labels, chunks)))
    return _join_time(nll), _join_time(pred), dz_unit, _join_time(keep)


def _softmax_ce_fwd(x, w, b, labels, chunks):
    nll, pred, dz_unit, keep = _fwd_blocks(x, w, b, labels, chunks)
    return (nll, pred), (x, w, b, dz_unit, keep)


def _softmax_ce_bwd(chunks, res, cts):
    x, w, b, dz_unit, keep = res
    g = jnp.where(keep, cts[0].astype(jnp.float32), 0.0)
    if chunks == 1:
        dx, dw, db = _backward(x, w, b, dz_unit, g)
    else:
        dx, dw, db = _backward(_split_time(x, chunks), w, b, dz_unit,
                               _split_time(g, chunks))
        dx = _join_time(dx)
    return dx, dw, db, None


_softmax_ce.defvjp(_softmax_ce_fwd, _softmax_ce_bwd)


def linear_softmax_ce(x: Array, w: Array, b: Optional[Array],
                      labels: Array, chunks: int = 1):
    """(nll, pred) of `softmax(x @ w + b)` against integer `labels`.

    x [..., D] in the compute dtype, w [D, C], b [C] / [1, C] or None,
    labels [...]: nll float32 [...] = min(logsumexp(z) - z[label],
    -log(1e-10)), pred int32 [...] = argmax z (first index on ties).  A
    [B, T, D] input is walked along T in `chunks` pieces (`time_chunks`
    gives the count), so only one piece's float32 logits are alive at a
    time."""
    return _softmax_ce(x, w, b, labels.astype(jnp.int32), int(chunks))
