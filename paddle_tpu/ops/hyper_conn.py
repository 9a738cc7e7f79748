"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections arXiv:2409.19606): the residual path of a block held as
`n` streams X [rows, n, C] a token, and, for each sublayer F, three maps
computed from that sublayer's own input X:

    x~     = RMSNorm(vec(X))                    over all n C values, no scale
    H~     = alpha * (x~ phi) + b               phi [n C, 2 n + n^2]
    H_pre  = sigmoid(H~_pre)        [n]         what the sublayer reads
    H_post = 2 sigmoid(H~_post)     [n]         where its output goes
    H_res  = SinkhornKnopp(exp(clip(H~_res)))   [n, n], doubly stochastic
    u  = sum_i H_pre[i] X[i]                    the READ
    X' = H_res X + H_post^T F(norm(u))          the WRITE (the stream pass)

`phi`'s columns are [pre (n) | post (n) | res (n^2, row j then column i)],
`b` likewise, `alpha` = [alpha_pre, alpha_post, alpha_res].  A map is a
function of its token alone: nothing here crosses rows.

The maps are float32 whatever the streams' dtype (a doubly stochastic
matrix rounded to bfloat16 does not sum to one); the streams stay in the
compute dtype.  x~ phi is taken as (X phi) * rsqrt(mean(X^2) + eps): the
streams' own values times `phi` accumulated in float32, the row's scale
applied to the 2 n + n^2 results — the same number, and X is read as it
lies.

`maps` packs the three as ONE array [rows, 2 n + n^2] float32 (the graph's
flat layer), `split` opens it.  `mix` is the stream pass in jnp: the oracle
of the Pallas kernel `mhc_mix` (ops/pallas_hyper_conn.py) and the path of
the CPU and of a differentiated graph; `write` picks between them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def map_width(n: int) -> int:
    return 2 * n + n * n


def sinkhorn(m: Array, iters: int, eps: float) -> Array:
    """Sinkhorn-Knopp on positive m [..., n, n]: each iteration divides the
    rows by their sums, then the columns by theirs (row j of H_res mixes
    the streams into stream j: sum over i = the last axis), `eps` in both
    denominators."""
    for _ in range(int(iters)):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def maps(x: Array, phi: Array, bias: Array, alpha: Array, *, n: int,
         iters: int, eps: float, clamp: tuple) -> Array:
    """x [rows, n C] (the streams, flat) -> [rows, 2 n + n^2] float32:
    H_pre | H_post | H_res (row-major)."""
    f32 = jnp.float32
    r = jax.lax.rsqrt(jnp.mean(jnp.square(x.astype(f32)), axis=-1,
                               keepdims=True) + eps)
    z = jnp.matmul(x, phi.astype(x.dtype), preferred_element_type=f32) * r
    # a gate a column: alpha_pre n times, alpha_post n, alpha_res n^2
    gate = alpha.astype(f32).reshape(-1)[np.repeat(np.arange(3),
                                                   [n, n, n * n])]
    h = z * gate + bias.astype(f32).reshape(-1)
    pre = jax.nn.sigmoid(h[:, :n])
    post = 2.0 * jax.nn.sigmoid(h[:, n:2 * n])
    res = jnp.exp(jnp.clip(h[:, 2 * n:], clamp[0], clamp[1]))
    res = sinkhorn(res.reshape(-1, n, n), iters, eps).reshape(-1, n * n)
    return jnp.concatenate([pre, post, res], axis=-1)


def split(m: Array, n: int):
    """m [rows, 2 n + n^2] -> (H_pre [rows, n], H_post [rows, n], H_res
    [rows, n, n])."""
    return m[:, :n], m[:, n:2 * n], m[:, 2 * n:].reshape(-1, n, n)


def read(x: Array, m: Array, n: int) -> Array:
    """u = sum_i H_pre[i] X[i]: x [rows, n C] -> [rows, C] in x's dtype."""
    rows = x.shape[0]
    pre, _, _ = split(m, n)
    xs = x.reshape(rows, n, -1).astype(jnp.float32)
    return jnp.einsum("ri,ric->rc", pre, xs).astype(x.dtype)


def mix(x: Array, y: Array, m: Array, n: int) -> Array:
    """X'[j] = sum_i H_res[j, i] X[i] + H_post[j] y: x [rows, n C], y
    [rows, C], m [rows, 2 n + n^2] float32 -> [rows, n C] in x's dtype,
    the sums in float32."""
    rows = x.shape[0]
    _, post, res = split(m, n)
    xs = x.reshape(rows, n, -1).astype(jnp.float32)
    out = jnp.einsum("rji,ric->rjc", res, xs) + \
        post[:, :, None] * y.astype(jnp.float32)[:, None, :]
    return out.reshape(rows, -1).astype(x.dtype)


def write(x: Array, y: Array, m: Array, n: int, *, kernel: bool) -> Array:
    """The stream pass: `mhc_mix` where the caller may use the kernel (it is
    forward only), `mix` otherwise."""
    if kernel:
        from paddle_tpu.ops import pallas_hyper_conn
        return pallas_hyper_conn.mhc_mix(x, y, m, n)
    return mix(x, y, m, n)
