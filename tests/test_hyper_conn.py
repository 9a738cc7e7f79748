"""Hyper-connections' pieces (ops/hyper_conn.py, ops/pallas_hyper_conn.py,
graph/layers_hc.py): the maps against their equations, Sinkhorn's result
doubly stochastic, the read and the write each alone against a loop over the
streams, the Pallas stream pass `mhc_mix` in interpret mode against the jnp
form at ragged row counts, and the graph keeping the jnp form where it is
differentiated (the kernel is forward only).  The Mosaic case — the chip's
compiler at the cell's shapes — is tests/test_mosaic_compile.py's
(`test_mhc_mix_at_the_xing_cells_shapes`: one file owns the TPU library)."""

import numpy as np
import pytest

N, C = 4, 128


def _inputs(rows, dtype, seed=0):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (rows, N * C), jnp.float32).astype(dtype)
    y = jax.random.normal(ks[1], (rows, C), jnp.float32).astype(dtype)
    phi = 0.05 * jax.random.normal(ks[2], (N * C, 2 * N + N * N), jnp.float32)
    bias = 0.5 * jax.random.normal(ks[3], (1, 2 * N + N * N), jnp.float32)
    alpha = jnp.asarray([[0.5, 0.4, 0.6]], jnp.float32)
    return x, y, phi, bias, alpha


def _maps(x, phi, bias, alpha, iters=20):
    from paddle_tpu.ops import hyper_conn
    return hyper_conn.maps(x, phi, bias, alpha, n=N, iters=iters, eps=1e-6,
                           clamp=(-30.0, 30.0))


def test_maps_against_their_equations():
    """H_pre = sigmoid, H_post = 2 sigmoid, H_res = Sinkhorn(exp(clip)) of
    alpha (RMSNorm(vec X) phi) + b, written out in numpy float64 a row."""
    import jax
    x, _, phi, bias, alpha = _inputs(7, "float32")
    with jax.default_matmul_precision("highest"):
        got = np.asarray(_maps(x, phi, bias, alpha))
    x64, phi64 = np.asarray(x, np.float64), np.asarray(phi, np.float64)
    b, a = np.asarray(bias, np.float64)[0], np.asarray(alpha, np.float64)[0]
    for r in range(7):
        xt = x64[r] / np.sqrt(np.mean(x64[r] ** 2) + 1e-6)
        z = xt @ phi64
        pre = 1 / (1 + np.exp(-(a[0] * z[:N] + b[:N])))
        post = 2 / (1 + np.exp(-(a[1] * z[N:2 * N] + b[N:2 * N])))
        m = np.exp(np.clip(a[2] * z[2 * N:] + b[2 * N:], -30, 30))
        m = m.reshape(N, N)
        for _ in range(20):
            m = m / (m.sum(1, keepdims=True) + 1e-6)
            m = m / (m.sum(0, keepdims=True) + 1e-6)
        want = np.concatenate([pre, post, m.ravel()])
        np.testing.assert_allclose(got[r], want, atol=2e-6)


def test_h_res_is_doubly_stochastic_after_20_iterations_in_float32():
    """Rows and columns sum to one within 1e-5 where H~_res spreads by
    about 0.4 (the iteration converges geometrically at a rate the spread
    sets: a matrix whose entries span decades is still off by 1e-3 after
    20) — and after ONE iteration the rows do not (what the
    `hc_sinkhorn_iters` 1 control changes); the clamp keeps exp finite
    where the dynamic term is huge."""
    from paddle_tpu.ops import hyper_conn
    x, _, phi, bias, alpha = _inputs(48, "float32", seed=3)
    phi, bias = 0.5 * phi, 0.5 * bias
    res = np.asarray(hyper_conn.split(_maps(x, phi, bias, alpha), N)[2])
    assert res.dtype == np.float32
    assert np.abs(res.sum(-1) - 1).max() < 1e-5
    assert np.abs(res.sum(-2) - 1).max() < 1e-5
    one = np.asarray(hyper_conn.split(
        _maps(x, phi, bias, alpha, iters=1), N)[2])
    assert np.abs(one.sum(-1) - 1).max() > 1e-2
    huge = np.asarray(_maps(x, 1e4 * phi, bias, alpha))
    assert np.isfinite(huge).all()


def test_maps_stay_float32_under_bfloat16_streams():
    x, _, phi, bias, alpha = _inputs(16, "bfloat16")
    m = _maps(x, phi.astype("bfloat16"), bias, alpha)
    assert str(m.dtype) == "float32"
    res = np.asarray(m[:, 2 * N:]).reshape(-1, N, N)
    assert np.abs(res.sum(-2) - 1).max() < 1e-5


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_read_and_write_against_a_loop_over_the_streams(dtype):
    """u = sum_i H_pre[i] X[i]; X'[j] = sum_i H_res[j, i] X[i] + H_post[j]
    y — each alone, the sums in float32, the result in the streams' dtype."""
    from paddle_tpu.ops import hyper_conn
    x, y, phi, bias, alpha = _inputs(9, dtype)
    m = _maps(x, phi, bias, alpha)
    pre, post, res = (np.asarray(a, np.float64)
                      for a in hyper_conn.split(m, N))
    xs = np.asarray(x.astype("float32"), np.float64).reshape(9, N, C)
    y64 = np.asarray(y.astype("float32"), np.float64)
    u = sum(pre[:, i, None] * xs[:, i] for i in range(N))
    out = np.stack([sum(res[:, j, i, None] * xs[:, i] for i in range(N))
                    + post[:, j, None] * y64 for j in range(N)], 1)
    tol = 1e-5 if dtype == "float32" else 4e-2      # one bf16 rounding
    got_u = hyper_conn.read(x, m, N)
    got_x = hyper_conn.mix(x, y, m, N)
    assert str(got_u.dtype) == str(got_x.dtype) == dtype
    np.testing.assert_allclose(np.asarray(got_u.astype("float32")), u,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(got_x.astype("float32")),
                               out.reshape(9, -1), atol=tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [1, 7, 48, 1088])
def test_mhc_mix_interpreted_against_the_jnp_form(rows, dtype, monkeypatch):
    """`mhc_mix` in interpret mode at ragged row counts (under a tile, a
    decode step's 48, a mixed step's 1,088): the same float32 sums as
    ops/hyper_conn.py `mix`, rounded once — bit-equal in float32 up to the
    order of five terms, and within one bf16 rounding in bfloat16."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    from paddle_tpu.ops import hyper_conn, pallas_hyper_conn
    assert pallas_hyper_conn.supported()
    x, y, phi, bias, alpha = _inputs(rows, dtype, seed=rows)
    m = _maps(x, phi, bias, alpha)
    want = hyper_conn.mix(x, y, m, N)
    got = hyper_conn.write(x, y, m, N, kernel=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    err = np.abs(np.asarray(got.astype("float32"))
                 - np.asarray(want.astype("float32"))).max()
    assert err < (1e-5 if dtype == "float32" else 4e-2), err


def test_a_differentiated_graph_keeps_the_jnp_form(monkeypatch):
    """The kernel is forward only (as `kda_seg`, ROADMAP R8): with the
    kernel supported, a graph in training mode runs `mix`, a served one
    `mhc_mix` — and the training graph's gradient flows through the maps."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    from paddle_tpu.graph import layers_hc
    from paddle_tpu.graph.context import TEST, TRAIN, ForwardContext
    from paddle_tpu.ops import hyper_conn, pallas_hyper_conn
    calls = []
    real = pallas_hyper_conn.mhc_mix
    monkeypatch.setattr(pallas_hyper_conn, "mhc_mix",
                        lambda *a: calls.append(1) or real(*a))
    assert not layers_hc.use_mix_kernel(ForwardContext(None, {}, mode=TRAIN))
    assert layers_hc.use_mix_kernel(ForwardContext(None, {}, mode=TEST))
    x, y, phi, bias, alpha = _inputs(8, "float32")

    def loss(phi, kernel):
        m = _maps(x, phi, bias, alpha)
        return jnp.sum(hyper_conn.write(x, y, m, N, kernel=kernel) ** 2)

    g = jax.grad(loss)(phi, False)
    assert not calls and float(jnp.abs(g).max()) > 0
    loss(phi, True)
    assert calls
