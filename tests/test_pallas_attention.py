"""Pallas flash attention vs the dense reference — numeric oracle
(the reference's CPU-vs-GPU comparison pattern, ref:
math/tests/test_matrixCompare.cpp; here: interpret-mode pallas vs the
fused-XLA dot_product_attention, forward AND gradients).  On real TPU the
same kernels compile natively.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.attention import blockwise_attention, dot_product_attention
from paddle_tpu.ops.pallas_attention import flash_attention

# interpret-mode kernel runs are heavy: excluded from the fast gate
# (pytest -m "not slow"); the rule's and the counters' unit tests below run
# no kernel and stay in it
slow = pytest.mark.slow



def _case(rng, B, Tq, Tk, H, D, ragged=True):
    q = jnp.asarray(rng.normal(size=(B, Tq, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Tk, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Tk, H, D)), jnp.float32)
    if ragged:
        klens = rng.integers(1, Tk + 1, B)
        qlens = rng.integers(1, Tq + 1, B)
        k_valid = jnp.asarray(np.arange(Tk)[None, :] < klens[:, None])
        q_valid = jnp.asarray(np.arange(Tq)[None, :] < qlens[:, None])
    else:
        k_valid = q_valid = None
    return q, k, v, q_valid, k_valid


@slow
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [
    (2, 20, 24, 2, 16),     # ragged, unaligned sizes (exercise padding)
    (1, 128, 128, 4, 32),   # aligned single block
    (2, 130, 70, 2, 8),     # multi-block q, tiny head dim
])
def test_flash_matches_dense(causal, shape):
    rng = np.random.default_rng(0)
    q, k, v, q_valid, k_valid = _case(rng, *shape)

    want = dot_product_attention(q, k, v, q_valid=q_valid,
                                 k_valid=k_valid, causal=causal)
    got = flash_attention(q, k, v, q_valid=q_valid, k_valid=k_valid,
                          causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    def loss(fn):
        def f(q, k, v):
            o = fn(q, k, v, q_valid=q_valid, k_valid=k_valid, causal=causal)
            return jnp.sum(jnp.sin(o))
        return f

    gw = jax.grad(loss(dot_product_attention), argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(lambda q, k, v: loss(
        lambda *a, **kw: flash_attention(*a, block_q=64, block_k=64, **kw)
    )(q, k, v), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gw, gg):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-5, atol=2e-5)


@slow
def test_flash_matches_blockwise_long():
    """Long-sequence case: flash vs the scan-based online-softmax path."""
    rng = np.random.default_rng(1)
    q, k, v, q_valid, k_valid = _case(rng, 1, 384, 384, 2, 16)
    want = blockwise_attention(q, k, v, q_valid=q_valid, k_valid=k_valid,
                               causal=True, block_k=128)
    got = flash_attention(q, k, v, q_valid=q_valid, k_valid=k_valid,
                          causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@slow
def test_flash_fully_masked_rows_zero():
    """A sequence whose keys are ALL invalid must output exactly 0 and
    contribute zero gradient (dot_product_attention's contract)."""
    rng = np.random.default_rng(2)
    q, k, v, _, _ = _case(rng, 2, 8, 8, 1, 8, ragged=False)
    k_valid = jnp.asarray(np.array([[True] * 8, [False] * 8]))
    out = flash_attention(q, k, v, k_valid=k_valid)
    assert np.all(np.asarray(out[1]) == 0.0)

    g = jax.grad(lambda v: jnp.sum(
        flash_attention(q, k, v, k_valid=k_valid)))(v)
    assert np.all(np.isfinite(np.asarray(g)))
    assert np.all(np.asarray(g[1]) == 0.0)


@slow
def test_flash_bf16_close():
    rng = np.random.default_rng(3)
    q, k, v, q_valid, k_valid = _case(rng, 2, 33, 47, 2, 16)
    want = dot_product_attention(q, k, v, q_valid=q_valid, k_valid=k_valid)
    got = flash_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                          v.astype(jnp.bfloat16), q_valid=q_valid,
                          k_valid=k_valid)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=0.05, atol=0.05)


@slow
def test_layer_selects_flash_when_supported(monkeypatch):
    """multi_head_attention layer picks the pallas kernel for long keys when
    the backend supports it, and the step trains end-to-end."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    import numpy as np

    # spy: the layer must actually route through the pallas kernel (a silent
    # fallback to blockwise would train identically on this tiny config)
    import paddle_tpu.graph.layers_attn as layers_attn_mod
    from paddle_tpu.ops import pallas_attention as pa_mod
    calls = []
    real = pa_mod.flash_attention

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(pa_mod, "flash_attention", spy)

    from paddle_tpu.config.parser import parse_config_callable
    from paddle_tpu.dsl import (
        MomentumOptimizer, SoftmaxActivation, classification_cost,
        data_layer, fc_layer, multi_head_attention_layer, pooling_layer,
        settings,
    )
    from paddle_tpu.dsl.poolings import AvgPooling
    from paddle_tpu.parameter.argument import Argument
    from paddle_tpu.trainer.trainer import Trainer

    def conf():
        settings(batch_size=4, learning_rate=0.1,
                 learning_method=MomentumOptimizer(momentum=0.9))
        x = data_layer(name="x", size=16)
        # block_k_min=8 forces the long-key path at T=16
        attn = multi_head_attention_layer(x, size=16, num_heads=2,
                                          causal=True, block_k_min=8,
                                          block_k=8)
        pooled = pooling_layer(input=attn, pooling_type=AvgPooling())
        out = fc_layer(input=pooled, size=4, act=SoftmaxActivation())
        classification_cost(input=out, label=data_layer(name="y", size=4))

    cfg = parse_config_callable(conf)
    tr = Trainer(cfg, seed=0)
    rng = np.random.default_rng(0)
    batch = {
        "x": Argument(value=rng.normal(size=(4, 16, 16)).astype(np.float32),
                      lengths=np.array([16, 12, 16, 7], np.int32)),
        "y": Argument(ids=rng.integers(0, 4, 4).astype(np.int32)),
    }
    losses = [float(tr.train_one_batch(batch)) for _ in range(8)]
    assert calls, "layer did not route through the pallas flash kernel"
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


@slow
class TestRingFlash:
    """Ring flash attention (pallas per hop + lse combine) vs the jnp ring
    fold and the full-sequence dense oracle, on the virtual 8-device mesh."""

    def _sharded(self, use_flash, q, k, v, q_valid, k_valid, causal):
        import functools

        from jax.sharding import PartitionSpec as P
        from paddle_tpu.utils.jax_compat import shard_map

        from paddle_tpu.ops.attention import ring_attention
        from paddle_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(seq=4)
        spec = P(None, "seq", None, None)
        vspec = P(None, "seq")

        def local(q, k, v, qm, km):
            return ring_attention(q, k, v, "seq", q_valid=qm, k_valid=km,
                                  causal=causal, use_flash=use_flash)

        # check_vma=False: pallas_call outputs carry no varying-mesh-axes
        # annotation (standard for custom kernels under manual sharding)
        fn = shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, spec, vspec, vspec),
                       out_specs=spec, check_vma=False)
        return fn(q, k, v, q_valid, k_valid)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_jnp_ring_and_dense(self, causal, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        rng = np.random.default_rng(0)
        B, T, H, D = 2, 64, 2, 16            # 4 shards of 16
        q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        lens = np.array([T, 37])
        valid = jnp.asarray(np.arange(T)[None, :] < lens[:, None])

        from paddle_tpu.ops.attention import dot_product_attention
        want = dot_product_attention(q, k, v, q_valid=valid, k_valid=valid,
                                     causal=causal)
        ring = self._sharded(False, q, k, v, valid, valid, causal)
        flash = self._sharded(True, q, k, v, valid, valid, causal)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(flash), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_jnp_ring(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        rng = np.random.default_rng(1)
        B, T, H, D = 1, 32, 2, 8             # 4 shards of 8
        q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        lens = np.array([25])
        valid = jnp.asarray(np.arange(T)[None, :] < lens[:, None])

        def loss(use_flash):
            def f(q, k, v):
                o = self._sharded(use_flash, q, k, v, valid, valid, True)
                return jnp.sum(jnp.sin(o))
            return f

        gw = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
        gg = jax.grad(loss(True), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gw, gg):
            assert np.all(np.isfinite(np.asarray(b)))
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=3e-5, atol=3e-5)


@slow
def test_attn_impl_validation():
    """Clear errors for an unknown attn_impl and for ring without a seq
    mesh (rather than an AttributeError deep in the ring plumbing)."""
    import numpy as np

    from paddle_tpu.config.parser import parse_config_callable
    from paddle_tpu.dsl import (
        MomentumOptimizer, SoftmaxActivation, classification_cost,
        data_layer, fc_layer, multi_head_attention_layer, pooling_layer,
        settings,
    )
    from paddle_tpu.dsl.poolings import AvgPooling
    from paddle_tpu.parameter.argument import Argument
    from paddle_tpu.trainer.trainer import Trainer

    def conf(impl):
        def f():
            settings(batch_size=2, learning_rate=0.1,
                     learning_method=MomentumOptimizer())
            x = data_layer(name="x", size=8)
            a = multi_head_attention_layer(x, size=8, num_heads=2,
                                           attn_impl=impl)
            p = pooling_layer(input=a, pooling_type=AvgPooling())
            out = fc_layer(input=p, size=2, act=SoftmaxActivation())
            classification_cost(input=out, label=data_layer(name="y", size=2))
        return f

    batch = {"x": Argument(value=np.zeros((2, 4, 8), np.float32),
                           lengths=np.full((2,), 4, np.int32)),
             "y": Argument(ids=np.zeros((2,), np.int32))}

    tr = Trainer(parse_config_callable(conf("Flash")), seed=0)
    with pytest.raises(ValueError, match="unknown attn_impl"):
        tr.train_one_batch(batch)

    tr2 = Trainer(parse_config_callable(conf("ring")), seed=0)
    with pytest.raises(ValueError, match="seq"):
        tr2.train_one_batch(batch)


# ---------------------------------------------------------------------------
# blocks derived from the shape (no block_q / block_k argument)
# ---------------------------------------------------------------------------

def _expand(x, H):
    return jnp.repeat(x, H // x.shape[2], axis=2)


DERIVED_CASES = {
    # name: (B, T, H, H_kv, D, kwargs, ragged)
    "causal_multi_tile": (1, 1100, 2, 2, 16, dict(causal=True), True),
    "gqa_24_2": (1, 500, 24, 2, 16, dict(causal=True), False),
    "ragged_T500": (2, 500, 2, 2, 16, dict(causal=True), True),
    "window": (1, 1100, 2, 1, 16, dict(causal=True, window=200), True),
    "window_noncausal": (1, 1100, 2, 2, 16, dict(window=150), False),
}


@slow
@pytest.mark.parametrize("case", list(DERIVED_CASES))
def test_flash_derived_blocks_match_dense(case):
    """Forward and gradients against dot_product_attention at the blocks
    the rule derives (T 1,100 walks a 9 x 9 grid of 128-wide tiles with
    dead, edge and inside tiles; T 500 is one 512 tile)."""
    B, T, H, H_kv, D, kw, ragged = DERIVED_CASES[case]
    rng = np.random.default_rng(7)
    q, _, _, q_valid, k_valid = _case(rng, B, T, T, H, D, ragged=ragged)
    k = jnp.asarray(rng.normal(size=(B, T, H_kv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H_kv, D)), jnp.float32)

    def dense(q, k, v):
        return dot_product_attention(q, _expand(k, H), _expand(v, H),
                                     q_valid=q_valid, k_valid=k_valid, **kw)

    def flash(q, k, v):
        return flash_attention(q, k, v, q_valid=q_valid, k_valid=k_valid,
                               **kw)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    gw = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a))),
                  argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gw, gg):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=3e-5, atol=3e-5)


@slow
def test_flash_derived_blocks_fully_masked_rows():
    """Rows with no valid key: output exactly 0, lse -inf, zero finite
    gradients — through the additive key bias and the exponent's floor."""
    rng = np.random.default_rng(8)
    q, k, v, _, _ = _case(rng, 2, 300, 300, 2, 16, ragged=False)
    k_valid = jnp.asarray(np.array([[True] * 300, [False] * 300]))
    out, lse = flash_attention(q, k, v, k_valid=k_valid, causal=True,
                               return_lse=True)
    assert np.all(np.asarray(out[1]) == 0.0)
    assert np.all(np.asarray(lse[1]) == -np.inf)
    assert np.all(np.isfinite(np.asarray(lse[0])))
    g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, k_valid=k_valid, causal=True)), argnums=(0, 1, 2))(q, k, v)
    for x in g:
        assert np.all(np.isfinite(np.asarray(x)))
        assert np.all(np.asarray(x[1]) == 0.0)


@slow
@pytest.mark.parametrize("q_off,k_off", [(512, 0), (0, 512), (384, 384)])
def test_flash_traced_offsets_lse_and_its_cotangent(q_off, k_off):
    """The ring caller's contract at derived blocks: traced q_offset /
    k_offset (read by the index maps off the scalar-prefetch channel),
    return_lse, and an lse cotangent; against the same shard pair cut from
    a dense computation on global positions.  k_off > q_off is the shard
    pair with no live tile at all."""
    rng = np.random.default_rng(9)
    B, T, H, D = 1, 384, 2, 16
    q, k, v, _, _ = _case(rng, B, T, T, H, D, ragged=False)

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
        ok = (k_off + jnp.arange(T))[None, :] <= (q_off + jnp.arange(T))[:, None]
        s = jnp.where(ok, s, -jnp.inf)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.where(ok, jnp.exp(s - jnp.where(jnp.isfinite(lse), lse,
                                                0.0)[..., None]), 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse

    @jax.jit
    def flash(q, k, v, qo, ko):
        return flash_attention(q, k, v, causal=True, q_offset=qo,
                               k_offset=ko, return_lse=True)

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            fin = jnp.isfinite(lse)
            return jnp.sum(jnp.sin(o)) + jnp.sum(
                jnp.where(fin, jnp.cos(jnp.where(fin, lse, 0.0)), 0.0))
        return f

    qo, ko = jnp.int32(q_off), jnp.int32(k_off)
    fl = lambda q, k, v: flash(q, k, v, qo, ko)
    o, lse = fl(q, k, v)
    wo, wlse = dense(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(wo),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(wlse),
                               rtol=2e-5, atol=2e-5)
    gw = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(loss(fl), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gw, gg):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=3e-5, atol=3e-5)


# -- the rule and the counters: no kernel runs, so these stay in the gate ----

@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("D", [64, 128, 192, 256])
@pytest.mark.parametrize("T", [16, 100, 500, 512, 600, 2100, 4096, 16384])
def test_block_rule(T, D, dtype):
    """derive_blocks: never above the padded length, multiples of the
    type's sublane minimum (128-multiples once the sequence passes one
    tile), nested so one padding serves all three kernels, padding at most
    an eighth of a long sequence, and each pick's estimate under the
    scoped VMEM limit — for head widths 64-256 (192 pads to 256 lanes)."""
    from paddle_tpu.ops import pallas_attention as pa
    dt = jnp.dtype(dtype)
    sub = 16 if dt.itemsize == 2 else 8
    picks = pa.derive_blocks(T, T, D, dt)
    assert set(picks) == set(pa.KERNELS)
    Tp = -(-T // 128) * 128
    for kern, (bq, bk) in picks.items():
        for b in (bq, bk):
            assert b % sub == 0
            assert b <= max(Tp, -(-T // sub) * sub)
            if T > 128:
                assert b % 128 == 0
                assert -(-T // b) * b - T <= max(T // 8, 127)
        assert pa.vmem_estimate(kern, bq, bk, -(-D // 128) * 128,
                                dt.itemsize) <= pa._SCOPED_VMEM_BYTES
    for axis in (0, 1):
        sizes = sorted({p[axis] for p in picks.values()})
        assert all(b % a == 0 for a, b in zip(sizes, sizes[1:]))
    if T >= 512:
        # a long sequence gets a matmul's worth of work a step
        assert min(bq * bk for bq, bk in picks.values()) >= 256 * 256


def test_block_rule_is_a_function_of_shape_and_type_only():
    from paddle_tpu.ops import pallas_attention as pa
    a = pa.derive_blocks(4096, 4096, 128, jnp.bfloat16)
    assert a == pa.derive_blocks(4096, 4096, 128, jnp.bfloat16)
    assert a["flash_fwd"][0] >= 512 and a["flash_fwd"][1] >= 512
    # the wider head and the wider type never get the larger tile
    for other in (pa.derive_blocks(4096, 4096, 256, jnp.bfloat16),
                  pa.derive_blocks(4096, 4096, 128, jnp.float32)):
        for kern in pa.KERNELS:
            assert other[kern][0] * other[kern][1] <= a[kern][0] * a[kern][1]


@pytest.mark.parametrize("causal,window", [(True, None), (True, 200),
                                           (False, 150), (True, 5000)])
@pytest.mark.parametrize("Bq,Bk", [(128, 128), (128, 256), (512, 128)])
@pytest.mark.parametrize("d", [0, 384, -384, 100, -1000])
def test_live_ranges_are_the_tiles_tile_live_keeps(d, Bq, Bk, causal, window):
    """The index maps clamp to [lo, hi] of `_live_k_range` / `_live_q_range`
    and the counters count it: both must be exactly the tiles `_tile_live`
    runs, for any offset between the shards."""
    from paddle_tpu.ops import pallas_attention as pa
    nq, nk = 1024 // Bq, 1024 // Bk
    live = np.array([[bool(pa._tile_live(d, 0, iq, ik, Bq, Bk, causal,
                                         window))
                      for ik in range(nk)] for iq in range(nq)])
    for iq in range(nq):
        lo, hi = pa._live_k_range(d + iq * Bq, Bq, Bk, causal, window)
        lo = 0 if lo is None else lo
        hi = nk - 1 if hi is None else hi
        assert [lo <= ik <= hi for ik in range(nk)] == list(live[iq])
    for ik in range(nk):
        lo, hi = pa._live_q_range(ik * Bk - d, Bq, Bk, causal, window)
        lo = 0 if lo is None else lo
        hi = nq - 1 if hi is None else hi
        assert [lo <= iq <= hi for iq in range(nq)] == list(live[:, ik])
    assert pa._count_live(
        [pa._live_k_range(d + iq * Bq, Bq, Bk, causal, window)
         for iq in range(nq)], nk) == int(live.sum())


def _flash_counters():
    from paddle_tpu.obs.metrics import process_counters
    return {k: v for k, v in process_counters().snapshot().items()
            if k.startswith("flash_")}


@pytest.mark.parametrize("blocks,steps,live", [
    (dict(block_q=128, block_k=128), 1024, 528),     # 51.6%
    (dict(block_q=512, block_k=512), 64, 36),        # 56.3%
])
def test_flash_counters_count_a_traced_call(blocks, steps, live):
    """flash_grid_steps_total / flash_live_tiles_total grow by the grid
    and its live tiles when a call is TRACED (eval_shape: nothing runs),
    per kernel; GQA 24 / 2 heads walk the same count in dk/dv's swapped
    grid; a call with traced offsets records neither."""
    B, T, H, H_kv, D = 1, 4096, 24, 2, 128
    shapes = [jax.ShapeDtypeStruct((B, T, h, D), jnp.bfloat16)
              for h in (H, H_kv, H_kv)]

    def loss(q, k, v, **kw):
        return jnp.sum(flash_attention(q, k, v, causal=True, **blocks,
                                       **kw).astype(jnp.float32))

    before = _flash_counters()
    jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), *shapes)
    after = _flash_counters()
    for kern in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        key = '{kernel="%s"}' % kern
        assert after["flash_grid_steps_total" + key] \
            - before.get("flash_grid_steps_total" + key, 0) == B * H * steps
        assert after["flash_live_tiles_total" + key] \
            - before.get("flash_live_tiles_total" + key, 0) == B * H * live

    jax.eval_shape(
        lambda q, k, v, o: jax.grad(
            lambda q, k, v: loss(q, k, v, q_offset=o[0], k_offset=o[1]),
            argnums=(0, 1, 2))(q, k, v),
        *shapes, jax.ShapeDtypeStruct((2,), jnp.int32))
    assert _flash_counters() == after


def test_flash_live_tile_share_reader(monkeypatch):
    """benchmark/layer_metrics/flash_live_tile_share.train.py: live over
    stepped of the process counters, all kernels; None where the program
    has no such counters (the reader laid over a parent commit)."""
    import os

    import paddle_tpu.obs.metrics as metrics
    from benchmark.lib.spec import Benchmark
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    reader = Benchmark(root).reader("flash_live_tile_share.train")
    pc = metrics.ProcessCounters()
    monkeypatch.setattr(metrics, "_PROCESS_COUNTERS", pc)
    assert reader.read(None) is None
    pc.add("serving_frame_writes_total", 5)
    assert reader.read(None) is None
    for kern, steps, live in (("flash_fwd", 64, 36), ("flash_bwd_dq", 64, 36),
                              ("flash_bwd_dkv", 128, 40)):
        pc.add('flash_grid_steps_total{kernel="%s"}' % kern, steps)
        pc.add('flash_live_tiles_total{kernel="%s"}' % kern, live)
    assert reader.read(None) == pytest.approx(100.0 * 112 / 256)
    monkeypatch.delattr(metrics, "process_counters")
    assert reader.read(None) is None
