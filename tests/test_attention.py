"""Attention ops + context parallelism tests.

Oracle strategy follows the reference's CPU-vs-GPU comparison tests
(SURVEY.md §4: test_matrixCompare) — dense attention is the oracle, the
blockwise and ring (context-parallel, 8-virtual-device mesh) paths must
match it in both forward values and gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.attention import (

    blockwise_attention,
    dot_product_attention,
    multi_head_attention,
)

pytestmark = pytest.mark.slow  # heavy: excluded from the fast gate (pytest -m "not slow")


def _rand_qkv(rng, B=2, T=16, H=2, D=4, Tk=None):
    Tk = Tk or T
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Tk, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Tk, H, D)), jnp.float32)
    return q, k, v


def _valid(lengths, T):
    return jnp.arange(T)[None, :] < jnp.asarray(lengths)[:, None]


class TestDense:
    def test_softmax_rows_sum_to_one(self):
        rng = np.random.default_rng(0)
        q, k, v = _rand_qkv(rng)
        ones = jnp.ones_like(v)
        out = dot_product_attention(q, k, ones)
        np.testing.assert_allclose(out, np.ones(out.shape), rtol=1e-5)

    def test_causal_first_token_attends_self_only(self):
        rng = np.random.default_rng(1)
        q, k, v = _rand_qkv(rng)
        out = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out[:, 0], v[:, 0], rtol=1e-5)

    def test_masked_rows_are_zero(self):
        rng = np.random.default_rng(2)
        B, T = 2, 8
        q, k, v = _rand_qkv(rng, B=B, T=T)
        valid = _valid([5, 8], T)
        out = dot_product_attention(q, k, v, q_valid=valid, k_valid=valid)
        np.testing.assert_allclose(out[0, 5:], np.zeros_like(out[0, 5:]))

    def test_masked_keys_do_not_contribute(self):
        rng = np.random.default_rng(3)
        B, T = 2, 8
        q, k, v = _rand_qkv(rng, B=B, T=T)
        valid = _valid([6, 6], T)
        out1 = dot_product_attention(q, k, v, k_valid=valid)
        # poison the masked keys/values; result must not change
        k2 = k.at[:, 6:].set(100.0)
        v2 = v.at[:, 6:].set(-50.0)
        out2 = dot_product_attention(q, k2, v2, k_valid=valid)
        np.testing.assert_allclose(out1, out2, rtol=1e-5)


class TestBlockwise:
    @pytest.mark.parametrize("block_k", [4, 5, 16, 64])
    def test_matches_dense(self, block_k):
        rng = np.random.default_rng(4)
        q, k, v = _rand_qkv(rng, T=16, Tk=20)
        ref = dot_product_attention(q, k, v)
        out = blockwise_attention(q, k, v, block_k=block_k)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)

    def test_matches_dense_causal_and_lengths(self):
        rng = np.random.default_rng(5)
        B, T = 3, 12
        q, k, v = _rand_qkv(rng, B=B, T=T)
        valid = _valid([12, 7, 3], T)
        ref = dot_product_attention(q, k, v, q_valid=valid, k_valid=valid,
                                    causal=True)
        out = blockwise_attention(q, k, v, q_valid=valid, k_valid=valid,
                                  causal=True, block_k=5)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)

    def test_grads_match_dense(self):
        rng = np.random.default_rng(6)
        q, k, v = _rand_qkv(rng, T=8)

        def loss_dense(q, k, v):
            return jnp.sum(jnp.square(dot_product_attention(q, k, v, causal=True)))

        def loss_block(q, k, v):
            return jnp.sum(jnp.square(
                blockwise_attention(q, k, v, causal=True, block_k=4)))

        g_ref = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        g_out = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_out):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_backward_memory_stays_blockwise(self):
        """The scan body is rematerialized: backward must NOT save every
        block's score tile (n_blocks x [B,H,Tq,block_k] residuals measured
        32 GB at T=16384 in a sweep since withdrawn, see ROADMAP S5 — it
        ran the chip out of memory).  Without remat, temp memory is quadratic in T (n_blocks
        tiles, each itself linear in T): doubling T must NOT ~4x the
        compiled backward's temp bytes.  Measured with remat: 106.9 ->
        246.6 MB (2.3x); without: would be >= 4.3x."""
        def temp_bytes(T, block=512):
            q = jnp.zeros((1, T, 2, 64), jnp.bfloat16)

            def loss(q, k, v):
                return blockwise_attention(
                    q, k, v, causal=True,
                    block_k=block).astype(jnp.float32).sum()

            c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))
                        ).lower(q, q, q).compile()
            return c.memory_analysis().temp_size_in_bytes

        t1, t2 = temp_bytes(2048), temp_bytes(4096)
        assert t2 < 3.0 * t1, (t1, t2)


class TestRing:
    """Context parallelism on the 8-virtual-device CPU mesh (conftest)."""

    def _mesh(self, data=2, seq=4):
        from paddle_tpu.parallel.mesh import make_mesh
        return make_mesh(data=data, seq=seq)

    @pytest.mark.parametrize("data,seq", [(1, 8), (2, 4)])
    def test_matches_dense(self, data, seq):
        from paddle_tpu.parallel.context import ring_attention_sharded
        rng = np.random.default_rng(7)
        q, k, v = _rand_qkv(rng, B=4, T=16)
        mesh = self._mesh(data, seq)
        ref = dot_product_attention(q, k, v)
        out = ring_attention_sharded(mesh, q, k, v)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)

    def test_matches_dense_causal_varlen(self):
        from paddle_tpu.parallel.context import ring_attention_sharded
        rng = np.random.default_rng(8)
        B, T = 4, 16
        q, k, v = _rand_qkv(rng, B=B, T=T)
        valid = _valid([16, 9, 3, 13], T)
        mesh = self._mesh(2, 4)
        ref = dot_product_attention(q, k, v, q_valid=valid, k_valid=valid,
                                    causal=True)
        out = ring_attention_sharded(mesh, q, k, v, q_valid=valid,
                                     k_valid=valid, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)

    def test_cross_attention_unequal_lengths_causal(self):
        """Tq != Tk: key-block global positions must use the KEY shard length."""
        from paddle_tpu.parallel.context import ring_attention_sharded
        rng = np.random.default_rng(19)
        q, k, v = _rand_qkv(rng, B=2, T=8, Tk=16)
        mesh = self._mesh(2, 4)
        ref = dot_product_attention(q, k, v, causal=True)
        out = ring_attention_sharded(mesh, q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)

    def test_seq_only_mesh_keeps_data_axis(self):
        """make_mesh always emits a data axis so shard_batch specs resolve."""
        from paddle_tpu.parallel.mesh import make_mesh
        from paddle_tpu.parallel.dp import shard_batch
        from paddle_tpu.parameter.argument import Argument
        mesh = make_mesh(data=1, seq=8)
        assert "data" in mesh.axis_names
        batch = {"x": Argument(value=jnp.zeros((4, 8)))}
        shard_batch(mesh, batch)  # must not raise

    def test_grads_match_dense(self):
        from paddle_tpu.parallel.context import ring_attention_sharded
        rng = np.random.default_rng(9)
        q, k, v = _rand_qkv(rng, B=2, T=8)
        mesh = self._mesh(1, 8)

        def loss_ref(q, k, v):
            return jnp.sum(jnp.square(dot_product_attention(q, k, v)))

        def loss_ring(q, k, v):
            return jnp.sum(jnp.square(ring_attention_sharded(mesh, q, k, v)))

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_out = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g_ref, g_out):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class TestMHALayer:
    def _build(self, mesh=None, causal=False, T=12):
        from paddle_tpu.config.parser import parse_config_callable
        from paddle_tpu.dsl import (
            MomentumOptimizer, data_layer, fc_layer, multi_head_attention_layer,
            classification_cost, pooling_layer, settings, SoftmaxActivation,
        )
        from paddle_tpu.dsl.poolings import AvgPooling

        def conf():
            settings(batch_size=4, learning_rate=0.01,
                     learning_method=MomentumOptimizer(momentum=0.9))
            x = data_layer(name="x", size=16)
            h = multi_head_attention_layer(x, size=16, num_heads=4,
                                           causal=causal)
            pooled = pooling_layer(input=h, pooling_type=AvgPooling())
            out = fc_layer(input=pooled, size=4, act=SoftmaxActivation())
            classification_cost(input=out, label=data_layer(name="y", size=4))

        from paddle_tpu.trainer.trainer import Trainer
        return Trainer(parse_config_callable(conf), seed=0, mesh=mesh)

    def _batch(self, B=4, T=12, D=16):
        from paddle_tpu.parameter.argument import Argument
        rng = np.random.default_rng(10)
        x = rng.normal(size=(B, T, D)).astype(np.float32)
        lens = np.array([T, T - 3, 5, T], np.int32)
        y = rng.integers(0, 4, B).astype(np.int32)
        return {"x": Argument(value=jnp.asarray(x), lengths=jnp.asarray(lens)),
                "y": Argument(ids=jnp.asarray(y))}

    def test_train_step_single_device(self):
        tr = self._build()
        loss = tr.train_one_batch(self._batch())
        assert np.isfinite(loss)

    def test_layer_flash_block_sizes_attrs_beat_env(self, monkeypatch):
        """The flash branch hands the kernel a layer's block_q/block_k
        attrs in BOTH the training path and the cached-decode prefill;
        with no attrs it passes none, and the kernel runs at the blocks
        `derive_blocks` gives the shape (the env defaults that once sat
        between the two are gone: the name of this test is from then)."""
        from paddle_tpu.config.parser import parse_config
        from paddle_tpu.graph.lm_decode import lm_generate
        from paddle_tpu.ops import pallas_attention
        from paddle_tpu.trainer.trainer import Trainer

        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        seen, ran = {}, []
        real, real_fwd = (pallas_attention.flash_attention,
                          pallas_attention._fwd_call)

        def spy(*a, **kw):
            seen.update({k: kw.get(k) for k in ("block_q", "block_k")})
            return real(*a, **kw)

        def spy_fwd(q, k, *a):
            # a = (v, kv_mask, q_off, k_off, H, scale, causal, window,
            # blocks); blocks[0] is the forward's (Bq, Bk)
            ran.append((q.shape[1], k.shape[1], a[-1][0]))
            return real_fwd(q, k, *a)

        monkeypatch.setattr(pallas_attention, "flash_attention", spy)
        monkeypatch.setattr(pallas_attention, "_fwd_call", spy_fwd)
        cfg = parse_config("demo/model_zoo/transformer_lm.py",
                           "dim=32,layers=1,heads=2,vocab=64,batch_size=2,"
                           "attn_impl=flash")
        tr = Trainer(cfg, seed=0)
        batch = next(tr.train_batches())
        tr.train_one_batch(batch)
        assert seen == {"block_q": None, "block_k": None}   # nothing pinned
        # ... so the kernel received the rule's pick for its shape
        T = int(jax.tree.leaves(batch)[0].shape[1])
        want = pallas_attention.derive_blocks(T, T, 16, jnp.float32)
        assert ran and all(r[2] == want["flash_fwd"] for r in ran), (ran, want)

        # cached-decode prefill: the same, no blocks pinned
        seen.clear()
        toks, _ = lm_generate(tr.executor, tr.params,
                              np.ones((1, 4), np.int32), max_new=2,
                              use_cache=True)
        assert seen == {"block_q": None, "block_k": None}

        # per-layer attrs beat the derived rule, in both paths
        for layer in cfg.model_config.layers:
            if layer.type == "multi_head_attention":
                layer.attrs["block_q"] = 128
                layer.attrs["block_k"] = 256
        tr2 = Trainer(cfg, seed=0)
        seen.clear()
        tr2.train_one_batch(next(tr2.train_batches()))
        assert seen == {"block_q": 128, "block_k": 256}
        seen.clear()
        lm_generate(tr2.executor, tr2.params, np.ones((1, 4), np.int32),
                    max_new=2, use_cache=True)
        assert seen == {"block_q": 128, "block_k": 256}

    def test_ring_path_matches_single_device(self):
        """Same params, same batch: seq-parallel mesh loss == local loss."""
        from paddle_tpu.parallel.mesh import make_mesh
        tr_local = self._build(causal=True)
        mesh = make_mesh(data=2, seq=4)
        tr_mesh = self._build(mesh=mesh, causal=True)
        # deep-copy: train_step donates its params buffer
        tr_mesh.params = {k: jnp.array(np.asarray(v))
                          for k, v in tr_local.params.items()}
        batch = self._batch()
        l_local = tr_local.train_one_batch(batch)
        l_mesh = tr_mesh.train_one_batch(batch)
        assert abs(l_local - l_mesh) < 1e-4, (l_local, l_mesh)


class TestUlysses:
    """All-to-all (Ulysses) context parallelism on the 8-device CPU mesh:
    tokens->heads resharding, local full-sequence attention, reshard back
    — must match dense exactly (same math, different layout)."""

    def _mesh(self, data=2, seq=4):
        from paddle_tpu.parallel.mesh import make_mesh
        return make_mesh(data=data, seq=seq)

    @pytest.mark.parametrize("data,seq,H", [(1, 8, 8), (2, 4, 4)])
    def test_matches_dense(self, data, seq, H):
        from paddle_tpu.parallel.context import ulysses_attention_sharded
        rng = np.random.default_rng(31)
        q, k, v = _rand_qkv(rng, B=4, T=16, H=H)
        mesh = self._mesh(data, seq)
        ref = dot_product_attention(q, k, v)
        out = ulysses_attention_sharded(mesh, q, k, v)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)

    def test_matches_dense_causal_varlen(self):
        from paddle_tpu.parallel.context import ulysses_attention_sharded
        rng = np.random.default_rng(32)
        B, T = 4, 16
        q, k, v = _rand_qkv(rng, B=B, T=T, H=4)
        valid = _valid([16, 9, 3, 13], T)
        mesh = self._mesh(2, 4)
        ref = dot_product_attention(q, k, v, q_valid=valid, k_valid=valid,
                                    causal=True)
        out = ulysses_attention_sharded(mesh, q, k, v, q_valid=valid,
                                        k_valid=valid, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)

    def test_head_divisibility_enforced(self):
        from paddle_tpu.parallel.context import ulysses_attention_sharded
        rng = np.random.default_rng(33)
        q, k, v = _rand_qkv(rng, B=2, T=8, H=2)    # 2 heads, seq axis 4
        with pytest.raises(AssertionError, match="divisible"):
            ulysses_attention_sharded(self._mesh(2, 4), q, k, v)

    def test_layer_attn_impl_ulysses_trains(self):
        """attn_impl='ulysses' through the config layer on a seq mesh:
        losses track the single-device dense run."""
        from paddle_tpu.config.parser import parse_config
        from paddle_tpu.trainer.trainer import Trainer

        args = ("dim=32,layers=1,heads=4,vocab=64,batch_size=8,"
                "attn_impl={}")
        steps = 4

        def run(impl, mesh):
            cfg = parse_config("demo/model_zoo/transformer_lm.py",
                               args.format(impl))
            tr = Trainer(cfg, seed=0, mesh=mesh)
            it = tr.train_batches()
            return [float(tr.train_one_batch(next(it)))
                    for _ in range(steps)]

        l_dense = run("dense", None)
        l_uly = run("ulysses", self._mesh(2, 4))
        np.testing.assert_allclose(l_uly, l_dense, rtol=5e-3, atol=5e-3)

        # a ulysses-trained config must DECODE too: the cached prefill
        # accepts the impl and falls through to local selection
        from paddle_tpu.config.parser import parse_config
        from paddle_tpu.graph.lm_decode import lm_generate
        from paddle_tpu.trainer.trainer import Trainer
        cfg = parse_config("demo/model_zoo/transformer_lm.py",
                           args.format("ulysses"))
        tr = Trainer(cfg, seed=0)          # decode runs un-meshed
        toks, _ = lm_generate(tr.executor, tr.params,
                              np.ones((2, 4), np.int32), max_new=3,
                              use_cache=True)
        assert np.asarray(toks).shape == (2, 7)


@pytest.mark.parametrize("masked", [False, True])
def test_flash_attn_fn_shards_batch_over_data_axis(masked):
    """Under a mesh the flash kernel runs inside shard_map on each device's
    batch shard (GSPMD cannot partition a Mosaic kernel — the chip's
    lowering refuses); values and gradients equal the unsharded call."""
    import functools

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.pallas_attention import flash_attention
    from paddle_tpu.parallel.context import flash_attn_fn
    from paddle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(data=4, devices=jax.devices()[:4])
    rng = np.random.default_rng(0)
    B, T, H, D = 8, 16, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
               for _ in range(3))
    valid = (jnp.asarray(np.arange(T)[None, :] <
                         rng.integers(1, T + 1, B)[:, None])
             if masked else None)
    flash = functools.partial(flash_attention, block_q=8, block_k=8)
    sharded = flash_attn_fn(mesh, flash)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, q_valid=valid, k_valid=valid,
                          causal=True) ** 2)

    put = lambda x: jax.device_put(x, NamedSharding(mesh, P("data")))
    got = jax.jit(jax.value_and_grad(functools.partial(loss, sharded),
                                     argnums=(0, 1, 2)))(put(q), put(k),
                                                         put(v))
    want = jax.value_and_grad(functools.partial(loss, flash),
                              argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_flash_attn_fn_refuses_a_seq_axis():
    import jax

    from paddle_tpu.parallel.context import flash_attn_fn
    from paddle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(data=2, seq=2, devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="ring"):
        flash_attn_fn(mesh, None)
