"""Jamba (Mamba-1 selective-scan layers with the three inner RMSNorms +
multi-query attention without rotation + dense SwiGLU MLPs) against the plain
reference (benchmark/reference/jamba.py): the shared parity tests of
tests/model_parity.py over its case — the whole sequence (and without the
`D x` term, and against a reference without the inner norms: both must
fail), the decode step and the ragged mixed step through the cache manager's
slot parts by the jnp forms and by the interpreted scan kernel (and from a
state rounded to bfloat16, which must fail), the parts declared by the layer
type, paused slots, re-admission — and what is this model's own: the
family's initializers, multi-query attention, the scan kernel against the
literal `lax.scan`, and the lone KV head's pool.  Its engines are
tests/test_jamba_engine.py's."""

import os

import numpy as np
import pytest

from tests.model_parity import (  # noqa: F401
    CASES, ROOT, build, case, cfg, logits, model, pytest_generate_tests, ref,
    ref_logits, test_a_paused_slots_parts_are_bit_equal_after_the_step,
    test_a_reused_slot_starts_from_zeros, test_layer_kinds_by_depth,
    test_ragged_chunks_then_decode_through_the_pools_on_logits,
    test_reference_imports_nothing_of_the_program,
    test_slot_parts_are_declared_by_the_layer_type,
    test_weights_fit_the_programs_parameters,
    test_whole_sequence_logits_against_the_reference)

CASE = CASES["jamba"]


def test_every_block_is_a_mixer_and_an_mlp_with_the_familys_initializers(
        model):
    import jax
    _, ex, w = model
    # each behind its norm; layer 1 is the attention layer, the others Mamba
    for i in range(5):
        mine = {k.split(".")[0] for k in w if k.startswith(f"_blk{i}_")}
        mixer = "attn" if i == 1 else "mamba"
        assert mine == {f"_blk{i}_ln1", f"_blk{i}_{mixer}", f"_blk{i}_ln2",
                        f"_blk{i}_ffn"}, mine
    assert w["_blk0_mamba.w0"].shape == (64, 256)          # x and z
    assert w["_blk0_mamba.w3"].shape == (128, 8 + 2 * 16)  # r, B, C
    assert w["_blk0_mamba.w7"].shape == (8, 128)
    assert w["_blk0_mamba.w9"].shape == (16, 128)          # A_log [N, d_in]
    assert w["_blk1_attn.w1"].shape == (64, 16)            # ONE KV head
    a_log = np.asarray(w["_blk0_mamba.w9"])
    np.testing.assert_allclose(
        a_log, np.log(np.arange(1, 17))[:, None] * np.ones((1, 128)),
        rtol=1e-6)
    assert bool((np.asarray(w["_blk0_mamba.w10"]) == 1).all())      # D
    dt = np.log1p(np.exp(np.asarray(w["_blk0_mamba.w8"], np.float64)))
    assert dt.min() >= 1e-3 * (1 - 1e-4) and dt.max() <= 0.1 * (1 + 1e-4)
    assert abs(np.asarray(w["_blk0_mamba.w7"])).max() <= 8 ** -0.5
    assert abs(np.asarray(w["_blk0_mamba.w1"])).max() <= 0.5
    # the program's own initializer draws from the same ranges
    own = ex.init_params(jax.random.PRNGKey(1))
    assert 0.3 < abs(np.asarray(own["_blk0_mamba.w1"])).max() <= 0.5
    assert 0.2 < abs(np.asarray(own["_blk0_mamba.w7"])).max() <= 8 ** -0.5


def test_attention_is_multi_query_without_rotation(model, ref):
    c, ex, w = model
    attn = next(l for l in ex.model.layers if l.name == "blk1_attn")
    assert "use_rope" not in attn.attrs and attn.attrs["num_kv_heads"] == 1
    rot = cfg(CASE, attn_use_rope=True)
    seq = np.random.default_rng(1).integers(0, 64, 16)
    got = np.asarray(logits(build(CASE, rot), w, seq[None])[0][0])
    assert float(np.abs(got - ref_logits(ref, rot, w, seq)).max()) < CASE.tol
    assert float(np.abs(got - ref_logits(ref, c, w, seq)).max()) > \
        50 * CASE.tol


def test_serving_names_no_recurrent_kind():
    for mod in ("paged_kv.py", "engine.py", "server.py"):
        with open(os.path.join(ROOT, "paddle_tpu", "serving", mod)) as f:
            code = f.read().split('"""', 2)[2]
        assert '"mamba"' not in code and "layers_mamba" not in code, mod


# -- the kernel -----------------------------------------------------------------------

def _scan_inputs(seed, S, P, d_in=256, N=16):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[0], (S + 1, N, d_in))
    A = -jnp.exp(2.0 * jax.random.uniform(ks[1], (N, d_in)))
    x = jax.random.normal(ks[2], (P, d_in))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (P, d_in)) - 2.0)
    return state, A, x, dt, jax.random.normal(ks[4], (P, N)), \
        jax.random.normal(ks[5], (P, N))


@pytest.mark.parametrize("run", [1, 5, 128], ids=lambda n: f"run-of-{n}")
def test_the_interpreted_kernel_is_the_jnp_scan(run, monkeypatch):
    """One run of 1, 5 and 128 tokens of slot 2, starting inside a pass of
    8 (row 3) and continuing from the slot's state: the kernel's time loop
    against the literal `lax.scan`, outputs and final state."""
    import jax.numpy as jnp
    from paddle_tpu.ops import selective_scan as ss
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    S, P = 4, 136
    state, A, x, dt, Bm, Cm = _scan_inputs(run, S, P)
    seg_slot = np.full(P, S, np.int32)
    seg_slot[3:3 + run] = 2
    seg_pos = np.zeros(P, np.int32)
    seg_pos[3:3 + run] = 11 + np.arange(run)
    y, new, n = ss.segment_rows(state, jnp.asarray(seg_slot),
                                jnp.asarray(seg_pos), x, Bm, Cm, dt, A,
                                use_kernel=True)
    want_y, want_h = ss.recurrent(x[None, 3:3 + run], Bm[None, 3:3 + run],
                                  Cm[None, 3:3 + run], dt[None, 3:3 + run],
                                  A, state[None, 2])
    assert int(n) == 1
    np.testing.assert_allclose(y[3:3 + run], want_y[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(new[2], want_h[0], rtol=2e-5, atol=2e-5)
    assert bool((y[:3] == 0).all()) and bool((y[3 + run:] == 0).all())
    for s in (0, 1, 3):
        assert bool((new[s] == state[s]).all())
    # the one-token case is the decode rows' call
    if run == 1:
        live = jnp.asarray([False, False, True, False])
        y1, new1 = ss.step_rows(state, None, live, x[1:5], Bm[1:5], Cm[1:5],
                                dt[1:5], A, use_kernel=True)
        np.testing.assert_allclose(y1[2], want_y[0, 0], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(new1[2], want_h[0], rtol=2e-5, atol=2e-5)
        assert bool((y1[jnp.asarray([0, 1, 3])] == 0).all())


def test_a_padded_run_is_the_run(monkeypatch):
    """dt = 0 is the identity: a run of 5 tokens followed by 3 rows of the
    same slot whose dt is 0 leaves the state the 5 tokens left, in the jnp
    scan and in the kernel; and more runs than a call holds (6 > 4) go
    through the second call."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_selective_scan as kernel
    from paddle_tpu.ops import selective_scan as ss
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    S, P = 6, 24
    state, A, x, dt, Bm, Cm = _scan_inputs(9, S, P)
    slot5 = jnp.asarray([1] * 5 + [S] * (P - 5), jnp.int32)
    slot8 = jnp.asarray([1] * 8 + [S] * (P - 8), jnp.int32)
    pos = jnp.asarray(list(range(4, 12)) + [0] * (P - 8), jnp.int32)
    dt0 = dt.at[5:8].set(0.0)
    for use in (False, True):
        _, a, _ = ss.segment_rows(state, slot5, pos, x, Bm, Cm, dt, A,
                                  use_kernel=use)
        _, b, _ = ss.segment_rows(state, slot8, pos, x, Bm, Cm, dt0, A,
                                  use_kernel=use)
        np.testing.assert_allclose(a[1], b[1], rtol=1e-6, atol=1e-6)
    assert kernel.RUNS_PER_CALL == 4
    many = jnp.asarray([2] * 5 + [0] + [4] * 7 + [1] * 3 + [5] * 2 + [3]
                       + [S] * 5, jnp.int32)
    mpos = jnp.asarray(list(range(5)) + [3] + list(range(4, 11))
                       + list(range(3)) + [9, 10] + [0] + [0] * 5, jnp.int32)
    y0, s0, n0 = ss.segment_rows(state, many, mpos, x, Bm, Cm, dt, A)
    y1, s1, n1 = ss.segment_rows(state, many, mpos, x, Bm, Cm, dt, A,
                                 use_kernel=True)
    assert int(n0) == int(n1) == 6
    np.testing.assert_allclose(y1, y0, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s1[:S], s0[:S], rtol=2e-5, atol=2e-5)


# -- the lone KV head's pool ---------------------------------------------------------

@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_a_lone_head_of_128_is_stored_two_tokens_a_row(kernel):
    """A bf16 pool [P, 16, 1, 128] would be padded to two rows a token by
    its HBM tile (tests/test_mosaic_compile.py holds the layout for the
    described v5e); the cache manager's shape for it is [P, 8, 2, 128]
    (ops/pallas_paged.py:kv_page_shape) — the same bytes in the same order,
    so the decode step and the ragged mixed step write and read it to the
    same result as the pool stored a row a token, on the gather path and
    through the interpreted kernel."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)
    from paddle_tpu.ops.pallas_paged import kv_page_shape, kv_row_shape
    S, H, D, PS, MAXP = 3, 20, 128, 16, 4
    assert kv_row_shape(1, D) == (1, 128)
    assert kv_page_shape(PS, 1, D, 2) == (8, 2, 128)
    assert kv_page_shape(PS, 1, D, 4) == (16, 1, 128)      # float32: dense
    assert kv_page_shape(PS, 2, D, 2) == (16, 2, 128)      # two heads: dense
    assert kv_page_shape(PS, 8, 64, 2) == (16, 4, 128)     # packed heads
    P = S * MAXP + 1
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    bf = jnp.bfloat16
    plain = [jax.random.normal(k, (P, PS, 1, D), bf) for k in ks[:2]]
    paired = [p.reshape(P, PS // 2, 2, D) for p in plain]
    table = jnp.arange(1, P, dtype=jnp.int32).reshape(S, MAXP)
    pos = jnp.array([5, 37, 16], jnp.int32)
    q, k, v = (jax.random.normal(kk, (S, 1, h, D), bf)
               for kk, h in zip(ks[2:5], (H, 1, 1)))
    want, wk, wv = paged_attention_step(q, k, v, *plain, table, pos,
                                        use_kernel=False)
    got, gk, gv = paged_attention_step(q, k, v, *paired, table, pos,
                                       use_kernel=kernel)
    assert gk.shape == (P, 8, 2, D)
    assert bool((gk.reshape(wk.shape) == wk).all())
    assert bool((gv.reshape(wv.shape) == wv).all())
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    # the mixed step: slot 1's decode row, then a chunk of 5 rows of slot 0
    row_slot = jnp.array([1, 0, 0, 0, 0, 0, S], jnp.int32)
    row_pos = jnp.array([38, 6, 7, 8, 9, 10, 0], jnp.int32)
    table1 = jnp.concatenate([table, jnp.zeros((1, MAXP), jnp.int32)])
    T = row_slot.shape[0]
    q, k, v = (jax.random.normal(kk, (T, h, D), bf)
               for kk, h in zip(ks[5:8], (H, 1, 1)))
    want, wk, wv = ragged_paged_attention_step(
        q, k, v, *plain, table1, row_slot, row_pos, use_kernel=False)
    got, gk, gv = ragged_paged_attention_step(
        q, k, v, *paired, table1, row_slot, row_pos, use_kernel=kernel)
    assert bool((gk.reshape(wk.shape) == wk).all())
    assert bool((gv.reshape(wv.shape) == wv).all())
    np.testing.assert_allclose(np.asarray(got[:-1], np.float32),
                               np.asarray(want[:-1], np.float32), atol=2e-2)


def test_the_cache_manager_stores_the_cells_kv_pool_without_padding():
    """The cell's attention layer at its published head sizes (20 query
    heads over ONE KV head of 128) in bfloat16: the K and V pools are
    [pages, 8, 2, 128], 512 B a token a layer."""
    import jax.numpy as jnp
    from paddle_tpu.serving import PagedKVCache
    c = cfg(CASE, num_attention_heads=20, head_dim=128, hidden_size=64,
               num_hidden_layers=2, attn_layer_period=2, attn_layer_offset=1)
    ex = build(CASE, c, compute_dtype="bfloat16")
    kv = PagedKVCache(ex, num_slots=2, page_size=16, pages_per_slot=2)
    assert kv.layer_specs == {"blk1_attn": (1, 128)}
    for part in ("k", "v"):
        pool = kv.pools["blk1_attn"][part]
        assert pool.shape == (kv.num_pages, 8, 2, 128)
        assert pool.dtype == jnp.bfloat16
        assert pool.nbytes == kv.num_pages * 16 * 256
