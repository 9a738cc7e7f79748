"""LFM2-MoE (gated short-convolution layers + QK-normed grouped-query
attention + MoE with every expert held) against the plain reference
(benchmark/reference/lfm2_moe.py): the shared parity tests of
tests/model_parity.py over its case — the whole sequence, the decode step and
the ragged mixed step through the cache manager's 2-token slot tails, the
slot parts declared by the layer type, paused slots, re-admission, the
configuration file — and what is this model's own: the QK norm, the three
conv paths, the narrow heads packed a lane tile, the expert-parallel share.
Its engines are tests/test_lfm2_moe_engine.py's."""

import json

import numpy as np
import pytest

from tests.model_parity import (  # noqa: F401
    CASES, ROOT, case, cfg, logits, model, pytest_generate_tests, ref,
    test_a_paused_slots_parts_are_bit_equal_after_the_step,
    test_a_reused_slot_starts_from_zeros,
    test_configuration_file_is_the_catalog_row_cut_as_it_says,
    test_dsl_defaults_equal_the_configuration_file,
    test_layer_kinds_by_depth,
    test_ragged_chunks_then_decode_through_the_pools_on_logits,
    test_reference_imports_nothing_of_the_program,
    test_slot_parts_are_declared_by_the_layer_type,
    test_weights_fit_the_programs_parameters,
    test_whole_sequence_logits_against_the_reference)

CASE = CASES["lfm2_moe"]


def test_conv_taps_and_head_norms_are_the_familys(model):
    import jax
    _, ex, w = model
    # 3 taps a channel, U(-1/sqrt 3, 1/sqrt 3); the two head norms 64 wide
    taps = np.asarray(w["_blk0_conv.w1"])
    assert taps.shape == (3, 256) and abs(taps).max() <= 3 ** -0.5
    assert w["_blk1_attn.w4"].shape == w["_blk1_attn.w5"].shape == (1, 64)
    # the program's own initializer draws the taps from the same range
    own = np.asarray(ex.init_params(jax.random.PRNGKey(1))["_blk0_conv.w1"])
    assert 0.5 < abs(own).max() <= 3 ** -0.5


def test_qk_norm_is_an_attr_and_changes_the_layer(model):
    """Without the attr the attention layer has its four parameters and
    compiles what it compiled before; with it, two more and other logits."""
    import os
    from paddle_tpu.config.parser import parse_config
    c, ex, w = model
    attn = next(l for l in ex.model.layers if l.name == "blk1_attn")
    assert attn.attrs["qk_norm"] is True and attn.attrs["rms_eps"] == 1e-5
    assert len(attn.inputs) == 6
    plain = parse_config(os.path.join(ROOT, "demo", "model_zoo",
                                      "transformer_lm.py"),
                         "vocab=64,dim=32,layers=1,heads=2,batch_size=2")
    mha = next(l for l in plain.model_config.layers
               if l.type == "multi_head_attention")
    assert "qk_norm" not in mha.attrs and len(mha.inputs) == 4
    seq = np.arange(12)[None] % c["vocab_size"]
    base, _ = logits(ex, w, seq)
    w2 = dict(w, **{"_blk1_attn.w4": w["_blk1_attn.w4"] * 3.0})
    scaled, _ = logits(ex, w2, seq)
    assert float(np.abs(np.asarray(base) - np.asarray(scaled)).max()) > 1e-3


def test_the_three_conv_paths_agree_across_a_split_window():
    """The layer alone: a whole sequence; the same tokens as one ragged
    step of two segments cut inside a 3-tap window followed by decode
    steps; all three give one output, and the tail left behind is the last
    two inputs of the convolution."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import short_conv
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    T, C, S = 11, 8, 2
    u = jax.random.normal(ks[0], (1, T, C))
    w = jax.random.normal(ks[1], (3, C))
    whole = short_conv.short_conv_whole(u, w)[0]
    tails = jnp.full((S + 1, 2, C), 7.0)         # a dirty pool
    # rows [0, S): padding; then slot 1's positions 0..3 (from zeros)
    cache = dict(row_slot=jnp.asarray([S, S, 1, 1, 1, 1], jnp.int32),
                 row_pos=jnp.asarray([0, 0, 0, 1, 2, 3], jnp.int32))
    x = jnp.concatenate([jnp.zeros((S, C)), u[0, :4]])
    y, tails = short_conv.short_conv_slots(
        x, w, tails, short_conv.slot_runs(cache, S, 6))
    np.testing.assert_allclose(y[S:], whole[:4], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(tails[1], u[0, 2:4])
    assert bool((tails[0] == 7.0).all())         # slot 0 untouched
    # positions 4 (one row: the window spans two steps) and 5..7
    for lo, hi in ((4, 5), (5, 8)):
        n = hi - lo
        cache = dict(row_slot=jnp.asarray([S, S] + [1] * n, jnp.int32),
                     row_pos=jnp.asarray([0, 0] + list(range(lo, hi)),
                                         jnp.int32))
        x = jnp.concatenate([jnp.zeros((S, C)), u[0, lo:hi]])
        y, tails = short_conv.short_conv_slots(
            x, w, tails, short_conv.slot_runs(cache, S, S + n))
        np.testing.assert_allclose(y[S:], whole[lo:hi], rtol=1e-6, atol=1e-6)
    for t in range(8, T):                        # decode steps, slot 1 runs
        cache = dict(pos=jnp.asarray([3, t], jnp.int32),
                     run=jnp.asarray([False, True]))
        x = jnp.stack([jnp.ones((C,)), u[0, t]])
        y, tails = short_conv.short_conv_slots(
            x, w, tails, short_conv.slot_runs(cache, S, S))
        np.testing.assert_allclose(y[1], whole[t], rtol=1e-6, atol=1e-6)
        assert bool((tails[0] == 7.0).all())     # the paused slot's tail
    np.testing.assert_array_equal(tails[1], u[0, T - 2:])


@pytest.mark.parametrize("h_kv,dh,want", [
    (8, 64, (4, 128)), (2, 64, (1, 128)), (4, 32, (1, 128)),
    (2, 128, (2, 128)), (8, 128, (8, 128)), (2, 256, (2, 256)),
    (1, 64, (1, 64)), (2, 16, (2, 16)), (3, 96, (3, 96))])
def test_narrow_heads_are_stored_whole_lane_tiles(h_kv, dh, want):
    from paddle_tpu.ops.pallas_paged import kv_row_shape
    assert kv_row_shape(h_kv, dh) == want


@pytest.mark.parametrize("rows", ["decode", "ragged"])
def test_paged_kernel_reads_a_packed_pool_as_the_gather_does(rows,
                                                             monkeypatch):
    """Interpreted: the kernel over a pool of 64-wide heads stored two a
    lane tile against the jnp gather over the same pool, grouped-query
    heads (8 query heads, 4 KV heads in 2 packed rows)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    S, H, Hkv, D, ps, maxp = 3, 8, 4, 64, 4, 5
    P = 1 + S * maxp
    pool = lambda k: jax.random.normal(k, (P, ps, 2, 128))
    kp, vp = pool(ks[0]), pool(ks[1])
    table = jnp.arange(1, P).reshape(S, maxp).astype(jnp.int32)
    if rows == "decode":
        q = jax.random.normal(ks[2], (S, 1, H, D))
        k = jax.random.normal(ks[3], (S, 1, Hkv, D))
        v = jax.random.normal(ks[4], (S, 1, Hkv, D))
        pos = jnp.asarray([0, 7, 18], jnp.int32)
        call = lambda uk: paged_attention_step(q, k, v, kp, vp, table, pos,
                                               use_kernel=uk)
    else:
        T = 6
        q = jax.random.normal(ks[2], (T, H, D))
        k = jax.random.normal(ks[3], (T, Hkv, D))
        v = jax.random.normal(ks[4], (T, Hkv, D))
        table = jnp.concatenate([table, jnp.zeros((1, maxp), jnp.int32)])
        slot = jnp.asarray([0, 2, S, 1, 1, 1], jnp.int32)
        pos = jnp.asarray([5, 11, 0, 8, 9, 10], jnp.int32)
        call = lambda uk: ragged_paged_attention_step(
            q, k, v, kp, vp, table, slot, pos, use_kernel=uk)
    with jax.default_matmul_precision("highest"):
        (o1, k1, v1), (o2, k2, v2) = call(False), call(True)
    assert k1.shape == kp.shape and bool((k1 == k2).all())
    assert bool((v1 == v2).all())
    live = np.ones(o1.shape[0], bool)
    if rows == "ragged":
        live[2] = False                          # the padding row
    assert float(jnp.abs(o1 - o2)[live].max()) < 2e-5


def test_the_ranks_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test: one expert layer of the PROGRAM as each of 8
    ranks would hold it (8 of 64 experts each, the draw's own count), added
    up, against the uncut REFERENCE layer — all 64 experts held, what the
    cell runs."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel.moe import moe_ffn
    uncut = cfg(CASE, num_experts=64, experts_held=64, ep_rank=0)
    w = ref.make_weights(uncut, 11)
    wl = {k[len("_blk1_"):]: v for k, v in w.items()
          if k.startswith("_blk1_")}
    x = jnp.asarray(np.random.default_rng(5).normal(size=(10, 256)),
                    jnp.float32)
    kw = dict(top_k=4, scoring="sigmoid",
              select_bias=wl["moe.w4"].reshape(-1))
    stacks = lambda sl: tuple(wl[k][sl] for k in ("moe.w1", "moe.w2",
                                                   "moe.w3"))
    with jax.default_matmul_precision("highest"):
        want = ref._moe(uncut, wl, x, None)
        total = jnp.zeros_like(want)
        seen = jnp.zeros((10, 0), bool)
        for rank in range(8):
            y, _, pairs = moe_ffn(
                x, wl["moe.w0"], stacks(slice(8 * rank, 8 * rank + 8)),
                first_expert=8 * rank,
                scale=uncut["routed_scaling_factor"], **kw)
            total = total + y
            seen = jnp.concatenate([seen, pairs], axis=1)
        # the same rank's share through the reference's own cut
        cut = dict(uncut, experts_held=8, ep_rank=3)
        held = stacks(slice(24, 32))
        wl3 = dict(wl, **dict(zip(("moe.w1", "moe.w2", "moe.w3"), held)))
        y3, _, _ = moe_ffn(x, wl["moe.w0"], held, first_expert=24, **kw)
        assert float(jnp.abs(y3 - ref._moe(cut, wl3, x, None)).max()) < 2e-5
    # the renormalisation's + 1e-6 against moe_route's clamp: far under this
    assert float(jnp.abs(total - want).max()) < 2e-5
    assert bool((jnp.sum(seen, axis=1) == 4).all())   # top-4, every row


def test_the_cut_keeps_the_published_widths(ref):
    with open(CASE.json_path) as f:
        c = json.load(f)
    # the published widths, uncut; every expert held; the whole vocabulary
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["conv_L_cache"], c["conv_bias"]) == \
        (2048, 32, 8, 64, 3, False)
    assert c["head_dim"] * c["num_attention_heads"] == c["hidden_size"]
    assert (c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["routed_scaling_factor"],
            c["intermediate_size"], c["vocab_size"]) == \
        (1536, 64, 4, 1, 11776, 65536)
    assert c["experts_held"] == c["num_experts"] and c["ep_rank"] == 0
    dep = c["deployment"]
    assert dep["chips_sharing_a_layer"] == 1 and dep["pipeline_stages"] == 8
    assert c["published"]["num_hidden_layers"] == \
        dep["pipeline_stages"] * c["num_hidden_layers"]
    # one leading dense layer and one whole period of the pattern
    assert ref.layer_kinds(c) == ["conv", "full_attention", "conv", "conv",
                                  "conv"]
    assert ref.layer_kinds(dict(c, num_hidden_layers=2)) == \
        ["conv", "full_attention"]
    assert c["published"]["layer_types"] == c["layer_types"]
    # the aliases the shared readers and server_argv read
    assert c["n_routed_experts"] == c["num_experts"]
    assert c["n_shared_experts"] == 0
    assert c["first_k_dense_replace"] == c["num_dense_layers"] == 1
    assert c["rope_theta"] == c["rope_parameters"]["rope_theta"]
    assert c["server_flags"] == {
        "slots": 256, "page_size": 16, "max_context": 4096,
        "prefill_chunk": 128, "max_step_tokens": 512, "max_queue": 1024,
        "decode_steps": 1, "spec_k": 0, "param_dtype": "bfloat16"}
    assert c["param_dtype"] == c["compute_dtype"] == "bfloat16"
    # 2,834.8 M parameters, 5.67 GB in bf16 (ISSUE 35 section 1)
    n = sum(int(np.prod(s)) for s, _ in ref.param_shapes(c).values())
    assert n == 2_834_872_704 and round(2 * n / 1e9, 2) == 5.67
