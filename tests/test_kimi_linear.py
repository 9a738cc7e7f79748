"""Kimi-Linear (KDA gated delta-rule layers + NoPE latent attention + MoE)
against the plain reference (benchmark/reference/kimi_linear.py): the shared
parity tests of tests/model_parity.py over its case — the whole sequence
(chunkwise), the decode step and the ragged mixed step through the cache
manager's slot state, the slot parts, paused slots, re-admission, the
configuration file — and what is this model's own: the chunkwise form
against the recurrence, the Pallas step kernel and the Pallas segment kernel
(`kda_seg`) interpreted, NoPE latent
attention without a query rank, the expert-parallel share.  Its engines are
tests/test_kimi_linear_engine.py's."""

import json

import numpy as np
import pytest

from tests.model_parity import (  # noqa: F401
    CASES, build, case, cfg, logits, model, pytest_generate_tests, ref,
    ref_logits, test_a_paused_slots_parts_are_bit_equal_after_the_step,
    test_a_reused_slot_starts_from_zeros,
    test_configuration_file_is_the_catalog_row_cut_as_it_says,
    test_dsl_defaults_equal_the_configuration_file,
    test_layer_kinds_by_depth,
    test_ragged_chunks_then_decode_through_the_pools_on_logits,
    test_reference_imports_nothing_of_the_program,
    test_slot_parts_are_declared_by_the_layer_type,
    test_weights_fit_the_programs_parameters,
    test_whole_sequence_logits_against_the_reference)

CASE = CASES["kimi_linear"]


def test_the_kda_initializers_are_the_familys(model):
    """A_log in [0, log 16], dt_bias a softplus^-1 of a step in
    [1e-3, 1e-1]; conv taps in [-1/2, 1/2]."""
    _, _, w = model
    a = np.asarray(w["_blk0_kda.w8"])
    assert a.min() >= 0 and a.max() <= np.log(16) + 1e-6
    dt = np.log1p(np.exp(np.asarray(w["_blk0_kda.w9"], np.float64)))
    assert dt.min() >= 1e-3 * 0.99 and dt.max() <= 1e-1 * 1.01
    assert float(abs(w["_blk0_kda.w3"]).max()) <= 0.5


@pytest.mark.parametrize("T", [1, 64, 150])
def test_chunkwise_equals_the_recurrence(T):
    """The WY form against the literal scan, also at a length that is no
    multiple of the chunk, and continued from a state."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    ks = jax.random.split(jax.random.PRNGKey(T), 6)
    B, H, d = 2, 3, 16
    q = kda.l2norm(jax.random.normal(ks[0], (B, T, H, d)))
    k = kda.l2norm(jax.random.normal(ks[1], (B, T, H, d)))
    v = jax.random.normal(ks[2], (B, T, H, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, d), minval=-6,
                                    maxval=2.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    S0 = jax.random.normal(ks[5], (B, H, d, d))
    for start in (None, S0):
        o1, S1 = kda.recurrent(q, k, v, g, beta, start)
        o2, S2 = kda.chunkwise(q, k, v, g, beta, start)
        assert float(jnp.abs(o1 - o2).max()) < 2e-5
        assert float(jnp.abs(S1 - S2).max()) < 2e-5


@pytest.mark.parametrize("rows", ["decode", "indirect"])
def test_step_kernel_interpreted_equals_the_jnp_step(rows, monkeypatch):
    """`kda_step` in interpret mode against ops/kda.py: the states of live
    rows move alike, a dead row's state is bit-equal to what it was, and
    with a slot indirection the rows reach the slots they name."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    R, H, d = 5, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    state = jax.random.normal(ks[0], (R + 3, H, d, d))
    q, k, g = (jax.random.normal(ks[i], (R, H, d)) for i in (1, 2, 3))
    g = -jnp.exp(g)
    v = jax.random.normal(ks[4], (R, H, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (R, H)))
    live = jnp.asarray([True, False, True, True, False])
    slot = None if rows == "decode" else jnp.asarray([6, 1, 0, 3, 2])
    o1, s1 = kda.step_rows(state, slot, live, q, k, v, g, beta)
    o2, s2 = kda.step_rows(state, slot, live, q, k, v, g, beta,
                           use_kernel=True)
    idx = np.arange(R) if slot is None else np.asarray(slot)
    lv = np.asarray(live)
    assert float(jnp.abs(o1 - o2)[lv].max()) < 1e-5
    assert float(jnp.abs(s1 - s2)[idx[lv]].max()) < 1e-5
    for s in (s1, s2):                   # dead rows and untouched slots
        keep = np.setdiff1d(np.arange(R + 2), idx[lv])
        assert bool((s[keep] == state[keep]).all())


# the runs of `kda_seg`'s cases: (slot, first row, rows, first position)
SEG_RUNS = {
    # a run from position 0 (its slot's state is NOT read) beside one that
    # continues its slot's state
    "from-zero-beside-continued": (192, [(2, 0, 64, 0), (0, 64, 64, 37)]),
    # lengths that are no multiple of the chunk, a run of one row, starts
    # at no multiple of 8
    "three-ragged-runs": (192, [(1, 0, 70, 0), (3, 70, 1, 5),
                                (0, 71, 100, 9)]),
    "padding-only": (192, []),
    # PR 46: a chunk takes the step's free rows — three chunks in one run
    "a-run-of-150": (192, [(2, 3, 150, 40)]),
    # a row list that is no multiple of 8 and shorter than a chunk
    "a-short-list": (21, [(0, 0, 5, 0), (1, 5, 16, 3)]),
}


@pytest.mark.parametrize("runs", list(SEG_RUNS))
def test_segment_kernel_interpreted_equals_the_recurrence(runs):
    """`kda_seg` in interpret mode at 32 heads (two head blocks of 16)
    against the literal recurrence over each run from the state it should
    start from, and against the jnp form of `segment_rows`: outputs, the
    states the runs leave; rows of no run read zeros; the trash row and the
    states of slots with no run are bit-equal to what they were."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    P, table = SEG_RUNS[runs]
    S, H, d = 4, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(len(runs)), 6)
    q = kda.l2norm(jax.random.normal(ks[0], (P, H, d)))
    k = kda.l2norm(jax.random.normal(ks[1], (P, H, d)))
    v = jax.random.normal(ks[2], (P, H, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (P, H, d), minval=-6, maxval=1))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (P, H)))
    state = jax.random.normal(ks[5], (S + 1, H, d, d))
    slot, pos = np.full(P, S, np.int32), np.zeros(P, np.int32)
    for s, at, n, p0 in table:
        slot[at:at + n], pos[at:at + n] = s, np.arange(p0, p0 + n)
    xs = (q, k, v, g, beta)
    o1, s1, n1 = jax.jit(kda.segment_rows)(state, slot, pos, *xs)
    o2, s2, n2 = jax.jit(lambda *a: kda.segment_rows(*a, use_kernel=True))(
        state, slot, pos, *xs)
    assert int(n1) == int(n2) == len(table)
    assert float(jnp.abs(o1 - o2).max()) < 2e-5
    assert float(jnp.abs(s1 - s2).max()) < 2e-5
    for s, at, n, p0 in table:
        S0 = None if p0 == 0 else state[s][None]
        want_o, want_S = kda.recurrent(
            *(a[None, at:at + n] for a in xs), S0)
        assert float(jnp.abs(o2[at:at + n] - want_o[0]).max()) < 2e-5
        assert float(jnp.abs(s2[s] - want_S[0]).max()) < 2e-5
    idle = np.setdiff1d(np.arange(S + 1), [s for s, *_ in table])
    assert bool((s2[idle] == state[idle]).all())
    assert not bool(o2[slot == S].any())


def test_nope_mla_without_a_query_rank_against_a_literal_loop(ref):
    """A model whose every layer is full attention (full_attn_layers 1;2):
    the program's whole-sequence logits against a literal per-head,
    per-position loop over the same weights, and the absorbed form over the
    dense cache against the expanded one."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.graph.lm_decode import init_kv_caches
    c = cfg(CASE, num_hidden_layers=2,
            linear_attn_config=dict(full_attn_layers=[1, 2]))
    ex = build(CASE, c, full_attn_layers="1;2")
    w = ref.make_weights(c, 5)
    assert [l.type for l in ex.model.layers
            if l.type.endswith("attention")] == ["mla_attention"] * 2
    assert "_blk0_attn.w4" in w and "_blk0_attn.w5" not in w
    ids = np.random.default_rng(2).integers(0, 64, (2, 14))
    whole, _ = logits(ex, w, ids)
    assert float(np.abs(np.asarray(whole[0]) - ref_logits(
        ref, c, w, ids[0])).max()) < 5e-5
    # one layer's mixer by a literal loop: no rotation anywhere
    H, nope, rope, vd, kr = 4, 8, 4, 8, 16
    wl = {k[len("_blk0_"):]: np.asarray(v, np.float64)
          for k, v in w.items() if k.startswith("_blk0_")}
    x = np.random.default_rng(3).normal(size=(5, 32))
    q = (x @ wl["attn.w0"]).reshape(5, H, nope + rope)
    ckv = x @ wl["attn.w1"]
    lat = ckv[:, :kr]
    lat = lat / np.sqrt((lat * lat).mean(-1, keepdims=True) +
                        c["rms_norm_eps"]) * wl["attn.w2"].reshape(-1)
    kvb = (lat @ wl["attn.w3"]).reshape(5, H, nope + vd)
    want = np.zeros((5, H * vd))
    for t in range(5):
        for h in range(H):
            s = [(q[t, h, :nope] @ kvb[j, h, :nope]
                  + q[t, h, nope:] @ ckv[j, kr:]) * (nope + rope) ** -0.5
                 for j in range(t + 1)]
            p = np.exp(np.asarray(s) - max(s))
            p /= p.sum()
            want[t, h * vd:(h + 1) * vd] = sum(
                p[j] * kvb[j, h, nope:] for j in range(t + 1))
    want = want @ wl["attn.w4"]
    with jax.default_matmul_precision("highest"):
        got = ref._attention(c, {k: jnp.asarray(v, jnp.float32)
                                 for k, v in wl.items()},
                             jnp.asarray(x, jnp.float32), None)
    assert float(np.abs(np.asarray(got) - want).max()) < 1e-4
    # absorbed over a dense cache = expanded
    lp, st = logits(ex, w, ids[:, :9], init_kv_caches(ex, 2, 14))
    assert float(jnp.abs(lp - whole[:, :9]).max()) < 2e-5
    for t in range(9, 14):
        lp, st = logits(ex, w, ids[:, t:t + 1], st)
        assert float(jnp.abs(lp[:, 0] - whole[:, t]).max()) < 5e-5


def test_the_ranks_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test: one expert layer of the PROGRAM as each of
    the 4 ranks holds it (4 of 16 experts each), the shared expert counted
    once, against the uncut REFERENCE layer (all 16 experts held)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.graph.layers_misc import gated_ffn
    from paddle_tpu.parallel.moe import moe_ffn
    uncut = cfg(CASE, experts_held=16, ep_rank=0)
    w = ref.make_weights(uncut, 11)
    wl = {k[len("_blk1_"):]: v for k, v in w.items()
          if k.startswith("_blk1_")}
    x = jnp.asarray(np.random.default_rng(5).normal(size=(10, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref._moe(uncut, wl, x, None)
        total = gated_ffn(x, wl["moe.w5"], wl["moe.w6"], wl["moe.w7"])
        for rank in range(4):
            sl = slice(4 * rank, 4 * rank + 4)
            y, _, _ = moe_ffn(
                x, wl["moe.w0"],
                (wl["moe.w1"][sl], wl["moe.w2"][sl], wl["moe.w3"][sl]),
                top_k=4, first_expert=4 * rank, scoring="sigmoid", n_group=1,
                topk_group=1, select_bias=wl["moe.w4"].reshape(-1),
                scale=uncut["routed_scaling_factor"])
            total = total + y
    assert float(jnp.abs(total - want).max()) < 2e-5


def test_the_cut_keeps_the_published_widths_and_lists():
    with open(CASE.json_path) as f:
        c = json.load(f)
    la = c["linear_attn_config"]
    assert (c["hidden_size"], la["num_heads"], la["head_dim"],
            la["short_conv_kernel_size"]) == (2304, 32, 128, 4)
    assert (c["kv_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"],
            c["num_attention_heads"]) == (512, 128, 64, 128, 32)
    assert c["q_lora_rank"] is None and c["mla_use_nope"] is True
    assert (c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_token"], c["routed_scaling_factor"],
            c["intermediate_size"]) == (1024, 256, 8, 2.446, 9216)
    assert c["state_dtype"] == "float32"
    assert c["experts_held"] * c["deployment"]["chips_sharing_a_layer"] \
        == c["num_experts"]
    assert c["ep_rank"] == c["deployment"]["rank_held"]
    assert c["published"]["full_attn_layers"] == la["full_attn_layers"]
    assert c["published"]["kda_layers"] == la["kda_layers"]
    # the KDA layers of the deepest stack the DSL builds are the published
    # list (tests/model_parity.py's `depths`: 27 layers, "KKKA" x 6 + "KKA")
    kinds = CASE.depths[-1][1]
    assert [i + 1 for i, k in enumerate(kinds) if k == "K"] == \
        la["kda_layers"]
    # the aliases the shared readers read say what Kimi's own keys say
    assert c["n_routed_experts"] == c["num_experts"]
    assert c["n_shared_experts"] == c["num_shared_experts"]
    # the guide's floors: whole periods past the dense layer, 8 experts,
    # 1/8 of the vocabulary
    assert (c["num_hidden_layers"] - c["first_k_dense_replace"]) % 4 == 0
    assert c["experts_held"] >= 8
    assert c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    assert c["server_flags"]["slots"] == 128
    assert c["server_flags"]["prefill_chunk"] == 128
