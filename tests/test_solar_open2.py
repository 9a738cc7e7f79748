"""Solar-Open2 (sigmoid-gated NoPE GQA over K/V pages + KDA layers whose
write strength reaches 2 + an expert layer from layer 0) against the plain
reference (benchmark/reference/solar_open2.py): the shared parity tests of
tests/model_parity.py over its case — the whole sequence with the controls
that must fail (the gate's matrix zeroed, the reference with no gate, the
reference with beta in (0, 1)), the decode step and the ragged mixed step
through the cache manager's slot state beside the K/V pages (and a state
rounded to bfloat16 told apart), the slot parts, paused slots, re-admission,
the configuration file — and what is this model's own: the KDA forms at
beta near 2, the gate over the dense cache, the eight ranks' shares, the
cut's arithmetic.  Its engines are tests/test_solar_open2_engine.py's."""

import functools
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

CASE = CASES["solar_open2"]

# float32 leaves 2e-6 on outputs of size 3 and 5e-6 on a state of size 7
# between two forms after 4,096 tokens (measured here; 1e-6 and 2e-6 against
# a float64 loop): with unit-norm keys a transition I - b k k^T has the
# eigenvalue 1 - b in (-1, 1) along k, so a rounding error flips sign and
# shrinks, it does not grow, and the decay shrinks every direction besides.
# The limit is ten times that.
NEG_TOL = 2e-5


@pytest.fixture(scope="module")
def near_two():
    """4,096 tokens of one sequence: unit-norm q and k, slow decays (a
    memory of hundreds of tokens), beta = 2 sigmoid(6 + n / 2) in
    (1.97, 2), and the literal recurrence over them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    T, H, d = 4096, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = kda.l2norm(jax.random.normal(ks[0], (1, T, H, d)), eps=0.0)
    k = kda.l2norm(jax.random.normal(ks[1], (1, T, H, d)), eps=0.0)
    v = jax.random.normal(ks[2], (1, T, H, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (1, T, H, d), minval=-7,
                                    maxval=-2))
    beta = 2 * jax.nn.sigmoid(6 + 0.5 * jax.random.normal(ks[4], (1, T, H)))
    assert 1.95 < float(beta.min()) and float(beta.max()) < 2
    o, S = jax.jit(kda.recurrent)(q, k, v, g, beta)
    assert float(jnp.abs(S).max()) > 3          # a state that holds something
    return (q, k, v, g, beta), o[0], S[0]


@pytest.mark.parametrize("form", ["chunkwise", "step_rows", "segment_rows",
                                  "segment_rows_kernel"])
def test_kda_forms_agree_at_beta_near_two_over_4096_tokens(near_two, form):
    """`allow_neg_eigval`: every form takes beta as data, and each agrees
    with the literal recurrence where beta is all but 2 — the chunkwise
    form's unit-lower solve with entries twice as large, the decode step a
    token at a time beside a paused row, and the mixed step's segments of
    uneven length continued from the slot's state — by the jnp form and by
    `kda_seg` interpreted (ten chunks a run, the ragged tenth masked)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    xs, want_o, want_S = near_two
    T, H, d = want_o.shape
    if form == "chunkwise":
        o, S = jax.jit(kda.chunkwise)(*xs)
        o, S = o[0], S[0]
    elif form == "step_rows":
        run = jnp.asarray([True, False])

        def body(state, x):
            o, state = kda.step_rows(state, None, run,
                                     *(jnp.stack([a, a]) for a in x))
            return state, o[0]

        state, o = jax.jit(lambda xs: jax.lax.scan(
            body, jnp.zeros((3, H, d, d)), xs))(tuple(a[0] for a in xs))
        S = state[0]
        assert not bool(state[1].any())         # the paused row's state
    else:
        state = jnp.full((3, H, d, d), 5.0)     # position 0 starts from zero
        seg = jax.jit(functools.partial(
            kda.segment_rows, use_kernel=form == "segment_rows_kernel"))
        P, outs, p = 600, [], 0
        while p < T:
            n = min(P - 7, T - p)
            slot = np.full(P, 2, np.int32)
            pos = np.zeros(P, np.int32)
            slot[:n], pos[:n] = 1, np.arange(p, p + n)
            rows = (jnp.pad(a[0, p:p + n], ((0, P - n),) +
                            ((0, 0),) * (a.ndim - 2)) for a in xs)
            o, state, _ = seg(state, jnp.asarray(slot), jnp.asarray(pos),
                              *rows)
            outs.append(o[:n])
            p += n
        o, S = jnp.concatenate(outs), state[1]
    assert float(jnp.abs(o - want_o).max()) < NEG_TOL
    assert float(jnp.abs(S - want_S).max()) < NEG_TOL


def test_step_kernel_interpreted_at_beta_near_two(near_two):
    """`kda_step` takes beta as data too: one step from the state 4,096
    tokens left, against the jnp step."""
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    xs, _, S = near_two
    state = jnp.stack([S, S * 0.5, jnp.zeros_like(S)])
    rows = [jnp.stack([a[0, 7], a[0, 9]]) for a in xs]
    live = jnp.asarray([True, True])
    o1, s1 = kda.step_rows(state, None, live, *rows)
    o2, s2 = kda.step_rows(state, None, live, *rows, use_kernel=True)
    assert float(jnp.abs(o1 - o2).max()) < 1e-5
    assert float(jnp.abs(s1 - s2).max()) < 1e-5


def test_the_layer_doubles_beta_only_where_it_is_asked(model):
    """`allow_neg_eigval` reaches the layer as an attr of the KDA layers
    alone, and the layer without it is Kimi's."""
    c, ex, _ = model
    kinds = {l.name: l for l in ex.model.layers}
    assert all(kinds[n].attrs["allow_neg_eigval"] for n in CASE.recurrent)
    plain = build(CASE, c, kda_allow_neg_eigval=0)
    assert not any("allow_neg_eigval" in l.attrs for l in plain.model.layers)
    assert kinds["blk0_attn"].attrs["out_gate"] == 4
    assert kinds["blk0_attn"].size == 64 and c["hidden_size"] == 32


def test_the_gate_over_the_dense_cache_against_the_whole_sequence(ref):
    """The fourth path: a stack whose every layer is gated GQA (no
    recurrent layer, so lm_decode's dense cache takes it) — prefill then
    one token at a time equals the whole sequence, which equals the
    reference; with the gate's matrix zeroed it does not."""
    import jax.numpy as jnp
    from paddle_tpu.graph.lm_decode import init_kv_caches
    c = cfg(CASE, num_hidden_layers=2, gqa_layers=[0, 1])
    ex, w = build(CASE, c), ref.make_weights(c, 5)
    assert [l.type for l in ex.model.layers if l.type.endswith("attention")] \
        == ["multi_head_attention"] * 2
    ids = np.random.default_rng(2).integers(0, 64, (2, 14))
    whole, _ = logits(ex, w, ids)
    assert float(np.abs(np.asarray(whole[0]) - ref_logits(
        ref, c, w, ids[0])).max()) < 5e-5
    lp, st = logits(ex, w, ids[:, :9], init_kv_caches(ex, 2, 14))
    assert float(jnp.abs(lp - whole[:, :9]).max()) < 2e-5
    for t in range(9, 14):
        lp, st = logits(ex, w, ids[:, t:t + 1], st)
        assert float(jnp.abs(lp[:, 0] - whole[:, t]).max()) < 5e-5
    off = dict(w, **{"_blk1_attn.w4": w["_blk1_attn.w4"] * 0})
    lp, _ = logits(ex, off, ids[:, 13:14], st)
    assert float(jnp.abs(lp[:, 0] - whole[:, 13]).max()) > 1e-2


def test_the_eight_ranks_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test at the published split: one expert layer of
    the PROGRAM as each of the 8 ranks holds it (5 of 40 experts each where
    the model has 40 of 320), the shared expert counted once, against the
    uncut REFERENCE layer (all 40 held) — layer 0, an expert layer here."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.graph.layers_misc import gated_ffn
    from paddle_tpu.parallel.moe import moe_ffn
    uncut = cfg(CASE, n_routed_experts=40, experts_held=40, ep_rank=0,
                num_experts_per_tok=8)
    w = ref.make_weights(uncut, 11)
    wl = {k[len("_blk0_"):]: v for k, v in w.items()
          if k.startswith("_blk0_")}
    x = jnp.asarray(np.random.default_rng(5).normal(size=(10, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref._moe(uncut, wl, x, None)
        total = gated_ffn(x, wl["moe.w5"], wl["moe.w6"], wl["moe.w7"])
        for rank in range(8):
            sl = slice(5 * rank, 5 * rank + 5)
            y, _, _ = moe_ffn(
                x, wl["moe.w0"],
                (wl["moe.w1"][sl], wl["moe.w2"][sl], wl["moe.w3"][sl]),
                top_k=8, first_expert=5 * rank, scoring="sigmoid", n_group=1,
                topk_group=1, select_bias=wl["moe.w4"].reshape(-1),
                scale=uncut["routed_scaling_factor"])
            total = total + y
    assert float(jnp.abs(total - want).max()) < 2e-5


def test_the_cut_keeps_the_published_widths_and_lists():
    with open(CASE.json_path) as f:
        c = json.load(f)
    la = c["linear_attn_config"]
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (4096, 64, 8, 128)
    assert (la["num_heads"], la["head_dim"], la["short_conv_kernel_size"],
            la["num_kv_heads"]) == (64, 128, 4, None)
    assert (c["moe_intermediate_size"], c["n_routed_experts"],
            c["num_experts_per_tok"], c["n_shared_experts"],
            c["routed_scaling_factor"], c["first_k_dense_replace"]) == \
        (1280, 320, 8, 1, 1, 0)
    assert c["use_rope"] is False and c["use_gqa_gate"] is True
    assert c["kda_allow_neg_eigval"] is True
    assert c["kda_use_full_proj"] is False and c["state_dtype"] == "float32"
    assert c["gqa_layers"] == list(range(0, 48, 4))
    dep = c["deployment"]
    assert c["experts_held"] * dep["chips_sharing_a_layer"] == \
        c["n_routed_experts"]
    assert c["ep_rank"] == dep["rank_held"]
    assert c["vocab_size"] * dep["chips_sharing_a_layer"] == \
        c["published"]["vocab_size"]
    assert c["num_hidden_layers"] * dep["pipeline_stages"] == \
        c["published"]["num_hidden_layers"]
    # the published group whole: the benchmark check refuses a key more
    assert sorted(la) == ["head_dim", "num_heads", "num_kv_heads",
                          "short_conv_kernel_size"]
    n = c["num_hidden_layers"]
    # the guide's floors: one whole period, 8 experts, 1/8 of the vocabulary
    assert n % 4 == 0 and c["experts_held"] >= 8
    assert c["server_flags"]["slots"] == 128
    assert c["server_flags"]["weights"] == "deferred"
