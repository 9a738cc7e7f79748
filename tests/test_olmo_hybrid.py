"""Olmo-Hybrid (Gated DeltaNet layers — the delta rule with ONE decay a head
and a rectangular state — beside full attention on as many KV heads as query
heads with a QK-norm over the whole projection, dense SwiGLU, the Olmo
block's norms on the sublayers' outputs) against the plain reference
(benchmark/reference/olmo_hybrid.py): the shared parity tests of
tests/model_parity.py over its case — the whole sequence with the controls
that must fail (the gate's matrix zeroed; the reference with beta in (0, 1),
with no decay, with the pre-norm block, with no QK-norm), the decode step
and the ragged mixed step through the cache manager's slot state beside the
K/V pages (and a state rounded to bfloat16 told apart), the slot parts,
paused slots, re-admission, the configuration file — and what is this
model's own: the four forms of the rule and both kernels at a decay a head,
dk != dv and beta near 2; the layer's attrs; the whole-projection QK-norm;
the cut's arithmetic.  Its engines are tests/test_olmo_hybrid_engine.py's."""

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

CASE = CASES["olmo_hybrid"]

# float32 leaves 3e-6 on a state of size 6 between two forms after 2,048
# tokens (measured here), as tests/test_solar_open2.py's square case does:
# with unit-norm keys a transition's eigenvalue along k is 1 - b in (-1, 1),
# so a rounding error shrinks.  The limit is ten times that.
NEG_TOL = 3e-5
H, DK, DV = 3, 8, 16            # a head count no power of two, dk != dv


@pytest.fixture(scope="module")
def near_two():
    """2,048 tokens of one sequence: unit-norm q and k [.., 8], v [.., 16],
    ONE slow decay a head (a memory of hundreds of tokens), beta =
    2 sigmoid(6 + n / 2) in (1.97, 2), and the literal recurrence over
    them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    T = 2048
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = kda.l2norm(jax.random.normal(ks[0], (1, T, H, DK)), eps=0.0)
    k = kda.l2norm(jax.random.normal(ks[1], (1, T, H, DK)), eps=0.0)
    v = jax.random.normal(ks[2], (1, T, H, DV))
    g = -jnp.exp(jax.random.uniform(ks[3], (1, T, H), minval=-7, maxval=-2))
    beta = 2 * jax.nn.sigmoid(6 + 0.5 * jax.random.normal(ks[4], (1, T, H)))
    assert 1.95 < float(beta.min()) and float(beta.max()) < 2
    o, S = jax.jit(kda.recurrent)(q, k, v, g, beta)
    assert S.shape == (1, H, DK, DV) and float(jnp.abs(S).max()) > 3
    return (q, k, v, g, beta), o[0], S[0]


@pytest.mark.parametrize("form", ["chunkwise", "step_rows", "step_rows_kernel",
                                  "segment_rows", "segment_rows_kernel"])
def test_the_rules_forms_agree_at_a_decay_a_head(near_two, form):
    """A decay a head is read from `g`'s rank by every form: each agrees
    with the literal recurrence at dk != dv, three heads and beta all but 2
    — the chunkwise form whose pairwise decays are one [64, 64] matrix, the
    decode step a token at a time beside a paused row (jnp and `gdn_step`
    interpreted, the kernel over the last 64 tokens), and the mixed step's
    segments of uneven length continued from the slot's state (jnp and
    `gdn_seg` interpreted: ten chunks a run, the ragged tenth masked)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    xs, want_o, want_S = near_two
    T = want_o.shape[0]
    if form == "chunkwise":
        o, S = jax.jit(kda.chunkwise)(*xs)
        o, S = o[0], S[0]
    elif form.startswith("step_rows"):
        kernel = form.endswith("kernel")
        # the kernel interpreted is slow: it takes the last 64 tokens on
        # from the state the recurrence held there
        t0 = T - 64 if kernel else 0
        S0 = jax.jit(kda.recurrent)(*(a[:, :t0] for a in xs))[1][0] \
            if t0 else jnp.zeros((H, DK, DV))
        run = jnp.asarray([True, False])

        def body(state, x):
            o, state = kda.step_rows(state, None, run,
                                     *(jnp.stack([a, a]) for a in x),
                                     use_kernel=kernel)
            return state, o[0]

        state, o = jax.jit(lambda st, xs: jax.lax.scan(body, st, xs))(
            jnp.stack([S0, jnp.zeros_like(S0), jnp.zeros_like(S0)]),
            tuple(a[0, t0:] for a in xs))
        S, want_o = state[0], want_o[t0:]
        assert not bool(state[1].any())         # the paused row's state
    else:
        state = jnp.full((3, H, DK, DV), 5.0)   # position 0 starts from zero
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
        assert bool((state[0] == 5.0).all())    # an untouched slot's state
    assert float(jnp.abs(o - want_o).max()) < NEG_TOL
    assert float(jnp.abs(S - want_S).max()) < NEG_TOL


def test_a_decay_a_head_is_the_channel_rule_with_one_number_repeated():
    """The two published rules are one: a decay a head equals the decay a
    channel with the head's number in every channel, in the recurrence, the
    chunkwise form and both kernels (`gdn_*` interpreted against the
    channel rule's jnp forms)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    T, d = 20, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = kda.l2norm(jax.random.normal(ks[0], (T, H, d)))
    k = kda.l2norm(jax.random.normal(ks[1], (T, H, d)))
    v = jax.random.normal(ks[2], (T, H, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (T, H), minval=-5, maxval=0))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    wide = jnp.broadcast_to(g[..., None], q.shape)
    for form in (kda.recurrent, kda.chunkwise):
        a, b = (jax.jit(form)(q[None], k[None], v[None], x[None], beta[None])
                for x in (g, wide))
        assert float(jnp.abs(a[0] - b[0]).max()) < 2e-6
        assert float(jnp.abs(a[1] - b[1]).max()) < 2e-6
    state = jax.random.normal(ks[0], (3, H, d, d))
    slot = jnp.asarray([1] * T, jnp.int32)
    pos = jnp.arange(5, 5 + T, dtype=jnp.int32)
    # `gdn_seg` and `gdn_step` interpreted against the channel rule's jnp
    # forms (the channel kernels' own parity is tests/test_kimi_linear.py's)
    seg, step = (lambda x: jax.jit(functools.partial(
        f, use_kernel=x.ndim == 2)) for f in (kda.segment_rows,
                                              kda.step_rows))
    outs = [seg(x)(state, slot, pos, q, k, v, x, beta) for x in (g, wide)]
    assert float(jnp.abs(outs[0][0] - outs[1][0]).max()) < 5e-6
    assert float(jnp.abs(outs[0][1] - outs[1][1]).max()) < 5e-6
    live = jnp.asarray([True, True])
    steps = [step(x)(state, None, live, q[:2], k[:2], v[:2], x[:2], beta[:2])
             for x in (g, wide)]
    assert float(jnp.abs(steps[0][0] - steps[1][0]).max()) < 1e-6
    assert float(jnp.abs(steps[0][1] - steps[1][1]).max()) < 1e-6


def test_the_kernels_carry_the_rules_names():
    """A trace tells the two rules apart: the calls are named by the
    decay's kind."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    q = jnp.zeros((4, H, DK))
    v = jnp.zeros((4, H, DV))
    state = jnp.zeros((3, H, DK, DV))
    idx = jnp.zeros((4,), jnp.int32)
    for g, names in ((jnp.zeros((4, H)), ("gdn_step", "gdn_seg")),
                     (jnp.zeros((4, H, DK)), ("kda_step", "kda_seg"))):
        vv = v if g.ndim == 2 else q
        st = state if g.ndim == 2 else jnp.zeros((3, H, DK, DK))
        step = jax.make_jaxpr(functools.partial(
            kda.step_rows, use_kernel=True))(
                st, idx, idx > 0, q, q, vv, g, jnp.zeros((4, H)))
        seg = jax.make_jaxpr(functools.partial(
            kda.segment_rows, use_kernel=True))(
                st, idx, idx, q, q, vv, g, jnp.zeros((4, H)))
        assert names[0] in str(step) and names[1] in str(seg)


def test_the_layer_reads_the_rule_from_its_attrs(model):
    """`decay`, `value_dim`, `full_proj` and `gate_act` reach the layer as
    attrs of the linear layers alone, 13 parameters where Kimi's has 15;
    the full layer's QK-norm scales are as wide as the whole projections;
    and `kda_slot_parts` declares what the docstring says: a state
    [H, dk, dv] and a tail of H (2 dk + dv) channels."""
    import jax.numpy as jnp
    from paddle_tpu.graph.layers_kda import kda_slot_parts
    c, ex, w = model
    kinds = {l.name: l for l in ex.model.layers}
    for n in CASE.recurrent:
        a = kinds[n].attrs
        assert (a["decay"], a["value_dim"], a["full_proj"], a["gate_act"],
                a["allow_neg_eigval"]) == ("head", 16, True, "silu", True)
        assert len(kinds[n].inputs) == 13
        parts = kda_slot_parts(kinds[n], jnp.bfloat16)
        assert parts["state"] == ((6, 8, 16), jnp.float32)
        assert parts["conv"] == ((3, 6 * (2 * 8 + 16)), jnp.bfloat16)
    attn = kinds["blk3_attn"]
    assert attn.attrs["qk_norm"] == "whole" and "use_rope" not in attn.attrs
    assert w["_blk3_attn.w4"].shape == w["_blk3_attn.w5"].shape == (1, 48)
    # the Olmo block: the norm's input is the sublayer, not the stream
    assert kinds["blk0_ln1"].inputs[0].input_layer_name == "blk0_gdn"
    assert kinds["blk0_ln2"].inputs[0].input_layer_name == "blk0_ffn"
    assert kinds["blk0_ffn"].inputs[0].input_layer_name == "blk0_res1"


def test_a_kda_layer_with_its_own_value_dim_declares_a_rectangular_state():
    """The satellite's case: a KDA layer (a decay a CHANNEL, low-rank
    projections) with dv != dk — its slot parts are [H, dk, dv] and
    H (2 dk + dv) channels, and the whole sequence equals the decode step
    through them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.config.schema import LayerConfig
    from paddle_tpu.graph.layers_kda import kda_slot_parts
    cfg_ = LayerConfig(name="x", type="kda_attention", size=8, active_type="")
    cfg_.attrs.update(num_heads=2, head_dim=4, value_dim=12, conv_size=4)
    parts = kda_slot_parts(cfg_, jnp.float32)
    assert parts == {"state": ((2, 4, 12), jnp.float32),
                     "conv": ((3, 2 * (2 * 4 + 12)), jnp.float32)}
    cfg_.attrs.pop("value_dim")
    assert kda_slot_parts(cfg_, jnp.float32)["state"][0] == (2, 4, 4)
    assert kda_slot_parts(cfg_, jnp.float32)["conv"][0] == (3, 24)
    # and the channel rule's forms agree at such a state
    from paddle_tpu.ops import kda
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q, k = (kda.l2norm(jax.random.normal(kk, (1, 90, 2, 4))) for kk in ks[:2])
    v = jax.random.normal(ks[2], (1, 90, 2, 12))
    g = -jnp.exp(jax.random.uniform(ks[3], (1, 90, 2, 4), minval=-5,
                                    maxval=0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, 90, 2)))
    (o1, s1), (o2, s2) = kda.recurrent(q, k, v, g, beta), \
        kda.chunkwise(q, k, v, g, beta)
    assert s1.shape == (1, 2, 4, 12)
    assert float(jnp.abs(o1 - o2).max()) < 2e-6
    assert float(jnp.abs(s1 - s2).max()) < 2e-6


def test_the_whole_projection_qk_norm_against_a_norm_a_head():
    """`qk_norm="whole"` norms q over all its heads at once: with every
    head's mean square equal the two forms agree, with one head ten times
    larger they do not — and the whole form is the reference's."""
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import project_qkv
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 5, 12)), jnp.float32)
    wq = jnp.asarray(rng.normal(size=(12, 12)), jnp.float32)
    wq = wq.at[:, :4].multiply(10.0)                   # head 0 ten times
    pos = jnp.arange(5)[None]
    whole = (jnp.ones((1, 12)), jnp.ones((1, 12)), 1e-6)
    head = (jnp.ones((1, 4)), jnp.ones((1, 4)), 1e-6)
    qw, kw, _ = project_qkv(x, x, x, wq, wq, wq, 3, 3, pos, pos,
                            qk_norm=whole)
    qh, _, _ = project_qkv(x, x, x, wq, wq, wq, 3, 3, pos, pos, qk_norm=head)
    y = x @ wq
    want = y * jnp.sqrt(1.0 / (jnp.mean(y * y, -1, keepdims=True) + 1e-6))
    assert float(jnp.abs(qw.reshape(1, 5, 12) - want).max()) < 1e-5
    assert float(jnp.abs(qw - kw).max()) == 0.0
    assert float(jnp.abs(qw - qh).max()) > 0.5


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_more_than_8_kv_heads_are_paged_in_whole_tiles_of_8(use_kernel,
                                                            monkeypatch):
    """12 query heads on 12 KV heads of 128 — group size 1, as the model's
    30 on 30 — are stored 16 heads a token (`kv_row_shape`: the chip holds
    them so in any case, and copies whole tiles only): a mixed step's chunk
    rows and a decode row through the pages, by the jnp gather and by
    `paged_attn` interpreted, equal the dense causal softmax, and the
    padding heads stay zeros."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import ragged_paged_attention_step
    from paddle_tpu.ops.pallas_paged import kv_row_shape
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    H, D, ps = 12, 128, 4
    assert kv_row_shape(H, D) == (16, D) and kv_row_shape(30, 128) == (32, 128)
    assert kv_row_shape(8, D) == (8, D) and kv_row_shape(6, 8) == (6, 8)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    T = 11
    q, k, v = (jax.random.normal(kk, (T, H, D), jnp.float32) for kk in ks)
    pools = [jnp.zeros((9, ps) + kv_row_shape(H, D), jnp.float32)] * 2
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [0, 0, 0, 0]], jnp.int32)
    # slot 1: rows 0-8 as one chunk, then rows 9, 10 a decode row each
    kp, vp = pools
    outs = []
    for rows in (range(9), [9], [10]):
        idx = jnp.asarray(list(rows))
        o, kp, vp = ragged_paged_attention_step(
            q[idx], k[idx], v[idx], kp, vp, table,
            jnp.ones((len(idx),), jnp.int32), idx.astype(jnp.int32),
            use_kernel=use_kernel)
        outs.append(o)
    got = jnp.concatenate(outs)
    s = jnp.einsum("thd,jhd->htj", q, k) * D ** -0.5
    s = jnp.where(jnp.arange(T)[None, :, None] >= jnp.arange(T)[None, None],
                  s, -jnp.inf)
    want = jnp.einsum("htj,jhd->thd", jax.nn.softmax(s, axis=-1), v)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert kp.shape[2:] == (16, D) and not bool(kp[:, :, H:].any())
    assert bool(kp[5:8, :, :H].any())


def test_the_cut_keeps_the_published_widths_and_lists():
    with open(CASE.json_path) as f:
        c = json.load(f)
    assert (c["hidden_size"], c["intermediate_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["vocab_size"]) == \
        (3840, 11008, 30, 30, 100352)
    assert (c["linear_num_key_heads"], c["linear_num_value_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"],
            c["linear_conv_kernel_dim"], c["linear_allow_neg_eigval"]) == \
        (30, 30, 96, 192, 4, True)
    assert c["rope_parameters"] == {"rope_theta": None}
    assert c["use_rope"] is False and c["norm_after_sublayer"] is True
    assert c["use_qk_norm"] is True and c["qk_norm_whole"] is True
    assert c["state_dtype"] == "float32"
    assert len(c["layer_types"]) == 32 and c["layer_types"] == \
        (["linear_attention"] * 3 + ["full_attention"]) * 8
    dep = c["deployment"]
    assert c["num_hidden_layers"] * dep["pipeline_stages"] == \
        c["published"]["num_hidden_layers"]
    assert dep["stage_held"] == 0 and dep["chips_sharing_a_layer"] == 1
    # the guide's floors: whole periods; no width, head or vocabulary cut
    assert c["num_hidden_layers"] % 4 == 0
    f = c["server_flags"]
    assert (f["slots"], f["page_size"], f["max_context"], f["decode_steps"],
            f["spec_k"], f["weights"]) == (24, 16, 9216, 1, 0, "deferred")
    for reading in ("norm_after_sublayer", "use_qk_norm, qk_norm_whole",
                    "use_rope"):
        assert "eighed against" in c["assumed"][reading], reading
