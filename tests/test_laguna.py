"""Laguna (window-512 GQA layers of 64 heads beside full layers of 48, each
kind with its own rotation; a sigmoid gate a head; a dense MLP then top-8 of
256 experts all held) against the plain reference
(benchmark/reference/laguna.py): the shared parity tests of
tests/model_parity.py over its case — the whole sequence with the controls
that must fail (the gate's matrix zeroed; the reference without the window,
with the whole head rotated in a full layer, with no gate), the decode step
and the ragged mixed step through the cache manager's pages under ONE
logical table (a cache built by hand: the window a mask of the read), the
configuration file against the catalog row and the DSL's defaults — and
what is this model's own: the same steps through the window layers' RINGS
of pages with contexts of several windows and rings, a chunk that crosses
the window's edge, the windowed kernel interpreted; each new attribute of
multi_head_attention_layer alone against its equation; the five layers held
as layers 0-4 of the uncut stack.  Its engines are
tests/test_laguna_engine.py's.

Tolerances: as tests/model_parity.py says — float32 under "highest" leaves
1e-5 to 2e-5 between two orders of the same sums at these sizes; the case's
1e-4 is five times that (five layers, a 16-expert sum a layer), and every
control moves the logits by more than fifty times it."""

import numpy as np
import pytest

from tests.model_parity import (  # noqa: F401
    AUTO, CASES, build, case, cfg, logits, model, pools_of,
    pytest_generate_tests, ref, ref_logits, state_of,
    test_configuration_file_is_the_catalog_row_cut_as_it_says,
    test_dsl_defaults_equal_the_configuration_file,
    test_layer_kinds_by_depth,
    test_ragged_chunks_then_decode_through_the_pools_on_logits,
    test_reference_imports_nothing_of_the_program,
    test_weights_fit_the_programs_parameters,
    test_whole_sequence_logits_against_the_reference)

CASE = CASES["laguna"]


# -- the rings ----------------------------------------------------------------------

def ring_cache(ex, S, step_tokens, pages=16):
    """A cache manager of S full slots whose window layers hold rings, and
    the two tables (the trash slot's row last)."""
    import jax.numpy as jnp
    from paddle_tpu.serving import PagedKVCache
    kv = PagedKVCache(ex, num_slots=S, page_size=4, pages_per_slot=pages,
                      step_tokens=step_tokens)
    for s in range(S):
        assert kv.try_grow(s, 4 * pages)
    table = jnp.asarray(np.vstack([kv.table,
                                   np.zeros((1, pages), np.int32)]))
    return kv, table


def ring_state(kv, pools, table, **kw):
    """`state_of` with each window layer's `ring_table` in the logical
    table's place, as serving/engine.py:_layer_state hands it."""
    import jax.numpy as jnp
    st = state_of(kv, pools, page_table=table, **kw)
    for name in kv.ring_specs:
        del st[name]["page_table"]
        st[name]["ring_table"] = jnp.asarray(
            kv.ring_table(name)[:table.shape[0]])
    return st


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
def test_chunks_then_decode_through_the_rings_on_logits(
        case, model, ref, kernel, monkeypatch):
    """A window of 8 tokens in rings of 5 pages of 4 (window + 7 rows a
    step, and a page): slot 1's 43-token prompt goes in chunks of up to 7
    rows — the first crosses the window's edge from inside it, the later
    ones start past it, and from the fourth on each lands on pages its
    slot's ring has already used — while slot 0 decodes beside it; then 12
    decode steps of both, to a context of 55 tokens = 6.9 windows = 2.75
    rings.  Every position's logits of both sequences against ONE full
    reference forward each, by the jnp gather and by the interpreted
    windowed kernel; the full layers read the logical table throughout."""
    import jax.numpy as jnp
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1" if kernel else "0")
    c, ex, w = model
    if kernel:
        ex = build(case, c, **AUTO)
    rng = np.random.default_rng(2)
    chunks = (7, 3, 7, 7, 5, 7, 7)
    S, P, D = 2, sum(chunks), 12
    seq0 = rng.integers(0, c["vocab_size"], 1 + len(chunks) + D)
    seq1 = rng.integers(0, c["vocab_size"], P + D)
    kv, table = ring_cache(ex, S, step_tokens=7)
    assert kv.ring_specs == {f"blk{i}_attn": 5 for i in (1, 2, 3)}
    pools = kv.pools
    got0 = np.zeros((len(seq0), c["vocab_size"]), np.float32)
    got1 = np.zeros((len(seq1), c["vocab_size"]), np.float32)
    T = S + max(chunks)

    def mixed(dec_rows, chunk_slot, chunk_pos):
        ids, slot, pos = np.zeros(T, int), np.full(T, S, int), np.zeros(T, int)
        for r, (s, (tok, p)) in enumerate(dec_rows.items()):
            ids[r], slot[r], pos[r] = tok, s, p
        n = len(chunk_pos)
        ids[S:S + n] = (seq1 if chunk_slot == 1 else seq0)[chunk_pos]
        slot[S:S + n], pos[S:S + n] = chunk_slot, chunk_pos
        st = ring_state(kv, pools, table,
                        row_slot=jnp.asarray(slot, jnp.int32),
                        row_pos=jnp.asarray(pos, jnp.int32))
        lp, out = logits(ex, w, ids[None], st)
        return np.asarray(lp[0]), pools_of(kv, pools, out)

    lp, pools = mixed({}, 0, np.arange(1))       # slot 0's first token
    got0[0] = lp[S]
    n0, c0 = 1, 0
    for n in chunks:
        lp, pools = mixed({0: (seq0[n0], n0)}, 1, np.arange(c0, c0 + n))
        got0[n0] = lp[0]
        got1[c0:c0 + n] = lp[S:S + n]
        n0, c0 = n0 + 1, c0 + n
    for t in range(D):
        pos = jnp.asarray([n0 + t, P + t], jnp.int32)
        st = ring_state(kv, pools, table[:S], pos=pos)
        lp, out = logits(ex, w, np.asarray([seq0[n0 + t],
                                            seq1[P + t]])[:, None], st)
        got0[n0 + t], got1[P + t] = np.asarray(lp[:, 0])
        pools = pools_of(kv, pools, out)
    assert P + D > 2 * 5 * 4                     # past two laps of a ring
    for got, seq in ((got0, seq0), (got1, seq1)):
        want = ref_logits(ref, c, w, seq)
        assert float(np.abs(got - want).max()) < case.ragged_tol
    kv.check()


def test_windowed_kernel_against_the_gather_at_the_cells_head_counts(
        monkeypatch):
    """`paged_attention(first=)` interpreted, 64 query heads over 8 K/V
    heads of 128 (the cell's window layers), rings of 7 pages of 16, a
    window of 40: decode rows deep into their rings, a chunk's rows, a row
    at position 0 and a padding row, against the jnp gather of the same
    pages — and against the window's own definition on the raw keys."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import ragged_paged_attention_step
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    S, ps, hkv, H, D, W, R = 3, 16, 8, 64, 128, 40, 7
    rng = np.random.default_rng(0)
    n = 150
    k = rng.standard_normal((S, n, hkv, D)).astype(np.float32)
    v = rng.standard_normal((S, n, hkv, D)).astype(np.float32)
    ring = np.zeros((S + 1, R), np.int32)
    ring[:S] = 1 + np.arange(S)[:, None] * R + np.arange(R)
    kp = jnp.zeros((1 + S * R, ps, hkv, D), jnp.float32)
    vp = jnp.zeros_like(kp)
    # fill the rings token by token up to each slot's start, then one step
    start = (131, 0, 77)
    for s in range(S):
        for lo in range(0, start[s], 32):
            hi = min(lo + 32, start[s])
            pos = jnp.arange(lo, hi)
            slot = jnp.full((hi - lo,), s, jnp.int32)
            _, kp, vp = ragged_paged_attention_step(
                jnp.zeros((hi - lo, H, D)), jnp.asarray(k[s, lo:hi]),
                jnp.asarray(v[s, lo:hi]), kp, vp, jnp.asarray(ring), slot,
                pos, window=W, use_kernel=False, ring=True)
    rows = [(0, 131), (1, 0)] + [(2, 77 + i) for i in range(9)] + [(S, 0)]
    slot = np.array([s for s, _ in rows], np.int32)
    pos = np.array([p for _, p in rows], np.int32)
    q = rng.standard_normal((len(rows), H, D)).astype(np.float32)
    live = slot < S
    kn = np.where(live[:, None, None], k[np.minimum(slot, S - 1), pos], 0)
    vn = np.where(live[:, None, None], v[np.minimum(slot, S - 1), pos], 0)
    outs = {}
    for use in (False, True):
        with jax.default_matmul_precision("highest"):
            outs[use], _, _ = ragged_paged_attention_step(
                jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn), kp, vp,
                jnp.asarray(ring), jnp.asarray(slot), jnp.asarray(pos),
                window=W, use_kernel=use, ring=True)
    got = np.asarray(outs[True])[live]
    assert float(np.abs(got - np.asarray(outs[False])[live]).max()) < 2e-5
    for r, (s, p) in enumerate(rows[:-1]):
        lo = max(0, p - W + 1)
        for h in (0, 9, 63):
            g = h // (H // hkv)
            sc = k[s, lo:p + 1, g] @ q[r, h] * D ** -0.5
            wt = np.exp(sc - sc.max())
            want = (wt / wt.sum()) @ v[s, lo:p + 1, g]
            assert float(np.abs(got[r, h] - want).max()) < 2e-5


# -- each new attribute alone -------------------------------------------------------

def test_rotary_dim_rotates_the_first_columns_alone():
    """rope(rotary_dim=r): columns [0, r) are the rotation of a head of r
    columns (pair c with c + r/2), columns [r, D) pass through."""
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import rope
    x = np.random.default_rng(0).standard_normal((2, 9, 3, 16)) \
        .astype(np.float32)
    pos = jnp.arange(9) + 5
    got = np.asarray(rope(jnp.asarray(x), pos, 500000.0, rotary_dim=8))
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    p = np.arange(4)
    ang = (np.arange(9) + 5)[:, None] * 500000.0 ** (-2.0 * p / 8)
    a, b = x[..., :4], x[..., 4:8]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    want = np.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    assert float(np.abs(got[..., :8] - want).max()) < 1e-5
    whole = np.asarray(rope(jnp.asarray(x), pos, 500000.0))
    assert float(np.abs(whole[..., 8:] - x[..., 8:]).max()) > 0.1


def test_yarn_frequencies_and_the_attention_factor(ref):
    """rope(rope_scaling=, attention_factor=) at the published full layers'
    numbers: the frequencies are the reference's own reading of
    `_compute_yarn_parameters` (kept above the fast correction pair,
    divided by 64 under the slow one, a ramp between), and cos and sin of
    the rotated columns — those alone — carry the factor."""
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import rope
    from paddle_tpu.ops.mla import yarn_inv_freq
    rp = dict(LAGUNA["rope_parameters"]["full_attention"])
    scaling = {k: rp[k] for k in ("factor", "beta_fast", "beta_slow",
                                  "original_max_position_embeddings")}
    f = np.asarray(ref.rotary_frequencies(64, rp))
    np.testing.assert_allclose(yarn_inv_freq(64, 500000.0, scaling), f,
                               rtol=1e-6)
    # pair 5.66 turns 64 times in 4,096 positions, pair 15.8 once: kept to
    # pair 5, divided by 64 from pair 16, a ramp between
    plain = 500000.0 ** (-2.0 * np.arange(32) / 64)
    assert np.allclose(f[:6], plain[:6]) and \
        np.allclose(f[16:], plain[16:] / 64)
    assert (f[6:16] < plain[6:16]).all() and \
        (f[6:16] > plain[6:16] / 64).all()
    x = np.random.default_rng(1).standard_normal((1, 6, 2, 128)) \
        .astype(np.float32)
    pos = jnp.asarray([0, 1, 511, 4095, 4096, 8000])
    got = np.asarray(rope(jnp.asarray(x), pos, 500000.0, rotary_dim=64,
                          rope_scaling=scaling,
                          attention_factor=rp["attention_factor"]))
    bare = np.asarray(rope(jnp.asarray(x), pos, 500000.0, rotary_dim=64,
                           rope_scaling=scaling))
    np.testing.assert_allclose(got[..., :64],
                               rp["attention_factor"] * bare[..., :64],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    # the rotation itself, from the frequencies, at a position past 4,096
    ang = 8000 * f
    want = x[0, 5, 0, :32] * np.cos(ang) - x[0, 5, 0, 32:64] * np.sin(ang)
    assert float(np.abs(bare[0, 5, 0, :32] - want).max()) < 2e-4


def test_out_gate_head_is_one_value_a_head():
    """project_out with w_g [d, H]: y = concat_h(a_h * sigmoid(x w_g)_h) w_o,
    where the elementwise gate's matrix is [d, H D]."""
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import project_out
    rng = np.random.default_rng(0)
    T, d, H, D = 5, 12, 3, 4
    o = rng.standard_normal((T, H, D)).astype(np.float32)
    x = rng.standard_normal((T, d)).astype(np.float32)
    w_o = rng.standard_normal((H * D, d)).astype(np.float32)
    w_g = rng.standard_normal((d, H)).astype(np.float32)
    got = np.asarray(project_out(jnp.asarray(o), jnp.asarray(x),
                                 jnp.asarray(w_o), None, jnp.asarray(w_g)))
    g = 1.0 / (1.0 + np.exp(-(x @ w_g)))
    want = (o * g[:, :, None]).reshape(T, H * D) @ w_o
    assert float(np.abs(got - want).max()) < 1e-5
    wide = np.repeat(w_g, D, axis=1)              # the same gate a column
    same = np.asarray(project_out(jnp.asarray(o), jnp.asarray(x),
                                  jnp.asarray(w_o), None, jnp.asarray(wide)))
    assert float(np.abs(same - want).max()) < 1e-5


def test_the_dsl_refuses_the_new_attributes_where_they_mean_nothing():
    from paddle_tpu.config.parser import parse_config_callable
    from paddle_tpu.dsl import data_layer, multi_head_attention_layer

    def net(**kw):
        x = data_layer(name="x", size=8)
        multi_head_attention_layer(x, size=8, num_heads=2, causal=True, **kw)

    for bad in (dict(rotary_dim=2), dict(rope_scaling={"factor": 2.0}),
                dict(attention_factor=1.2),
                dict(use_rope=True, rotary_dim=3),
                dict(use_rope=True, rotary_dim=8),
                dict(out_gate="column")):
        with pytest.raises(AssertionError):
            parse_config_callable(net, **bad)
    parse_config_callable(net, use_rope=True, rotary_dim=2, out_gate="head")


# -- the cut ------------------------------------------------------------------------

LAGUNA = None


def _published():
    global LAGUNA
    if LAGUNA is None:
        import json
        with open(CASE.json_path) as f:
            LAGUNA = json.load(f)
    return LAGUNA


@pytest.fixture(autouse=True)
def _load_published():
    _published()


def test_the_stage_is_layers_0_to_4_of_the_uncut_stack(case, ref):
    """No expert is absent, so no sum of parts applies; instead: the five
    layers with the published lists cut to depth 5 ARE layers 0-4 of the
    reference at depth 8 — the same kinds, head counts and weights by name,
    and the hidden state after layer 4 of the deeper stack (its final norm
    aside) is the stage's."""
    import jax
    import jax.numpy as jnp
    c5 = cfg(case)
    c8 = cfg(case, num_hidden_layers=8)
    w8 = ref.make_weights(c8, 11)
    w5 = {k: v for k, v in w8.items() if k in ref.param_shapes(c5)}
    assert {k: v[0] for k, v in ref.param_shapes(c5).items()} == \
        {k: tuple(v.shape) for k, v in w5.items()}
    kinds = [(ref.is_window(c8, i), ref.heads_of(c8, i), ref.is_sparse(c8, i))
             for i in range(8)]
    assert kinds[:5] == [(False, 6, False), (True, 8, True), (True, 8, True),
                         (True, 8, True), (False, 6, True)]
    assert kinds[5:] == [(True, 8, True)] * 3
    seq = jnp.asarray(np.random.default_rng(5).integers(0, 64, 24))
    with jax.default_matmul_precision("highest"):
        h5 = ref.hidden_states(w5, c5, seq)
        # depth 8 stopped after layer 4: the same function over the same
        # weights with the deeper configuration's lists
        h8 = ref.hidden_states(
            dict(w5, **{"_final_ln.w0": w8["_final_ln.w0"]}),
            dict(c8, num_hidden_layers=5), seq)
    np.testing.assert_array_equal(np.asarray(h5), np.asarray(h8))
    # and the published file: layers 0-4 of its lists
    pub = _published()
    assert pub["layer_types"][:5] == ["full_attention"] + \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert pub["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert pub["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert len(pub["layer_types"]) == pub["published"]["num_hidden_layers"]
