"""Jamba (Mamba-1 selective-scan layers with the three inner RMSNorms +
multi-query attention without rotation + dense SwiGLU MLPs) through the
normal path at a tiny size on the CPU, seeded weights, float32: the program
(config DSL -> GraphExecutor -> ServingEngine) against the plain reference
(benchmark/reference/jamba.py) and against itself across its paths — the
whole sequence, the decode step and the ragged mixed step through the cache
manager's slot parts, the scan kernel interpreted — plus what a fourth kind
of slot state forced: the parts declared by the layer type, the float32
[N, d_in] state pool, paused slots, re-admission, the token counters, the
refusals, and a start-up that holds one weight set (`--weights deferred`).

The tolerances: float32 under `jax.default_matmul_precision("highest")`
leaves 1e-5 to 2e-5 between two orders of the same sums at these sizes
(init_std 0.3, so the logits spread over several nats); 2e-4 on
log-probabilities is ten times that and far under what a reference without
the inner norms, a dropped `D x` term or a state kept in bfloat16 move them
by — all three are tried below and must fail."""

import json
import os

import numpy as np
import pytest

from tests.test_lfm2_moe import (_logits, _pools_of, _slot_cache,  # noqa: F401
                                 _state_of)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON = os.path.join(ROOT, "benchmark", "configs", "jamba2-3b-serve.json")
DSL = os.path.join(ROOT, "benchmark", "configs", "jamba.py")
TOL = 2e-4

# the published ratios at a tiny size: d_in = 2 x 64 = 128 channels of 16
# state elements, a time-step rank of 8; 4 query heads over ONE KV head of
# 16; 5 layers of which layer 1 is attention (period 4, offset 1)
TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
            num_key_value_heads=1, head_dim=16, num_hidden_layers=5,
            attn_layer_period=4, attn_layer_offset=1, vocab_size=64,
            mamba_dt_rank=8, param_dtype="float32", init_std=0.3)
MAMBAS = ["blk0_mamba", "blk2_mamba", "blk3_mamba", "blk4_mamba"]
DSL_KEYS = ("head_dim", "attn_layer_period", "attn_layer_offset",
            "mamba_expand", "mamba_d_state", "mamba_dt_rank", "mamba_d_conv",
            "rms_norm_eps")


def _cfg(**over):
    with open(JSON) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg.update(over)
    return cfg


def _args(cfg: dict, attn_impl: str = "dense", **extra):
    return (f"vocab={cfg['vocab_size']},dim={cfg['hidden_size']},"
            f"layers={cfg['num_hidden_layers']},"
            f"heads={cfg['num_attention_heads']},"
            f"kv_heads={cfg['num_key_value_heads']},"
            f"ffn={cfg['intermediate_size']},"
            f"rope_theta={cfg['rope_theta']},compute_dtype=,"
            f"attn_impl={attn_impl},init_std={cfg['init_std']},"
            f"attn_use_rope={int(cfg['attn_use_rope'])},"
            + ",".join(f"{k}={cfg[k]}" for k in DSL_KEYS)
            + "".join(f",{k}={v}" for k, v in extra.items()))


def _parse(args):
    from paddle_tpu.config.parser import parse_config
    cwd = os.getcwd()
    os.chdir(ROOT)
    try:
        return parse_config(DSL, args)
    finally:
        os.chdir(cwd)


def _build(cfg, compute_dtype="", **extra):
    from paddle_tpu.graph import GraphExecutor
    args = _args(cfg, **extra).replace("compute_dtype=,",
                                       f"compute_dtype={compute_dtype},")
    return GraphExecutor(_parse(args).model_config,
                         compute_dtype=compute_dtype)


@pytest.fixture(scope="module")
def ref():
    from benchmark.lib.spec import Benchmark
    return Benchmark(ROOT).reference("jamba")


@pytest.fixture(scope="module")
def model(ref):
    cfg = _cfg()
    return cfg, _build(cfg), ref.make_weights(cfg, 7)


def _ref_logits(ref, cfg, w, seq):
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.jitted("log_probs", cfg)(
            w, jnp.asarray(seq), jnp.arange(len(seq))))


# -- the reference and the whole sequence -----------------------------------------

def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference", "jamba.py")) as f:
        src = f.read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]


def test_weights_fit_the_programs_parameters(model):
    import jax
    cfg, ex, w = model
    shapes = jax.eval_shape(ex.init_params, jax.random.PRNGKey(0))
    assert {k: (v.shape, str(v.dtype)) for k, v in shapes.items()} == \
        {k: (v.shape, str(v.dtype)) for k, v in w.items()}
    # every block is a mixer AND an MLP, each behind its norm; layer 1 is
    # the attention layer, the others Mamba
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
    # the family's initializers
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


def test_whole_sequence_logits_against_the_reference(model, ref):
    cfg, ex, w = model
    seq = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    got, _ = _logits(ex, w, seq[None])
    want = _ref_logits(ref, cfg, w, seq)
    assert float(np.abs(np.asarray(got[0]) - want).max()) < TOL
    # the tolerance separates what it must: no `D x` term ...
    w0 = dict(w, **{"_blk0_mamba.w10": w["_blk0_mamba.w10"] * 0})
    off, _ = _logits(ex, w0, seq[None])
    assert float(np.abs(np.asarray(off[0]) - want).max()) > 50 * TOL
    # ... and a reference WITHOUT Jamba's three inner norms
    bare = _ref_logits(ref, dict(cfg, inner_norms=False), w, seq)
    assert float(np.abs(np.asarray(got[0]) - bare).max()) > 50 * TOL


def test_attention_is_multi_query_without_rotation(model, ref):
    cfg, ex, w = model
    attn = next(l for l in ex.model.layers if l.name == "blk1_attn")
    assert "use_rope" not in attn.attrs and attn.attrs["num_kv_heads"] == 1
    rot = _cfg(attn_use_rope=True)
    ex2 = _build(rot)
    seq = np.random.default_rng(1).integers(0, 64, 16)
    got, _ = _logits(ex2, w, seq[None])
    assert float(np.abs(np.asarray(got[0]) -
                        _ref_logits(ref, rot, w, seq)).max()) < TOL
    assert float(np.abs(np.asarray(got[0]) -
                        _ref_logits(ref, cfg, w, seq)).max()) > 50 * TOL


# -- the three paths and the slot parts --------------------------------------------

@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
def test_ragged_chunks_then_decode_through_the_slot_parts_on_logits(
        model, ref, kernel, monkeypatch):
    """Slot 1's 23-token prompt in mixed steps whose chunk rows split it at
    uneven places — 1, 2, 4, 7 and 9 rows: inside a 4-tap window, inside and
    across the kernel's passes of 8 tokens; segments that start at 0 and
    that continue from the slot's state — while slot 0 decodes beside it in
    the steps' decode rows, then 6 decode steps of both: every position's
    logits of both sequences against ONE full reference forward each, by
    the jnp forms and by the interpreted kernel."""
    import jax.numpy as jnp
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1" if kernel else "0")
    cfg, ex, w = model
    if kernel:
        ex = _build(cfg, attn_impl="auto")
    rng = np.random.default_rng(1)
    S, P = 2, 23
    seq0 = rng.integers(0, cfg["vocab_size"], 16)
    seq1 = rng.integers(0, cfg["vocab_size"], P + 6)
    kv, table = _slot_cache(ex, S)
    pools = kv.pools
    got0 = np.zeros((len(seq0), cfg["vocab_size"]), np.float32)
    got1 = np.zeros((len(seq1), cfg["vocab_size"]), np.float32)
    T = S + 9

    def mixed(dec_rows, chunk_slot, chunk_pos):
        """dec_rows: {slot: (token, pos)}; the chunk rows from row S on"""
        ids = np.zeros(T, int)
        slot = np.full(T, S, int)
        pos = np.zeros(T, int)
        for r, (s, (tok, p)) in enumerate(dec_rows.items()):
            ids[r], slot[r], pos[r] = tok, s, p
        n = len(chunk_pos)
        src = seq1 if chunk_slot == 1 else seq0
        ids[S:S + n] = src[chunk_pos]
        slot[S:S + n], pos[S:S + n] = chunk_slot, chunk_pos
        st = _state_of(kv, pools, page_table=table,
                       row_slot=jnp.asarray(slot, jnp.int32),
                       row_pos=jnp.asarray(pos, jnp.int32))
        lp, out = _logits(ex, w, ids[None], st)
        return np.asarray(lp[0]), _pools_of(kv, pools, out), out

    lp, pools, _ = mixed({}, 0, np.arange(1))    # slot 0's first token
    got0[0] = lp[S]
    n0, c0 = 1, 0
    for n in (1, 2, 4, 7, 9):
        lp, pools, out = mixed({0: (seq0[n0], n0)}, 1, np.arange(c0, c0 + n))
        got0[n0] = lp[0]
        got1[c0:c0 + n] = lp[S:S + n]
        n0, c0 = n0 + 1, c0 + n
        # one decode row and one segment: two states moved a Mamba layer,
        # 1 + n rows through it
        assert [int(out[c]["updates"]) for c in MAMBAS] == [2] * 4
        assert int(out[MAMBAS[0]]["rows"]) == 1 + n
    assert c0 == P
    pos = jnp.asarray([n0, P], jnp.int32)
    run = jnp.ones((S,), bool)
    for t in range(6):
        st = _state_of(kv, pools, page_table=table[:S], pos=pos, run=run)
        lp, out = _logits(ex, w, np.asarray([[seq0[n0 + t]], [seq1[P + t]]]),
                          st)
        got0[n0 + t], got1[P + t] = np.asarray(lp[0, 0]), np.asarray(lp[1, 0])
        assert int(out[MAMBAS[0]]["rows"]) == 2
        pools = _pools_of(kv, pools, out)
        pos = pos + 1
    want1 = _ref_logits(ref, cfg, w, seq1)
    assert float(np.abs(got0[:n0 + 6] - _ref_logits(
        ref, cfg, w, seq0[:n0 + 6])).max()) < TOL
    assert float(np.abs(got1 - want1).max()) < TOL
    # what the tolerance must separate: the same decode steps from a state
    # rounded to bfloat16 once
    rounded = {n: (dict(p, state=p["state"].astype(jnp.bfloat16).astype(
        jnp.float32)) if n in MAMBAS else p) for n, p in pools.items()}
    st = _state_of(kv, rounded, page_table=table[:S], pos=pos, run=run)
    nxt = rng.integers(0, cfg["vocab_size"], 2)
    lp_r, _ = _logits(ex, w, nxt[:, None], st)
    st = _state_of(kv, pools, page_table=table[:S], pos=pos, run=run)
    lp_e, _ = _logits(ex, w, nxt[:, None], st)
    assert float(np.abs(np.asarray(lp_r) - np.asarray(lp_e)).max()) > 5 * TOL


def test_a_paused_slots_state_and_tail_are_bit_equal_after_the_step(model):
    """The run mask reaches the Mamba layers: a row whose mask is false
    leaves its state and its tail exactly as they were."""
    import jax
    import jax.numpy as jnp
    cfg, ex, w = model
    S = 3
    kv, table = _slot_cache(ex, S)
    key = jax.random.PRNGKey(0)
    pools = {n: ({part: jax.random.normal(key, a.shape, a.dtype)
                  for part, a in p.items()} if n in kv.slot_specs else p)
             for n, p in kv.pools.items()}
    st = _state_of(kv, pools, page_table=table[:S],
                   pos=jnp.asarray([5, 9, 2], jnp.int32),
                   run=jnp.asarray([True, False, True]))
    _, out = _logits(ex, w, np.asarray([[3], [4], [5]]), st)
    assert sorted(kv.slot_specs) == sorted(MAMBAS)
    for n in MAMBAS:
        for part in ("state", "conv"):
            assert bool((out[n][part][1] == pools[n][part][1]).all()), n
            assert not bool((out[n][part][0] == pools[n][part][0]).all())
        assert bool((out[n]["conv"][0, 0] == pools[n]["conv"][0, 1]).all())
        assert int(out[n]["rows"]) == 2 and int(out[n]["updates"]) == 2


def test_a_reused_slot_starts_from_zeros(model):
    """Re-admission: a slot that holds another request's state and tail
    gives, for a prompt that begins at position 0, the logits of a fresh
    slot — inside the compiled step, nothing is cleared at admission."""
    import jax
    import jax.numpy as jnp
    cfg, ex, w = model
    S = 2
    kv, table = _slot_cache(ex, S)
    ids = np.random.default_rng(4).integers(0, cfg["vocab_size"], 6)
    row_ids = np.concatenate([np.zeros(S, int), ids])[None]
    kw = dict(page_table=table,
              row_slot=jnp.asarray([S] * S + [1] * 6, jnp.int32),
              row_pos=jnp.asarray([0] * S + list(range(6)), jnp.int32))
    fresh, _ = _logits(ex, w, row_ids, _state_of(kv, kv.pools, **kw))
    dirty = {n: ({part: 3.0 + jax.random.normal(jax.random.PRNGKey(1),
                                                a.shape, a.dtype)
                  for part, a in p.items()} if n in kv.slot_specs else p)
             for n, p in kv.pools.items()}
    again, _ = _logits(ex, w, row_ids, _state_of(kv, dirty, **kw))
    assert bool((fresh[0, S:] == again[0, S:]).all())


def test_slot_parts_are_declared_by_the_layer_type():
    """A fourth type in the one registry: the cache manager builds the
    Mamba layer's float32 [N, d_in] state (`state_dtype` of the
    configuration file) and compute-dtype tail from it, and names neither
    in serving/."""
    import jax.numpy as jnp
    from paddle_tpu.graph.registry import slot_state_types
    from paddle_tpu.serving import PagedKVCache
    from paddle_tpu.serving.paged_kv import slot_state_specs
    assert sorted(slot_state_types) == ["kda_attention", "mamba", "mamba2",
                                        "short_conv"]
    cfg = _cfg()
    ex = _build(cfg, compute_dtype="bfloat16")
    specs = slot_state_specs(ex.model, jnp.bfloat16)
    assert specs == {n: {"state": ((16, 128), jnp.dtype(cfg["state_dtype"])),
                         "conv": ((3, 128), jnp.bfloat16)} for n in MAMBAS}
    kv = PagedKVCache(ex, num_slots=3, page_size=4, pages_per_slot=4)
    assert sorted(kv.layer_specs) == ["blk1_attn"]
    assert kv.layer_specs["blk1_attn"] == (1, 16)
    for n in MAMBAS:
        assert kv.pools[n]["state"].shape == (4, 16, 128)    # [S+1, N, d_in]
        assert str(kv.pools[n]["state"].dtype) == cfg["state_dtype"] \
            == "float32"
        assert str(kv.pools[n]["conv"].dtype) == "bfloat16"
    assert kv.slot_state_bytes == 4 * 4 * (16 * 128 * 4 + 3 * 128 * 2)
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
    cfg = _cfg(num_attention_heads=20, head_dim=128, hidden_size=64,
               num_hidden_layers=2, attn_layer_period=2, attn_layer_offset=1)
    ex = _build(cfg, compute_dtype="bfloat16")
    kv = PagedKVCache(ex, num_slots=2, page_size=16, pages_per_slot=2)
    assert kv.layer_specs == {"blk1_attn": (1, 128)}
    for part in ("k", "v"):
        pool = kv.pools["blk1_attn"][part]
        assert pool.shape == (kv.num_pages, 8, 2, 128)
        assert pool.dtype == jnp.bfloat16
        assert pool.nbytes == kv.num_pages * 16 * 256
