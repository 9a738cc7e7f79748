"""LFM2-MoE (gated short-convolution layers + QK-normed grouped-query
attention + MoE with every expert held) through the normal path at a tiny
size on the CPU, seeded weights, float32: the program (config DSL ->
GraphExecutor -> ServingEngine) against the plain reference
(benchmark/reference/lfm2_moe.py) and against itself across its paths — the
whole sequence, the decode step and the ragged mixed step through the cache
manager's slot tails, the scanned step — plus what the 2-token tail as a
second kind of slot state forced: the slot parts declared by the layer
type, paused slots, re-admission, the refusals, checkpoint/restore, the
narrow heads packed a lane tile, the expert-parallel share, and the DSL's
defaults against the configuration file."""

import json
import os
import re

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON = os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b-serve.json")
DSL = os.path.join(ROOT, "benchmark", "configs", "lfm2_moe.py")

# heads of 64 as published, two KV heads: one packed 128-lane row a token
TINY = dict(hidden_size=256, intermediate_size=64, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=5, vocab_size=64,
            moe_intermediate_size=16, num_experts=16, experts_held=16,
            ep_rank=0, num_experts_per_tok=4, param_dtype="float32",
            init_std=0.3, select_bias_std=0.3)
CONVS = ["blk0_conv", "blk2_conv", "blk3_conv", "blk4_conv"]


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
            + ",".join(f"{k}={cfg[k]}" for k in (
                "moe_intermediate_size", "num_experts", "experts_held",
                "ep_rank", "num_experts_per_tok", "num_dense_layers"))
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
    return Benchmark(ROOT).reference("lfm2_moe")


@pytest.fixture(scope="module")
def model(ref):
    cfg = _cfg()
    return cfg, _build(cfg), ref.make_weights(cfg, 7)


def _logits(ex, w, ids, state=None):
    """Log-probabilities [B, T, V] of the head, and the new state."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parameter.argument import Argument
    ids = jnp.asarray(ids, jnp.int32)
    n = jnp.full((ids.shape[0],), ids.shape[1], jnp.int32)
    with jax.default_matmul_precision("highest"):
        out, _, st = ex.forward(w, {"tokens": Argument(ids=ids, lengths=n)},
                                state, "test", None)
    return jnp.log(out["lm_head"].value), st


def _ref_logits(ref, cfg, w, seq):
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.jitted("log_probs", cfg)(
            w, jnp.asarray(seq), jnp.arange(len(seq))))


# -- the reference and the whole sequence -----------------------------------------

def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "lfm2_moe.py")) as f:
        src = f.read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]


def test_weights_fit_the_programs_parameters(model):
    import jax
    cfg, ex, w = model
    shapes = jax.eval_shape(ex.init_params, jax.random.PRNGKey(0))
    assert {k: (v.shape, str(v.dtype)) for k, v in shapes.items()} == \
        {k: (v.shape, str(v.dtype)) for k, v in w.items()}
    # 3 taps a channel, U(-1/sqrt 3, 1/sqrt 3); the two head norms 64 wide
    taps = np.asarray(w["_blk0_conv.w1"])
    assert taps.shape == (3, 256) and abs(taps).max() <= 3 ** -0.5
    assert w["_blk1_attn.w4"].shape == w["_blk1_attn.w5"].shape == (1, 64)
    # the program's own initializer draws the taps from the same range
    own = np.asarray(ex.init_params(jax.random.PRNGKey(1))["_blk0_conv.w1"])
    assert 0.5 < abs(own).max() <= 3 ** -0.5


def test_whole_sequence_logits_against_the_reference(model, ref):
    cfg, ex, w = model
    seq = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    got, _ = _logits(ex, w, seq[None])
    assert float(np.abs(np.asarray(got[0]) -
                        _ref_logits(ref, cfg, w, seq)).max()) < 2e-4


def test_qk_norm_is_an_attr_and_changes_the_layer(model):
    """Without the attr the attention layer has its four parameters and
    compiles what it compiled before; with it, two more and other logits."""
    cfg, ex, w = model
    attn = next(l for l in ex.model.layers if l.name == "blk1_attn")
    assert attn.attrs["qk_norm"] is True and attn.attrs["rms_eps"] == 1e-5
    assert len(attn.inputs) == 6
    from paddle_tpu.config.parser import parse_config
    plain = parse_config(os.path.join(ROOT, "demo", "model_zoo",
                                      "transformer_lm.py"),
                         "vocab=64,dim=32,layers=1,heads=2,batch_size=2")
    mha = next(l for l in plain.model_config.layers
               if l.type == "multi_head_attention")
    assert "qk_norm" not in mha.attrs and len(mha.inputs) == 4
    seq = np.arange(12)[None] % cfg["vocab_size"]
    base, _ = _logits(ex, w, seq)
    w2 = dict(w, **{"_blk1_attn.w4": w["_blk1_attn.w4"] * 3.0})
    scaled, _ = _logits(ex, w2, seq)
    assert float(np.abs(np.asarray(base) - np.asarray(scaled)).max()) > 1e-3


# -- the three conv paths and the slot tails ---------------------------------------

def _slot_cache(ex, S, pages=8):
    import jax.numpy as jnp
    from paddle_tpu.serving import PagedKVCache
    kv = PagedKVCache(ex, num_slots=S, page_size=4, pages_per_slot=pages)
    for s in range(S):
        assert kv.try_grow(s, 4 * pages)
    table = jnp.asarray(np.vstack([kv.table,
                                   np.zeros((1, pages), np.int32)]))
    return kv, table


def _state_of(kv, pools, **kw):
    out = {}
    for n, p in pools.items():
        if n in kv.slot_specs:
            out[n] = dict(p, **kw)
        else:
            shared = {k: v for k, v in kw.items() if k != "run"}
            out[n] = dict({part + "_pages": a for part, a in p.items()},
                          **shared)
    return out


def _pools_of(kv, pools, out):
    return {n: {part: out[n][part if n in kv.slot_specs else part + "_pages"]
                for part in p} for n, p in pools.items()}


def test_ragged_chunks_then_decode_through_the_tails_on_logits(model, ref):
    """Slot 1's 23-token prompt in mixed steps whose chunk rows split it at
    uneven places — 1, 2 and 4 rows: every way a chunk boundary can cut a
    3-tap window — while slot 0 decodes beside it in the steps' decode
    rows, then 6 decode steps of both: every position's logits of both
    sequences against ONE full reference forward each."""
    import jax.numpy as jnp
    cfg, ex, w = model
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
        # one decode row and one segment: two tails written a conv layer
        assert [int(out[c]["updates"]) for c in CONVS] == [2] * 4
        assert int(out[CONVS[0]]["rows"]) == 1 + n
    assert c0 == P
    pos = jnp.asarray([n0, P], jnp.int32)
    run = jnp.ones((S,), bool)
    for t in range(6):
        st = _state_of(kv, pools, page_table=table[:S], pos=pos, run=run)
        lp, out = _logits(ex, w, np.asarray([[seq0[n0 + t]], [seq1[P + t]]]),
                          st)
        got0[n0 + t], got1[P + t] = np.asarray(lp[0, 0]), np.asarray(lp[1, 0])
        pools = _pools_of(kv, pools, out)
        pos = pos + 1
    assert float(np.abs(got0[:n0 + 6] - _ref_logits(
        ref, cfg, w, seq0[:n0 + 6])).max()) < 2e-4
    assert float(np.abs(got1 - _ref_logits(ref, cfg, w, seq1)).max()) < 2e-4


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


def test_a_paused_slots_tail_is_bit_equal_after_the_step(model):
    """The run mask reaches the conv layers: a row whose mask is false
    leaves its tail exactly as it was."""
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
    assert sorted(kv.slot_specs) == sorted(CONVS)
    for n in CONVS:
        assert bool((out[n]["conv"][1] == pools[n]["conv"][1]).all()), n
        assert not bool((out[n]["conv"][0] == pools[n]["conv"][0]).all())
        assert bool((out[n]["conv"][0, 0] == pools[n]["conv"][0, 1]).all())
        assert int(out[n]["rows"]) == 2 and int(out[n]["updates"]) == 2


def test_a_reused_slot_starts_from_zeros(model):
    """Re-admission: a slot that holds another request's tail gives, for a
    prompt that begins at position 0, the logits of a fresh slot — inside
    the compiled step, nothing is cleared at admission."""
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
    """One registry gives a recurrent layer's parts, row shapes and dtypes;
    the cache manager builds every kind from it: the KDA layer's float32
    state and compute-dtype tail, this model's tail alone; and the K/V pool
    of 64-wide heads is stored two heads a lane tile."""
    import jax.numpy as jnp
    from paddle_tpu.graph.registry import slot_state_types
    from paddle_tpu.serving import PagedKVCache
    from paddle_tpu.serving.paged_kv import slot_state_specs
    assert {"kda_attention", "short_conv"} <= set(slot_state_types)
    ex = _build(_cfg(), compute_dtype="bfloat16")
    specs = slot_state_specs(ex.model, jnp.bfloat16)
    assert specs == {n: {"conv": ((2, 256), jnp.bfloat16)} for n in CONVS}
    kv = PagedKVCache(ex, num_slots=3, page_size=4, pages_per_slot=4)
    assert kv.slot_specs == {n: {"conv": (2, 256)} for n in CONVS}
    assert sorted(kv.layer_specs) == ["blk1_attn"]
    for n in CONVS:
        assert set(kv.pools[n]) == {"conv"}
        assert kv.pools[n]["conv"].shape == (4, 2, 256)
        assert str(kv.pools[n]["conv"].dtype) == "bfloat16"
    assert kv.slot_state_bytes == 4 * (4 * 2 * 256 * 2)
    # 2 KV heads of 64 = one 128-lane row a token, K and V
    assert kv.layer_specs["blk1_attn"] == (1, 128)
    assert kv.pools["blk1_attn"]["k"].shape == (kv.num_pages, 4, 1, 128)
    assert kv.pool_bytes == 2 * kv.num_pages * 4 * 128 * 2
    # the hybrid model's parts, by the same registry
    import tests.test_kimi_linear as kimi
    kex = kimi._build(kimi._cfg())
    kspecs = slot_state_specs(kex.model, jnp.bfloat16)
    assert kspecs["blk0_kda"] == {"state": ((4, 8, 8), jnp.float32),
                                  "conv": ((3, 96), jnp.bfloat16)}


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


# -- the engine --------------------------------------------------------------------

def _requests(n_tokens, max_new=6, seed=3):
    import jax
    from paddle_tpu.serving import Request
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}", rng.integers(2, 64, n).astype(np.int32),
                    max_new=max_new, rng=jax.random.PRNGKey(40 + i))
            for i, n in enumerate(n_tokens)]


def _check_against_lm_generate(ex, w, reqs, results):
    from paddle_tpu.graph.lm_decode import lm_generate
    for r in reqs:
        toks, lens = lm_generate(ex, w, r.prompt_ids[None, :],
                                 max_new=r.max_new, rng=r.rng)
        np.testing.assert_array_equal(
            np.asarray(toks)[0, :int(np.asarray(lens)[0])],
            results[r.req_id])


@pytest.mark.parametrize("chunk,kernel,k", [(5, False, 1), (5, True, 1),
                                            (32, False, 1), (5, False, 4)],
                         ids=["chunked-jnp", "chunked-kernel", "one-chunk",
                              "scanned-k4"])
def test_engine_greedy_tokens_match_lm_generate(model, chunk, kernel, k,
                                                monkeypatch):
    """Greedy tokens of the engine — chunked prefill through mixed steps,
    slots re-admitted after other requests, the packed pool through the
    interpreted kernel, the scanned step (--decode-steps 4) — are
    lm_generate's whole-sequence tokens."""
    import jax
    from paddle_tpu.serving import ServingEngine
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1" if kernel else "0")
    cfg, ex, w = model
    if kernel:
        ex = _build(cfg, attn_impl="auto")
    reqs = _requests((3, 19, 9, 17, 26))
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                            prefill_chunk=chunk, decode_steps=k)
        assert eng.prefix is None
        results = eng.run(reqs)
        _check_against_lm_generate(ex, w, reqs, results)
    eng.kv.check_reclaimed()
    if k > 1:
        assert eng.n_scan_flushes > 0
    # the recurrent counters are fed by this kind too: every counted step,
    # at most one tail a slot a conv layer a step
    assert eng.recurrent_steps >= eng.n_decode_steps > 0
    assert 0 < eng.recurrent_slot_updates <= \
        4 * len(eng.slots) * eng.recurrent_steps
    assert eng.recurrent_rows >= eng.recurrent_slot_updates // 4
    assert eng.moe_steps == eng.recurrent_steps
    assert eng.kv.slot_state_bytes == 4 * 3 * 2 * 256 * 4


def test_engine_serves_the_same_tokens_on_the_grouped_form(model,
                                                            monkeypatch):
    """The expert block forced onto its grouped form (the rule's constant
    lowered: every row count passes the ridge; 4 slots an expert, so the
    steps run one round to several; all 16 experts held) serves
    the greedy tokens the dense form serves, and
    `serving_moe_grouped_steps_total{kind}` counts every step landed — none
    where the rule keeps the dense form."""
    import jax
    from paddle_tpu.obs.metrics import counter_key, process_counters
    from paddle_tpu.parallel import moe
    from paddle_tpu.serving import ServingEngine
    cfg, ex, w = model
    keys = {k: counter_key("serving_moe_grouped_steps_total", kind=k)
            for k in ("decode", "mixed")}
    traced, grouped_form = [], moe._experts_grouped
    monkeypatch.setattr(moe, "_experts_grouped", lambda x, *a, **kw: (
        traced.append(x.shape[0]), grouped_form(x, *a, **kw))[1])

    def serve():
        before = process_counters().snapshot()
        reqs = _requests((3, 19, 9, 17, 26))
        with jax.default_matmul_precision("highest"):
            eng = ServingEngine(ex, w, num_slots=2, page_size=4,
                                max_context=48, prefill_chunk=5)
            results = eng.run(reqs)
        after = process_counters().snapshot()
        return eng, results, {k: after.get(key, 0) - before.get(key, 0)
                              for k, key in keys.items()}

    dense, want, counted = serve()
    assert dense.moe_grouped_steps == {} and not any(counted.values())
    assert not traced
    monkeypatch.setattr(moe, "_GROUPED_OVER_RIDGE", 0.0)
    monkeypatch.setattr(moe, "_GROUP_SLOTS", 4)
    grouped, got, counted = serve()
    # the step programs themselves were traced to it: the decode step at
    # the slots' rows, the mixed step at its token budget
    assert {len(grouped.slots), grouped.max_step_tokens} <= set(traced)
    assert set(got) == set(want)
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
    assert grouped.moe_steps == dense.moe_steps > 0
    assert counted == grouped.moe_grouped_steps
    assert counted["mixed"] == grouped.n_mixed_steps > 0
    assert counted["decode"] == grouped.moe_steps - counted["mixed"] > 0


def test_a_paused_slot_does_not_advance_in_the_scanned_step(model):
    """--decode-steps 4 with a request that ends inside a dispatch: the
    slot's remaining bodies run masked, and the request that takes the slot
    next decodes what an undisturbed engine decodes."""
    import jax
    from paddle_tpu.serving import ServingEngine
    cfg, ex, w = model
    reqs = _requests((7, 5, 9), max_new=6) + _requests((4,), max_new=3,
                                                       seed=9)
    reqs[-1].req_id = "short"
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                            prefill_chunk=5, decode_steps=4)
        results = eng.run(reqs)
        _check_against_lm_generate(ex, w, reqs, results)
    assert eng.n_scan_flushes > 0


def test_checkpoint_and_restore_round_trip_the_tails(model):
    import jax
    from paddle_tpu.serving import ServingEngine
    cfg, ex, w = model
    reqs = _requests((9, 13), max_new=8)

    def engine():
        return ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                             prefill_chunk=5)

    with jax.default_matmul_precision("highest"):
        a = engine()
        for r in reqs:
            a.add_request(r)
        for _ in range(6):
            a.step()
        snap = a.checkpoint_state()
        assert snap["config"]["slot_specs"]["blk0_conv"] == \
            {"conv": (2, 256)}
        assert set(snap["pools"]["blk0_conv"]) == {"conv"}
        b = engine()
        b.restore_state(snap)
        for n in b.kv.slot_specs:
            for part, arr in b.kv.pools[n].items():
                assert bool((np.asarray(arr) ==
                             snap["pools"][n][part]).all())
        results = b.run()
        _check_against_lm_generate(ex, w, reqs, results)


@pytest.mark.parametrize("what", ["prefix", "spill", "spec", "mesh",
                                  "export", "import", "role", "dense_cache"])
def test_what_needs_a_state_snapshot_is_refused_by_the_same_sentences(
        model, what):
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.paged_kv import RECURRENT_REFUSALS
    cfg, ex, w = model

    def engine(**kw):
        return ServingEngine(ex, w, num_slots=2, page_size=4,
                             max_context=32, **kw)

    with pytest.raises(ValueError) as e:
        if what == "prefix":
            engine().set_prefix_cache(True)
        elif what == "spill":
            engine(spill_bytes_budget=1 << 20)
        elif what == "spec":
            engine(spec_k=2)
        elif what == "mesh":
            from paddle_tpu.parallel.mesh import model_mesh
            engine(mesh=model_mesh(2))
        elif what == "export":
            engine().export_prefix([1, 2, 3, 4])
        elif what == "import":
            engine().import_prefix([1, 2, 3, 4], {"n_pages": 1}, b"")
        elif what == "role":
            from paddle_tpu.serving.server import ServingServer
            ServingServer(engine(), role="prefill")
        else:
            from paddle_tpu.graph.lm_decode import init_kv_caches
            init_kv_caches(ex, 1, 8)
    msg = str(e.value)
    assert "recurrent" in msg
    if what != "dense_cache":
        assert RECURRENT_REFUSALS[what][1] in msg and "(4 here" in msg, msg


# -- the share ---------------------------------------------------------------------

def test_the_ranks_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test: one expert layer of the PROGRAM as each of 8
    ranks would hold it (8 of 64 experts each, the draw's own count), added
    up, against the uncut REFERENCE layer — all 64 experts held, what the
    cell runs."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel.moe import moe_ffn
    uncut = _cfg(num_experts=64, experts_held=64, ep_rank=0)
    w = ref.make_weights(uncut, 11)
    wl = {k[len("_blk1_"):]: v for k, v in w.items()
          if k.startswith("_blk1_")}
    x = jnp.asarray(np.random.default_rng(5).normal(size=(10, 256)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref._moe(uncut, wl, x, None)
        total = jnp.zeros_like(want)
        seen = jnp.zeros((10, 0), bool)
        for rank in range(8):
            sl = slice(8 * rank, 8 * rank + 8)
            y, _, pairs = moe_ffn(
                x, wl["moe.w0"],
                (wl["moe.w1"][sl], wl["moe.w2"][sl], wl["moe.w3"][sl]),
                top_k=4, first_expert=8 * rank, scoring="sigmoid",
                select_bias=wl["moe.w4"].reshape(-1),
                scale=uncut["routed_scaling_factor"])
            total = total + y
            seen = jnp.concatenate([seen, pairs], axis=1)
        # the same rank's share through the reference's own cut
        cut = dict(uncut, experts_held=8, ep_rank=3)
        wl3 = dict(wl, **{k: wl[k][24:32] for k in ("moe.w1", "moe.w2",
                                                    "moe.w3")})
        y3, _, _ = moe_ffn(
            x, wl["moe.w0"], (wl3["moe.w1"], wl3["moe.w2"], wl3["moe.w3"]),
            top_k=4, first_expert=24, scoring="sigmoid",
            select_bias=wl["moe.w4"].reshape(-1))
        assert float(jnp.abs(y3 - ref._moe(cut, wl3, x, None)).max()) < 2e-5
    # the renormalisation's + 1e-6 against moe_route's clamp: far under this
    assert float(jnp.abs(total - want).max()) < 2e-5
    assert bool((jnp.sum(seen, axis=1) == 4).all())   # top-4, every row


# -- build_engine ------------------------------------------------------------------

def test_build_engine_serves_the_model_in_bf16(monkeypatch):
    """tools/serve.py:build_engine, no new flag: the model serves with bf16
    parameters, its tails in bf16 beside the packed K/V pool, and the flags
    that need a state snapshot are refused from the command line."""
    import importlib.util
    from paddle_tpu.serving import Request
    cfg = _cfg()
    monkeypatch.chdir(ROOT)
    spec = importlib.util.spec_from_file_location(
        "tools_serve_l", os.path.join(ROOT, "tools", "serve.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    got = {}

    async def capture(a):
        got["args"] = a
        return 0

    tool.amain = capture
    argv = ["--config", DSL, "--config-args",
            _args(cfg).replace("compute_dtype=,", "compute_dtype=bfloat16,"),
            "--slots", "2", "--page-size", "4", "--max-context", "32",
            "--prefill-chunk", "8", "--param-dtype", "bfloat16"]
    tool.main(argv)
    eng = tool.build_engine(got["args"])
    assert {str(v.dtype) for v in eng.params.values()} == {"bfloat16"}
    assert str(eng.kv.pools["blk0_conv"]["conv"].dtype) == "bfloat16"
    assert eng.kv.pools["blk1_attn"]["k"].shape[2:] == (1, 128)
    out = eng.run([Request("a", np.asarray([3, 5, 7], np.int32), max_new=4)])
    assert len(out["a"]) == 7
    tool.main(argv + ["--spec-k", "2"])
    with pytest.raises(ValueError, match="recurrent"):
        tool.build_engine(got["args"])


# -- the configuration ------------------------------------------------------------

def test_configuration_file_is_the_catalog_row_cut_as_it_says(ref):
    with open(JSON) as f:
        cfg = json.load(f)
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(cat):
        with open(cat) as f:
            row = next(json.loads(ln) for ln in f
                       if '"LFM2-24B-A2B"' in ln)
        assert cfg["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k in cfg["reduced"]:
                assert cfg[k] != v and cfg["published"][k] == v, k
            else:
                assert cfg[k] == v, k
        assert cfg["published"]["layer_types"] == row["config"]["layer_types"]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_dense_layers"}
    # the published widths, uncut; every expert held; the whole vocabulary
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["conv_L_cache"], cfg["conv_bias"]) == \
        (2048, 32, 8, 64, 3, False)
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert (cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["intermediate_size"], cfg["vocab_size"]) == \
        (1536, 64, 4, 1, 11776, 65536)
    assert cfg["experts_held"] == cfg["num_experts"] and cfg["ep_rank"] == 0
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 1 and dep["pipeline_stages"] == 8
    assert cfg["published"]["num_hidden_layers"] == \
        dep["pipeline_stages"] * cfg["num_hidden_layers"]
    # one leading dense layer and one whole period of the pattern
    assert ref.layer_kinds(cfg) == ["conv", "full_attention", "conv", "conv",
                                    "conv"]
    assert ref.layer_kinds(dict(cfg, num_hidden_layers=2)) == \
        ["conv", "full_attention"]
    # the aliases the shared readers and server_argv read
    assert cfg["n_routed_experts"] == cfg["num_experts"]
    assert cfg["n_shared_experts"] == 0
    assert cfg["first_k_dense_replace"] == cfg["num_dense_layers"] == 1
    assert cfg["rope_theta"] == cfg["rope_parameters"]["rope_theta"]
    assert cfg["server_flags"] == {
        "slots": 256, "page_size": 16, "max_context": 4096,
        "prefill_chunk": 128, "max_step_tokens": 512, "max_queue": 1024,
        "decode_steps": 1, "spec_k": 0, "param_dtype": "bfloat16"}
    assert cfg["param_dtype"] == cfg["compute_dtype"] == "bfloat16"
    # 2,834.8 M parameters, 5.67 GB in bf16 (ISSUE 35 section 1)
    n = sum(int(np.prod(s)) for s, _ in ref.param_shapes(cfg).values())
    assert n == 2_834_872_704 and round(2 * n / 1e9, 2) == 5.67


def test_dsl_defaults_equal_the_configuration_file(ref):
    """benchmark/kinds/serve.py sends ten sizes; every other one reaches
    the model as the DSL file's default — held to the JSON here."""
    with open(JSON) as f:
        cfg = json.load(f)
    with open(DSL) as f:
        src = f.read()
    defaults = {m.group(1): m.group(2).strip() for m in re.finditer(
        r'get_config_arg\(\s*"(\w+)",\s*\w+,\s*([^)]+)\)', src)}
    sent = {"vocab", "dim", "layers", "heads", "kv_heads", "ffn",
            "rope_theta", "batch_size", "compute_dtype", "attn_impl",
            "seq_len"}
    checked = 0
    for name, text in defaults.items():
        if name in sent:
            continue
        if name == "layer_types":
            assert text.strip('"').split(";") == ref.layer_kinds(cfg)
        else:
            assert float(text) == float(cfg[name]), name
        checked += 1
    assert checked == 11
    assert float(defaults["rope_theta"]) == float(cfg["rope_theta"])


@pytest.mark.parametrize("depth,dense,want", [
    (5, 1, "CACCC"), (2, 1, "CA"), (3, 2, "CAC")])
def test_layer_kinds_by_depth(depth, dense, want):
    cfg = _cfg(num_hidden_layers=depth, num_dense_layers=dense)
    layers = _parse(_args(cfg)).model_config.layers
    kinds = "".join({"short_conv": "C", "multi_head_attention": "A"}[l.type]
                    for l in layers
                    if l.type in ("short_conv", "multi_head_attention"))
    assert kinds == want
    ffn = [l.type for l in layers if l.type in ("gated_ffn", "moe")]
    assert ffn == ["gated_ffn"] * dense + ["moe"] * (depth - dense)
