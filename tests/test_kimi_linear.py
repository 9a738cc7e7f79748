"""Kimi-Linear (KDA gated delta-rule layers + NoPE latent attention + MoE)
through the normal path at a tiny size on the CPU, seeded weights, float32:
the program (config DSL -> GraphExecutor -> ServingEngine) against the plain
reference (benchmark/reference/kimi_linear.py) and against itself across its
paths — whole sequence (chunkwise), the decode step and the ragged mixed
step through the cache manager's slot state (jnp and the Pallas step kernel
interpreted), the scanned step — plus what the recurrent state forced:
paused slots, re-admission, preempt-and-replay, the refusals, the slot
parts under checkpoint/restore, the expert-parallel share, and the DSL's
defaults against the configuration file."""

import json
import os
import re

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON = os.path.join(ROOT, "benchmark", "configs",
                    "kimi-linear-48b-a3b-serve.json")
DSL = os.path.join(ROOT, "benchmark", "configs", "kimi_linear.py")

TINY = dict(hidden_size=32, intermediate_size=64, num_attention_heads=4,
            num_hidden_layers=4, vocab_size=64, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            moe_intermediate_size=16, num_experts=16, experts_held=4,
            ep_rank=1, num_experts_per_token=4, param_dtype="float32",
            init_std=0.3, select_bias_std=0.3)
KDA = dict(head_dim=8, num_heads=4)


def _cfg(**over):
    with open(JSON) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"], **KDA)
    cfg.update(over)
    return cfg


def _args(cfg: dict, attn_impl: str = "dense", **extra):
    la = cfg["linear_attn_config"]
    return (f"vocab={cfg['vocab_size']},dim={cfg['hidden_size']},"
            f"layers={cfg['num_hidden_layers']},"
            f"heads={cfg['num_attention_heads']},"
            f"ffn={cfg['intermediate_size']},compute_dtype=,"
            f"attn_impl={attn_impl},init_std={cfg['init_std']},"
            f"kda_head_dim={la['head_dim']},kda_num_heads={la['num_heads']},"
            + ",".join(f"{k}={cfg[k]}" for k in (
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "moe_intermediate_size", "num_experts",
                "experts_held", "ep_rank", "num_experts_per_token",
                "first_k_dense_replace"))
            + "".join(f",{k}={v}" for k, v in extra.items()))


def _build(cfg, **extra):
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.graph import GraphExecutor
    cwd = os.getcwd()
    os.chdir(ROOT)
    try:
        pc = parse_config(DSL, _args(cfg, **extra))
    finally:
        os.chdir(cwd)
    return GraphExecutor(pc.model_config, compute_dtype="")


@pytest.fixture(scope="module")
def ref():
    from benchmark.lib.spec import Benchmark
    return Benchmark(ROOT).reference("kimi_linear")


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


# -- the reference and the ops ------------------------------------------------

def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "kimi_linear.py")) as f:
        src = f.read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]


def test_weights_fit_the_programs_parameters(model, ref):
    import jax
    cfg, ex, w = model
    shapes = jax.eval_shape(ex.init_params, jax.random.PRNGKey(0))
    assert {k: (v.shape, str(v.dtype)) for k, v in shapes.items()} == \
        {k: (v.shape, str(v.dtype)) for k, v in w.items()}
    # the KDA initializers: A_log in [0, log 16], dt_bias a softplus^-1 of
    # a step in [1e-3, 1e-1]; conv taps in [-1/2, 1/2]
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


def test_whole_sequence_logits_match_the_reference(model, ref):
    cfg, ex, w = model
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (1, 150))
    got, _ = _logits(ex, w, ids)
    assert float(np.abs(np.asarray(got[0]) - _ref_logits(
        ref, cfg, w, ids[0])).max()) < 1e-4


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


# -- the slot state through the layers ----------------------------------------

def _slot_cache(ex, S, pages=8):
    from paddle_tpu.serving import PagedKVCache
    kv = PagedKVCache(ex, num_slots=S, page_size=4, pages_per_slot=pages)
    for s in range(S):
        assert kv.try_grow(s, 4 * pages)
    import jax.numpy as jnp
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
            out[n] = dict(kv_pages=p["kv"], **shared)
    return out


def _pools_of(kv, pools, out):
    return {n: ({part: out[n][part] for part in p} if n in kv.slot_specs
                else {"kv": out[n]["kv_pages"]}) for n, p in pools.items()}


def test_ragged_chunks_then_decode_through_the_slot_state_on_logits(
        model, ref):
    """Slot 1's 23-token prompt in mixed steps whose chunk rows split it at
    uneven places (7, 9, 4, 3 rows) while slot 0 decodes beside it in the
    steps' decode rows, then 6 decode steps of both — every position's
    logits of both sequences against ONE full reference forward each."""
    import jax.numpy as jnp
    cfg, ex, w = model
    rng = np.random.default_rng(1)
    S, P = 2, 23
    seq0 = rng.integers(0, cfg["vocab_size"], 12)
    seq1 = rng.integers(0, cfg["vocab_size"], P + 6)
    kv, table = _slot_cache(ex, S)
    pools = kv.pools
    got0 = np.zeros((len(seq0), cfg["vocab_size"]), np.float32)
    got1 = np.zeros((len(seq1), cfg["vocab_size"]), np.float32)
    # slot 0's first token as a one-row chunk of its own
    T = S + 9
    n0 = 0

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
        return np.asarray(lp[0]), _pools_of(kv, pools, out)

    lp, pools = mixed({}, 0, np.arange(1))
    got0[0] = lp[S]
    n0 = 1
    c0 = 0
    for n in (7, 9, 4, 3):
        lp, pools = mixed({0: (seq0[n0], n0)}, 1, np.arange(c0, c0 + n))
        got0[n0] = lp[0]
        got1[c0:c0 + n] = lp[S:S + n]
        n0, c0 = n0 + 1, c0 + n
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


def test_a_paused_slots_state_is_bit_equal_after_the_step(model):
    """The run mask reaches the recurrent layers: a row whose mask is false
    leaves `state` and `conv` exactly as they were (a K/V write at a frozen
    position is idempotent; a recurrence is not)."""
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
    for n in kv.slot_specs:
        for part in ("state", "conv"):
            assert bool((out[n][part][1] == pools[n][part][1]).all()), \
                (n, part)
            assert not bool((out[n][part][0] == pools[n][part][0]).all())
        assert int(out[n]["rows"]) == 2 and int(out[n]["updates"]) == 2


def test_a_segment_at_position_0_starts_from_the_zero_state(model):
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


def test_slot_state_pool_is_the_configurations_dtype_and_shape(model):
    """state_dtype is part of the configuration: the pool is float32
    whatever the compute dtype, one row a slot plus the trash row."""
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.graph import GraphExecutor
    from paddle_tpu.serving import PagedKVCache
    cfg = _cfg()
    assert cfg["state_dtype"] == "float32"
    cwd = os.getcwd()
    os.chdir(ROOT)
    try:
        pc = parse_config(DSL, _args(cfg).replace("compute_dtype=,",
                                                  "compute_dtype=bfloat16,"))
    finally:
        os.chdir(cwd)
    ex = GraphExecutor(pc.model_config, compute_dtype="bfloat16")
    kv = PagedKVCache(ex, num_slots=3, page_size=4, pages_per_slot=4)
    assert sorted(kv.slot_specs) == ["blk0_kda", "blk1_kda", "blk2_kda"]
    assert sorted(kv.layer_specs) == ["blk3_attn"]
    for n in kv.slot_specs:
        assert kv.pools[n]["state"].shape == (4, 4, 8, 8)
        assert str(kv.pools[n]["state"].dtype) == cfg["state_dtype"]
        assert kv.pools[n]["conv"].shape == (4, 3, 3 * 4 * 8)
        assert str(kv.pools[n]["conv"].dtype) == "bfloat16"
    assert kv.slot_state_bytes == 3 * (4 * 4 * 8 * 8 * 4 + 4 * 3 * 96 * 2)
    assert kv.pool_bytes == kv.num_pages * 4 * 128 * 2
    assert kv.page_nbytes == 4 * 128 * 2       # the paged parts alone


# -- the engine ------------------------------------------------------------------

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


@pytest.mark.parametrize("chunk,kernel,k,mst", [
    (5, False, 1, None), (5, True, 1, None), (32, False, 1, None),
    (5, False, 4, None), (5, False, 1, 34)],
    ids=["chunked-jnp", "chunked-kernel", "one-chunk", "scanned-k4",
         "free-rows"])
def test_engine_greedy_tokens_match_lm_generate(model, chunk, kernel, k,
                                                mst, monkeypatch):
    """Greedy tokens of the engine — chunked prefill through mixed steps,
    slots re-admitted after other requests, the scanned step (k = 4 bodies
    a dispatch = four single steps), a step with free rows for a whole
    prompt (32 chunk rows: a run of 26 tokens where the share is 5, one
    segment of `kda.segment_rows`) — are lm_generate's."""
    import jax
    from paddle_tpu.serving import ServingEngine
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1" if kernel else "0")
    cfg, ex, w = model
    if kernel:
        ex = _build(cfg, attn_impl="auto")
    reqs = _requests((3, 19, 9, 17, 26))
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                            prefill_chunk=chunk, decode_steps=k,
                            max_step_tokens=mst)
        assert eng.prefix is None
        results = eng.run(reqs)
        _check_against_lm_generate(ex, w, reqs, results)
    eng.kv.check_reclaimed()
    if mst:
        # every prompt went in one run: 51 of the 74 rows past a share of 5
        assert eng.n_prefill_chunks == 5 and eng.n_chunk_rows == 74
        assert eng.n_chunk_extra_rows == 14 + 4 + 12 + 21
    if k > 1:
        assert eng.n_scan_flushes > 0
    # the recurrent counters came back with the tokens: every counted step,
    # and at most one state a slot a layer a step
    assert eng.recurrent_steps >= eng.n_decode_steps > 0
    assert 0 < eng.recurrent_slot_updates <= \
        3 * len(eng.slots) * eng.recurrent_steps
    assert eng.recurrent_rows >= eng.recurrent_slot_updates // 3
    assert eng.moe_steps == eng.recurrent_steps


def test_preempt_and_replay_gives_the_tokens_of_an_undisturbed_run(model):
    """With no prefix index the victim prefills again from position 0 and
    its state is rebuilt: correct, and slow."""
    import jax
    from paddle_tpu.serving import ServingEngine
    cfg, ex, w = model
    reqs = _requests((11, 14, 7), max_new=8)
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                            prefill_chunk=5)
        for r in reqs:
            eng.add_request(r)
        for _ in range(7):
            eng.step()
        victim = max((s for s in range(2) if eng.slots[s] is not None),
                     key=lambda s: eng.slots[s].admit_seq)
        assert eng.slots[victim].gen > 0        # mid-decode
        eng._preempt(victim)
        results = eng.run()
        assert eng.n_preemptions == 1
        _check_against_lm_generate(ex, w, reqs, results)
    eng.kv.check_reclaimed()


def test_checkpoint_and_restore_round_trip_the_slot_state(model):
    """checkpoint_state / restore_state carry the slot-indexed parts: a
    run frozen mid-flight and resumed on a fresh engine finishes with the
    undisturbed run's tokens."""
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
        assert snap["config"]["slot_specs"]["blk0_kda"]["state"] == (4, 8, 8)
        assert set(snap["pools"]["blk0_kda"]) == {"state", "conv"}
        b = engine()
        b.restore_state(snap)
        for n in b.kv.slot_specs:
            for part, arr in b.kv.pools[n].items():
                assert bool((np.asarray(arr) ==
                             snap["pools"][n][part]).all())
        results = b.run()
        _check_against_lm_generate(ex, w, reqs, results)


@pytest.mark.parametrize("what", ["prefix", "spill", "spill_later", "spec",
                                  "spec_later", "mesh", "export", "import",
                                  "role", "dense_cache"])
def test_what_needs_a_state_snapshot_is_refused_by_name(model, what):
    """Each mechanism that assumes the pages ARE the context raises for a
    model with recurrent layers, with a sentence naming what is missing."""
    from paddle_tpu.serving import ServingEngine
    cfg, ex, w = model

    def engine(**kw):
        return ServingEngine(ex, w, num_slots=2, page_size=4,
                             max_context=32, **kw)

    with pytest.raises(ValueError) as e:
        if what == "prefix":
            engine().set_prefix_cache(True)
        elif what == "spill":
            engine(spill_bytes_budget=1 << 20)
        elif what == "spill_later":
            engine().set_spill_budget(1 << 20)
        elif what == "spec":
            engine(spec_k=2)
        elif what == "spec_later":
            engine().set_speculation(2)
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
    want = {"prefix": "prefix index", "spill": "spill", "spill_later":
            "spill", "spec": "rolled back", "spec_later": "rolled back",
            "mesh": "head-sharded", "export": "export_prefix", "import":
            "import_prefix", "role": "--role prefill|decode", "dense_cache":
            "no dense cache"}[what]
    assert want in msg, msg


def test_models_without_recurrent_layers_hand_their_layers_no_run_mask():
    """The existing configurations' step programs gain no operand: the
    state a K/V layer is handed holds what it held before."""
    import jax.numpy as jnp
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.graph import GraphExecutor
    from paddle_tpu.serving import ServingEngine
    cwd = os.getcwd()
    os.chdir(ROOT)
    try:
        pc = parse_config("benchmark/configs/starcoder2.py",
                          "vocab=64,dim=32,layers=1,heads=4,kv_heads=2,"
                          "ffn=64,batch_size=1,compute_dtype=,"
                          "attn_impl=dense")
    finally:
        os.chdir(cwd)
    ex = GraphExecutor(pc.model_config, compute_dtype="")
    import jax
    eng = ServingEngine(ex, ex.init_params(jax.random.PRNGKey(0)),
                        num_slots=2, page_size=4, max_context=16)
    eng._sync_device_state()
    st = eng._layer_state(eng._build_state(), jnp.ones((2,), bool),
                          page_table=eng._d_table[:2], pos=eng._d_pos)
    (name, got), = st.items()
    assert set(got) == {"k_pages", "v_pages", "page_table", "pos"}
    assert not eng._recurrent and eng.prefix is not None
    assert eng.kv.slot_state_bytes == 0


# -- latent attention without a query rank and without rotation -------------------

def test_nope_mla_without_a_query_rank_against_a_literal_loop(ref):
    """A model whose every layer is full attention (full_attn_layers 1;2):
    the program's whole-sequence logits against a literal per-head,
    per-position loop over the same weights, and the absorbed form over the
    dense cache against the expanded one."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.graph.lm_decode import init_kv_caches
    cfg = _cfg(num_hidden_layers=2)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"],
                                     full_attn_layers=[1, 2])
    ex = _build(cfg, full_attn_layers="1;2")
    w = ref.make_weights(cfg, 5)
    assert [l.type for l in ex.model.layers
            if l.type.endswith("attention")] == ["mla_attention"] * 2
    assert "_blk0_attn.w4" in w and "_blk0_attn.w5" not in w
    ids = np.random.default_rng(2).integers(0, 64, (2, 14))
    whole, _ = _logits(ex, w, ids)
    assert float(np.abs(np.asarray(whole[0]) - _ref_logits(
        ref, cfg, w, ids[0])).max()) < 5e-5
    # one layer's mixer by a literal loop: no rotation anywhere
    H, nope, rope, vd, kr = 4, 8, 4, 8, 16
    wl = {k[len("_blk0_"):]: np.asarray(v, np.float64)
          for k, v in w.items() if k.startswith("_blk0_")}
    x = np.random.default_rng(3).normal(size=(5, 32))
    q = (x @ wl["attn.w0"]).reshape(5, H, nope + rope)
    ckv = x @ wl["attn.w1"]
    c = ckv[:, :kr]
    c = c / np.sqrt((c * c).mean(-1, keepdims=True) + cfg["rms_norm_eps"]) \
        * wl["attn.w2"].reshape(-1)
    kvb = (c @ wl["attn.w3"]).reshape(5, H, nope + vd)
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
        got = ref._attention(cfg, {k: jnp.asarray(v, jnp.float32)
                                   for k, v in wl.items()},
                             jnp.asarray(x, jnp.float32), None)
    assert float(np.abs(np.asarray(got) - want).max()) < 1e-4
    # absorbed over a dense cache = expanded
    lp, st = _logits(ex, w, ids[:, :9], init_kv_caches(ex, 2, 14))
    assert float(jnp.abs(lp - whole[:, :9]).max()) < 2e-5
    for t in range(9, 14):
        lp, st = _logits(ex, w, ids[:, t:t + 1], st)
        assert float(jnp.abs(lp[:, 0] - whole[:, t]).max()) < 5e-5


# -- the share ------------------------------------------------------------------

def test_the_ranks_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test: one expert layer of the PROGRAM as each of
    the 4 ranks holds it (4 of 16 experts each), the shared expert counted
    once, against the uncut REFERENCE layer (all 16 experts held)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.graph.layers_misc import gated_ffn
    from paddle_tpu.parallel.moe import moe_ffn
    uncut = _cfg(experts_held=16, ep_rank=0)
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


# -- build_engine ------------------------------------------------------------------

def test_build_engine_serves_the_model_in_bf16(monkeypatch):
    """tools/serve.py:build_engine, no new flag: the model serves with
    bf16 parameters, its slot state float32 beside the latent pool, and the
    flags that need a state snapshot are refused from the command line."""
    import importlib.util
    from paddle_tpu.serving import Request
    cfg = _cfg()
    monkeypatch.chdir(ROOT)
    spec = importlib.util.spec_from_file_location(
        "tools_serve_k", os.path.join(ROOT, "tools", "serve.py"))
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
            "--param-dtype", "bfloat16"]
    tool.main(argv)
    eng = tool.build_engine(got["args"])
    assert {str(v.dtype) for v in eng.params.values()} == {"bfloat16"}
    assert str(eng.kv.pools["blk0_kda"]["state"].dtype) == "float32"
    out = eng.run([Request("a", np.asarray([3, 5, 7], np.int32), max_new=4)])
    assert len(out["a"]) == 7
    tool.main(argv + ["--spec-k", "2"])
    with pytest.raises(ValueError, match="recurrent"):
        tool.build_engine(got["args"])


# -- the configuration ------------------------------------------------------------

def test_configuration_file_is_the_catalog_row_cut_as_it_says():
    with open(JSON) as f:
        cfg = json.load(f)
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(cat):
        with open(cat) as f:
            row = next(json.loads(ln) for ln in f
                       if '"Kimi-Linear-48B-A3B-Instruct"' in ln)
        assert cfg["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k in cfg["reduced"] and k != "num_experts":
                assert cfg[k] != v and cfg["published"][k] == v, k
            else:
                assert cfg[k] == v, k
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size"}
    # the published widths, uncut
    la = cfg["linear_attn_config"]
    assert (cfg["hidden_size"], la["num_heads"], la["head_dim"],
            la["short_conv_kernel_size"]) == (2304, 32, 128, 4)
    assert (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["num_attention_heads"]) == (512, 128, 64, 128, 32)
    assert cfg["q_lora_rank"] is None and cfg["mla_use_nope"] is True
    assert (cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_token"], cfg["routed_scaling_factor"],
            cfg["intermediate_size"]) == (1024, 256, 8, 2.446, 9216)
    assert cfg["state_dtype"] == "float32"
    assert cfg["experts_held"] * cfg["deployment"]["chips_sharing_a_layer"] \
        == cfg["num_experts"]
    assert cfg["ep_rank"] == cfg["deployment"]["rank_held"]
    assert cfg["published"]["full_attn_layers"] == la["full_attn_layers"]
    assert cfg["published"]["kda_layers"] == la["kda_layers"]
    # the aliases the shared readers read say what Kimi's own keys say
    assert cfg["n_routed_experts"] == cfg["num_experts"]
    assert cfg["n_shared_experts"] == cfg["num_shared_experts"]
    # the guide's floors: whole periods past the dense layer, 8 experts,
    # 1/8 of the vocabulary
    assert (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]) % 4 == 0
    assert cfg["experts_held"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["server_flags"]["param_dtype"] == cfg["param_dtype"] \
        == "bfloat16"
    assert cfg["server_flags"]["slots"] == 128
    assert cfg["server_flags"]["prefill_chunk"] == 128


def test_dsl_defaults_equal_the_configuration_file():
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
    la = cfg["linear_attn_config"]
    nested = {"kda_num_heads": la["num_heads"],
              "kda_head_dim": la["head_dim"],
              "short_conv_kernel_size": la["short_conv_kernel_size"]}
    checked = 0
    for name, text in defaults.items():
        if name in sent:
            continue
        if name == "full_attn_layers":
            assert [int(i) for i in text.strip('"').split(",")] == \
                la["full_attn_layers"]
        else:
            want = nested[name] if name in nested else cfg[name]
            assert float(text) == float(want), name
        checked += 1
    assert checked == 20
    assert float(defaults["rope_theta"]) == float(cfg["rope_theta"])


@pytest.mark.parametrize("depth,want", [
    (13, "KKKAKKKAKKKAK"), (2, "KA"), (27, "KKKAKKKAKKKAKKKAKKKAKKKAKKA")])
def test_layer_kinds_by_depth(depth, want):
    """The published lists cut to the depth; a depth that holds no
    full-attention layer (a rehearsal at 2) ends in one."""
    from paddle_tpu.config.parser import parse_config
    cfg = _cfg(num_hidden_layers=depth)
    cwd = os.getcwd()
    os.chdir(ROOT)
    try:
        pc = parse_config(DSL, _args(cfg))
    finally:
        os.chdir(cwd)
    kinds = "".join({"kda_attention": "K", "mla_attention": "A"}[l.type]
                    for l in pc.model_config.layers
                    if l.type in ("kda_attention", "mla_attention"))
    assert kinds == want
    ffn = [l.type for l in pc.model_config.layers
           if l.type in ("gated_ffn", "moe")]
    assert ffn == ["gated_ffn"] + ["moe"] * (depth - 1)
    with open(JSON) as f:
        la = json.load(f)["linear_attn_config"]
    if depth == 27:
        assert [i + 1 for i, k in enumerate(kinds) if k == "K"] == \
            la["kda_layers"]
