"""Nemotron-H (Mamba-2 state-space layers + grouped-query attention without
rotation + bias-free relu^2 experts with a shared expert, every block ONE
mixer) through the normal path at a tiny size on the CPU, seeded weights,
float32: the program (config DSL -> GraphExecutor -> ServingEngine) against
the plain reference (benchmark/reference/nemotron_h.py) and against itself
across its paths — the whole sequence chunkwise, the decode step and the
ragged mixed step through the cache manager's slot parts, the scanned step —
plus what a third kind of slot state forced: the parts declared by the layer
type, the float32 state pool, paused slots, re-admission, a stack with no
page-indexed part, the refusals, the third expert form, and the
expert-parallel share.

The tolerances: float32 under `jax.default_matmul_precision("highest")`
leaves 1e-5 to 2e-5 between two orders of the same sums at these sizes
(init_std 0.3, so the logits spread over several nats); 2e-4 on
log-probabilities is ten times that and a hundred times under what a state
kept in bfloat16 (5e-2) or a dropped `D x` term (1e-1) move them by — both
are tried below and must fail."""

import json
import os

import numpy as np
import pytest

# the executor's log-probabilities and the cache manager's pools as a
# layer's state: the helpers the two other recurrent kinds' tests use
from tests.test_lfm2_moe import (_logits, _pools_of, _slot_cache,  # noqa: F401
                                 _state_of)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON = os.path.join(ROOT, "benchmark", "configs",
                    "nemotron3-nano-30b-a3b-serve.json")
DSL = os.path.join(ROOT, "benchmark", "configs", "nemotron_h.py")
TOL = 2e-4

# the published ratios at a tiny size: 4 Mamba-2 heads of 16 in 2 groups,
# state 16; 4 query heads over 2 KV heads of 16, a width (64) the hidden
# size (48) is not; 16 experts, top-3; published layers 3-7, `MEM*E`
TINY = dict(hidden_size=48, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, num_hidden_layers=5, first_layer=3, vocab_size=64,
            mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
            n_groups=2, chunk_size=8, moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=40, n_routed_experts=16,
            experts_held=16, ep_rank=0, num_experts_per_tok=3,
            param_dtype="float32", init_std=0.3, select_bias_std=0.3)
SSMS = ["blk0_ssm", "blk2_ssm"]
DSL_KEYS = ("head_dim", "mamba_num_heads", "mamba_head_dim",
            "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "n_routed_experts", "experts_held", "ep_rank",
            "num_experts_per_tok", "routed_scaling_factor", "norm_eps")


def _cfg(**over):
    with open(JSON) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg.update(over)
    return cfg


def _pattern(cfg):
    first = cfg["first_layer"] - 1
    return cfg["hybrid_override_pattern"][
        first:first + cfg["num_hidden_layers"]]


def _args(cfg: dict, attn_impl: str = "dense", **extra):
    return (f"vocab={cfg['vocab_size']},dim={cfg['hidden_size']},"
            f"layers={cfg['num_hidden_layers']},"
            f"heads={cfg['num_attention_heads']},"
            f"kv_heads={cfg['num_key_value_heads']},"
            f"ffn={cfg['intermediate_size']},"
            f"rope_theta={cfg['rope_theta']},compute_dtype=,"
            f"attn_impl={attn_impl},init_std={cfg['init_std']},"
            f"pattern={_pattern(cfg)},"
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
    return Benchmark(ROOT).reference("nemotron_h")


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
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "nemotron_h.py")) as f:
        src = f.read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]


def test_weights_fit_the_programs_parameters(model):
    import jax
    cfg, ex, w = model
    shapes = jax.eval_shape(ex.init_params, jax.random.PRNGKey(0))
    assert {k: (v.shape, str(v.dtype)) for k, v in shapes.items()} == \
        {k: (v.shape, str(v.dtype)) for k, v in w.items()}
    # every block is one norm and ONE mixer: no second norm, no FFN
    kinds = {"M": "ssm", "E": "moe", "*": "attn"}
    for i, letter in enumerate(_pattern(cfg)):
        mine = {k.split(".")[0] for k in w if k.startswith(f"_blk{i}_")}
        assert mine == {f"_blk{i}_ln", f"_blk{i}_{kinds[letter]}"}, mine
    # in: z 64 + xBC 64 + 2 x 2 x 16 + dt 4; 4 taps and a bias over x, B, C
    assert w["_blk0_ssm.w0"].shape == (48, 64 + 128 + 4)
    assert w["_blk0_ssm.w1"].shape == (4, 128)
    assert w["_blk0_ssm.w2"].shape == (1, 128)
    assert abs(np.asarray(w["_blk0_ssm.w1"])).max() <= 0.5
    a_log = np.asarray(w["_blk0_ssm.w3"])
    assert a_log.min() >= 0 and a_log.max() <= np.log(16.0) + 1e-6
    assert bool((np.asarray(w["_blk0_ssm.w4"]) == 1).all())      # D
    dt = np.log1p(np.exp(np.asarray(w["_blk0_ssm.w5"], np.float64)))
    assert dt.min() >= 1e-3 * (1 - 1e-4) and dt.max() <= 0.1 * (1 + 1e-4)
    # bias-free non-gated experts: two stacks, no bias; the shared expert
    # of the same form; attention 4 heads of 16 beside a hidden size of 48
    assert w["_blk1_moe.w1"].shape == (16, 48, 24)
    assert w["_blk1_moe.w2"].shape == (16, 24, 48)
    assert w["_blk1_moe.w4"].shape == (48, 40)
    assert w["_blk1_moe.w5"].shape == (40, 48)
    assert w["_blk3_attn.w0"].shape == (48, 64)
    assert w["_blk3_attn.w3"].shape == (64, 48)
    # the program's own initializer draws the taps from the same range
    own = np.asarray(ex.init_params(jax.random.PRNGKey(1))["_blk0_ssm.w1"])
    assert 0.3 < abs(own).max() <= 0.5


def test_whole_sequence_logits_against_the_reference(model, ref):
    """40 tokens, five chunks of 8: the chunkwise form from the zero state
    against the reference's per-token scan."""
    cfg, ex, w = model
    seq = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    got, _ = _logits(ex, w, seq[None])
    want = _ref_logits(ref, cfg, w, seq)
    assert float(np.abs(np.asarray(got[0]) - want).max()) < TOL
    # the tolerance separates what it must: no `D x` term
    w0 = dict(w, **{"_blk0_ssm.w4": w["_blk0_ssm.w4"] * 0})
    off, _ = _logits(ex, w0, seq[None])
    assert float(np.abs(np.asarray(off[0]) - want).max()) > 50 * TOL


def test_attention_applies_no_rotation_unless_the_file_says_so(model, ref):
    cfg, ex, w = model
    attn = next(l for l in ex.model.layers if l.name == "blk3_attn")
    assert "use_rope" not in attn.attrs and attn.attrs["num_kv_heads"] == 2
    assert attn.size == 64                 # the heads' width, not the model's
    rot = _cfg(attn_use_rope=True)
    ex2 = _build(rot)
    assert next(l for l in ex2.model.layers
                if l.name == "blk3_attn").attrs["use_rope"] is True
    seq = np.random.default_rng(1).integers(0, 64, 16)
    got, _ = _logits(ex2, w, seq[None])
    assert float(np.abs(np.asarray(got[0]) -
                        _ref_logits(ref, rot, w, seq)).max()) < TOL
    assert float(np.abs(np.asarray(got[0]) -
                        _ref_logits(ref, cfg, w, seq)).max()) > 50 * TOL


# -- the three paths and the slot parts --------------------------------------------

def test_ragged_chunks_then_decode_through_the_slot_parts_on_logits(model,
                                                                    ref):
    """Slot 1's 23-token prompt in mixed steps whose chunk rows split it at
    uneven places — 1, 2, 4, 7 and 9 rows: inside a 4-tap window, inside
    and across the chunks of 8; segments that start at 0 and that continue
    from the slot's state — while slot 0 decodes beside it in the steps'
    decode rows, then 6 decode steps of both: every position's logits of
    both sequences against ONE full reference forward each."""
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
        # one decode row and one segment: two states moved a Mamba-2 layer
        assert [int(out[c]["updates"]) for c in SSMS] == [2] * 2
        assert int(out[SSMS[0]]["rows"]) == 1 + n
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
    want1 = _ref_logits(ref, cfg, w, seq1)
    assert float(np.abs(got0[:n0 + 6] - _ref_logits(
        ref, cfg, w, seq0[:n0 + 6])).max()) < TOL
    assert float(np.abs(got1 - want1).max()) < TOL
    # what the tolerance must separate: the same decode steps from a state
    # rounded to bfloat16 once
    rounded = {n: (dict(p, state=p["state"].astype(jnp.bfloat16).astype(
        jnp.float32)) if n in SSMS else p) for n, p in pools.items()}
    st = _state_of(kv, rounded, page_table=table[:S], pos=pos, run=run)
    nxt = rng.integers(0, cfg["vocab_size"], 2)
    lp_r, _ = _logits(ex, w, nxt[:, None], st)
    st = _state_of(kv, pools, page_table=table[:S], pos=pos, run=run)
    lp_e, _ = _logits(ex, w, nxt[:, None], st)
    assert float(np.abs(np.asarray(lp_r) - np.asarray(lp_e)).max()) > 5 * TOL


def test_a_paused_slots_state_and_tail_are_bit_equal_after_the_step(model):
    """The run mask reaches the Mamba-2 layers: a row whose mask is false
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
    assert sorted(kv.slot_specs) == sorted(SSMS)
    for n in SSMS:
        for part in ("state", "conv"):
            assert bool((out[n][part][1] == pools[n][part][1]).all()), n
            assert not bool((out[n][part][0] == pools[n][part][0]).all())
        # the tail moved on by one position
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
    """A third type in the one registry: the cache manager builds the
    Mamba-2 layer's float32 state (`state_dtype` of the configuration file)
    and compute-dtype tail from it, and names neither in serving/."""
    import jax.numpy as jnp
    from paddle_tpu.graph.registry import slot_state_types
    from paddle_tpu.serving import PagedKVCache
    from paddle_tpu.serving.paged_kv import slot_state_specs
    assert sorted(slot_state_types) == ["kda_attention", "mamba", "mamba2",
                                        "short_conv"]
    cfg = _cfg()
    ex = _build(cfg, compute_dtype="bfloat16")
    specs = slot_state_specs(ex.model, jnp.bfloat16)
    assert specs == {n: {"state": ((4, 16, 16), jnp.dtype(cfg["state_dtype"])),
                         "conv": ((3, 128), jnp.bfloat16)} for n in SSMS}
    kv = PagedKVCache(ex, num_slots=3, page_size=4, pages_per_slot=4)
    assert sorted(kv.layer_specs) == ["blk3_attn"]
    for n in SSMS:
        assert kv.pools[n]["state"].shape == (4, 4, 16, 16)
        assert str(kv.pools[n]["state"].dtype) == cfg["state_dtype"] \
            == "float32"
        assert str(kv.pools[n]["conv"].dtype) == "bfloat16"
    assert kv.slot_state_bytes == 2 * 4 * (4 * 16 * 16 * 4 + 3 * 128 * 2)
    assert kv.layer_specs["blk3_attn"] == (2, 16)
    with open(os.path.join(ROOT, "paddle_tpu", "serving",
                           "paged_kv.py")) as f:
        code = f.read().split('"""', 2)[2]
    assert "mamba" not in code and '"state"' not in code


def test_the_other_recurrent_kinds_pools_are_what_they_were():
    """The hybrid and the short-convolution models' slot parts and pool
    bytes after the lift into graph/slot_steps.py and ops/slot_rows.py: the
    numbers their own tests pinned before it (their logits and tokens
    through the lifted code are tests/test_kimi_linear.py's and
    tests/test_lfm2_moe.py's; that the KDA step still compiles to ONE
    kernel named `kda_step` is tests/test_mosaic_compile.py's)."""
    from paddle_tpu.serving import PagedKVCache
    import tests.test_kimi_linear as kimi
    import tests.test_lfm2_moe as lfm2
    kex = kimi._build(kimi._cfg())
    kv = PagedKVCache(kex, num_slots=3, page_size=4, pages_per_slot=4)
    assert kv.slot_specs["blk0_kda"] == {"state": (4, 8, 8), "conv": (3, 96)}
    n_kda = len(kv.slot_specs)
    assert kv.slot_state_bytes == n_kda * 4 * (4 * 8 * 8 * 4 + 3 * 96 * 4)
    lex = lfm2._build(lfm2._cfg())
    lv = PagedKVCache(lex, num_slots=3, page_size=4, pages_per_slot=4)
    assert lv.slot_specs == {n: {"conv": (2, 256)} for n in lfm2.CONVS}
    assert lv.slot_state_bytes == 4 * 4 * 2 * 256 * 4
    assert lv.pool_bytes == 2 * lv.num_pages * 4 * 128 * 4
