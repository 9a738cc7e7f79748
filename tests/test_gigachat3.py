"""GigaChat3 / DeepSeek-V3 block through the normal path at a tiny size on
the CPU, seeded weights, float32: the program (config DSL -> GraphExecutor
-> ServingEngine) against the plain reference
(benchmark/reference/gigachat3.py) and against itself across its paths —
whole sequence (expanded latent attention), dense latent cache, paged
latent pool (decode and mixed steps, jnp and the Pallas kernel interpreted)
— plus the pieces the configuration forced: YaRN frequencies, the
expert-parallel share, the latent pool under COW / transfer / spill,
build_engine without a Trainer, and the DSL's defaults against the
configuration file."""

import json
import math
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON = os.path.join(ROOT, "benchmark", "configs",
                    "gigachat3.1-702b-a36b-serve.json")
DSL = os.path.join(ROOT, "benchmark", "configs", "gigachat3.py")

TINY = dict(hidden_size=32, intermediate_size=64, num_attention_heads=4,
            num_hidden_layers=2, vocab_size=64, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=12, moe_intermediate_size=16, n_routed_experts=16,
            experts_held=4, ep_rank=1, n_group=4, topk_group=2,
            num_experts_per_tok=4, param_dtype="float32", init_std=0.3,
            select_bias_std=0.3)


def _args(cfg: dict, compute_dtype: str = "", attn_impl: str = "dense"):
    return (f"vocab={cfg['vocab_size']},dim={cfg['hidden_size']},"
            f"layers={cfg['num_hidden_layers']},"
            f"heads={cfg['num_attention_heads']},"
            f"ffn={cfg['intermediate_size']},compute_dtype={compute_dtype},"
            f"attn_impl={attn_impl},init_std={cfg['init_std']},"
            + ",".join(f"{k}={cfg[k]}" for k in (
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size",
                "n_routed_experts", "experts_held", "ep_rank", "n_group",
                "topk_group", "num_experts_per_tok",
                "first_k_dense_replace")))


def _cfg(**over):
    with open(JSON) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg.update(over)
    return cfg


def _build(cfg):
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.graph import GraphExecutor
    cwd = os.getcwd()
    os.chdir(ROOT)
    try:
        pc = parse_config(DSL, _args(cfg))
    finally:
        os.chdir(cwd)
    return GraphExecutor(pc.model_config, compute_dtype="")


@pytest.fixture(scope="module")
def ref():
    from benchmark.lib.spec import Benchmark
    return Benchmark(ROOT).reference("gigachat3")


@pytest.fixture(scope="module")
def model(ref):
    cfg = _cfg()
    return cfg, _build(cfg), ref.make_weights(cfg, 7)


def _logits(ex, w, ids, state=None, lengths=None):
    """Log-probabilities [B, T, V] of the head, and the new state."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parameter.argument import Argument
    ids = jnp.asarray(ids, jnp.int32)
    n = jnp.full((ids.shape[0],), ids.shape[1], jnp.int32) \
        if lengths is None else jnp.asarray(lengths, jnp.int32)
    with jax.default_matmul_precision("highest"):
        out, _, st = ex.forward(w, {"tokens": Argument(ids=ids, lengths=n)},
                                state, "test", None)
    return jnp.log(out["lm_head"].value), st


# -- the reference ------------------------------------------------------------

def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "gigachat3.py")) as f:
        src = f.read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]


def test_weights_fit_the_programs_parameters(model, ref):
    import jax
    cfg, ex, w = model
    shapes = jax.eval_shape(ex.init_params, jax.random.PRNGKey(0))
    assert {k: (v.shape, str(v.dtype)) for k, v in shapes.items()} == \
        {k: (v.shape, str(v.dtype)) for k, v in w.items()}
    # bf16 storage: the same names and shapes in the stored dtype
    w16 = jax.eval_shape(lambda: ref.make_weights(
        dict(cfg, param_dtype="bfloat16"), 7))
    assert {str(v.dtype) for v in w16.values()} == {"bfloat16"}
    # the router's selection bias is non-zero (selection != weighting)
    assert float(abs(w["_blk1_moe.w4"]).max()) > 0


def test_whole_sequence_logits_match_the_reference(model, ref):
    import jax
    import jax.numpy as jnp
    cfg, ex, w = model
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (1, 24))
    got, _ = _logits(ex, w, ids)
    with jax.default_matmul_precision("highest"):
        want = ref.jitted("log_probs", cfg)(w, jnp.asarray(ids[0]),
                                            jnp.arange(24))
    assert float(jnp.abs(got[0] - want).max()) < 2e-5


def test_chunked_prefill_then_decode_through_the_latent_pool_on_logits(
        model, ref):
    """A 13-token prompt in ragged chunks of 5 rows (a mixed step's shape:
    chunk rows of slot 1 beside a padding row), then 6 decode steps of two
    slots, through a latent PagedKVCache — every position's logits against
    ONE full reference forward of the 19 tokens."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving import PagedKVCache
    cfg, ex, w = model
    rng = np.random.default_rng(1)
    seq = rng.integers(0, cfg["vocab_size"], 19)
    P = 13
    kv = PagedKVCache(ex, num_slots=2, page_size=4, pages_per_slot=6)
    assert kv.try_grow(1, 19) and kv.try_grow(0, 19)
    table = jnp.asarray(np.vstack([kv.table, np.zeros((1, 6), np.int32)]))
    pools = kv.pools
    got = np.zeros((19, cfg["vocab_size"]), np.float32)

    def state_of(pools, **kw):
        return {n: dict(kv_pages=p["kv"], **kw) for n, p in pools.items()}

    for c0 in range(0, P, 5):
        rows = list(range(c0, min(c0 + 5, P)))
        pad = 6 - len(rows)             # padding rows aim at trash row 2
        ids = np.concatenate([seq[rows], np.zeros(pad, int)])[None]
        st = state_of(pools, page_table=table,
                      row_slot=jnp.asarray([1] * len(rows) + [2] * pad),
                      row_pos=jnp.asarray(rows + [0] * pad))
        lp, out = _logits(ex, w, ids, st)
        got[rows] = np.asarray(lp[0, :len(rows)])
        pools = {n: {"kv": out[n]["kv_pages"]} for n in pools}
    pos = jnp.asarray([0, P], jnp.int32)       # slot 0 idles on garbage
    for t in range(P, 19):
        st = state_of(pools, page_table=table[:2], pos=pos)
        lp, out = _logits(ex, w, np.asarray([[0], [seq[t]]]), st)
        got[t] = np.asarray(lp[1, 0])
        pools = {n: {"kv": out[n]["kv_pages"]} for n in pools}
        pos = out["blk0_attn"]["pos"].at[0].set(0)
    with jax.default_matmul_precision("highest"):
        want = ref.jitted("log_probs", cfg)(w, jnp.asarray(seq),
                                            jnp.arange(19))
    assert float(np.abs(got - np.asarray(want)).max()) < 5e-5


def test_absorbed_over_a_dense_cache_equals_expanded(model):
    """Prefill 9 tokens into the dense latent cache (expanded form), decode
    5 more one at a time (absorbed form): the logits of the whole-sequence
    forward, position for position."""
    import jax.numpy as jnp
    from paddle_tpu.graph.lm_decode import init_kv_caches
    cfg, ex, w = model
    ids = np.random.default_rng(2).integers(0, cfg["vocab_size"], (2, 14))
    whole, _ = _logits(ex, w, ids)
    lp, st = _logits(ex, w, ids[:, :9], init_kv_caches(ex, 2, 14))
    assert float(jnp.abs(lp - whole[:, :9]).max()) < 2e-5
    for t in range(9, 14):
        lp, st = _logits(ex, w, ids[:, t:t + 1], st)
        assert float(jnp.abs(lp[:, 0] - whole[:, t]).max()) < 5e-5


@pytest.mark.parametrize("chunk,kernel", [(4, False), (4, True),
                                          (32, False)],
                         ids=["chunked-jnp", "chunked-kernel", "one-chunk"])
def test_engine_greedy_tokens_match_lm_generate(model, chunk, kernel,
                                                monkeypatch):
    import jax
    from paddle_tpu.graph.lm_decode import lm_generate
    from paddle_tpu.serving import Request, ServingEngine
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1" if kernel else "0")
    cfg, ex, w = model
    rng = np.random.default_rng(3)
    reqs = [Request(f"r{i}", rng.integers(2, 64, n).astype(np.int32),
                    max_new=6, rng=jax.random.PRNGKey(40 + i))
            for i, n in enumerate((3, 19, 9, 17))]
    eng = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=32,
                        prefill_chunk=chunk,
                        # one-chunk: a whole prompt in ONE mixed step
                        max_step_tokens=7 if chunk == 4 else None)
    results = eng.run(reqs)
    for r in reqs:
        toks, lens = lm_generate(ex, w, r.prompt_ids[None, :],
                                 max_new=r.max_new, rng=r.rng)
        np.testing.assert_array_equal(
            np.asarray(toks)[0, :int(np.asarray(lens)[0])],
            results[r.req_id])
    eng.kv.check_reclaimed()
    # the held experts' load reached the engine's counters with the tokens
    assert eng.moe_steps == eng.n_decode_steps > 0
    assert 0 < eng.moe_pairs_max_sum <= eng.moe_pairs_total


# -- the pieces -----------------------------------------------------------------

def test_yarn_frequencies_against_a_hand_table():
    """dim 64, base 100000, factor 64, 4096 original positions, beta 32/1:
    the correction dims are floor(8.41) = 8 and ceil(18.04) = 19, so pairs
    0..8 keep theta^(-2i/64), pairs 19.. take it over 64, and pair 13 sits
    5/11 of the way down the ramp."""
    from paddle_tpu.ops import mla
    rs = dict(factor=64, original_max_position_embeddings=4096, beta_fast=32,
              beta_slow=1, mscale=1, mscale_all_dim=1)
    f = mla.yarn_inv_freq(64, 100000.0, rs)
    base = 100000.0 ** (-np.arange(32) / 32.0)
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e5))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(1e5))
    assert (math.floor(low), math.ceil(high)) == (8, 19)
    np.testing.assert_allclose(f[:9], base[:9], rtol=1e-6)
    np.testing.assert_allclose(f[19:], base[19:] / 64, rtol=1e-6)
    r = 5 / 11
    np.testing.assert_allclose(f[13], base[13] * (1 - r) + base[13] / 64 * r,
                               rtol=1e-6)
    # the temperature: 0.1 ln 64 + 1, squared into the softmax scale
    m = 0.1 * math.log(64) + 1
    assert mla.softmax_scale(192, rs) == pytest.approx(192 ** -0.5 * m * m)
    assert mla.rope_amplitude(rs) == pytest.approx(1.0)
    # no scaling: plain rotary frequencies
    np.testing.assert_allclose(mla.yarn_inv_freq(64, 1e5, None), base,
                               rtol=1e-6)


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
                top_k=4, first_expert=4 * rank, scoring="sigmoid", n_group=4,
                topk_group=2, select_bias=wl["moe.w4"].reshape(-1),
                scale=2.5)
            total = total + y
    assert float(jnp.abs(total - want).max()) < 2e-5
    # and the program's own rank-1 layer is the reference's rank-1 layer
    cut = _cfg()
    with jax.default_matmul_precision("highest"):
        one = ref._moe(cut, {k: (v[4:8] if k in ("moe.w1", "moe.w2", "moe.w3")
                                 else v) for k, v in wl.items()}, x, None)
        sl = slice(4, 8)
        y, _, _ = moe_ffn(
            x, wl["moe.w0"],
            (wl["moe.w1"][sl], wl["moe.w2"][sl], wl["moe.w3"][sl]), top_k=4,
            first_expert=4, scoring="sigmoid", n_group=4, topk_group=2,
            select_bias=wl["moe.w4"].reshape(-1), scale=2.5)
        mine = y + gated_ffn(x, wl["moe.w5"], wl["moe.w6"], wl["moe.w7"])
    assert float(jnp.abs(mine - one).max()) < 2e-5


def test_latent_pool_cow_transfer_and_spill_round_trips(model):
    """One tensor a layer, 128 lanes wide here (20 -> 128): the allocator's
    COW copy, export -> import into another pool, and spill -> restore all
    carry a marker row bit-exactly, and the allocators stay consistent."""
    from paddle_tpu.serving import PagedKVCache
    cfg, ex, _ = model

    def kv(**kw):
        return PagedKVCache(ex, num_slots=2, page_size=4, pages_per_slot=3,
                            num_pages=8, **kw)

    src, dst = kv(spill_bytes_budget=1 << 20), kv()
    name = sorted(src.pools)[0]
    assert list(src.pools[name]) == ["kv"]
    assert src.pools[name]["kv"].shape == (8, 4, 128)
    assert src.layer_specs[name] == (128,)
    assert src.page_nbytes == 2 * 4 * 128 * 4
    assert src.pool_bytes == 2 * 8 * 4 * 128 * 4

    assert src.try_grow(0, 12)
    pages = [int(src.table[0, j]) for j in range(3)]
    src.pools[name]["kv"] = src.pools[name]["kv"].at[pages[1], 2, 5].set(7.5)
    for p in pages:
        src.cache_page(p)
    # COW: slot 1 maps the shared run, then writes into its boundary page
    src.map_shared(1, pages[:2])
    assert src.ensure_writable(1, 1) is True
    mine = int(src.table[1, 1])
    assert mine != pages[1]
    assert float(src.pools[name]["kv"][mine, 2, 5]) == 7.5
    src.release(1)
    src.release(0)
    src.check()

    # transfer
    meta, payload = src.export_pages(pages)
    assert meta["layers"][0]["parts"] == ["kv"]
    assert meta["layers"][0]["row"] == [128]
    assert len(payload) == 3 * src.page_nbytes
    taken = dst.take_pages(3)
    with pytest.raises(ValueError):
        dst.import_pages(dict(meta, layers=[dict(meta["layers"][0], row=[64])]
                              + meta["layers"][1:]), payload, taken)
    dst.import_pages(meta, payload, taken)
    dst.adopt_restored(taken)
    assert float(dst.pools[name]["kv"][taken[1], 2, 5]) == 7.5
    dst.check()

    # spill and restore
    hid = src.spill_page(pages[1])
    assert hid is not None and src.host_bytes == src.page_nbytes
    back = src.take_pages(1)
    src.restore_pages([hid], back)
    src.adopt_restored(back)
    assert float(src.pools[name]["kv"][back[0], 2, 5]) == 7.5
    for p in (pages[0], pages[2], back[0]):
        src.uncache_page(p)
    for p in taken:
        dst.uncache_page(p)
    src.check_reclaimed()
    dst.check_reclaimed()


def test_latent_layers_refuse_a_model_mesh(model):
    from paddle_tpu.parallel.mesh import model_mesh
    from paddle_tpu.serving import ServingEngine
    cfg, ex, w = model
    with pytest.raises(ValueError, match="latent"):
        ServingEngine(ex, w, num_slots=2, page_size=4, max_context=32,
                      mesh=model_mesh(2))


# -- build_engine ----------------------------------------------------------------

def _serve_args(config, config_args, **kw):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tools_serve_t", os.path.join(ROOT, "tools", "serve.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    got = {}

    async def capture(a):
        got["args"] = a
        return 0

    tool.amain = capture
    argv = ["--config", config, "--config-args", config_args, "--slots", "2",
            "--page-size", "4", "--max-context", "32"]
    for k, v in kw.items():
        argv += ["--" + k.replace("_", "-"), str(v)]
    tool.main(argv)
    return tool, got["args"]


def test_build_engine_holds_no_optimizer_state_and_serves_bf16(monkeypatch):
    """tools/serve.py:build_engine for the new model: no Trainer is built
    (its import would fail the test), parameters come out in --param-dtype,
    and the engine serves."""
    import sys

    import jax
    from paddle_tpu.serving import Request
    cfg = _cfg()
    monkeypatch.chdir(ROOT)
    monkeypatch.setitem(sys.modules, "paddle_tpu.trainer.trainer", None)
    tool, args = _serve_args(DSL, _args(cfg), param_dtype="bfloat16")
    eng = tool.build_engine(args)
    assert {str(v.dtype) for v in eng.params.values()} == {"bfloat16"}
    live = sum(x.nbytes for x in jax.live_arrays())
    weights = sum(v.nbytes for v in eng.params.values())
    assert live < weights + eng.kv.pool_bytes + (1 << 20), \
        "something beside the weights and the pool is resident"
    out = eng.run([Request("a", np.asarray([3, 5, 7], np.int32), max_new=4)])
    assert len(out["a"]) == 7


def test_default_param_dtype_keeps_the_starcoder2_cells_parameters():
    """Without --param-dtype the engine's parameters are what the Trainer
    gave it before: the same names, shapes, dtypes and — the same seed —
    values."""
    import jax
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.trainer.trainer import Trainer
    cargs = ("vocab=64,dim=32,layers=1,heads=4,kv_heads=2,ffn=64,"
             "batch_size=1,compute_dtype=bfloat16,attn_impl=dense")
    cwd = os.getcwd()
    os.chdir(ROOT)
    try:
        tool, args = _serve_args("benchmark/configs/starcoder2.py", cargs,
                                 seed=5)
        executor, params = tool.build_model(args)
        tr = Trainer(parse_config("benchmark/configs/starcoder2.py", cargs),
                     seed=5)
    finally:
        os.chdir(cwd)
    assert executor.compute_dtype == tr.executor.compute_dtype == "bfloat16"
    assert list(params) == list(tr.params)
    for k, v in tr.params.items():
        assert (params[k].shape, params[k].dtype) == (v.shape, v.dtype)
        assert bool((params[k] == v).all()), k


# -- the configuration ------------------------------------------------------------

def test_configuration_file_is_the_catalog_row_cut_as_it_says():
    with open(JSON) as f:
        cfg = json.load(f)
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(cat):
        with open(cat) as f:
            row = next(json.loads(ln) for ln in f
                       if '"GigaChat3.1-702B-A36B"' in ln)
        assert cfg["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k in cfg["reduced"] and k != "n_routed_experts":
                assert cfg[k] != v and cfg["published"][k] == v, k
            else:
                assert cfg[k] == v, k
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert cfg["experts_held"] * cfg["deployment"]["chips_sharing_a_layer"] \
        == cfg["n_routed_experts"]
    assert cfg["ep_rank"] == cfg["deployment"]["rank_held"]
    # the guide's floors: a period + 4 expert layers, 8 experts, 1/8 vocab
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["experts_held"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["server_flags"]["param_dtype"] == cfg["param_dtype"] \
        == "bfloat16"


def test_dsl_defaults_equal_the_configuration_file():
    """benchmark/kinds/serve.py sends ten sizes; every other one reaches
    the model as the DSL file's default — held to the JSON here."""
    import re
    with open(JSON) as f:
        cfg = json.load(f)
    with open(DSL) as f:
        src = f.read()
    defaults = {m.group(1): m.group(2) for m in re.finditer(
        r'get_config_arg\(\s*"(\w+)",\s*\w+,\s*([^)]+)\)', src)}
    sent = {"vocab", "dim", "layers", "heads", "kv_heads", "ffn",
            "rope_theta", "batch_size", "compute_dtype", "attn_impl",
            "seq_len"}
    checked = 0
    for name, text in defaults.items():
        if name in sent:
            continue
        if name.startswith("rope_") and name != "rope_theta":
            want = cfg["rope_scaling"][name[len("rope_"):]]
        else:
            want = cfg[name]
        assert float(text) == float(want), name
        checked += 1
    assert checked == 23
    assert float(defaults["rope_theta"]) == float(cfg["rope_theta"])
