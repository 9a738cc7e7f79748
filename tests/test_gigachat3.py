"""GigaChat3 / DeepSeek-V3 block (latent attention under YaRN + MoE, an
expert-parallel rank's share) against the plain reference
(benchmark/reference/gigachat3.py): the shared parity tests of
tests/model_parity.py over its case — the whole sequence (expanded latent
attention), the decode and mixed steps through the paged latent pool, the
engine by the jnp forms and the Pallas kernel interpreted, the configuration
file — and what is this model's own: the absorbed form over the dense latent
cache, YaRN's frequencies, the expert-parallel share, the latent pool under
COW / transfer / spill, and build_engine without a Trainer."""

import json
import math

import numpy as np
import pytest

from tests.model_parity import (  # noqa: F401
    CASES, ROOT, case, cfg, engines, logits, model,
    pytest_generate_tests, ref, serve_argv, serve_tool,
    test_configuration_file_is_the_catalog_row_cut_as_it_says,
    test_dsl_defaults_equal_the_configuration_file,
    test_engine_serves_lm_generates_tokens,
    test_ragged_chunks_then_decode_through_the_pools_on_logits,
    test_reference_imports_nothing_of_the_program,
    test_slot_parts_are_declared_by_the_layer_type,
    test_weights_fit_the_programs_parameters,
    test_whole_sequence_logits_against_the_reference)

CASE = CASES["gigachat3"]


def test_weights_store_in_bf16_and_the_router_selects_by_its_bias(model,
                                                                   ref):
    import jax
    c, _, w = model
    # bf16 storage: the same names and shapes in the stored dtype
    w16 = jax.eval_shape(lambda: ref.make_weights(
        dict(c, param_dtype="bfloat16"), 7))
    assert {str(v.dtype) for v in w16.values()} == {"bfloat16"}
    assert {k: v.shape for k, v in w16.items()} == \
        {k: v.shape for k, v in w.items()}
    # the router's selection bias is non-zero (selection != weighting)
    assert float(abs(w["_blk1_moe.w4"]).max()) > 0


def test_absorbed_over_a_dense_cache_equals_expanded(model):
    """Prefill 9 tokens into the dense latent cache (expanded form), decode
    5 more one at a time (absorbed form): the logits of the whole-sequence
    forward, position for position."""
    import jax.numpy as jnp
    from paddle_tpu.graph.lm_decode import init_kv_caches
    cfg, ex, w = model
    ids = np.random.default_rng(2).integers(0, cfg["vocab_size"], (2, 14))
    whole, _ = logits(ex, w, ids)
    lp, st = logits(ex, w, ids[:, :9], init_kv_caches(ex, 2, 14))
    assert float(jnp.abs(lp - whole[:, :9]).max()) < 2e-5
    for t in range(9, 14):
        lp, st = logits(ex, w, ids[:, t:t + 1], st)
        assert float(jnp.abs(lp[:, 0] - whole[:, t]).max()) < 5e-5


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
                top_k=4, first_expert=4 * rank, scoring="sigmoid", n_group=4,
                topk_group=2, select_bias=wl["moe.w4"].reshape(-1),
                scale=2.5)
            total = total + y
    assert float(jnp.abs(total - want).max()) < 2e-5
    # and the program's own rank-1 layer is the reference's rank-1 layer
    cut = cfg(CASE)
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

def test_build_engine_holds_no_optimizer_state_and_serves_bf16(monkeypatch):
    """tools/serve.py:build_engine for the new model: no Trainer is built
    (its import would fail the test), parameters come out in --param-dtype,
    and the engine serves."""
    import gc
    import sys

    import jax
    from paddle_tpu.serving import Request
    monkeypatch.chdir(ROOT)
    monkeypatch.setitem(sys.modules, "paddle_tpu.trainer.trainer", None)
    tool, parse = serve_tool()
    eng = tool.build_engine(parse(serve_argv(
        CASE, cfg(CASE), "--param-dtype", "bfloat16", compute_dtype="")))
    assert {str(v.dtype) for v in eng.params.values()} == {"bfloat16"}
    # live_arrays() is the whole process's: collect what earlier tests of
    # this worker left in cycles (their trainers, engines), or the sum
    # depends on which files ran before this one
    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    weights = sum(v.nbytes for v in eng.params.values())
    assert live < weights + eng.kv.pool_bytes + (1 << 20), \
        "something beside the weights and the pool is resident"
    out = eng.run([Request("a", np.asarray([3, 5, 7], np.int32), max_new=4)])
    assert len(out["a"]) == 7


def test_default_param_dtype_keeps_the_starcoder2_cells_parameters(
        monkeypatch):
    """Without --param-dtype the engine's parameters are what the Trainer
    gave it before: the same names, shapes, dtypes and — the same seed —
    values."""
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.trainer.trainer import Trainer
    cargs = ("vocab=64,dim=32,layers=1,heads=4,kv_heads=2,ffn=64,"
             "batch_size=1,compute_dtype=bfloat16,attn_impl=dense")
    monkeypatch.chdir(ROOT)
    tool, parse = serve_tool()
    executor, params = tool.build_model(parse(
        ["--config", "benchmark/configs/starcoder2.py", "--config-args",
         cargs, "--slots", "2", "--page-size", "4", "--max-context", "32",
         "--seed", "5"]))
    tr = Trainer(parse_config("benchmark/configs/starcoder2.py", cargs),
                 seed=5)
    assert executor.compute_dtype == tr.executor.compute_dtype == "bfloat16"
    assert list(params) == list(tr.params)
    for k, v in tr.params.items():
        assert (params[k].shape, params[k].dtype) == (v.shape, v.dtype)
        assert bool((params[k] == v).all()), k


def test_the_cut_holds_a_ranks_experts_and_the_guides_floors():
    with open(CASE.json_path) as f:
        c = json.load(f)
    assert c["experts_held"] * c["deployment"]["chips_sharing_a_layer"] \
        == c["n_routed_experts"]
    assert c["ep_rank"] == c["deployment"]["rank_held"]
    # the guide's floors: a period + 4 expert layers, 8 experts, 1/8 vocab
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["experts_held"] >= 8
    assert c["vocab_size"] * 8 >= c["published"]["vocab_size"]
