"""Xing4.0 (the DeepSeek-V3 block — latent attention under YaRN, a dense MLP
then top-4 of 64 experts all held — on FOUR residual streams mixed by
Sinkhorn-normalised hyper-connections) against the plain reference
(benchmark/reference/xing4.py): the shared parity tests of
tests/model_parity.py over its case — the whole sequence with the controls
that must fail (a sublayer's phi zeroed; the reference with Sinkhorn cut to
one iteration, with H_post = sigmoid, with the dynamic term left out, with
one stream and a plain residual), prefill then decode and the ragged mixed
step through the paged latent cache by the jnp forms and by the interpreted
kernels (`mhc_mix`, `mla_paged_attn`), the configuration file against the
catalog row and the DSL's defaults — and what is this model's own: the two
ties that stand where no expert is absent (at hc_mult 1 with unit maps the
reference IS benchmark/reference/gigachat3.py on the same weights; the
stage's six layers are the first six of the reference built one layer
deeper), the absorbed form over the dense latent cache, the cut and its
bytes.  Its engines are tests/test_xing4_engine.py's; the maps, the read
and the write alone against their equations are tests/test_hyper_conn.py's.

Tolerances: as tests/model_parity.py says — float32 under "highest" leaves
1e-5 to 2e-5 between two orders of the same sums at these sizes; the case's
1e-4 is five times that (three layers, a 16-expert sum and two 20-iteration
Sinkhorn loops a layer whose divisions the program and the reference order
alike but fuse differently), and every control moves the logits by more
than fifty times it."""

import json

import numpy as np

from tests.model_parity import (  # noqa: F401
    CASES, ROOT, case, cfg, logits, model, pytest_generate_tests, ref,
    ref_logits,
    test_configuration_file_is_the_catalog_row_cut_as_it_says,
    test_dsl_defaults_equal_the_configuration_file,
    test_layer_kinds_by_depth,
    test_ragged_chunks_then_decode_through_the_pools_on_logits,
    test_reference_imports_nothing_of_the_program,
    test_weights_fit_the_programs_parameters,
    test_whole_sequence_logits_against_the_reference)

CASE = CASES["xing4"]


def test_weights_store_in_bf16_and_the_seeded_maps_are_large(model, ref):
    import jax
    c, _, w = model
    w16 = jax.eval_shape(lambda: ref.make_weights(
        dict(c, param_dtype="bfloat16"), 7))
    assert {str(v.dtype) for v in w16.values()} == {"bfloat16"}
    assert {k: v.shape for k, v in w16.items()} == \
        {k: v.shape for k, v in w.items()}
    # the gates near hc_alpha_init, not the published 0.01; biases non-zero
    a = np.asarray(w["_blk1_hc2_maps.w2"])
    assert a.shape == (1, 3) and (np.abs(a - c["hc_alpha_init"]) < 0.3).all()
    assert float(abs(w["_blk1_hc2_maps.w1"]).max()) > 0.1
    assert w["_blk1_hc2_maps.w0"].shape == (4 * 32, 24)
    assert float(abs(w["_blk1_moe.w4"]).max()) > 0      # the selection bias


def test_one_stream_with_unit_maps_is_the_gigachat_reference(ref):
    """The tie to an accepted model: at hc_mult 1 with H_pre = 1 (its bias
    at +30: sigmoid 1 - 1e-13), H_post = 2 sigmoid(0) = 1 and the gates 0,
    H_res is the 1 x 1 Sinkhorn fixed point 1 / (1 + eps) and a sublayer is
    h + F(RMSNorm(h)): benchmark/reference/gigachat3.py on the SAME weights
    (every expert held, one group) gives the same log-probabilities."""
    import jax.numpy as jnp
    from benchmark.lib.spec import Benchmark
    giga = Benchmark(ROOT).reference("gigachat3")
    c = cfg(CASE, hc_mult=1)
    w = dict(ref.make_weights(c, 5))
    for name in [k for k in w if k.endswith("_maps.w1")]:
        w[name] = jnp.asarray([[30.0, 0.0, 0.0]], jnp.float32)
        w[name[:-1] + "2"] = jnp.zeros((1, 3), jnp.float32)
    gc = dict(c, experts_held=c["n_routed_experts"], ep_rank=0)
    gw = {k: v for k, v in w.items() if "_maps." not in k}
    seq = np.random.default_rng(2).integers(0, c["vocab_size"], 24)
    mine = ref_logits(ref, c, w, seq)
    theirs = ref_logits(giga, gc, gw, seq)
    # 12 sublayers x 20 iterations of x / (x + 1e-6): the streams shrink by
    # 4e-5 a sublayer, which the norms take out; what is left is rounding
    assert float(np.abs(mine - theirs).max()) < 1e-4
    # and the tie sees the maps: a post bias off zero breaks it
    for name in [k for k in w if k.endswith("hc2_maps.w1")]:
        w[name] = jnp.asarray([[30.0, 1.0, 0.0]], jnp.float32)
    assert float(np.abs(ref_logits(ref, c, w, seq) - theirs).max()) > 1e-2


def test_the_stage_is_the_first_layers_of_the_deeper_reference(model, ref):
    """The cut is the depth alone: the streams after this stage's three
    layers are the streams after layer 2 of the reference built one layer
    deeper on the same weights (its further layer's drawn beside them)."""
    import jax
    import jax.numpy as jnp
    c, _, w = model
    deep = cfg(CASE, num_hidden_layers=c["num_hidden_layers"] + 1)
    wd = ref.make_weights(deep, 7)
    assert all(bool((wd[k] == v).all()) for k, v in w.items())
    seq = jnp.asarray(np.random.default_rng(3).integers(
        0, c["vocab_size"], 24))

    def streams(cfg_, w_, upto):
        # hidden_states with the final norm's scale at one and the head
        # left out, cut after `upto` layers
        return ref.hidden_states(
            dict(w_, **{"_final_ln.w0": jnp.ones_like(w_["_final_ln.w0"])}),
            dict(cfg_, num_hidden_layers=upto), seq)

    with jax.default_matmul_precision("highest"):
        a = streams(c, w, c["num_hidden_layers"])
        b = streams(deep, wd, c["num_hidden_layers"])
        further = streams(deep, wd, deep["num_hidden_layers"])
    assert float(jnp.abs(a - b).max()) == 0.0
    assert float(jnp.abs(further - b).max()) > 1e-2


def test_absorbed_over_a_dense_cache_equals_expanded(model):
    """Prefill 9 tokens into the dense latent cache (expanded form), decode
    5 more one at a time (absorbed form) through the hyper-connected
    blocks: the whole-sequence forward's logits, position for position."""
    import jax.numpy as jnp
    from paddle_tpu.graph.lm_decode import init_kv_caches
    c, ex, w = model
    ids = np.random.default_rng(2).integers(0, c["vocab_size"], (2, 14))
    whole, _ = logits(ex, w, ids)
    lp, st = logits(ex, w, ids[:, :9], init_kv_caches(ex, 2, 14))
    assert float(jnp.abs(lp - whole[:, :9]).max()) < 5e-5
    for t in range(9, 14):
        lp, st = logits(ex, w, ids[:, t:t + 1], st)
        assert float(jnp.abs(lp[:, 0] - whole[:, t]).max()) < 1e-4


def test_the_graph_holds_two_stream_passes_a_layer(model):
    c, ex, _ = model
    kinds = [l.type for l in ex.model.layers]
    n = c["num_hidden_layers"]
    assert kinds.count("hyper_write") == kinds.count("hyper_read") == \
        kinds.count("hyper_maps") == 2 * n
    assert kinds.count("hyper_expand") == kinds.count("hyper_collapse") == 1
    assert "addto" not in kinds
    sizes = {l.name: l.size for l in ex.model.layers}
    assert sizes["hc_expand"] == sizes["blk0_res1"] == 4 * c["hidden_size"]
    assert sizes["blk0_hc1_maps"] == 24 and sizes["blk0_hc1"] == 32
    maps = next(l for l in ex.model.layers if l.name == "blk2_hc2_maps")
    assert maps.attrs["sinkhorn_iters"] == 20 and maps.attrs["eps"] == 1e-6
    assert maps.attrs["res_clamp"] == [-30.0, 30.0]


def test_the_cut_its_bytes_and_the_guides_floors():
    """Every number of the file's `departures` and `published` is
    benchmark/lib/mhc_latent_moe.py's; the guide's floors hold; the traffic
    file is ISSUE 57's."""
    from benchmark.lib import mhc_latent_moe as lib
    with open(CASE.json_path) as f:
        c = json.load(f)
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["experts_held"] == c["n_routed_experts"] == 64
    assert c["ep_size"] == 1 and c["hc_mult"] == 4
    p = lib.stage_params(c)
    r = lib.resident_bytes(c)
    text = " ".join(c["departures"])
    assert f"{p['total'] / 1e6:,.1f} M parameters" in text
    assert f"{2 * p['total'] / 1e9:.3f} GB in bf16" in text
    for part, gb in (("attention", 0.341), ("routed_experts", 7.046),
                     ("dense_mlp", 0.198), ("maps", 0.008),
                     ("embedding_head", 1.879), ("latent_pool", 2.718)):
        assert round(r[part] / 1e9, 3) == gb and f"{gb:.3f} GB" in text, part
    assert round((r["shared_experts"] + r["router"]) / 1e9, 3) == 0.112
    assert round(r["total"] / 1e9, 2) == 12.30 and "12.30 GB" in text
    pub = lib.published_params(c)
    assert round(pub["total"] / 1e9, 2) == 29.51
    assert round(pub["active"] / 1e9, 2) == 3.93
    assert "29.51 B parameters, 3.93 B active" in c["published"]["params"]
    f = c["server_flags"]
    assert (f["slots"], f["prefill_chunk"], f["max_step_tokens"],
            f["max_context"], f["weights"]) == (48, 512, 1088, 8192,
                                                "deferred")
    with open(f"{ROOT}/benchmark/traffic/long-prompt-48.json") as fh:
        t = json.load(fh)
    assert (t["clients"], t["requests_per_client"], t["prompt_len"],
            t["output_len"], t["output_len_step"], t["ramp_s"], t["drain_s"],
            t["check_requests"], t["check_max_tokens"], t["trace_s"],
            t["max_context"], t["loop"]) == (
        48, 12, {"dist": "uniform", "lo": 2048, "hi": 7168},
        {"dist": "uniform", "lo": 128, "hi": 512}, 64, 20.0, 0.0, 6, 4096,
        12.0, 8192, "closed")
    for item in ("sinkhorn_order", "hc_eps", "res_clamp", "maps_norm",
                 "expand_collapse", "both_sublayers", "seeded_maps",
                 "select_bias_std", "init_std"):
        assert item in c["assumed"], item
