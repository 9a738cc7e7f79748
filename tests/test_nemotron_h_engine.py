"""Nemotron-H through a real ServingEngine at the tiny size of
tests/test_nemotron_h.py (a file of its own because `--dist loadfile` gives
one file to one worker): the shared engine tests of tests/model_parity.py
over its case — chunked prefill then decode against the reference's one full
forward, the state through the interpreted `ssd_step`, `--decode-steps` 2,
free rows for a whole prompt (one segment of `ssd.segment_rows`), checkpoint
and restore, the refusals, tools/serve.py:build_engine — and what is this
model's own: the `ME` stack with no page-indexed part, the third expert form
in both formulations, the shared expert's form and the four-share test."""

import os

import numpy as np
import pytest

from tests.model_parity import (  # noqa: F401
    CASES, ROOT, args, build, case, cfg, engines, margin, model,
    nemotron_pattern, parse, pytest_generate_tests, ref, requests,
    test_build_engine_serves_the_model_in_bf16,
    test_checkpoint_and_restore_round_trip_the_slot_parts,
    test_engine_serves_lm_generates_tokens,
    test_what_needs_a_state_snapshot_is_refused_by_name)

CASE = CASES["nemotron_h"]


def test_a_stack_with_no_page_indexed_part_serves(ref):
    """`ME`, published layers 1-2 (what benchmark/rehearse.json's two layers
    cut this model to, and what a pipeline stage can be): no attention
    layer, so no pool is built; the page table and the allocator keep their
    logical pages, and the engine serves the reference's tokens."""
    import jax
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.graph import GraphExecutor
    from paddle_tpu.serving import PagedKVCache, ServingEngine
    c = cfg(CASE, num_hidden_layers=2, first_layer=1)
    assert nemotron_pattern(c) == "ME"
    ex, w = build(CASE, c), ref.make_weights(c, 5)
    reqs = requests((3, 19, 9, 17))
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                            prefill_chunk=5)
        assert eng.kv.layer_specs == {} and eng.kv.pool_bytes == 0
        assert sorted(eng.kv.slot_specs) == ["blk0_ssm"]
        results = eng.run(reqs)
    m = margin(ref, c, w, reqs, results)
    assert m["worst_nats"] < CASE.tol and m["tokens"] == 24, m
    eng.kv.check_reclaimed()
    snap = eng.checkpoint_state()
    assert snap["config"]["layer_specs"] == {}
    assert set(snap["pools"]) == {"blk0_ssm"}
    # a model with neither kind of part still has nothing to serve from
    plain = parse_config(os.path.join(ROOT, "demo", "quick_start",
                                      "trainer_config.lr.py"), "")
    with pytest.raises(AssertionError, match="no attention layers to page"):
        PagedKVCache(GraphExecutor(plain.model_config), num_slots=2,
                     page_size=4, pages_per_slot=2)


def _expert_layer(ref, rows):
    """Layer 1's weights under their names in the layer, `rows` inputs, and
    what every call of the relu^2 expert block takes."""
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe
    c = cfg(CASE)
    w = ref.make_weights(c, 11)
    wl = {k[len("_blk1_"):]: v for k, v in w.items()
          if k.startswith("_blk1_")}
    x = jnp.asarray(np.random.default_rng(5).normal(size=(rows, 48)),
                    jnp.float32)
    kw = dict(top_k=3, scoring="sigmoid",
              activation=moe.expert_activation("relu2"),
              select_bias=wl["moe.w3"].reshape(-1),
              scale=c["routed_scaling_factor"])
    return c, wl, x, kw


def test_relu2_experts_dense_against_grouped(ref):
    """The third expert form (w_up, w_down), relu^2, through both
    formulations of the block against the reference's loop over the held
    experts; the unknown name is refused."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe
    c, wl, x, kw = _expert_layer(ref, 40)
    act = kw["activation"]
    assert float(act(jnp.asarray(-2.0))) == 0 and float(
        act(jnp.asarray(3.0))) == 9
    with pytest.raises(ValueError, match="unknown expert activation"):
        moe.expert_activation("gelu")
    with jax.default_matmul_precision("highest"):
        want = ref._moe(c, wl, x, None) - ref._relu2_mlp(
            x, wl["moe.w4"], wl["moe.w5"], None)
        dense, _, _ = moe.moe_ffn(x, wl["moe.w0"],
                                  (wl["moe.w1"], wl["moe.w2"]),
                                  form="dense", **kw)
        grouped, _, _ = moe.moe_ffn(x, wl["moe.w0"],
                                    (wl["moe.w1"], wl["moe.w2"]),
                                    form="grouped", **kw)
    # relu^2 of init_std 0.3 gives outputs of tens: relative to their size
    tol = 2e-6 * float(jnp.abs(want).max())
    assert float(jnp.abs(dense - want).max()) < tol
    assert float(jnp.abs(grouped - want).max()) < tol
    # the rule is untouched: the cell's decode step dense, its mixed grouped
    assert moe.expert_form(256, 6, 128, 2) == "dense"
    assert moe.expert_form(512, 6, 128, 2) == "grouped"


def test_the_shared_expert_takes_the_experts_form(tmp_path):
    """Beside gated experts the shared expert is the gated product it was
    (three matrices); beside bias-free plain ones it is (w_up, w_down) with
    the experts' nonlinearity; beside plain experts with biases there is
    none to ask for."""
    from paddle_tpu.config.parser import parse_config
    giga = parse(CASES["gigachat3"], "vocab=64,dim=32,layers=2,heads=2,"
                 "ffn=32,attn_impl=dense").model_config
    g = next(l for l in giga.layers if l.type == "moe")
    assert g.attrs["gated"] is True and "expert_act" not in g.attrs
    assert len(g.inputs) == 1 + 3 + 1 + 3
    mine = parse(CASE, args(CASE, cfg(CASE))).model_config
    m = next(l for l in mine.layers if l.type == "moe")
    assert m.attrs["expert_act"] == "relu2" and m.attrs["expert_bias"] is False
    assert "gated" not in m.attrs and len(m.inputs) == 1 + 2 + 1 + 2
    bad = tmp_path / "biased_shared.py"
    bad.write_text("from paddle_tpu.dsl import *\n"
                   "x = data_layer(name='x', size=8)\n"
                   "moe_layer(x, num_experts=4, expert_hidden=8, "
                   "shared_hidden=8)\n")
    with pytest.raises(AssertionError, match="bias-free"):
        parse_config(str(bad), "")


def test_the_four_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test: one expert layer of the PROGRAM as each of
    the 4 chips that share it would hold it (4 of 16 experts each, the
    deployment's own count), the routed parts added up and the shared
    expert counted ONCE, against the uncut REFERENCE layer (all 16 held)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe
    uncut, wl, x, kw = _expert_layer(ref, 10)
    with jax.default_matmul_precision("highest"):
        want = ref._moe(uncut, wl, x, None)
        total = kw["activation"](x @ wl["moe.w4"]) @ wl["moe.w5"]    # once
        seen = jnp.zeros((10, 0), bool)
        for rank in range(4):
            sl = slice(4 * rank, 4 * rank + 4)
            y, _, pairs = moe.moe_ffn(
                x, wl["moe.w0"], (wl["moe.w1"][sl], wl["moe.w2"][sl]),
                first_expert=4 * rank, **kw)
            total = total + y
            seen = jnp.concatenate([seen, pairs], axis=1)
        # the same rank's share through the reference's own cut
        cut = dict(uncut, experts_held=4, ep_rank=2)
        wl2 = dict(wl, **{k: wl[k][8:12] for k in ("moe.w1", "moe.w2")})
        y2, _, _ = moe.moe_ffn(x, wl["moe.w0"],
                               (wl2["moe.w1"], wl2["moe.w2"]),
                               first_expert=8, **kw)
        shared = ref._relu2_mlp(x, wl["moe.w4"], wl["moe.w5"], None)
        tol = 2e-6 * float(jnp.abs(want).max())
        assert float(jnp.abs(y2 + shared -
                             ref._moe(cut, wl2, x, None)).max()) < tol
    assert float(jnp.abs(total - want).max()) < tol
    assert bool((jnp.sum(seen, axis=1) == 3).all())   # top-3, every row
