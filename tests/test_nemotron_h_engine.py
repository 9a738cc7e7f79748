"""Nemotron-H through a real ServingEngine at the tiny size of
tests/test_nemotron_h.py (whose helpers it takes; a file of its own because
`--dist loadfile` gives one file to one worker): chunked prefill then decode
against the reference's one full forward, `--decode-steps` 2, the `ME` stack
with no page-indexed part, checkpoint and restore, the refusals, the third
expert form in both formulations, the shared expert's form, the four-share
test and tools/serve.py:build_engine."""

import os

import numpy as np
import pytest

from tests.test_nemotron_h import (DSL, ROOT, TOL, _args, _build, _cfg,
                                   _parse, _pattern, model, ref)  # noqa: F401


# -- the engine --------------------------------------------------------------------

def _requests(n_tokens, max_new=6, seed=3):
    import jax
    from paddle_tpu.serving import Request
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}", rng.integers(2, 64, n).astype(np.int32),
                    max_new=max_new, rng=jax.random.PRNGKey(40 + i))
            for i, n in enumerate(n_tokens)]


def _margin(ref, cfg, w, reqs, results):
    """How far (nats) the reference's log-probability of each served token
    trails its own argmax, teacher-forced on prompt + served tokens through
    ONE full forward a request: the benchmark's own comparison."""
    import jax
    from benchmark.lib.check import served_margin
    served = [(list(r.prompt_ids), list(results[r.req_id][len(r.prompt_ids):]))
              for r in reqs]
    return served_margin(jax, ref, cfg, w, served, 48)


@pytest.mark.parametrize("chunk,kernel,k,mst", [
    (5, False, 1, None), (5, True, 1, None), (32, False, 1, None),
    (5, False, 2, None), (5, False, 1, 34)],
    ids=["chunked-jnp", "chunked-kernel", "one-chunk", "decode-steps-2",
         "free-rows"])
def test_engine_prefill_in_chunks_then_decode_against_the_reference(
        model, ref, chunk, kernel, k, mst, monkeypatch):
    """A real ServingEngine — chunked prefill through mixed steps, slots
    re-admitted after other requests, the state through the interpreted
    `ssd_step`, the scanned step (--decode-steps 2), a step with free rows
    for a whole prompt (32 chunk rows: a run of 26 tokens where the share
    is 5, one segment of `ssd.segment_rows`): every served token is
    the argmax of the reference's ONE full forward over prompt + served
    tokens to within the logits' tolerance, and the tokens are
    lm_generate's whole-sequence ones."""
    import jax
    from paddle_tpu.graph.lm_decode import lm_generate
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
        for r in reqs:
            toks, lens = lm_generate(ex, w, r.prompt_ids[None, :],
                                     max_new=r.max_new, rng=r.rng)
            np.testing.assert_array_equal(
                np.asarray(toks)[0, :int(np.asarray(lens)[0])],
                results[r.req_id])
    m = _margin(ref, cfg, w, reqs, results)
    assert m["worst_nats"] < TOL and m["tokens"] == 30, m
    eng.kv.check_reclaimed()
    if mst:
        # every prompt went in one run: 51 of the 74 rows past a share of 5
        assert eng.n_prefill_chunks == 5 and eng.n_chunk_rows == 74
        assert eng.n_chunk_extra_rows == 14 + 4 + 12 + 21
    if k > 1:
        assert eng.n_scan_flushes > 0
    # the recurrent counters are fed by this kind too: every counted step,
    # at most one state a slot a Mamba-2 layer a step
    assert eng.recurrent_steps >= eng.n_decode_steps > 0
    assert 0 < eng.recurrent_slot_updates <= \
        2 * len(eng.slots) * eng.recurrent_steps
    assert eng.recurrent_rows >= eng.recurrent_slot_updates // 2
    assert eng.moe_steps == eng.recurrent_steps
    assert eng.kv.slot_state_bytes == 2 * 3 * (4 * 16 * 16 + 3 * 128) * 4


def test_a_stack_with_no_page_indexed_part_serves(ref):
    """`ME`, published layers 1-2 (what benchmark/rehearse.json's two layers
    cut this model to, and what a pipeline stage can be): no attention
    layer, so no pool is built; the page table and the allocator keep their
    logical pages, and the engine serves the reference's tokens."""
    import jax
    from paddle_tpu.serving import ServingEngine
    cfg = _cfg(num_hidden_layers=2, first_layer=1)
    assert _pattern(cfg) == "ME"
    ex, w = _build(cfg), ref.make_weights(cfg, 5)
    reqs = _requests((3, 19, 9, 17))
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                            prefill_chunk=5)
        assert eng.kv.layer_specs == {} and eng.kv.pool_bytes == 0
        assert sorted(eng.kv.slot_specs) == ["blk0_ssm"]
        results = eng.run(reqs)
    m = _margin(ref, cfg, w, reqs, results)
    assert m["worst_nats"] < TOL and m["tokens"] == 24, m
    eng.kv.check_reclaimed()
    snap = eng.checkpoint_state()
    assert snap["config"]["layer_specs"] == {}
    assert set(snap["pools"]) == {"blk0_ssm"}
    # a model with neither kind of part still has nothing to serve from
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.graph import GraphExecutor
    from paddle_tpu.serving import PagedKVCache
    plain = parse_config(os.path.join(ROOT, "demo", "quick_start",
                                      "trainer_config.lr.py"), "")
    with pytest.raises(AssertionError, match="no attention layers to page"):
        PagedKVCache(GraphExecutor(plain.model_config), num_slots=2,
                     page_size=4, pages_per_slot=2)


def test_checkpoint_and_restore_round_trip_the_state(model):
    import jax
    from paddle_tpu.graph.lm_decode import lm_generate
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
        assert snap["config"]["slot_specs"]["blk0_ssm"] == \
            {"state": (4, 16, 16), "conv": (3, 128)}
        assert set(snap["pools"]["blk0_ssm"]) == {"state", "conv"}
        b = engine()
        b.restore_state(snap)
        for n in b.kv.slot_specs:
            for part, arr in b.kv.pools[n].items():
                assert bool((np.asarray(arr) ==
                             snap["pools"][n][part]).all())
        results = b.run()
        for r in reqs:
            toks, lens = lm_generate(ex, w, r.prompt_ids[None, :],
                                     max_new=r.max_new, rng=r.rng)
            np.testing.assert_array_equal(
                np.asarray(toks)[0, :int(np.asarray(lens)[0])],
                results[r.req_id])


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
        assert RECURRENT_REFUSALS[what][1] in msg and "(2 here" in msg, msg


# -- the experts -------------------------------------------------------------------

def test_relu2_experts_dense_against_grouped(ref):
    """The third expert form (w_up, w_down), relu^2, through both
    formulations of the block against the reference's loop over the held
    experts; the unknown name is refused."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe
    cfg = _cfg()
    w = ref.make_weights(cfg, 11)
    wl = {k[len("_blk1_"):]: v for k, v in w.items()
          if k.startswith("_blk1_")}
    x = jnp.asarray(np.random.default_rng(5).normal(size=(40, 48)),
                    jnp.float32)
    act = moe.expert_activation("relu2")
    assert float(act(jnp.asarray(-2.0))) == 0 and float(
        act(jnp.asarray(3.0))) == 9
    with pytest.raises(ValueError, match="unknown expert activation"):
        moe.expert_activation("gelu")
    kw = dict(top_k=3, scoring="sigmoid", activation=act,
              select_bias=wl["moe.w3"].reshape(-1),
              scale=cfg["routed_scaling_factor"])
    with jax.default_matmul_precision("highest"):
        want = ref._moe(cfg, wl, x, None) - ref._relu2_mlp(
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
    giga = parse_config(os.path.join(ROOT, "benchmark", "configs",
                                     "gigachat3.py"),
                        "vocab=64,dim=32,layers=2,heads=2,ffn=32,"
                        "attn_impl=dense").model_config
    g = next(l for l in giga.layers if l.type == "moe")
    assert g.attrs["gated"] is True and "expert_act" not in g.attrs
    assert len(g.inputs) == 1 + 3 + 1 + 3
    mine = _parse(_args(_cfg())).model_config
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
    uncut = _cfg()
    w = ref.make_weights(uncut, 11)
    wl = {k[len("_blk1_"):]: v for k, v in w.items()
          if k.startswith("_blk1_")}
    x = jnp.asarray(np.random.default_rng(5).normal(size=(10, 48)),
                    jnp.float32)
    act = moe.expert_activation("relu2")
    kw = dict(top_k=3, scoring="sigmoid", activation=act,
              select_bias=wl["moe.w3"].reshape(-1),
              scale=uncut["routed_scaling_factor"])
    with jax.default_matmul_precision("highest"):
        want = ref._moe(uncut, wl, x, None)
        total = act(x @ wl["moe.w4"]) @ wl["moe.w5"]      # once
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


# -- build_engine ------------------------------------------------------------------

def test_build_engine_serves_the_model_in_bf16(monkeypatch):
    """tools/serve.py:build_engine, no new flag: the model serves with bf16
    parameters, its state float32 and its tails bf16 beside the K/V pool,
    and the flags that need a state snapshot are refused from the command
    line."""
    import importlib.util
    from paddle_tpu.serving import Request
    cfg = _cfg()
    monkeypatch.chdir(ROOT)
    spec = importlib.util.spec_from_file_location(
        "tools_serve_n", os.path.join(ROOT, "tools", "serve.py"))
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
    assert str(eng.kv.pools["blk0_ssm"]["state"].dtype) == "float32"
    assert str(eng.kv.pools["blk0_ssm"]["conv"].dtype) == "bfloat16"
    assert eng.kv.pools["blk3_attn"]["k"].shape[2:] == (2, 16)
    out = eng.run([Request("a", np.asarray([3, 5, 7], np.int32), max_new=4)])
    assert len(out["a"]) == 7
    tool.main(argv + ["--spec-k", "2"])
    with pytest.raises(ValueError, match="recurrent"):
        tool.build_engine(got["args"])
