"""Jamba through a real ServingEngine at the tiny size of tests/test_jamba.py
(whose helpers it takes; a file of its own because `--dist loadfile` gives
one file to one worker): chunked prefill then decode against the
reference's one full forward, by the jnp forms and by the interpreted scan
kernel; the token counters of the scan's two calls; checkpoint and restore;
the refusals; and tools/serve.py:build_engine with `--weights deferred` —
an engine that holds no weight bytes, refuses a step by name, and serves
`--weights init`'s tokens once the weights are assigned."""

import importlib.util
import os

import numpy as np
import pytest

from tests.test_jamba import (DSL, MAMBAS, ROOT, TOL, _args, _build, _cfg,
                              model, ref)  # noqa: F401


def _requests(n_tokens, max_new=6, seed=3):
    import jax
    from paddle_tpu.serving import Request
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}", rng.integers(2, 64, n).astype(np.int32),
                    max_new=max_new, rng=jax.random.PRNGKey(40 + i))
            for i, n in enumerate(n_tokens)]


def _margin(ref, cfg, w, reqs, results):
    """The benchmark's own comparison: how far (nats) the reference's
    log-probability of each served token trails its own argmax,
    teacher-forced through ONE full forward a request."""
    import jax
    from benchmark.lib.check import served_margin
    served = [(list(r.prompt_ids), list(results[r.req_id][len(r.prompt_ids):]))
              for r in reqs]
    return served_margin(jax, ref, cfg, w, served, 48)


@pytest.mark.parametrize("chunk,kernel,k,mst", [
    (5, False, 1, None), (5, True, 1, None), (32, False, 1, None),
    (5, False, 2, None), (5, False, 1, 34), (5, True, 1, 34)],
    ids=["chunked-jnp", "chunked-kernel", "one-chunk", "decode-steps-2",
         "free-rows-jnp", "free-rows-kernel"])
def test_engine_prefill_in_chunks_then_decode_against_the_reference(
        model, ref, chunk, kernel, k, mst, monkeypatch):
    """A real ServingEngine — chunked prefill through mixed steps, slots
    re-admitted after other requests, the state through the interpreted
    scan kernel, the scanned step (--decode-steps 2), a step with free rows
    for a whole prompt (32 chunk rows: a run of 26 tokens where the share
    is 5, through `selective_scan_seg`): every served token is
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
    # at most one state a slot a Mamba layer a step
    assert eng.recurrent_steps >= eng.n_decode_steps > 0
    assert 0 < eng.recurrent_slot_updates <= \
        4 * len(eng.slots) * eng.recurrent_steps
    assert eng.kv.slot_state_bytes == 4 * 3 * (16 * 128 + 3 * 128) * 4
    # the recurrent layers' tokens by the call that ran them, one layer's
    # worth, counted where the step is packed: every prompt token in a
    # chunk's run (74 = 3 + 19 + 9 + 17 + 26), every served token but a
    # request's first as a decode row
    toks = eng.recurrent_tokens
    assert toks["segment"] == 74 and toks["step"] == 5 * 5, toks
    assert toks["step"] + toks["segment"] == eng.recurrent_rows


def test_the_token_counters_reach_stats_and_metrics(model):
    from paddle_tpu.obs.metrics import counter_key, process_counters
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.server import ServingServer
    cfg, ex, w = model
    before = process_counters().snapshot()
    eng = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                        prefill_chunk=8)
    eng.run(_requests((9, 5), max_new=4))
    assert eng.recurrent_tokens == {"step": 6, "segment": 14}
    after = process_counters().snapshot()
    for kind, n in eng.recurrent_tokens.items():
        key = counter_key("serving_recurrent_tokens_total", kind=kind)
        assert after[key] - before.get(key, 0) == n
    srv = ServingServer(eng)
    assert srv._engine_stats()["recurrent_tokens"] == \
        {"step": 6, "segment": 14}
    text = srv.metrics.render()
    assert 'serving_recurrent_tokens_total{kind="segment"} 14' in text
    assert 'serving_recurrent_tokens_total{kind="step"} 6' in text
    assert "# HELP serving_recurrent_tokens_total" in text
    # the count is the scheduler's own and names no layer: another
    # recurrent kind (the short-convolution stack) is counted the same way
    import jax
    import tests.test_lfm2_moe as lfm2
    ex2 = lfm2._build(lfm2._cfg())
    other = ServingEngine(ex2, ex2.init_params(jax.random.PRNGKey(0)),
                          num_slots=2, page_size=4, max_context=32,
                          prefill_chunk=8)
    other.run(_requests((9, 5), max_new=4))
    assert other.recurrent_tokens == {"step": 6, "segment": 14}
    assert sum(other.recurrent_tokens.values()) == other.recurrent_rows


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
        assert snap["config"]["slot_specs"]["blk0_mamba"] == \
            {"state": (16, 128), "conv": (3, 128)}
        b = engine()
        b.restore_state(snap)
        results = b.run()
        for r in reqs:
            toks, lens = lm_generate(ex, w, r.prompt_ids[None, :],
                                     max_new=r.max_new, rng=r.rng)
            np.testing.assert_array_equal(
                np.asarray(toks)[0, :int(np.asarray(lens)[0])],
                results[r.req_id])


@pytest.mark.parametrize("what", ["prefix", "spill", "spec", "mesh",
                                  "export", "import", "role"])
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
        else:
            from paddle_tpu.serving.server import ServingServer
            ServingServer(engine(), role="prefill")
    msg = str(e.value)
    assert "recurrent" in msg and RECURRENT_REFUSALS[what][1] in msg \
        and "(4 here" in msg, msg


# -- build_engine and the one weight set -----------------------------------------------

def _serve_tool():
    spec = importlib.util.spec_from_file_location(
        "tools_serve_j", os.path.join(ROOT, "tools", "serve.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    got = {}

    async def capture(a):
        got["args"] = a
        return 0

    tool.amain = capture

    def parse(argv):
        tool.main(argv)
        return got["args"]

    return tool, parse


def _argv(cfg, *more):
    return ["--config", DSL, "--config-args",
            _args(cfg).replace("compute_dtype=,", "compute_dtype=bfloat16,"),
            "--slots", "2", "--page-size", "4", "--max-context", "32",
            "--prefill-chunk", "8", "--param-dtype", "bfloat16", *more]


def test_deferred_weights_build_an_engine_that_holds_none(ref, monkeypatch):
    """`--weights deferred`: the engine is built around the parameter
    tree's shapes — no weight bytes, the pools as ever —, a step with work
    to do is refused in one sentence, and once the caller's weights are
    assigned it serves what an `init` engine serves with the same
    weights."""
    import jax
    from benchmark.lib.common import check_weights_fit
    from paddle_tpu.obs.hbm import tree_bytes
    from paddle_tpu.serving import Request
    cfg = _cfg(param_dtype="bfloat16")
    monkeypatch.chdir(ROOT)
    tool, parse = _serve_tool()
    assert parse(_argv(cfg)).weights == "init"        # today's behaviour
    args = parse(_argv(cfg, "--weights", "deferred"))
    eng = tool.build_engine(args)
    assert all(isinstance(v, jax.ShapeDtypeStruct)
               for v in eng.params.values())
    assert tree_bytes(eng.params) == 0 and eng.step_weight_bytes == 0
    assert str(eng.kv.pools["blk0_mamba"]["state"].dtype) == "float32"
    assert eng.kv.pools["blk0_mamba"]["state"].shape == (3, 16, 128)
    assert str(eng.kv.pools["blk0_mamba"]["conv"].dtype) == "bfloat16"
    assert eng.kv.pools["blk1_attn"]["k"].shape[2:] == (1, 16)
    assert eng.step() is False                        # idle: nothing to refuse
    req = lambda: Request("a", np.asarray([3, 5, 7, 9, 11], np.int32),
                          max_new=5)
    eng.add_request(req())
    with pytest.raises(RuntimeError, match="holds no weights.*--weights "
                                           "deferred.*engine.params"):
        eng.step()
    # the benchmark's own road: the reference's weights fit the abstract
    # tree by name, shape and type, and take its place
    w = ref.make_weights(cfg, 5)
    check_weights_fit(eng.params, w)
    eng.params = w
    assert tree_bytes(eng.params) > 0 and eng.step_weight_bytes == 0
    got = eng.run()["a"]
    init = tool.build_engine(parse(_argv(cfg)))
    assert {str(v.dtype) for v in init.params.values()} == {"bfloat16"}
    init.params = w
    np.testing.assert_array_equal(init.run([req()])["a"], got)
    assert len(got) == 10
    # the flags that need a state snapshot are refused from the command line
    with pytest.raises(ValueError, match="recurrent"):
        tool.build_engine(parse(_argv(cfg, "--spec-k", "2")))


def test_a_checkpoint_is_loaded_into_the_abstract_tree(tmp_path, monkeypatch):
    """`--checkpoint` takes the same road: `init_params` is never run — a
    loaded leaf is put into the tree of shapes, not over an initialised
    one — and the engine serves the checkpoint's weights."""
    import jax
    from paddle_tpu.graph import GraphExecutor
    from paddle_tpu.serving import Request
    from paddle_tpu.trainer.checkpoint import save_checkpoint
    cfg = _cfg()
    monkeypatch.chdir(ROOT)
    tool, parse = _serve_tool()
    args = parse(_argv(cfg)[:-2])                     # float32 parameters
    ex, params = tool.build_model(args)
    params = {k: v + 0.01 for k, v in params.items()}
    save_checkpoint(str(tmp_path), 0, params)
    calls = []
    real = GraphExecutor.init_params

    def counted(self, *a, **kw):
        import jax.core
        calls.append(isinstance(a[0], jax.core.Tracer))
        return real(self, *a, **kw)

    monkeypatch.setattr(GraphExecutor, "init_params", counted)
    args = parse(_argv(cfg)[:-2] + ["--checkpoint", str(tmp_path)])
    ex2, loaded = tool.build_model(args)
    assert calls == [True]            # traced for its shapes, never run
    for k, v in params.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(loaded[k]))
    eng = tool.build_engine(args)
    out = eng.run([Request("a", np.asarray([3, 5, 7], np.int32), max_new=3)])
    assert len(out["a"]) == 6
