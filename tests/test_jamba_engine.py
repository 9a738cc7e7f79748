"""Jamba through a real ServingEngine at the tiny size of tests/test_jamba.py
(a file of its own because `--dist loadfile` gives one file to one worker):
the shared engine tests of tests/model_parity.py over its case — chunked
prefill then decode against the reference's one full forward, by the jnp
forms and by the interpreted scan kernel, the token counters of the scan's
two calls, `--decode-steps` 2, free rows for a whole prompt (through
`selective_scan_seg`), checkpoint and restore, the refusals — and what is
this model's own: the token counters in `stats` and `metrics`, and
tools/serve.py:build_engine with `--weights deferred` — an engine that holds
no weight bytes, refuses a step by name, and serves `--weights init`'s tokens
once the weights are assigned."""

import numpy as np
import pytest

from tests.model_parity import (  # noqa: F401
    CASES, ROOT, build, case, cfg, engines, model, pytest_generate_tests,
    ref, requests, serve_argv, serve_tool,
    test_checkpoint_and_restore_round_trip_the_slot_parts,
    test_engine_serves_lm_generates_tokens,
    test_what_needs_a_state_snapshot_is_refused_by_name)

CASE = CASES["jamba"]


def test_the_token_counters_reach_stats_and_metrics(model):
    from paddle_tpu.obs.metrics import counter_key, process_counters
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.server import ServingServer
    _, ex, w = model
    before = process_counters().snapshot()
    eng = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                        prefill_chunk=8)
    eng.run(requests((9, 5), max_new=4))
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
    lfm2 = CASES["lfm2_moe"]
    ex2 = build(lfm2, cfg(lfm2))
    other = ServingEngine(ex2, ex2.init_params(jax.random.PRNGKey(0)),
                          num_slots=2, page_size=4, max_context=32,
                          prefill_chunk=8)
    other.run(requests((9, 5), max_new=4))
    assert other.recurrent_tokens == {"step": 6, "segment": 14}
    assert sum(other.recurrent_tokens.values()) == other.recurrent_rows


# -- build_engine and the one weight set -----------------------------------------------

def _argv(c, *more):
    return serve_argv(CASE, c, "--prefill-chunk", "8", "--param-dtype",
                      "bfloat16", *more)


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
    c = cfg(CASE, param_dtype="bfloat16")
    monkeypatch.chdir(ROOT)
    tool, parse = serve_tool()
    assert parse(_argv(c)).weights == "init"        # today's behaviour
    args = parse(_argv(c, "--weights", "deferred"))
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
    w = ref.make_weights(c, 5)
    check_weights_fit(eng.params, w)
    eng.params = w
    assert tree_bytes(eng.params) > 0 and eng.step_weight_bytes == 0
    got = eng.run()["a"]
    init = tool.build_engine(parse(_argv(c)))
    assert {str(v.dtype) for v in init.params.values()} == {"bfloat16"}
    init.params = w
    np.testing.assert_array_equal(init.run([req()])["a"], got)
    assert len(got) == 10
    # the flags that need a state snapshot are refused from the command line
    with pytest.raises(ValueError, match="recurrent"):
        tool.build_engine(parse(_argv(c, "--spec-k", "2")))


def test_a_checkpoint_is_loaded_into_the_abstract_tree(tmp_path, monkeypatch):
    """`--checkpoint` takes the same road: `init_params` is never run — a
    loaded leaf is put into the tree of shapes, not over an initialised
    one — and the engine serves the checkpoint's weights."""
    import jax
    from paddle_tpu.graph import GraphExecutor
    from paddle_tpu.serving import Request
    from paddle_tpu.trainer.checkpoint import save_checkpoint
    c = cfg(CASE)
    monkeypatch.chdir(ROOT)
    tool, parse = serve_tool()
    args = parse(_argv(c)[:-2])                     # float32 parameters
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
    args = parse(_argv(c)[:-2] + ["--checkpoint", str(tmp_path)])
    ex2, loaded = tool.build_model(args)
    assert calls == [True]            # traced for its shapes, never run
    for k, v in params.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(loaded[k]))
    eng = tool.build_engine(args)
    out = eng.run([Request("a", np.asarray([3, 5, 7], np.int32), max_new=3)])
    assert len(out["a"]) == 6
