"""Xing4.0 through a real ServingEngine at the tiny size of
tests/test_xing4.py (a file of its own because `--dist loadfile` gives one
file to one worker): chunked prefill through mixed steps then decode over
the paged latent cache with every block's residual path a stream pass —
every served token lm_generate's and the argmax of the reference's ONE full
forward, by the jnp forms and by the interpreted kernels (`mhc_mix`,
`mla_paged_attn`), a whole prompt in one chunk; the stream pass's counters
in `stats` and the metrics text; the prefix index ON (the maps are a
function of the token alone: nothing in the cache manager changes);
tools/serve.py:build_engine with deferred weights."""

import numpy as np

from tests.model_parity import (  # noqa: F401
    CASES, ROOT, case, cfg, engines, model, pytest_generate_tests, ref,
    requests, serve_argv, serve_tool,
    test_engine_serves_lm_generates_tokens)

CASE = CASES["xing4"]


def test_stream_pass_counters_in_stats_and_metrics(model):
    """Every row of every step, padding included, passes 2 x layers stream
    passes: `serving_mhc_rows_total` over `serving_mhc_calls_total` is the
    rows of a call; the gauge says four streams; the process's counters
    carry the same growth (the benchmark's readers read them there) beside
    the mixed steps' chunk and padding rows."""
    import jax
    from paddle_tpu.obs.metrics import process_counters
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.server import ServingServer
    c, ex, w = model
    # an engine of its own: a ServingServer takes its engine over
    eng = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=32,
                        prefill_chunk=4, max_step_tokens=7)
    writes = 2 * c["num_hidden_layers"]
    assert eng.residual_streams == 4 and len(eng._mhc_writes) == writes
    before = process_counters().snapshot()
    rows0, calls0, steps0 = eng.n_mhc_rows, eng.n_mhc_calls, \
        eng.n_decode_steps
    mixed0, chunk0, pad0 = eng.n_mixed_steps, eng.n_chunk_rows, \
        eng.n_step_pad_rows
    att0, fet0 = eng.kv_tokens_attended, eng.kv_tokens_fetched
    with jax.default_matmul_precision("highest"):
        eng.run(requests((9, 17), max_new=5))
    steps = eng.n_decode_steps - steps0
    mixed = eng.n_mixed_steps - mixed0
    assert eng.n_mhc_calls - calls0 == writes * steps
    # a mixed step carries 7 rows, a decode step the 2 slots
    assert eng.n_mhc_rows - rows0 == writes * (7 * mixed + 2 * (steps - mixed))
    after = process_counters().snapshot()
    grew = lambda k: after.get(k, 0) - before.get(k, 0)
    assert grew("serving_mhc_rows_total") == eng.n_mhc_rows - rows0
    assert grew("serving_mhc_calls_total") == eng.n_mhc_calls - calls0
    assert grew("serving_mixed_steps_total") == mixed > 0
    assert grew("serving_chunk_rows_total") == eng.n_chunk_rows - chunk0 == 26
    assert grew("serving_step_pad_rows_total") == eng.n_step_pad_rows - pad0
    # the contexts the kernel's rows attended and fetched: what the whole
    # step's roofline reader takes from a traced slice
    assert grew("serving_kv_tokens_attended_total") == \
        eng.kv_tokens_attended - att0 > 0
    assert grew("serving_kv_tokens_fetched_total") == \
        eng.kv_tokens_fetched - fet0 > 0
    srv = ServingServer(eng)
    st = srv._engine_stats()
    assert (st["residual_streams"], st["mhc_rows"], st["mhc_calls"]) == \
        (4, eng.n_mhc_rows, eng.n_mhc_calls)
    text = srv.metrics.render()
    for family in ("serving_mhc_rows_total", "serving_mhc_calls_total",
                   "serving_residual_streams",
                   "serving_kv_tokens_attended_total",
                   "serving_kv_tokens_fetched_total"):
        assert f"# HELP {family}" in text and f"# TYPE {family}" in text
    assert "serving_residual_streams 4" in text


def test_a_model_without_streams_counts_none(engines):
    """The plain residual: the gauge reads 1 and the counters stay 0."""
    from tests.model_parity import build
    import jax
    giga = CASES["gigachat3"]
    from benchmark.lib.spec import Benchmark
    c = cfg(giga)
    ex = build(giga, c)
    w = Benchmark(ROOT).reference("gigachat3").make_weights(c, 7)
    eng = engines(ex, w, max_context=32, prefill_chunk=4)
    with jax.default_matmul_precision("highest"):
        eng.run(requests((5,), max_new=3))
    assert (eng.residual_streams, eng.n_mhc_rows, eng.n_mhc_calls) == \
        (1, 0, 0)


def test_the_prefix_index_stays_on_and_shares_pages(model, engines):
    """No state crosses tokens in a hyper-connection: the latent pages ARE
    the context, so a second request with the first one's prompt is a
    prefix hit and serves the same tokens."""
    import jax
    _, ex, w = model
    eng = engines(ex, w, max_context=32, prefill_chunk=4, max_step_tokens=7)
    assert eng.prefix is not None
    from paddle_tpu.serving import Request
    prompt = np.random.default_rng(9).integers(2, 64, 17).astype(np.int32)
    hits = eng.n_prefix_hits
    with jax.default_matmul_precision("highest"):
        a = eng.run([Request("p0", prompt, max_new=4)])["p0"]
        b = eng.run([Request("p1", prompt.copy(), max_new=4)])["p1"]
    np.testing.assert_array_equal(a, b)
    assert eng.n_prefix_hits == hits + 1


def test_build_engine_serves_the_model_in_bf16_with_deferred_weights(
        case, ref, monkeypatch):
    """tools/serve.py:build_engine as the cell starts it: `--weights
    deferred` builds the engine around the parameters' shapes, the seeded
    weights are assigned (benchmark/kinds/serve.py:seeded_weights), bf16
    parameters and latent pool, float32 maps inside the step — and it
    serves."""
    import jax
    from benchmark.lib.common import check_weights_fit
    from paddle_tpu.serving import Request
    monkeypatch.chdir(ROOT)
    tool, parse = serve_tool()
    c = cfg(case, param_dtype="bfloat16")
    eng = tool.build_engine(parse(serve_argv(
        case, c, "--prefill-chunk", "8", "--param-dtype", "bfloat16",
        "--weights", "deferred")))
    w = ref.make_weights(c, 3)
    check_weights_fit(eng.params, w)
    eng.params = w
    assert {str(v.dtype) for v in eng.params.values()} == {"bfloat16"}
    for n, row in case.paged.items():
        for pool in eng.kv.pools[n].values():
            assert pool.shape[2:] == row and str(pool.dtype) == "bfloat16"
    out = eng.run([Request("a", np.asarray([3, 5, 7], np.int32), max_new=4)])
    assert len(out["a"]) == 7
    assert eng.n_mhc_calls > 0
