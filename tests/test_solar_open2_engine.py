"""Solar-Open2 through a real ServingEngine at the tiny size of
tests/test_solar_open2.py (a file of its own because `--dist loadfile` gives
one file to one worker): the shared engine tests of tests/model_parity.py
over its case — chunked prefill through mixed steps then decode, the KDA
state beside the K/V pages, every served token the argmax of the reference's
ONE full forward; the paged kernel and `kda_step` interpreted in one step;
free rows for a whole prompt; checkpoint and restore; the refusals;
tools/serve.py:build_engine — and what is this model's own: the gauge of
gated layers, and the bytes the cache manager holds, by part."""

from tests.model_parity import (  # noqa: F401
    CASES, case, engines, model, pytest_generate_tests, ref, requests,
    test_build_engine_serves_the_model_in_bf16,
    test_checkpoint_and_restore_round_trip_the_slot_parts,
    test_engine_serves_lm_generates_tokens,
    test_what_needs_a_state_snapshot_is_refused_by_name)

CASE = CASES["solar_open2"]


def test_stats_hold_the_gated_layers_and_the_bytes_by_part(model, engines):
    """What a reader holds the configuration file's table to: the engine
    counts the attention layers whose result is gated, the cache manager's
    bytes come by part — the K/V pool a layer, each slot part a layer — and
    add up to what it reports whole; both reach `stats` and the gauge the
    metrics text.  The KDA layers' and the experts' counters count with
    layer 0 an expert layer and three KDA layers behind one GQA layer."""
    from paddle_tpu.serving.server import ServingServer
    _, ex, w = model
    eng = engines(ex, w)
    steps, moe = eng.recurrent_steps, eng.moe_steps
    eng.run(requests((9, 5), max_new=4))
    assert eng.recurrent_steps - steps == eng.moe_steps - moe > 0
    assert eng.moe_pairs_total > 0 and eng.recurrent_slot_updates > 0
    srv = ServingServer(eng)
    st = srv._engine_stats()
    assert st["attn_gated_layers"] == eng.attn_gated_layers == 1
    by, kv = st["cache_bytes_by_part"], eng.kv
    assert set(by) == {"blk0_attn.k", "blk0_attn.v"} | {
        f"{n}.{part}" for n in CASE.recurrent for part in CASE.slot_parts}
    assert by["blk0_attn.k"] + by["blk0_attn.v"] == kv.pool_bytes
    assert sum(v for k, v in by.items() if "_kda." in k) == \
        kv.slot_state_bytes
    # 3 rows (2 slots and the trash row) of [4, 8, 8] float32 a KDA layer
    assert by["blk1_kda.state"] == 3 * 4 * 8 * 8 * 4
    text = srv.metrics.render()
    assert "serving_attn_gated_layers 1" in text
    assert "# HELP serving_attn_gated_layers" in text
