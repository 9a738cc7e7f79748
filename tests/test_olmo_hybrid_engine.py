"""Olmo-Hybrid through a real ServingEngine at the tiny size of
tests/test_olmo_hybrid.py (a file of its own because `--dist loadfile` gives
one file to one worker): the shared engine tests of tests/model_parity.py
over its case — chunked prefill through mixed steps then decode, the
rectangular Gated DeltaNet state beside the 6-KV-head pages, every served
token the argmax of the reference's ONE full forward; the paged kernel,
`gdn_step` and `gdn_seg` interpreted in one step with free rows for a whole
prompt; checkpoint and restore; the refusals; tools/serve.py:build_engine —
and what is this model's own: the recurrent counters counting these layers,
and the bytes the cache manager holds, by part."""

from tests.model_parity import (  # noqa: F401
    CASES, case, engines, model, pytest_generate_tests, ref, requests,
    test_build_engine_serves_the_model_in_bf16,
    test_checkpoint_and_restore_round_trip_the_slot_parts,
    test_engine_serves_lm_generates_tokens,
    test_what_needs_a_state_snapshot_is_refused_by_name)

CASE = CASES["olmo_hybrid"]


def test_stats_hold_the_rectangular_state_and_the_counters(model, engines):
    """What a reader holds the configuration file's table to: the cache
    manager's bytes come by part — the K/V pool of the full layer, each
    linear layer's [6, 8, 16] float32 state and 192-channel tail — and add
    up to what it reports whole; the recurrent counters the KDA layers have
    count these layers (tokens by kind, rows, slot updates), in `stats` and
    in the metrics text."""
    from paddle_tpu.serving.server import ServingServer
    _, ex, w = model
    eng = engines(ex, w)
    steps, rows = eng.recurrent_steps, eng.recurrent_rows
    tokens = dict(eng.recurrent_tokens)
    eng.run(requests((9, 5), max_new=4))
    assert eng.recurrent_steps > steps and eng.recurrent_slot_updates > 0
    assert eng.recurrent_tokens["segment"] - tokens.get("segment", 0) == 14
    assert eng.recurrent_tokens["step"] - tokens.get("step", 0) == 6
    assert eng.recurrent_rows - rows == 20
    assert eng.moe_steps == 0                   # a dense model
    srv = ServingServer(eng)
    st = srv._engine_stats()
    by, kv = st["cache_bytes_by_part"], eng.kv
    assert set(by) == {"blk3_attn.k", "blk3_attn.v"} | {
        f"{n}.{part}" for n in CASE.recurrent for part in CASE.slot_parts}
    assert by["blk3_attn.k"] + by["blk3_attn.v"] == kv.pool_bytes
    assert sum(v for k, v in by.items() if "_gdn." in k) == \
        kv.slot_state_bytes
    # 3 rows (2 slots and the trash row) of [6, 8, 16] float32 a layer
    assert by["blk0_gdn.state"] == 3 * 6 * 8 * 16 * 4
    assert by["blk0_gdn.conv"] == 3 * 3 * 192 * 4
    text = srv.metrics.render()
    assert 'serving_recurrent_tokens_total{kind="segment"}' in text
    assert "serving_recurrent_slot_updates_total" in text
