"""bench.py's record helpers: last-known-good selection (newest complete
record, errored/skipped extras stripped), the degraded-record merge, and
the PERF_LOG append gate.  `bench.py:main()` no longer reaches the
last-known-good helpers (no TPU or a failed headline exits 1 and replays
nothing); they and these tests go with the benchmark PR (ROADMAP D1).
"""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_assemble_lkg_stitches_per_config_records(tmp_path):
    """The round-5 short-window queue banks ONE config per PERF_LOG record
    (bench.py + BENCH_ONLY); the assembler must stitch the newest
    occurrence of every part — whether nested under a full run or its own
    top-level record — each stamped measured_at, with errored/skipped
    parts never advertised as known-good."""
    bench = _load_bench()
    M = bench._METRIC_OF
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        {"ts": "2026-07-29T10:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0, "vs_baseline": 2.0,
                    "platform": "tpu",
                    "seq2seq": {"metric": M["seq2seq"], "value": 5.0},
                    "mnist": {"skipped": "budget"},
                    "lm": {"error": "timeout"}}},
        # newer per-config records (the BENCH_ONLY queue shape)
        {"ts": "2026-07-30T10:00:00+00:00",
         "record": {"metric": M["sentiment"], "value": 9.0,
                    "vs_baseline": 1.0,
                    "measured_at": "2026-07-30T10:00:00+00:00"}},
        {"ts": "2026-07-30T11:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 200.0, "vs_baseline": 4.0,
                    "platform": "tpu", "device_kind": "TPU v5 lite",
                    "measured_at": "2026-07-30T11:00:00+00:00"}},
        # decode-phase record merges into the seq2seq part
        {"ts": "2026-07-30T12:00:00+00:00",
         "record": {"metric": "wmt14_seq2seq_beam_decode_tokens_per_sec",
                    "value": 60000.0, "beam_decode_tokens_per_sec": 60000.0,
                    "measured_at": "2026-07-30T12:00:00+00:00"}},
        {"ts": "2026-07-30T13:00:00+00:00",
         "record": {"metric": M["vgg"], "error": "boom", "value": 0.0}},
        "not json at all",
    ]
    log.write_text("\n".join(r if isinstance(r, str) else json.dumps(r)
                             for r in rows) + "\n")
    bench._PERF_LOG = str(log)

    out = bench._assemble_lkg()
    assert out["value"] == 200.0                      # newest valid headline
    assert out["measured_at"] == "2026-07-30T11:00:00+00:00"
    assert out["platform"] == "tpu"                   # provenance preserved
    assert out["sentiment"]["value"] == 9.0
    # errored/skipped parts must NOT be advertised as known-good
    assert "mnist" not in out and "lm" not in out
    # seq2seq train came from the old full run; decode merged from the
    # newer phase-isolated record
    assert out["seq2seq"]["value"] == 5.0
    assert out["seq2seq"]["beam_decode_tokens_per_sec"] == 60000.0
    assert out["seq2seq"]["beam_decode_measured_at"] == \
        "2026-07-30T12:00:00+00:00"


def test_assemble_lkg_stitches_serving_record(tmp_path):
    """The continuous-batching serving metric (lm_serving_tok_per_sec)
    rides the same per-config queue shape: a top-level BENCH_ONLY=serving
    record must stitch into the assembled fallback under the `serving`
    key, newest occurrence winning."""
    bench = _load_bench()
    M = bench._METRIC_OF
    assert M["serving"] == "lm_serving_tok_per_sec"
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        {"ts": "2026-07-30T09:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0, "vs_baseline": 2.0,
                    "serving": {"metric": M["serving"], "value": 1000.0}}},
        {"ts": "2026-07-31T10:00:00+00:00",
         "record": {"metric": M["serving"], "value": 2000.0,
                    "occupancy": 0.9,
                    "measured_at": "2026-07-31T10:00:00+00:00"}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._assemble_lkg()
    assert out["serving"]["value"] == 2000.0
    assert out["serving"]["occupancy"] == 0.9


def test_assemble_lkg_stitches_serving_prefix_record(tmp_path):
    """PR 7 wiring: the prefix-cache record (lm_serving_prefix_hit_rate +
    the prefill-tokens-saved companion) rides the same per-config queue
    shape — a top-level BENCH_ONLY=serving_prefix record must stitch into
    the assembled fallback under the `serving_prefix` key with its
    companion fields intact."""
    bench = _load_bench()
    M = bench._METRIC_OF
    assert M["serving_prefix"] == "lm_serving_prefix_hit_rate"
    assert "serving_prefix" in bench.BENCHES
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        {"ts": "2026-08-01T09:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0, "vs_baseline": 2.0}},
        {"ts": "2026-08-02T10:00:00+00:00",
         "record": {"metric": M["serving_prefix"], "value": 0.94,
                    "lm_serving_prefill_tokens_saved_total": 5760,
                    "first_tok_ms_p50": 449.2,
                    "baseline_first_tok_ms_p50": 835.5,
                    "measured_at": "2026-08-02T10:00:00+00:00"}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._assemble_lkg()
    assert out["serving_prefix"]["value"] == 0.94
    assert out["serving_prefix"][
        "lm_serving_prefill_tokens_saved_total"] == 5760
    assert out["serving_prefix"]["baseline_first_tok_ms_p50"] == 835.5


def test_assemble_lkg_stitches_serving_chunked_record(tmp_path):
    """PR 8 wiring: the chunked-prefill record (lm_serving_p99_itl_chunked_ms
    + the baseline/first-token tail companions) rides the same per-config
    queue shape — a top-level BENCH_ONLY=serving_chunked record must
    stitch into the assembled fallback under the `serving_chunked` key
    with the A/B companion fields intact."""
    bench = _load_bench()
    M = bench._METRIC_OF
    assert M["serving_chunked"] == "lm_serving_p99_itl_chunked_ms"
    assert "serving_chunked" in bench.BENCHES
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        {"ts": "2026-08-02T09:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0, "vs_baseline": 2.0}},
        {"ts": "2026-08-03T10:00:00+00:00",
         "record": {"metric": M["serving_chunked"], "value": 12.4,
                    "baseline_itl_ms_p99": 310.7,
                    "itl_ms_p50": 9.8,
                    "baseline_first_tok_ms_p99": 1200.0,
                    "first_tok_ms_p99": 640.2,
                    "p99_itl_improved": True,
                    "measured_at": "2026-08-03T10:00:00+00:00"}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._assemble_lkg()
    assert out["serving_chunked"]["value"] == 12.4
    assert out["serving_chunked"]["baseline_itl_ms_p99"] == 310.7
    assert out["serving_chunked"]["p99_itl_improved"] is True


def test_assemble_lkg_stitches_serving_fleet_record(tmp_path):
    """ISSUE 10 wiring (+ ISSUE 13's fleet trace-overhead probe): the
    fleet-router record (affinity-arm tok/s + the affinity-vs-random
    hit-rate comparison companions + the router-path tracing-overhead
    pct) rides the same per-config queue shape — a top-level
    BENCH_ONLY=serving_fleet record must stitch into the assembled
    fallback under the `serving_fleet` key with the companions intact."""
    bench = _load_bench()
    M = bench._METRIC_OF
    assert M["serving_fleet"] == "lm_serving_fleet_tok_per_sec"
    assert "serving_fleet" in bench.BENCHES
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        {"ts": "2026-08-03T09:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0, "vs_baseline": 2.0}},
        {"ts": "2026-08-04T10:00:00+00:00",
         "record": {"metric": M["serving_fleet"], "value": 5120.4,
                    "single_tok_per_sec": 2700.1,
                    "speedup_vs_single": 1.896,
                    "hit_rate_affinity": 0.91,
                    "hit_rate_random": 0.55,
                    "affinity_hit_gt_random": True,
                    "lm_serving_fleet_trace_overhead_pct": 0.7,
                    "trace_on_tok_per_sec": 5084.6,
                    "measured_at": "2026-08-04T10:00:00+00:00"}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._assemble_lkg()
    assert out["serving_fleet"]["value"] == 5120.4
    assert out["serving_fleet"]["hit_rate_affinity"] == 0.91
    assert out["serving_fleet"]["hit_rate_random"] == 0.55
    assert out["serving_fleet"]["affinity_hit_gt_random"] is True
    # the fleet trace-overhead probe (router + replica tracing ON through
    # the router path, <= 2% budget) survives the per-part stitch
    assert out["serving_fleet"][
        "lm_serving_fleet_trace_overhead_pct"] == 0.7
    assert out["serving_fleet"]["trace_on_tok_per_sec"] == 5084.6


def test_assemble_lkg_stitches_serving_disagg_record(tmp_path):
    """ISSUE 19 wiring: the disaggregated prefill/decode record
    (role-split tok/s vs the colocated arm + the kv_push transfer
    ledger) rides the same per-config queue shape — a top-level
    BENCH_ONLY=serving_disagg record must stitch into the assembled
    fallback under the `serving_disagg` key with the companions
    intact."""
    bench = _load_bench()
    M = bench._METRIC_OF
    assert M["serving_disagg"] == "lm_serving_disagg_tok_per_sec"
    assert "serving_disagg" in bench.BENCHES
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        {"ts": "2026-08-03T09:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0, "vs_baseline": 2.0}},
        {"ts": "2026-08-05T10:00:00+00:00",
         "record": {"metric": M["serving_disagg"], "value": 4980.2,
                    "coloc_tok_per_sec": 4410.7,
                    "speedup_vs_coloc": 1.129,
                    "first_tok_ms_p50": 21.4,
                    "first_tok_ms_p99": 48.9,
                    "coloc_first_tok_ms_p50": 35.6,
                    "coloc_first_tok_ms_p99": 92.3,
                    "kv_pushes": 64.0,
                    "kv_push_failures": 0.0,
                    "kv_fallbacks": 0.0,
                    "pages_shipped": 512.0,
                    "ok": True,
                    "measured_at": "2026-08-05T10:00:00+00:00"}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._assemble_lkg()
    assert out["serving_disagg"]["value"] == 4980.2
    assert out["serving_disagg"]["coloc_tok_per_sec"] == 4410.7
    assert out["serving_disagg"]["speedup_vs_coloc"] == 1.129
    # the transfer-plane reconcile ledger (pages genuinely shipped,
    # zero push failures or fallbacks) survives the per-part stitch
    assert out["serving_disagg"]["kv_pushes"] == 64.0
    assert out["serving_disagg"]["kv_push_failures"] == 0.0
    assert out["serving_disagg"]["kv_fallbacks"] == 0.0
    assert out["serving_disagg"]["pages_shipped"] == 512.0
    assert out["serving_disagg"]["ok"] is True


def test_assemble_lkg_stitches_serving_tp_record(tmp_path):
    """ISSUE 11 wiring: the tensor-parallel sharded-decode record
    (lm_serving_tp_tok_per_sec + the 1-vs-N-shard A/B companions incl.
    the per-shard pool bytes) rides the same per-config queue shape —
    a top-level BENCH_ONLY=serving_tp record must stitch into the
    assembled fallback under the `serving_tp` key with the companions
    intact."""
    bench = _load_bench()
    M = bench._METRIC_OF
    assert M["serving_tp"] == "lm_serving_tp_tok_per_sec"
    assert "serving_tp" in bench.BENCHES
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        {"ts": "2026-08-03T09:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0, "vs_baseline": 2.0}},
        {"ts": "2026-08-04T11:00:00+00:00",
         "record": {"metric": M["serving_tp"], "value": 8412.9,
                    "mesh_model": 2,
                    "single_tok_per_sec": 5100.3,
                    "speedup_vs_single": 1.65,
                    "pool_bytes_per_shard": 402653184,
                    "single_pool_bytes": 805306368,
                    "pool_shrink_vs_single": 2.0,
                    "sig_stable": True,
                    "measured_at": "2026-08-04T11:00:00+00:00"}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._assemble_lkg()
    assert out["serving_tp"]["value"] == 8412.9
    assert out["serving_tp"]["pool_shrink_vs_single"] == 2.0
    assert out["serving_tp"]["speedup_vs_single"] == 1.65
    assert out["serving_tp"]["sig_stable"] is True


def test_assemble_lkg_stitches_serving_spec_record(tmp_path):
    """ISSUE 12 wiring: the speculative-decoding record
    (lm_serving_spec_tok_per_sec + the accept rate and the drafted/
    accepted/emitted reconciliation companions) rides the same
    per-config queue shape — a top-level BENCH_ONLY=serving_spec record
    must stitch into the assembled fallback under the `serving_spec`
    key with the companions intact."""
    bench = _load_bench()
    M = bench._METRIC_OF
    assert M["serving_spec"] == "lm_serving_spec_tok_per_sec"
    assert "serving_spec" in bench.BENCHES
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        {"ts": "2026-08-03T09:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0, "vs_baseline": 2.0}},
        {"ts": "2026-08-04T12:00:00+00:00",
         "record": {"metric": M["serving_spec"], "value": 9120.7,
                    "lm_serving_spec_accept_rate": 0.62,
                    "baseline_tok_per_sec": 4100.2,
                    "speedup_vs_baseline": 2.22,
                    "drafted": 12000, "accepted": 7440,
                    "chains": 4210, "spec_tokens": 11650,
                    "reconcile_ok": True, "sig_stable": True,
                    "measured_at": "2026-08-04T12:00:00+00:00"}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._assemble_lkg()
    assert out["serving_spec"]["value"] == 9120.7
    assert out["serving_spec"]["lm_serving_spec_accept_rate"] == 0.62
    assert out["serving_spec"]["speedup_vs_baseline"] == 2.22
    assert out["serving_spec"]["reconcile_ok"] is True
    assert out["serving_spec"]["sig_stable"] is True


def test_assemble_lkg_stitches_serving_spill_record(tmp_path):
    """ISSUE 17 wiring: the host-spill record (lm_serving_spill_hit_rate
    + the off-arm comparison and spill/restore page counters) rides the
    same per-config queue shape — a top-level BENCH_ONLY=serving_spill
    record must stitch into the assembled fallback under the
    `serving_spill` key with the companions intact."""
    bench = _load_bench()
    M = bench._METRIC_OF
    assert M["serving_spill"] == "lm_serving_spill_hit_rate"
    assert "serving_spill" in bench.BENCHES
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        {"ts": "2026-08-03T09:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0, "vs_baseline": 2.0}},
        {"ts": "2026-08-04T12:00:00+00:00",
         "record": {"metric": M["serving_spill"], "value": 0.91,
                    "lm_serving_spill_tok_per_sec": 5120.5,
                    "off_hit_rate": 0.42, "hit_rate_improved": True,
                    "spilled_pages": 480, "restored_pages": 455,
                    "restore_hits": 120, "restore_tokens_saved": 6900,
                    "reconcile_ok": True, "sig_stable": True,
                    "measured_at": "2026-08-04T12:00:00+00:00"}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._assemble_lkg()
    assert out["serving_spill"]["value"] == 0.91
    assert out["serving_spill"]["lm_serving_spill_tok_per_sec"] == 5120.5
    assert out["serving_spill"]["off_hit_rate"] == 0.42
    assert out["serving_spill"]["hit_rate_improved"] is True
    assert out["serving_spill"]["restored_pages"] == 455
    assert out["serving_spill"]["reconcile_ok"] is True
    assert out["serving_spill"]["sig_stable"] is True


def test_serving_latency_fields_ride_the_lkg_and_freshness_paths(tmp_path):
    """The serving record's p99 per-token latency companion
    (lm_serving_p99_tok_latency_ms) must survive _assemble_lkg."""
    bench = _load_bench()
    M = bench._METRIC_OF
    log = tmp_path / "PERF_LOG.jsonl"
    old = {"ts": "2026-08-01T10:00:00+00:00",
           "record": {"metric": M["serving"], "value": 1500.0,
                      "measured_at": "2026-08-01T10:00:00+00:00"}}
    new = {"ts": "2026-08-02T10:00:00+00:00",
           "record": {"metric": M["serving"], "value": 2100.0,
                      "tok_latency_ms_p50": 4.2,
                      "lm_serving_p99_tok_latency_ms": 9.7,
                      "measured_at": "2026-08-02T10:00:00+00:00"}}
    log.write_text(json.dumps(old) + "\n" + json.dumps(new) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._assemble_lkg()
    assert out["serving"]["lm_serving_p99_tok_latency_ms"] == 9.7


def test_assemble_lkg_decode_only_survives_missing_train(tmp_path):
    """s2s_decode can bank while s2s_train wedges — the measured decode
    number must still surface in the assembled fallback."""
    bench = _load_bench()
    M = bench._METRIC_OF
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        {"ts": "2026-07-30T09:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0, "vs_baseline": 2.0}},
        {"ts": "2026-07-30T12:00:00+00:00",
         "record": {"metric": "wmt14_seq2seq_beam_decode_tokens_per_sec",
                    "value": 61000.0,
                    "beam_decode_tokens_per_sec": 61000.0,
                    "measured_at": "2026-07-30T12:00:00+00:00"}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._assemble_lkg()
    assert out["seq2seq"]["beam_decode_tokens_per_sec"] == 61000.0


def test_ts_newer_parses_before_comparing():
    """ADVICE r5 regression: measured_at ordering must ISO-parse, not
    string-compare — a non-UTC offset (or naive-vs-aware mix) can rank a
    STALE timestamp above a newer one lexicographically."""
    bench = _load_bench()
    # 15:00+05:00 == 10:00Z, OLDER than 11:00Z — but string-wise "15" > "11"
    assert not bench._ts_newer("2026-07-30T15:00:00+05:00",
                               "2026-07-30T11:00:00+00:00")
    assert bench._ts_newer("2026-07-30T11:00:00+00:00",
                           "2026-07-30T15:00:00+05:00")
    # 'Z' suffix and naive (assumed UTC) both parse
    assert bench._ts_newer("2026-07-30T11:00:00Z", "2026-07-30T10:59:59")
    # unparseable falls back to the string compare (empty = oldest)
    assert bench._ts_newer("2026-07-30T11:00:00+00:00", "")
    assert not bench._ts_newer("", "2026-07-30T11:00:00+00:00")


def test_assemble_lkg_orders_mixed_timestamp_formats(tmp_path):
    """A per-config top-level record measured at 11:00Z must supersede a
    nested part stamped 15:00+05:00 (= 10:00Z): the lexicographic compare
    picked the stale nested part here (ADVICE r5)."""
    bench = _load_bench()
    M = bench._METRIC_OF
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        {"ts": "2026-07-30T12:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0, "vs_baseline": 2.0,
                    "measured_at": "2026-07-30T12:00:00+00:00",
                    "mnist": {"metric": M["mnist"], "value": 111.0,
                              "measured_at": "2026-07-30T15:00:00+05:00"}}},
        {"ts": "2026-07-30T11:00:00+00:00",
         "record": {"metric": M["mnist"], "value": 222.0,
                    "vs_baseline": 1.0,
                    "measured_at": "2026-07-30T11:00:00+00:00"}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._assemble_lkg()
    assert out["mnist"]["value"] == 222.0, (
        "stale +05:00-stamped part selected over the newer UTC record")


def test_degraded_record_merges_lkg(tmp_path):
    bench = _load_bench()
    log = tmp_path / "PERF_LOG.jsonl"
    log.write_text(json.dumps(
        {"ts": "2026-07-30T10:00:00+00:00",
         "record": {"metric": "vgg16_cifar10_train_samples_per_sec_per_chip",
                    "value": 123.0, "vs_baseline": 2.5, "mfu": 0.41,
                    "platform": "tpu"}}) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._degraded_record("backend died")
    assert out["error"] == "backend died" and out["degraded"] is True
    assert out["value"] == 123.0 and out["mfu"] == 0.41
    assert out["platform"] == "tpu"           # provenance preserved
    assert "last-known-good" in out["degraded_source"]
    json.dumps(out)                           # always serializable


def test_degraded_record_without_lkg(tmp_path):
    bench = _load_bench()
    bench._PERF_LOG = str(tmp_path / "absent.jsonl")
    out = bench._degraded_record("nothing ever measured")
    assert out["value"] == 0.0 and out["vs_baseline"] == 0.0
    assert out["degraded"] is True and "degraded_source" not in out


def test_append_perf_log_roundtrip(tmp_path):
    bench = _load_bench()
    bench._PERF_LOG = str(tmp_path / "PERF_LOG.jsonl")
    bench._append_perf_log({"metric": bench._METRIC_OF["vgg"], "value": 7.0,
                            "vs_baseline": 1.1})
    out = bench._assemble_lkg()
    assert out["value"] == 7.0
    assert "T" in out["measured_at"]          # ISO timestamp (from log ts)


def test_spawn_reports_timeout_as_error():
    bench = _load_bench()
    rc, out, err = bench._run_group(
        [sys.executable, "-c", "import time; time.sleep(30)"], 1.5)
    assert rc is None                         # timed out, group killed


def test_spawn_recovers_interim_record_on_timeout(monkeypatch):
    """A child killed mid-phase (the seq2seq decode wedge) must yield its
    last banked BENCH_JSON line, marked partial — not a bare timeout."""
    bench = _load_bench()
    interim = {"metric": "wmt14_seq2seq_train_samples_per_sec_per_chip",
               "value": 123.0, "beam_decode": "pending"}
    stdout = ("noise\nBENCH_JSON:" + json.dumps(interim) +
              "\nmore noise after the bank\n")
    monkeypatch.setattr(bench, "_run_group",
                        lambda argv, t: (None, stdout, ""))
    out = bench._spawn("seq2seq", 900)
    assert out["value"] == 123.0
    assert "partial" in out and "error" not in out
    # ISSUE 6: the interim record carries the degraded provenance flag —
    # it was measured inside a wedging window (the r04/r05 init-hang
    # pattern), so LKG assembly must be able to skip it explicitly
    assert out["degraded"] is True

    # no banked line -> the plain timeout error as before
    monkeypatch.setattr(bench, "_run_group",
                        lambda argv, t: (None, "no json here", ""))
    out = bench._spawn("seq2seq", 900)
    assert "error" in out and "timeout" in out["error"]


def test_assemble_lkg_skips_degraded_records_explicitly(tmp_path):
    """ISSUE 6: records (and nested parts) flagged `degraded` — a wedged
    child's interim numbers, or parts echoed into a degraded fallback —
    must be skipped by provenance, NOT by hoping a healthy record has a
    newer timestamp.  Here the degraded records are strictly NEWER than
    the healthy ones, which timestamp ordering alone would get wrong."""
    bench = _load_bench()
    M = bench._METRIC_OF
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        # the healthy measurements — OLDER than everything degraded
        {"ts": "2026-07-28T10:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0, "vs_baseline": 2.0,
                    "platform": "tpu",
                    "measured_at": "2026-07-28T10:00:00+00:00",
                    "lm": {"metric": M["lm"], "value": 5000.0,
                           "measured_at": "2026-07-28T10:00:00+00:00"}}},
        # a newer top-level record measured in a degraded window (a killed
        # child's interim bank — _spawn stamps partial + degraded)
        {"ts": "2026-07-29T10:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 1.0, "vs_baseline": 0.1,
                    "partial": "child killed after 900s; interim record",
                    "degraded": True,
                    "measured_at": "2026-07-29T10:00:00+00:00"}},
        # a newer full record whose nested lm part is a degraded interim
        {"ts": "2026-07-30T10:00:00+00:00",
         "record": {"metric": M["sentiment"], "value": 9.0,
                    "measured_at": "2026-07-30T10:00:00+00:00",
                    "lm": {"metric": M["lm"], "value": 2.0,
                           "degraded": True,
                           "measured_at": "2026-07-30T10:00:00+00:00"}}},
        # a degraded fallback record echoing LKG parts (parent flag) —
        # its nested serving echo must not read as a fresh measurement
        {"ts": "2026-07-31T10:00:00+00:00",
         "record": {"error": "backend died", "degraded": True,
                    "metric": M["vgg"], "value": 100.0,
                    "serving": {"metric": M["serving"], "value": 777.0,
                                "measured_at":
                                    "2026-07-31T10:00:00+00:00"}}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    bench._PERF_LOG = str(log)

    out = bench._assemble_lkg()
    assert out["value"] == 100.0              # healthy headline, not 1.0
    assert out["measured_at"] == "2026-07-28T10:00:00+00:00"
    assert out["lm"]["value"] == 5000.0       # healthy part, not 2.0
    # the degraded fallback's echoed serving part never became "measured"
    assert "serving" not in out
    assert out["sentiment"]["value"] == 9.0   # healthy parts still stitch


def test_assemble_lkg_stitches_train_dist_record(tmp_path):
    """ISSUE 14 wiring: the parameter-server training record
    (train_dist_samples_per_sec + the 1-trainer arm and scaling
    efficiency) rides the per-config queue shape — a top-level
    BENCH_ONLY=train_dist record must stitch into the assembled fallback
    under the `train_dist` key with the companions intact."""
    bench = _load_bench()
    M = bench._METRIC_OF
    assert M["train_dist"] == "train_dist_samples_per_sec"
    assert "train_dist" in bench.BENCHES
    log = tmp_path / "PERF_LOG.jsonl"
    rows = [
        {"ts": "2026-08-03T09:00:00+00:00",
         "record": {"metric": M["vgg"], "value": 100.0,
                    "vs_baseline": 2.0}},
        {"ts": "2026-08-04T12:00:00+00:00",
         "record": {"metric": M["train_dist"], "value": 5321.7,
                    "trainers": 2,
                    "single_samples_per_sec": 2900.4,
                    "scaling_efficiency": 0.9174,
                    "fleet_wall_s": 3.2,
                    "train_dist_trace_overhead_pct": 0.8,
                    "trace_overhead_spread_pct": 2.1,
                    "trace_off_samples_per_sec": 5400.0,
                    "trace_on_samples_per_sec": 5356.8,
                    "measured_at": "2026-08-04T12:00:00+00:00"}},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    bench._PERF_LOG = str(log)
    out = bench._assemble_lkg()
    assert out["train_dist"]["value"] == 5321.7
    assert out["train_dist"]["scaling_efficiency"] == 0.9174
    assert out["train_dist"]["single_samples_per_sec"] == 2900.4
    # ISSUE 15 wiring: the live-flip trace-overhead probe's fields ride
    # the same record through the fallback assembly
    assert out["train_dist"]["train_dist_trace_overhead_pct"] == 0.8
    assert out["train_dist"]["trace_overhead_spread_pct"] == 2.1
    assert out["train_dist"]["trace_off_samples_per_sec"] == 5400.0


def test_main_without_a_tpu_exits_1_and_replays_nothing(tmp_path):
    """No TPU: the error on stderr, nothing on stdout (no last-known-good
    number replayed from PERF_LOG.jsonl), exit 1."""
    import subprocess

    log = tmp_path / "PERF_LOG.jsonl"
    log.write_text(json.dumps({
        "ts": "2026-08-01T10:00:00+00:00",
        "record": {"metric": "vgg16_cifar10_train_samples_per_sec_per_chip",
                   "value": 51393.97, "platform": "tpu"}}) + "\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "BENCH_PERF_LOG": str(log)},
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 1
    assert out.stdout.strip() == ""
    assert "no TPU backend" in out.stderr
    assert "51393" not in out.stdout + out.stderr


def test_unknown_device_kind_has_no_assumed_peak(monkeypatch):
    import types

    import jax

    bench = _load_bench()
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(device_kind="TPU v5 lite")])
    assert bench._chip_peak_tflops("bfloat16") == 197.0
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(device_kind="Some Future Chip")])
    with pytest.raises(ValueError, match="some future chip"):
        bench._chip_peak_tflops("bfloat16")
