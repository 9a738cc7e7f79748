"""Deep-introspection suite (ISSUE 6): compile watch + storm detector,
flight-recorder ring + atomic postmortem bundles, device-memory
accounting, and the tools/postmortem.py round-trip.

The serving-server trigger paths (pump death, watchdog wedge, `dump`
RPC) are exercised over TCP in tests/test_server.py; this file owns the
unit semantics plus the REAL bucket-churn storm: an engine fed prompts
across distinct prefill buckets must fire the recompile-storm detector
EXACTLY ONCE.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from paddle_tpu.obs.compile_watch import (CompileWatch, compile_collector,
                                          get_compile_watch, signature_of)
from paddle_tpu.obs.flight import (BUNDLE_FILES, FlightRecorder,
                                   flight_collector, load_bundle)
from paddle_tpu.obs.hbm import hbm_collector, hbm_snapshot, tree_bytes


# ---------------------------------------------------------------------------
# compile watch
# ---------------------------------------------------------------------------

def test_signature_of_describes_shapes_and_scalars():
    a = np.zeros((4, 8), np.float32)
    sig = signature_of((a, 3, "greedy"), {"flag": True})
    assert "float32[4,8]" in sig and "3" in sig and "True" in sig
    # nested pytrees walk deterministically (dict order by key)
    s1 = signature_of(({"b": a, "a": np.zeros(2, np.int32)},), {})
    s2 = signature_of(({"a": np.zeros(2, np.int32), "b": a},), {})
    assert s1 == s2
    # a big pytree digests down to a bounded signature
    big = tuple(np.zeros(i + 1) for i in range(64))
    assert len(signature_of((big,), {})) < 160


class _FakeJit:
    """Jit stand-in: cache grows on each new input shape."""

    def __init__(self):
        self.sigs = set()
        self.calls = 0

    def _cache_size(self):
        return len(self.sigs)

    def __call__(self, x):
        self.calls += 1
        self.sigs.add(np.asarray(x).shape)
        return x

    def lower(self):
        return "lowered"


def test_wrap_jit_detects_compiles_by_cache_growth_and_proxies_attrs():
    cw = CompileWatch(storm_n=99)
    fn = cw.wrap_jit("t.site", _FakeJit())
    fn(np.zeros((2, 2)))                      # compile 1
    fn(np.zeros((2, 2)))                      # cache hit
    fn(np.zeros((4, 4)))                      # compile 2
    snap = cw.snapshot()["t.site"]
    assert snap["compiles"] == 2 and snap["signatures"] == 2
    assert snap["storms"] == 0
    # introspection flows through the proxy (the oracle tests use
    # ._cache_size() and .lower() on the wrapped object)
    assert fn._cache_size() == 2
    assert fn.lower() == "lowered"
    assert fn.calls == 3


def test_watch_context_records_first_key_only():
    cw = CompileWatch()
    with cw.watch("lm.gen", (2, 8, 4)):
        pass
    with cw.watch("lm.gen", (2, 8, 4)):       # repeat key: no event
        pass
    with cw.watch("lm.gen", (2, 16, 4)):      # new key: event
        pass
    snap = cw.snapshot()["lm.gen"]
    assert snap["compiles"] == 2 and snap["signatures"] == 2
    # an exception inside the watched block records nothing (the call
    # never finished; the NEXT successful call owns the compile event)
    with pytest.raises(RuntimeError):
        with cw.watch("lm.gen", (9, 9, 9)):
            raise RuntimeError("boom")
    assert cw.snapshot()["lm.gen"]["compiles"] == 2


def test_storm_detector_fires_once_then_rearms_after_window_drains():
    cw = CompileWatch(storm_n=3, storm_window_s=0.25)
    for i in range(5):                        # 5 distinct sigs in-window
        cw.record("site", f"sig{i}", 0.01)
    assert cw.storms["site"] == 1, \
        "a sustained storm must be ONE alert, not an alert storm"
    time.sleep(0.3)                           # window drains -> re-arm
    for i in range(3):
        cw.record("site", f"late{i}", 0.01)
    assert cw.storms["site"] == 2


def test_compile_collector_emits_catalog_names_per_site():
    cw = CompileWatch()
    cw.record("a.site", "s0", 0.5)
    cw.record("a.site", "s1", 0.25)
    out = compile_collector(cw)()
    by_name = {}
    for name, kind, labels, val in out:
        assert labels == {"site": "a.site"}
        by_name[name] = (kind, val)
    assert by_name["jit_compiles_total"] == ("counter", 2.0)
    assert by_name["jit_signatures"] == ("gauge", 2.0)
    assert by_name["jit_compile_seconds"][1] == pytest.approx(0.75)
    assert by_name["jit_recompile_storms_total"] == ("counter", 0.0)


def test_bucket_churn_fires_storm_exactly_once(monkeypatch):
    """The acceptance storm: REAL compiles at a site that churns.  The
    mixed step is one signature per token budget, so three distinct
    max_step_tokens values against storm_n=3 fire the detector exactly
    once at serving.mixed_step — and the decode step stays one signature
    throughout (no storm there)."""
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.serving import Request, ServingEngine
    from paddle_tpu.trainer.trainer import Trainer

    fresh = CompileWatch(storm_n=3, storm_window_s=300.0)
    monkeypatch.setattr("paddle_tpu.serving.engine.get_compile_watch",
                        lambda: fresh)
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=31,dim=16,layers=1,heads=2,batch_size=4")
    tr = Trainer(cfg, seed=7)
    rng = np.random.default_rng(0)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                        max_context=64)
    for i, budget in enumerate((10, 12, 14)):
        eng.set_chunking(8, max_step_tokens=budget)
        eng.run([Request(i, rng.integers(2, 31, 12).astype(np.int32),
                         max_new=3)])

    snap = fresh.snapshot()
    assert snap["serving.mixed_step"]["signatures"] == 3
    assert snap["serving.mixed_step"]["storms"] == 1, \
        "3 distinct mixed-step signatures at storm_n=3 must fire EXACTLY once"
    assert snap["serving.decode_step"]["signatures"] == 1
    assert snap["serving.decode_step"].get("storms", 0) == 0


# ---------------------------------------------------------------------------
# flight recorder ring
# ---------------------------------------------------------------------------

def test_flight_ring_bounded_keeps_newest_in_order():
    fr = FlightRecorder(capacity=4)
    fr.record("dropped_while_disabled")
    assert fr.recorded == 0
    fr.enabled = True
    for i in range(10):
        fr.record("ev", i=i)
    assert fr.recorded == 10 and fr.dropped == 6
    evs = fr.snapshot()
    assert [e["data"]["i"] for e in evs] == [6, 7, 8, 9]
    assert [e["seq"] for e in evs] == [6, 7, 8, 9]
    assert all(e["kind"] == "ev" for e in evs)


def test_flight_collector_reports_ring_accounting():
    fr = FlightRecorder(capacity=2)
    fr.enabled = True
    for _ in range(5):
        fr.record("x")
    fr.bundles_written = 1
    vals = {name: v for name, _k, _l, v in flight_collector(fr)()}
    assert vals["flight_events_recorded_total"] == 5.0
    assert vals["flight_events_dropped_total"] == 3.0
    assert vals["postmortem_bundles_total"] == 1.0


# ---------------------------------------------------------------------------
# postmortem bundles
# ---------------------------------------------------------------------------

def _dump(fr, out_dir, **kw):
    kw.setdefault("spans", [{"seq": 0, "name": "queued", "track": "req:a",
                             "ts": 0.0, "dur": 0.5}])
    kw.setdefault("engine", {"n_decode_steps": 3, "slots": [None],
                             "queued": [], "pages_in_use": 0,
                             "free_pages": 7, "num_pages": 8,
                             "page_size": 8})
    kw.setdefault("metrics", {"pump_alive": 1.0})
    kw.setdefault("config", {"num_slots": 1})
    return fr.dump(str(out_dir), "test_reason", **kw)


def test_bundle_dump_load_roundtrip_schema(tmp_path):
    fr = FlightRecorder()
    fr.enabled = True
    fr.record("queued", req="r0")
    fr.record("pump_death", error="boom")
    path = _dump(fr, tmp_path, error="RuntimeError: boom\n  traceback")

    assert os.path.basename(path).startswith("postmortem-")
    assert not path.endswith(".tmp")
    for name in BUNDLE_FILES:
        assert os.path.exists(os.path.join(path, name)), name
    b = load_bundle(path)
    assert b["meta"]["reason"] == "test_reason"
    assert b["meta"]["pid"] == os.getpid()
    assert "python" in b["meta"]["versions"]
    assert b["meta"]["error"].startswith("RuntimeError: boom")
    assert [e["kind"] for e in b["events"]] == ["queued", "pump_death"]
    assert b["spans"][0]["name"] == "queued"
    assert b["engine"]["free_pages"] == 7
    assert b["metrics"]["pump_alive"] == 1.0
    assert b["config"]["num_slots"] == 1
    # bundle spans are tools/trace_dump.py food directly
    from tools.trace_dump import load_spans, summarize

    spans = load_spans(os.path.join(path, "spans.jsonl"))
    assert "queued" in summarize(spans)


def test_bundle_same_second_redump_and_unserializable_part(tmp_path):
    fr = FlightRecorder()
    fr.enabled = True
    fr.record("ev")
    p1 = _dump(fr, tmp_path)
    circular = {}
    circular["self"] = circular                # json refuses: ValueError
    p2 = _dump(fr, tmp_path, engine=circular)
    assert p1 != p2                            # same-second dump: suffixed
    assert fr.bundles_written == 2
    b2 = load_bundle(p2)
    # the broken part degraded to a stub; the bundle itself committed
    assert "snapshot_error" in b2["engine"]
    assert b2["meta"]["reason"] == "test_reason"


def test_load_bundle_refuses_tmp_straggler_and_nondir(tmp_path):
    frag = tmp_path / "postmortem-x.tmp"
    frag.mkdir()
    (frag / "meta.json").write_text("{}")      # crashed mid-dump
    with pytest.raises(ValueError, match="incomplete bundle"):
        load_bundle(str(frag))
    with pytest.raises(ValueError, match="not a bundle"):
        load_bundle(str(tmp_path / "absent"))


def test_postmortem_tool_renders_and_exits_nonzero_on_bad(tmp_path, capsys):
    from tools.postmortem import main

    fr = FlightRecorder()
    fr.enabled = True
    fr.record("queued", req="r0")
    fr.record("wedge", age_s=31.2)
    path = _dump(fr, tmp_path, engine={
        "n_decode_steps": 5, "tokens_generated": 12, "n_preemptions": 1,
        "n_cancelled": 0, "n_expired": 0,
        "slots": [{"slot": 0, "req_id": "r0", "pos": 9, "generated": 2,
                   "max_new": 8}, None],
        "queued": ["r1", "r2"], "pages_in_use": 3, "free_pages": 5,
        "num_pages": 8, "page_size": 8,
        "compile_watch": {"serving.prefill": {
            "compiles": 4, "seconds": 1.25, "signatures": 4, "storms": 1}},
        "hbm": {"kv_pool_bytes": 4096, "param_bytes": 1 << 20},
    }, metrics={"pump_alive": 0.0, "pump_last_step_age_s": 31.5})

    assert main([path]) == 0
    out = capsys.readouterr().out
    assert "test_reason" in out
    assert "[0] r0 pos=9 gen=2/8" in out
    assert "queued (2)" in out
    assert "3 in use" in out
    assert "serving.prefill" in out and "STORMS=1" in out
    assert "kv_pool=4.0KiB" in out and "param=1.0MiB" in out
    assert "wedge" in out

    assert main([path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["meta"]["reason"] == "test_reason"

    # a .tmp straggler (or junk path) is a loud exit 2
    frag = tmp_path / "postmortem-y.tmp"
    frag.mkdir()
    assert main([str(frag)]) == 2


# ---------------------------------------------------------------------------
# device-memory accounting
# ---------------------------------------------------------------------------

def test_tree_bytes_walks_mixed_pytrees_exactly():
    tree = {"w": np.zeros((4, 4), np.float32),       # 64
            "nested": [np.zeros(8, np.int32),        # 32
                       (np.zeros(2, np.float64),)],  # 16
            "scalar": 3, "none": None}
    assert tree_bytes(tree) == 64 + 32 + 16
    assert tree_bytes({}) == 0


def test_hbm_collector_cpu_safe_and_param_kv_gauges():
    """On the CPU test backend every probe may be absent — the collector
    must still answer, and the duck-typed param/KV gauges are always
    present when their accessors are given."""
    params = {"layer": {"w": np.zeros((16, 16), np.float32)}}

    class KV:
        pools = [np.zeros((8, 8), np.float32), np.zeros((8, 8), np.float32)]

    out = hbm_collector(params_fn=lambda: params, kv_fn=lambda: KV())()
    vals = {name: v for name, _k, _l, v in out}
    assert vals["hbm_param_bytes"] == 16 * 16 * 4
    assert vals["hbm_kv_pool_bytes"] == 2 * 8 * 8 * 4
    for name, kind, labels, _v in out:
        assert kind == "gauge" and labels is None
    # accessors optional: a bare registry still renders
    assert isinstance(hbm_collector()(), list)

    snap = hbm_snapshot(params=params)
    assert snap["param_bytes"] == 16 * 16 * 4
    json.dumps(snap)                           # bundle-ready


def test_hbm_gauges_ride_a_strict_registry_render():
    """The hbm_*/jit_*/flight_* names are CATALOG rows — a strict
    registry (what the server and trainer build) accepts the collectors
    and renders them."""
    from paddle_tpu.obs import MetricsRegistry

    reg = MetricsRegistry(strict=True)
    reg.register_collector(hbm_collector(
        params_fn=lambda: {"w": np.zeros(4, np.float32)}))
    cw = CompileWatch()
    cw.record("s", "sig", 0.1)
    reg.register_collector(compile_collector(cw))
    fr = FlightRecorder()
    reg.register_collector(flight_collector(fr))
    text = reg.render()
    assert "hbm_param_bytes 16" in text
    assert 'jit_compiles_total{site="s"} 1' in text
    assert "postmortem_bundles_total 0" in text
