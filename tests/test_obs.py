"""Observability suite (paddle_tpu/obs): tracer ring semantics, Chrome
trace export validity, metrics registry + Prometheus render, Stat
thread-safety, full request-lifecycle traces out of the serving engine
(incl. preempt + replay), and the trainer's metrics.jsonl sink."""

import json
import os
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from paddle_tpu.obs import (CATALOG, MetricsRegistry, Tracer,
                            barrier_collector, get_tracer,
                            spans_to_chrome, statset_collector)
from paddle_tpu.utils.stat import SAMPLE_WINDOW, Stat, StatSet


# ---------------------------------------------------------------------------
# tracer ring
# ---------------------------------------------------------------------------

def test_ring_overflow_keeps_newest_in_order():
    t = Tracer(capacity=8)
    t.enabled = True
    for i in range(20):
        t.add(f"s{i}", ts=float(i), dur=0.5)
    assert t.recorded == 20 and t.dropped == 12
    snap = t.snapshot()
    assert [s["name"] for s in snap] == [f"s{i}" for i in range(12, 20)]
    assert [s["seq"] for s in snap] == list(range(12, 20))
    # under capacity: everything retained, oldest first
    t.clear()
    t.add("a", 0.0, 1.0)
    t.add("b", 2.0, 1.0)
    assert [s["name"] for s in t.snapshot()] == ["a", "b"]
    assert t.dropped == 0


def test_tracer_overflow_surfaces_through_collector():
    """ISSUE 13 satellite: the ring drops spans SILENTLY when full — the
    only visibility is tracer_collector's accounting, so a strict
    registry must render recorded/dropped totals plus the capacity they
    are read against after an overflow."""
    from paddle_tpu.obs import tracer_collector

    t = Tracer(capacity=4)
    t.enabled = True
    for i in range(10):
        t.add(f"s{i}", float(i), 0.1)
    assert len(t.snapshot()) == 4          # the drop is silent...
    reg = MetricsRegistry(strict=True)
    reg.register_collector(tracer_collector(t))
    snap = reg.snapshot()                  # ...but not invisible
    assert snap["trace_spans_recorded_total"] == 10.0
    assert snap["trace_spans_dropped_total"] == 6.0
    assert snap["trace_ring_capacity"] == 4.0
    text = reg.render()
    assert "trace_spans_dropped_total 6" in text
    assert "trace_ring_capacity 4" in text


def test_merge_chrome_aligns_clocks_across_process_tracks():
    """ISSUE 13: merge_chrome applies each source's offset before the
    global rebase, gives every source its own pid + process_name, and
    two spans simultaneous in wall time land at the same merged ts even
    when the source perf_counter epochs differ wildly."""
    from paddle_tpu.obs import merge_chrome

    # process A's epoch: event at local t=100.0; process B's epoch is
    # 90s behind (same wall moment reads 10.0 there) -> offset_s=+90
    src_a = {"spans": [{"seq": 0, "name": "ingress", "track": "req:x",
                        "ts": 100.0, "dur": 2.0}],
             "process": {"role": "router", "pid": 11,
                         "addr": "h:1"}, "offset_s": 0.0}
    src_b = {"spans": [{"seq": 0, "name": "queued", "track": "req:x",
                        "ts": 10.0, "dur": 1.0},
                       {"seq": 1, "name": "done", "track": "req:x",
                        "ts": 11.5, "dur": 0.0, "instant": True}],
             "process": {"role": "replica", "pid": 11,
                         "addr": "h:2"}, "offset_s": 90.0}
    merged = merge_chrome([src_a, src_b])
    evs = merged["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in evs
             if e.get("name") == "process_name"}
    assert len(procs) == 2                 # same OS pid, distinct tracks
    assert "router" in procs[1] and "replica" in procs[2]
    ing = next(e for e in evs if e["name"] == "ingress")
    qd = next(e for e in evs if e["name"] == "queued")
    done = next(e for e in evs if e["name"] == "done")
    assert ing["ts"] == 0.0                # global rebase to earliest
    assert qd["ts"] == 0.0                 # same wall moment, aligned
    assert done["ts"] == pytest.approx(1.5e6)
    assert done["ph"] == "i" and qd["ph"] == "X"


def test_disabled_tracer_records_nothing():
    t = Tracer(capacity=8)
    t.add("x", 0.0, 1.0)
    t.instant("y")
    with t.span("z"):
        pass
    assert t.end(t.begin("w")) is None
    assert t.recorded == 0 and t.snapshot() == []


def test_begin_end_and_span_record_attrs_and_durations():
    t = Tracer()
    t.enabled = True
    h = t.begin("queued", track="req:a", max_new=4)
    t.end(h, reason="length")
    with t.span("prefill", track="req:a", bucket=16):
        pass
    t.instant("done", track="req:a")
    snap = t.snapshot()
    assert [s["name"] for s in snap] == ["queued", "prefill", "done"]
    assert snap[0]["attrs"] == {"max_new": 4, "reason": "length"}
    assert snap[1]["attrs"] == {"bucket": 16}
    assert snap[2].get("instant") is True
    assert all(s["dur"] >= 0.0 for s in snap)


def test_chrome_export_schema_and_track_nesting():
    """Chrome trace_event validity: metadata thread names per track, "X"
    complete events with non-negative ts/dur, instants as "i" — and spans
    on one track are monotonically ordered and non-overlapping (the
    sequential-phase contract a lifecycle trace relies on)."""
    t = Tracer()
    t.enabled = True
    t.add("queued", 10.0, 0.5, track="req:a")
    t.add("prefill", 10.5, 0.25, track="req:a", attrs={"bucket": 16})
    t.add("decode", 10.75, 1.0, track="req:a")
    t.instant("done", track="req:a")
    t.add("dispatch", 10.2, 0.1, track="trainer")
    doc = t.chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == {"req:a", "trainer"}
    xs = [e for e in evs if e["ph"] == "X"]
    ins = [e for e in evs if e["ph"] == "i"]
    assert len(xs) == 4 and len(ins) == 1
    for e in xs + ins:
        assert e["ts"] >= 0 and e["name"]
        assert {"pid", "tid"} <= set(e)
    assert all(e["dur"] >= 0 for e in xs)
    # per-track phases nest monotonically: next span starts at/after the
    # previous one's end (1us grid tolerance)
    tid_a = next(m["tid"] for m in meta if m["args"]["name"] == "req:a")
    lane = sorted((e for e in xs if e["tid"] == tid_a),
                  key=lambda e: e["ts"])
    assert [e["name"] for e in lane] == ["queued", "prefill", "decode"]
    for prev, nxt in zip(lane, lane[1:]):
        assert nxt["ts"] >= prev["ts"] + prev["dur"] - 1.0
    # attrs survive as args
    assert next(e for e in xs if e["name"] == "prefill")["args"] == \
        {"bucket": 16}
    # json-serializable end to end
    json.dumps(doc)


def test_trace_dump_tool_roundtrip(tmp_path):
    from tools.trace_dump import load_spans, main, summarize

    t = Tracer()
    t.enabled = True
    t.add("queued", 0.0, 0.5, track="req:a")
    t.instant("done", track="req:a")
    src = tmp_path / "spans.jsonl"
    assert t.export_jsonl(str(src)) == 2
    spans = load_spans(str(src))
    assert [s["name"] for s in spans] == ["queued", "done"]
    assert "queued" in summarize(spans)
    out = tmp_path / "trace.json"
    assert main([str(src), "-o", str(out)]) == 0
    with open(out) as f:
        doc = json.load(f)
    assert any(e.get("name") == "queued" for e in doc["traceEvents"])
    # empty input is a loud exit 2, not a silent empty trace
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert main([str(empty), "-o", str(out)]) == 2
    # a complete span missing dur (hand-edited / foreign JSONL) is the
    # clean error path too, not a KeyError traceback from spans_to_chrome
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"name": "x", "ts": 1.0}\n')
    assert main([str(bad), "-o", str(out)]) == 2


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram_render():
    reg = MetricsRegistry()
    c = reg.counter("demo_requests_total", "requests")
    c.inc()
    c.inc(2)
    g = reg.gauge("demo_depth", "queue depth", labels=("lane",))
    g.set(3, lane="a")
    g.set_fn(lambda: 7.0, lane="b")
    h = reg.histogram("demo_latency_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.render()
    assert "# HELP demo_requests_total requests" in text
    assert "# TYPE demo_requests_total counter" in text
    assert "demo_requests_total 3" in text
    assert 'demo_depth{lane="a"} 3' in text
    assert 'demo_depth{lane="b"} 7' in text
    assert "# TYPE demo_latency_seconds histogram" in text
    assert 'demo_latency_seconds_bucket{le="0.1"} 1' in text
    assert 'demo_latency_seconds_bucket{le="1"} 2' in text
    assert 'demo_latency_seconds_bucket{le="+Inf"} 3' in text
    assert "demo_latency_seconds_count 3" in text
    snap = reg.snapshot()
    assert snap["demo_requests_total"] == 3.0
    assert snap['demo_depth{lane="b"}'] == 7.0
    # re-declaration is idempotent; kind mismatch is loud
    assert reg.counter("demo_requests_total") is c
    with pytest.raises(ValueError, match="re-declared"):
        reg.gauge("demo_requests_total")
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    with pytest.raises(ValueError, match="declared labels"):
        g.set(1, wrong="x")


def test_strict_registry_pins_names_to_catalog():
    reg = MetricsRegistry(strict=True)
    reg.gauge("serving_queue_depth")             # catalogued: fine
    with pytest.raises(ValueError, match="CATALOG"):
        reg.gauge("not_a_documented_metric")
    reg.register_collector(lambda: [("rogue_metric", "gauge", None, 1.0)])
    with pytest.raises(ValueError, match="uncataloged"):
        reg.render()
    # every catalog name is docs-lintable (the tools/check_metrics_names
    # grammar): lowercase identifier
    for name in CATALOG:
        assert name[0].isalpha() and name == name.lower()


def test_statset_and_barrier_collectors():
    from paddle_tpu.parallel.barrier_stat import BarrierTimer

    ss = StatSet("t")
    for v in (0.01, 0.02, 0.03):
        ss.get("phase_a").add(v)
    reg = MetricsRegistry()
    reg.register_collector(statset_collector(
        ss, "trainer_host_phase_seconds", "trainer_host_phase_count",
        label="phase", total_metric="trainer_host_phase_seconds_total"))
    bt = BarrierTimer()
    bt.dispatch_s.extend([0.001, 0.002])
    reg.register_collector(barrier_collector(bt))
    snap = reg.snapshot()
    assert snap['trainer_host_phase_count{phase="phase_a"}'] == 3.0
    assert abs(snap['trainer_host_phase_seconds_total{phase="phase_a"}']
               - 0.06) < 1e-9
    p50 = snap['trainer_host_phase_seconds{phase="phase_a",quantile="p50"}']
    assert abs(p50 - 0.02) < 1e-9
    disp = snap['trainer_barrier_seconds{quantile="p50",window="dispatch"}']
    assert abs(disp - 0.0015) < 1e-9


# ---------------------------------------------------------------------------
# Stat thread-safety (pump add() vs stats-RPC percentiles())
# ---------------------------------------------------------------------------

def test_stat_concurrent_add_and_percentiles_exact():
    ss = StatSet("conc")
    n_threads, per = 4, 5000
    stop = threading.Event()
    errors = []

    def reader():
        try:
            while not stop.is_set():
                ss.percentiles("hot", (50.0, 99.0))
        except Exception as e:                     # noqa: BLE001
            errors.append(e)

    def writer(k):
        try:
            for i in range(per):
                ss.get("hot").add((k * per + i) * 1e-6)
        except Exception as e:                     # noqa: BLE001
            errors.append(e)

    readers = [threading.Thread(target=reader) for _ in range(2)]
    writers = [threading.Thread(target=writer, args=(k,))
               for k in range(n_threads)]
    for th in readers + writers:
        th.start()
    for th in writers:
        th.join()
    stop.set()
    for th in readers:
        th.join()
    assert not errors, errors
    s = ss.get("hot")
    # the lock makes accounting EXACT under contention, not approximate
    assert s.count == n_threads * per
    assert len(s.samples) == min(SAMPLE_WINDOW, s.count)
    total = sum((k * per + i) * 1e-6
                for k in range(n_threads) for i in range(per))
    assert abs(s.total_s - total) < 1e-9
    p = ss.percentiles("hot", (50.0,))
    assert p["p50"] > 0.0


def test_statset_get_creation_race_single_object():
    ss = StatSet("race")
    got = []

    def grab():
        got.append(ss.get("only"))

    ths = [threading.Thread(target=grab) for _ in range(8)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert all(s is got[0] for s in got)


# ---------------------------------------------------------------------------
# engine request-lifecycle traces (the oracle-implied phase regression)
# ---------------------------------------------------------------------------

@pytest.fixture()
def lifecycle_tracer():
    t = get_tracer()
    saved = (t.enabled, t._ring, t._n)
    t.clear()
    t.enabled = True
    yield t
    t.enabled, t._ring, t._n = saved


def _phases(tracer, rid):
    return [s["name"] for s in tracer.snapshot()
            if s["track"] == f"req:{rid}"]


def test_request_lifecycle_phases_incl_preempt_replay(lifecycle_tracer):
    """A full serving run traces exactly the lifecycle the oracle run
    implies: queued -> prefill -> decode -> done for untroubled requests;
    a page-pool preemption inserts preempt -> queued -> prefill -> replay
    before the terminal phase.  Durations are sane: phases on one request
    track are sequential and the decode span covers the decode steps."""
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.serving import Request, ServingEngine
    from paddle_tpu.trainer.trainer import Trainer

    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=31,dim=16,layers=1,heads=2,batch_size=4")
    tr = Trainer(cfg, seed=7)

    # -- no preemption: exact phase list ---------------------------------
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                        max_context=64)
    rng = np.random.default_rng(0)
    eng.add_request(Request("plain", rng.integers(2, 31, 5), max_new=4))
    res = eng.run()
    assert len(res["plain"]) == 9
    assert _phases(lifecycle_tracer, "plain") == \
        ["queued", "prefill", "decode", "done"]
    spans = {s["name"]: s for s in lifecycle_tracer.snapshot()
             if s["track"] == "req:plain"}
    # chunked prefill (the default): the span carries the chunk size and
    # prompt length instead of a legacy bucket
    assert spans["prefill"]["attrs"]["prompt_len"] == 5
    assert spans["prefill"]["attrs"]["chunk"] == eng.prefill_chunk
    assert spans["done"]["attrs"]["reason"] == "length"
    # sequential, non-overlapping phases
    order = [spans[n] for n in ("queued", "prefill", "decode")]
    for a, b in zip(order, order[1:]):
        assert b["ts"] >= a["ts"] + a["dur"] - 1e-6
    # the engine lane recorded one span per compiled step, the step's
    # kind in its name (the chunk step that sampled token 0 is `mixed`)
    steps = [s for s in lifecycle_tracer.snapshot()
             if s["track"] == "engine"
             and s["name"] in ("pt.step.decode", "pt.step.mixed")]
    assert len(steps) == eng.n_decode_steps
    assert [s["attrs"]["step"] for s in steps] == \
        list(range(1, eng.n_decode_steps + 1))
    # span-vs-stats reconciliation: the decode span covers every PURE
    # decode step this (only) request was live for — the mixed prefill
    # step ran inside the `prefill` phase, before decode opened
    assert spans["decode"]["dur"] >= sum(
        s["dur"] for s in steps if s["name"] == "pt.step.decode") - 1e-6

    # -- overcommitted pool: preempt + replay phases ---------------------
    lifecycle_tracer.clear()
    eng2 = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                         max_context=16, num_pages=6)
    rng = np.random.default_rng(1)
    reqs = [Request(f"r{i}", rng.integers(2, 31, 8), max_new=8)
            for i in range(2)]
    out = eng2.run(reqs)
    assert eng2.n_preemptions > 0, "pool was never overcommitted"
    assert set(out) == {"r0", "r1"}
    preempted = [s["track"][4:] for s in lifecycle_tracer.snapshot()
                 if s["name"] == "preempt"]
    assert preempted, "no preempt instant recorded"
    survivors = {"r0", "r1"} - set(preempted)
    for rid in survivors:
        assert _phases(lifecycle_tracer, rid) == \
            ["queued", "prefill", "decode", "done"]
    for rid in set(preempted):
        # a preempted victim's re-admission may prefix-hit its own donated
        # pages (the PR-7 donation, preserved across doomed retries by the
        # allocator's feasibility gate) — the `prefix_hit` instant rides
        # the same track; drop it when checking the phase SHAPE
        ph = [n for n in _phases(lifecycle_tracer, rid)
              if n != "prefix_hit"]
        # one preempt cycle: the oracle-implied shape is
        #   queued prefill decode (preempt queued prefill replay)+ ... done
        assert ph[:4] == ["queued", "prefill", "decode", "preempt"]
        assert "replay" in ph, f"preempted {rid} never traced a replay: {ph}"
        assert ph[-1] == "done"
        i = ph.index("replay")
        assert ph[i - 2:i] == ["queued", "prefill"], ph
        # replay happened strictly after the preempt marker
        assert i > ph.index("preempt")


@pytest.fixture(scope="module")
def shared_out_server():
    """A prompt of 21 served under a share of 4 and a step of 12: runs of
    12 and 9, 13 rows past the share, 3 rows of the second step empty."""
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.serving import Request, ServingEngine
    from paddle_tpu.serving.server import ServingServer
    from paddle_tpu.trainer.trainer import Trainer

    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=31,dim=16,layers=1,heads=2,batch_size=4")
    tr = Trainer(cfg, seed=7)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=4,
                        max_context=32, prefill_chunk=4, max_step_tokens=12)
    eng.run([Request("r", np.arange(2, 23, dtype=np.int32), max_new=1)])
    return eng, ServingServer(eng).metrics.render()


@pytest.mark.parametrize("name,attr,want", [
    ("serving_chunk_rows_total", "n_chunk_rows", 21),
    ("serving_chunk_extra_rows_total", "n_chunk_extra_rows", 8 + 5),
    ("serving_step_pad_rows_total", "n_step_pad_rows", 3)])
def test_share_out_counters_reach_the_metrics_frame(shared_out_server, name,
                                                    attr, want):
    """How a step's rows were shared out, as the engine counts it and as
    the server's collector renders it under a catalogued name."""
    eng, text = shared_out_server
    assert name in CATALOG
    assert getattr(eng, attr) == want
    assert f"# HELP {name} " in text and f"# TYPE {name} counter" in text
    assert f"\n{name} {want}" in text


def test_cancel_and_deadline_terminal_phases(lifecycle_tracer):
    """Aborted requests close their open phase and mark the right
    terminal event: cancelled (client abort while decoding) and deadline
    (expired while queued — no slot ever held)."""
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.serving import Request, ServingEngine
    from paddle_tpu.trainer.trainer import Trainer

    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=31,dim=16,layers=1,heads=2,batch_size=4")
    tr = Trainer(cfg, seed=3)
    eng = ServingEngine(tr.executor, tr.params, num_slots=1, page_size=8,
                        max_context=64)
    eng.clock = lambda: float(eng.n_decode_steps)
    eng.add_request(Request("work", [3, 4, 5], max_new=30))
    # expires while QUEUED: the single slot is busy with "work"
    eng.add_request(Request("late", [4, 5], max_new=30, deadline=2.0))
    for _ in range(4):
        eng.step()
    eng.cancel("work")
    ph_w = _phases(lifecycle_tracer, "work")
    assert ph_w == ["queued", "prefill", "decode", "cancelled"]
    ph_l = _phases(lifecycle_tracer, "late")
    assert ph_l == ["queued", "deadline"]


# ---------------------------------------------------------------------------
# trainer metrics.jsonl sink
# ---------------------------------------------------------------------------

def test_trainer_metrics_jsonl_sink(tmp_path):
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.parameter.argument import Argument
    from paddle_tpu.trainer.trainer import Trainer

    cfg_src = (
        "from paddle_tpu.dsl import *\n"
        "settings(batch_size=8, learning_rate=0.1)\n"
        "x = data_layer(name='x', size=4)\n"
        "out = fc_layer(input=x, size=2, act=SoftmaxActivation(), "
        "name='out')\n"
        "classification_cost(input=out, label=data_layer(name='y', "
        "size=2))\n")
    cfg_file = tmp_path / "cfg.py"
    cfg_file.write_text(cfg_src)
    tr = Trainer(parse_config(str(cfg_file), ""), seed=0)
    rng = np.random.default_rng(0)

    def batches():
        for _ in range(3):
            x = rng.normal(size=(8, 4)).astype(np.float32)
            yield {"x": Argument(value=x),
                   "y": Argument(ids=(x.sum(-1) > 0).astype(np.int32))}

    stats = tr.train_one_pass(batches=batches())
    path = tr.append_metrics(str(tmp_path / "run"), extra=stats)
    assert path.endswith("metrics.jsonl")
    with open(path) as f:
        recs = [json.loads(ln) for ln in f]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["pass_id"] == 1 and "ts" in rec
    assert rec["cost"] == pytest.approx(stats["cost"])
    m = rec["metrics"]
    assert m["trainer_pass_id"] == 1.0
    assert m["trainer_batches_total"] == 3.0
    assert m["trainer_samples_total"] == 24.0
    # the global StatSet host phases flowed through the collector
    assert any(k.startswith('trainer_host_phase_count{phase="trainOneBatch"')
               for k in m), sorted(m)[:8]
    # appends accumulate (one line per pass)
    tr.append_metrics(str(tmp_path / "run"))
    with open(path) as f:
        assert len(f.readlines()) == 2


# ---------------------------------------------------------------------------
# one span() call, two sinks: the ring and the profiler's timeline
# ---------------------------------------------------------------------------

def _profiler_events(trace_dir):
    """{name: [stats dict, ...]} of the `pt.` events a jax.profiler session
    left on the host planes, and the per-line (name, start, end) lists."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    by_name, lines = {}, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("pt.")]
            if evs:
                lines.append(evs)
            for name, _, _, stats in evs:
                by_name.setdefault(name, []).append(stats)
    return by_name, lines


def _nested_ok(evs):
    """One thread's events nest properly: any two are disjoint or one
    holds the other (1 us of clock slack)."""
    for i, (_, s0, e0, _) in enumerate(evs):
        for _, s1, e1, _ in evs[i + 1:]:
            disjoint = e0 <= s1 + 1000 or e1 <= s0 + 1000
            holds = (s0 <= s1 + 1000 and e1 <= e0 + 1000) or \
                    (s1 <= s0 + 1000 and e0 <= e1 + 1000)
            if not (disjoint or holds):
                return False
    return True


def test_one_span_feeds_both_sinks(tmp_path):
    import jax

    from paddle_tpu.obs.trace import _NULL, annotation, current_span

    t = Tracer()
    # disabled and outside a session: nothing is recorded anywhere
    with t.span("pt.test.off", track="engine", n=1):
        assert current_span("pt.") == "pt.test.off"
    assert current_span() is None
    assert t.recorded == 0
    assert annotation("pt.test.off") is _NULL
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("pt.test.outer", track="engine", live=3, step=7):
            with t.span("pt.test.inner", track="engine"):
                pass
            h = t.begin("pt.test.begun", track="engine", rows=5)
            assert current_span("pt.") == "pt.test.begun"
            t.end(h)
        t.enabled = True                   # the ring: only while enabled
        with t.span("pt.test.ring", track="engine", k=2):
            pass
        with annotation("pt.test.noring"):     # the profiler sink alone
            pass
    finally:
        jax.profiler.stop_trace()
    assert [(s["name"], s["track"], s.get("attrs")) for s in t.snapshot()] \
        == [("pt.test.ring", "engine", {"k": 2})]
    by_name, lines = _profiler_events(tmp_path)
    assert set(by_name) == {"pt.test.outer", "pt.test.inner",
                            "pt.test.begun", "pt.test.ring",
                            "pt.test.noring"}       # not pt.test.off
    assert by_name["pt.test.outer"] == [{"live": 3, "step": 7}]
    assert by_name["pt.test.begun"] == [{"rows": 5}]
    assert len(lines) == 1 and _nested_ok(lines[0])


def test_span_sink_is_fed_from_the_spans_own_clock():
    """global_stat / BarrierTimer sites go through span(): the sink gets
    the very duration the ring records, enabled or not."""
    from paddle_tpu.parallel.barrier_stat import BarrierTimer

    t = Tracer()
    got = []
    with t.span("pt.test.sink", sink=got.append):
        pass
    assert len(got) == 1 and got[0] >= 0.0 and t.recorded == 0
    t.enabled = True
    bt = BarrierTimer(tracer=t)
    with bt.time_dispatch():
        pass
    with bt.time_dispatch(windowed=False):      # a compiling dispatch
        pass
    with bt.time_sync():
        pass
    with bt.time_h2d():
        pass
    with bt.time_scan():
        pass
    snap = t.snapshot()
    assert [(s["name"], s["track"]) for s in snap] == [
        ("pt.train.dispatch", "trainer"), ("pt.train.dispatch", "trainer"),
        ("pt.train.drain", "trainer"), ("pt.feeder.stage", "trainer:h2d"),
        ("pt.train.scan", "trainer")]
    assert list(bt.dispatch_s) == [snap[0]["dur"]]
    assert list(bt.sync_s) == [snap[2]["dur"]]
    assert list(bt.h2d_s) == [snap[3]["dur"]]
    assert list(bt.scan_s) == [snap[4]["dur"]]
    assert set(bt.local_summary()) == {"dispatch", "sync", "h2d", "scan"}


def test_trace_module_imports_and_records_with_jax_blocked():
    """The client / router import path: obs.trace must work where JAX
    cannot be imported, ring-only."""
    import subprocess

    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from paddle_tpu.obs.trace import Tracer, current_span\n"
        "import paddle_tpu.obs.compile_watch as cw\n"
        "t = Tracer(); t.enabled = True\n"
        "with t.span('pt.x', k=1):\n"
        "    assert current_span('pt.') == 'pt.x'\n"
        "t.end(t.begin('pt.y'))\n"
        "assert [s['name'] for s in t.snapshot()] == ['pt.x', 'pt.y']\n"
        "assert cw.listen() is False\n"
        "assert 'jax.profiler' not in sys.modules\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_trace_module_never_imports_jax_itself():
    import subprocess

    code = ("import sys\n"
            "import paddle_tpu.obs.trace as tr\n"
            "with tr.get_tracer().span('pt.x'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'span() imported jax'\n"
            "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_eager_compile_is_counted_under_the_open_span():
    """PERF.md section 6 finding 1: the admission-time key split compiles
    for every new length, outside every wrapped site.  compile_watch now
    counts it under the innermost open `pt.` span of the thread."""
    import jax

    from paddle_tpu.obs.compile_watch import (UNATTRIBUTED,
                                              get_compile_watch)

    cw = get_compile_watch()
    seen = {"n": 0}

    def count(event, secs, **kw):
        seen["n"] += event.endswith("/backend_compile_duration")

    jax.monitoring.register_event_duration_secs_listener(count)
    key = jax.random.PRNGKey(0)
    wrapped = cw.wrap_jit("test.obs.site", jax.jit(lambda x: x * 3 + 1))
    before = cw.snapshot()
    n0 = seen["n"]
    t = Tracer()
    with t.span("pt.step.admit", track="engine"):
        jax.random.split(key, 1237)        # a length nothing else splits
        wrapped(np.ones(1237, np.float32))     # a wrapped site keeps its own
    jax.random.split(key, 1238)            # outside any span
    after = cw.snapshot()

    def grew(site, field):
        return after.get(site, {}).get(field, 0) - \
            before.get(site, {}).get(field, 0)

    assert grew("pt.step.admit", "unwrapped") >= 1
    assert grew("pt.step.admit", "compiles") == 0
    assert grew(UNATTRIBUTED, "unwrapped") >= 1
    assert grew("test.obs.site", "compiles") == 1
    assert grew("test.obs.site", "unwrapped") == 0
    # every backend compile of the process is accounted for
    total = sum(grew(s, "compiles") + grew(s, "unwrapped") for s in after)
    assert total == seen["n"] - n0
    # jit_compiles_total carries both kinds
    from paddle_tpu.obs.compile_watch import compile_collector
    rows = {(r[0], r[2]["site"]): r[3] for r in compile_collector(cw)()}
    assert rows[("jit_compiles_total", "pt.step.admit")] == \
        after["pt.step.admit"]["unwrapped"]


def test_site_compiles_counts_signatures_and_backend_executables():
    """What `correct` holds to 0 in a window (benchmark/lib/common.py sums
    a site's `compiles`): a new signature is exactly 1 even where XLA built
    nothing (a cached executable), a known signature is 0, and a call that
    does build counts its backend executables, known signature or not."""
    import jax

    from paddle_tpu.obs.compile_watch import CompileWatch

    cw = CompileWatch()
    site = "test.obs.cached"
    with cw.watch(site, ("sig", 1)):       # new signature, no backend event
        pass
    assert cw.snapshot()[site]["compiles"] == 1
    with cw.watch(site, ("sig", 1)):       # known, nothing built
        pass
    assert cw.snapshot()[site]["compiles"] == 1
    with cw.watch(site, ("sig", 1)):       # known, yet it builds one
        jax.random.split(jax.random.PRNGKey(1), 1301)
    snap = cw.snapshot()[site]
    assert snap["compiles"] >= 2 and snap["signatures"] == 1
    assert snap["unwrapped"] == 0


def test_trainer_loop_spans_per_batch_and_fused(tmp_path):
    """Every `pt.train.*` / `pt.feeder.*` name of docs/observability.md,
    in the ring, from both loops; the step's global_stat timer is fed by
    the span."""
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.parameter.argument import Argument
    from paddle_tpu.trainer.trainer import Trainer
    from paddle_tpu.utils import global_stat

    cfg_file = tmp_path / "cfg.py"
    cfg_file.write_text(
        "from paddle_tpu.dsl import *\n"
        "settings(batch_size=8, learning_rate=0.1)\n"
        "x = data_layer(name='x', size=4)\n"
        "out = fc_layer(input=x, size=2, act=SoftmaxActivation())\n"
        "classification_cost(input=out, label=data_layer(name='y', "
        "size=2))\n")
    tr = Trainer(parse_config(str(cfg_file), ""), seed=0)
    rng = np.random.default_rng(0)

    def batches(n):
        for _ in range(n):
            x = rng.normal(size=(8, 4)).astype(np.float32)
            yield {"x": Argument(value=x),
                   "y": Argument(ids=(x.sum(-1) > 0).astype(np.int32))}

    t = get_tracer()
    saved = (t.enabled, t._ring, t._n)
    t.clear()
    t.enabled = True
    try:
        n0 = global_stat.get("trainOneBatch").count
        tr.train_one_pass(batches=batches(3))
        names = [s["name"] for s in t.snapshot() if s["track"] == "trainer"]
        assert names.count("pt.train.step") == 3
        assert names.count("pt.train.next_batch") == 4   # the last finds END
        assert names.count("pt.train.stage") == 3
        assert names.count("pt.train.dispatch") == 3
        assert names.count("pt.train.drain") == 1
        assert names[-1] == "train_pass"
        assert global_stat.get("trainOneBatch").count == n0 + 3
        # stage and dispatch lie inside their step
        spans = [s for s in t.snapshot() if s["track"] == "trainer"]
        steps = [s for s in spans if s["name"] == "pt.train.step"]
        for child in (s for s in spans
                      if s["name"] in ("pt.train.stage",
                                       "pt.train.dispatch")):
            assert any(p["ts"] <= child["ts"] and child["ts"] + child["dur"]
                       <= p["ts"] + p["dur"] + 1e-6 for p in steps)
        t.clear()
        tr.train_one_pass(batches=batches(4), steps_per_dispatch=2)
        snap = t.snapshot()
        names = [s["name"] for s in snap]
        assert names.count("pt.train.scan") == 2
        assert names.count("pt.train.step") == 2
        assert names.count("pt.feeder.stage") == 2
        assert {s["track"] for s in snap
                if s["name"] == "pt.feeder.stage"} == {"trainer:h2d"}
        assert names.count("pt.train.drain") == 1
    finally:
        t.enabled, t._ring, t._n = saved
