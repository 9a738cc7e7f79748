"""The step clock (docs/observability.md "The step clock").

The pump thread's spans hand their seconds to always-on counters through
the span's own `sink`, a step in flight carries its launch time to its
land, and the process's counters can be read over a window.  None of it
needs the ring or a profiler session, and with both off none of it writes
a ring record or builds an annotation."""

import importlib
import os
import sys
import time

import numpy as np
import pytest

import jax

from paddle_tpu.config.parser import parse_config
from paddle_tpu.obs import Tracer
from paddle_tpu.obs import trace as trace_mod
from paddle_tpu.obs.metrics import (CATALOG, ProcessCounters, SpanSeconds,
                                    process_counters, split_labels)
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.serving.client import ServingClient
from paddle_tpu.serving.server import (STEP_CLOCK_COUNTERS, ServingServer,
                                       step_clock_stats)
from paddle_tpu.trainer.trainer import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SEC = 'serving_pump_seconds_total{span="%s"}'
N = 'serving_pump_spans_total{span="%s"}'
FLIGHT = 'serving_step_flight_seconds_total{kind="%s"}'
LANDED = 'serving_steps_landed_total{kind="%s"}'


@pytest.fixture(scope="module")
def tr():
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=61,dim=32,layers=2,heads=4,batch_size=4")
    return Trainer(cfg, seed=7)


def _engine(tr, depth=0, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_context", 64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_step_tokens", 12)
    eng = ServingEngine(tr.executor, tr.params, tracer=Tracer(), **kw)
    eng.lookahead = depth
    return eng


def _requests(n=6):
    rng = np.random.default_rng(0)
    return [Request(f"r{i}", rng.integers(2, 61, 3 + 4 * i).astype(np.int32),
                    max_new=4 + i, rng=jax.random.PRNGKey(40 + i))
            for i in range(n)]


def _grew(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


# -- the span's third taker ---------------------------------------------------

def test_a_span_with_a_sink_and_both_other_sinks_off_feeds_the_sink_alone(
        monkeypatch):
    """Ring off, no profiler session: the sink gets the seconds of the
    span's own clock pair; no ring record, no annotation object."""
    built = []

    class Annotation:
        def __init__(self, *a, **kw):
            built.append(a)

        @staticmethod
        def is_enabled():
            return False

    monkeypatch.setattr(trace_mod, "_annotation", Annotation)
    t = Tracer()
    got = []
    with t.span("pt.step.plan", track="engine", sink=got.append, rows=3):
        time.sleep(0.002)
    h = t.begin("pt.step.plan", track="engine", sink=got.append)
    t.end(h)
    assert len(got) == 2 and got[0] >= 0.002 and 0 <= got[1] < got[0]
    assert t.recorded == 0 and t.snapshot() == [] and built == []
    # and a span without one still reads no clock at all
    with t.span("pt.step.plan") as sp:
        pass
    assert sp.t0 == 0.0


def test_span_seconds_gathers_lock_free_and_flushes_once():
    pc = ProcessCounters()
    clock = SpanSeconds("serving_pump_seconds_total",
                        "serving_pump_spans_total", counters=pc)
    plan = clock.sink("pt.step.plan")
    assert clock.sink("pt.step.plan") is plan
    plan(0.25)
    plan(0.5)
    clock.sink("pt.kv.evict")(0.125)
    clock.add(LANDED % "decode", 1)
    assert pc.snapshot() == {}              # nothing before the flush
    clock.flush()
    assert pc.snapshot() == {
        SEC % "pt.step.plan": 0.75, N % "pt.step.plan": 2,
        SEC % "pt.kv.evict": 0.125, N % "pt.kv.evict": 1,
        LANDED % "decode": 1}
    clock.flush()                           # nothing gathered: no change
    plan(0.25)
    clock.flush()
    assert pc.snapshot()[SEC % "pt.step.plan"] == 1.0
    assert split_labels(SEC % "pt.step.plan") == (
        "serving_pump_seconds_total", {"span": "pt.step.plan"})
    assert split_labels("serving_loop_sends_total") == (
        "serving_loop_sends_total", None)


# -- window deltas --------------------------------------------------------------

def _ticking_counters():
    """`a` grows 1 a second and `b` 10 a second, checkpointed every 0.5 s
    of a hand-made clock from t = 100 to t = 110."""
    pc = ProcessCounters()
    for i in range(21):
        pc.checkpoint(now=100.0 + 0.5 * i)
        pc.add_many({"a": 0.5, "b": 5})
    return pc


def test_between_reads_a_window_from_the_checkpoints_inside_it():
    pc = _ticking_counters()
    growth, seconds = pc.between(102.2, 106.9)
    # nearest inside: 102.5 and 106.5
    assert seconds == pytest.approx(4.0)
    assert growth == {"a": pytest.approx(4.0), "b": pytest.approx(40)}
    growth, seconds = pc.between(100.0, 110.0)
    assert seconds == pytest.approx(10.0) and growth["b"] == 100


def test_between_leaves_out_an_excluded_stretch():
    pc = _ticking_counters()
    growth, seconds = pc.between(101.0, 109.0, exclude=[(103.2, 105.8)])
    # [101, 103.2] reads 101.0-103.0, [105.8, 109] reads 106.0-109.0
    assert seconds == pytest.approx(2.0 + 3.0)
    assert growth == {"a": pytest.approx(5.0), "b": pytest.approx(50)}
    # an exclusion outside the window, or empty, changes nothing
    assert pc.between(101.0, 109.0, exclude=[(90.0, 95.0), (104, 104)]) == \
        pc.between(101.0, 109.0)
    # a counter born inside the window counts from 0
    pc.add("late", 3)
    pc.checkpoint(now=110.5)
    assert pc.between(108.0, 110.5)[0]["late"] == 3


@pytest.mark.parametrize("t0,t1,exclude", [
    (90.0, 105.0, ()),            # checkpointing had not begun
    (105.0, 115.0, ()),           # ... had stopped
    (103.1, 103.4, ()),           # no checkpoint inside at all
    (101.0, 109.0, [(101.2, 108.9)]),      # what is left holds none
], ids=["before", "after", "between-two", "excluded"])
def test_between_refuses_what_the_checkpoints_do_not_cover(t0, t1, exclude):
    with pytest.raises(LookupError, match="no checkpoints cover"):
        _ticking_counters().between(t0, t1, exclude=exclude)


def test_the_checkpoint_ring_is_bounded():
    pc = ProcessCounters()
    for i in range(ProcessCounters.CHECKPOINTS + 10):
        pc.checkpoint(now=float(i))
    assert len(pc._checkpoints) == ProcessCounters.CHECKPOINTS
    with pytest.raises(LookupError):
        pc.between(0.0, 100.0)          # the ring has wrapped past it


# -- a step in flight carries its clock ----------------------------------------

@pytest.mark.parametrize("depth", [0, 1])
def test_every_landed_step_has_one_flight(tr, depth):
    """Ring on: one `pt.step.flight` a landed step on the `flight` lane,
    from its launch span's start to its read-back's end; `step=` on the
    read-back and the emit are the flight's; consecutive flights overlap
    at `lookahead` 1 and do not at 0.  The counters say the same."""
    pc0 = process_counters().snapshot()
    eng = _engine(tr, depth)
    eng.tracer.enabled = True
    eng.run(_requests())
    spans = eng.tracer.snapshot()
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    flights = by["pt.step.flight"]
    assert all(s["track"] == "flight" for s in flights)
    assert [s["attrs"]["step"] for s in flights] == \
        list(range(1, eng.n_decode_steps + 1))
    kinds = [s["attrs"]["kind"] for s in flights]
    assert kinds.count("mixed") == eng.n_mixed_steps > 0
    assert kinds.count("decode") == eng.n_decode_steps - eng.n_mixed_steps
    launches = {s["attrs"]["step"]: s for s in
                by["pt.step.decode"] + by["pt.step.mixed"]}
    readbacks = {s["attrs"]["step"]: s for s in by["pt.step.readback"]}
    emits = {s["attrs"]["step"]: s for s in by["pt.step.emit"]}
    assert len(readbacks) == len(emits) == len(flights)
    for f in flights:
        a = f["attrs"]
        launch, rb, em = (d[a["step"]] for d in (launches, readbacks, emits))
        assert launch["name"] == "pt.step." + a["kind"]
        assert rb["attrs"]["kind"] == em["attrs"]["kind"] == a["kind"]
        assert f["ts"] == launch["ts"]
        end = f["ts"] + f["dur"]
        assert rb["ts"] + rb["dur"] <= end <= em["ts"]
    overlaps = sum(1 for a, b in zip(flights, flights[1:])
                   if b["ts"] < a["ts"] + a["dur"])
    if depth:
        assert overlaps >= len(flights) - 3 and \
            overlaps == eng.n_lookahead_steps
    else:
        assert overlaps == 0
    grew = _grew(pc0, process_counters().snapshot())
    landed = {k: grew.get(LANDED % k, 0) for k in ("decode", "mixed")}
    assert landed == {"decode": kinds.count("decode"),
                      "mixed": kinds.count("mixed")}
    for k in ("decode", "mixed"):
        assert grew[FLIGHT % k] == pytest.approx(
            sum(f["dur"] for f in flights if f["attrs"]["kind"] == k))
    assert grew[N % "pt.step.readback"] == grew[N % "pt.step.emit"] == \
        len(flights)
    assert "serving_lookahead_steps_total" not in grew      # no twin


def test_spec_steps_record_flights_where_they_launched(tr):
    kind = "spec"
    pc0 = process_counters().snapshot()
    eng = _engine(tr, 0, max_step_tokens=None, spec_k=2)
    eng.tracer.enabled = True
    prompts = [np.tile(np.random.default_rng(i).integers(2, 61, 4), 4)
               for i in range(3)]
    eng.run([Request(f"p{i}", p, max_new=9) for i, p in enumerate(prompts)])
    spans = eng.tracer.snapshot()
    flights = [s for s in spans if s["name"] == "pt.step.flight"]
    assert len(flights) == eng.n_decode_steps
    mine = [f for f in flights if f["attrs"]["kind"] == kind]
    assert len(mine) == eng.n_spec_steps > 0
    outer = {s["attrs"]["step"]: s for s in spans
             if s["name"] == "pt.step." + kind}
    for f in mine:                  # the flight lies inside its own step
        o = outer[f["attrs"]["step"]]
        assert f["ts"] == o["ts"] and f["dur"] <= o["dur"]
    grew = _grew(pc0, process_counters().snapshot())
    assert grew[LANDED % kind] == len(mine)
    # the draft's other taker still gets it
    assert grew[N % "pt.step.draft"] >= eng.n_draft_steps > 0
    assert eng.draft_ms_hist.samples()


def test_landed_step_kinds_are_decode_mixed_and_spec():
    """A step lands as one of three kinds, each with its flight seconds and
    its landed count in the step clock's counters, and no fourth."""
    from paddle_tpu.serving import engine
    kinds = ("decode", "mixed", "spec")
    assert tuple(engine._FLIGHT_COUNTERS) == kinds
    assert {k for pair in engine._FLIGHT_COUNTERS.values() for k in pair} \
        == {c % k for c in (FLIGHT, LANDED) for k in kinds}


def test_off_means_off_with_the_clock_running(tr, monkeypatch):
    """Ring and profiler off: the counters grow, the ring stays empty and
    no annotation is built."""
    built = []

    class Annotation:
        def __init__(self, *a, **kw):
            built.append(a)

        @staticmethod
        def is_enabled():
            return False

    monkeypatch.setattr(trace_mod, "_annotation", Annotation)
    pc0 = process_counters().snapshot()
    eng = _engine(tr, 1)
    eng.run(_requests(3))
    grew = _grew(pc0, process_counters().snapshot())
    assert grew[LANDED % "decode"] + grew[LANDED % "mixed"] == \
        eng.n_decode_steps
    assert grew[SEC % "pt.step.plan"] > 0
    assert eng.tracer.recorded == 0 and built == []


# -- the pump's books ------------------------------------------------------------

def test_the_pumps_counters_sum_to_its_wall_time(tr):
    """A depth-1 server run: the seconds of the pump's three top-level
    spans (commands, engine.step, wait) and what is in none of them —
    `outside` — are the pump thread's wall time, which the checkpoints at
    its start and its stop bracket; outside is under 2% of it.  Inside
    `pt.engine.step` the phases and the step's own time add up again."""
    eng = _engine(tr, 0)
    srv = ServingServer(eng, max_queue=32)
    pc = process_counters()
    t0 = time.perf_counter()
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            ids = [c.submit(r.prompt_ids.tolist(), max_new=r.max_new + 20,
                            stream=True) for r in _requests()]
            c.collect(ids)
            time.sleep(0.6)         # an idle stretch: pt.pump.wait
            stats = c.stats()
            text = c.metrics()
    finally:
        srv.stop_background(drain=True)
    t1 = time.perf_counter()
    growth, wall = pc.between(t0, t1, max_edge=30.0)
    top = sum(growth[SEC % n] for n in
              ("pt.pump.commands", "pt.engine.step", "pt.pump.wait"))
    outside = wall - top
    assert wall > 0.6 and growth[SEC % "pt.pump.wait"] > 0.5
    assert 0 <= outside <= 0.02 * wall, (outside, wall)
    inner = sum(growth.get(SEC % ("pt.step." + n), 0) for n in
                ("admit", "plan", "dispatch", "readback", "emit"))
    own = growth[SEC % "pt.engine.step"] - inner
    launches = growth[SEC % "pt.step.decode"] + growth[SEC % "pt.step.mixed"]
    assert 0 < launches - growth[SEC % "pt.step.dispatch"] <= own
    landed = growth[LANDED % "decode"] + growth[LANDED % "mixed"]
    assert landed == eng.n_decode_steps
    assert growth[N % "pt.engine.step"] >= landed
    assert growth["serving_loop_sends_total"] >= srv.n_frame_writes > 0
    assert 0 < growth["serving_loop_send_seconds_total"] < wall
    # the stats frame's `steps` block and the metrics frame read the same
    # counters (no twin on the engine or the server)
    steps = stats["steps"]
    assert set(steps) == {"pump_seconds", "pump_spans", "step_flight_seconds",
                          "steps_landed", "loop_send_seconds", "loop_sends"}
    assert steps["steps_landed"].keys() <= {"decode", "mixed", "spec"}
    assert steps["pump_spans"]["pt.step.readback"] >= landed
    assert step_clock_stats()["pump_spans"]["pt.pump.wait"] >= \
        steps["pump_spans"]["pt.pump.wait"]
    assert 'serving_pump_seconds_total{span="pt.pump.wait"}' in text
    assert 'serving_steps_landed_total{kind="decode"}' in text
    assert "# TYPE serving_step_flight_seconds_total counter" in text
    assert not hasattr(eng, "n_steps_landed")


def test_the_pump_checkpoints_every_tenth_of_a_second(tr):
    eng = _engine(tr, 0)
    srv = ServingServer(eng, max_queue=8)
    pc = process_counters()
    n0 = len(pc._checkpoints)
    host, port = srv.start_background()
    try:
        with ServingClient(host, port) as c:
            c.collect([c.submit([3, 4, 5], max_new=40)])
            time.sleep(1.2)
    finally:
        srv.stop_background(drain=True)
    got = len(pc._checkpoints) - n0
    # start + stop + one a 0.1 s while stepping, one a 0.5 s wait when idle
    assert 4 <= got <= 40, got


def test_trace_dump_draws_the_flight_lane(tr, tmp_path, capsys):
    """A --trace-out file of a depth-1 run: the summary's flight-lane part
    pairs every flight with its read-back by `step=`, and the Chrome trace
    has a `flight` thread."""
    sys.path.insert(0, ROOT)
    from tools.trace_dump import flight_breakdown, load_spans, main

    eng = _engine(tr, 1)
    eng.tracer.enabled = True
    eng.run(_requests())
    src = tmp_path / "spans.jsonl"
    eng.tracer.export_jsonl(str(src))
    assert main([str(src), "--summary"]) == 0
    out = capsys.readouterr().out
    n = eng.n_decode_steps
    assert f"flight lane ({n} steps, {n} paired with their read-back" in out
    rows = {ln.split()[0]: ln.split() for ln in
            out.split("flight lane", 1)[1].splitlines()[2:4]}
    assert int(rows["decode"][1]) + int(rows["mixed"][1]) == n
    assert int(rows["decode"][4]) + int(rows["mixed"][4]) == \
        eng.n_lookahead_steps
    assert flight_breakdown([s for s in load_spans(str(src))
                             if s["track"] != "flight"]) == ""
    chrome = eng.tracer.chrome_trace()["traceEvents"]
    tid = next(e["tid"] for e in chrome if e["ph"] == "M"
               and e["args"]["name"] == "flight")
    assert sum(1 for e in chrome if e["ph"] == "X" and e["tid"] == tid) == n


# -- names -----------------------------------------------------------------------

def test_the_new_names_are_in_the_catalog_and_the_docs():
    for name in STEP_CLOCK_COUNTERS:
        assert name in CATALOG
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        lint = importlib.import_module("check_metrics_names")
    finally:
        sys.path.pop(0)
    assert lint.main([]) == 0


def test_the_metrics_frame_renders_the_process_only_families(tr):
    """The weight-cast counters live in the process's counters alone, as
    the step clock's do: `metrics` renders both from there."""
    eng = _engine(tr, 0)
    process_counters().add("serving_step_weight_casts_total", 0)
    text = ServingServer(eng, max_queue=4).metrics.render()
    assert "# TYPE serving_step_weight_casts_total counter" in text
    assert "serving_pump_seconds_total" in text
