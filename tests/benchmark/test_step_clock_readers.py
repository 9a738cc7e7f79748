"""The seven readers of the serving step clock (benchmark/lib/step_clock.py)
and the entries PERF.md section 7 row 20 holds for them until a `benchmark`
PR can append to `per_layer` (tests/benchmark/test_dense_decode_roofline.py
pins its last entry)."""

import json
import os
import re
import shutil
import subprocess
import sys
import time
import types

import pytest

from benchmark.lib import step_clock
from benchmark.lib.spec import Benchmark, load_module

from test_only_grew import _only_grew, _pr26_in_place

NAMES = ["host_ms_per_step.serve", "readback_wait_share.serve",
         "evict_ms_per_step.serve", "decode_flight_ms.serve",
         "mixed_flight_ms.serve", "no_work_share.serve",
         "loop_send_ms_per_step.serve"]
CELLS = ["sc2-3b-serve.decode-saturated", "sc2-3b-serve.chat",
         "gigachat3.1-702b-serve.long-output",
         "kimi-linear-48b-serve.long-output-128",
         "lfm2-24b-serve.long-output-256"]
SEC, N = step_clock.SECONDS, step_clock.SPANS


def _reader(root, name):
    return load_module(os.path.join(root, "benchmark", "layer_metrics",
                                    name + ".py"), "metric_" + name)


def withheld_entries(root) -> list:
    """The seven `per_layer` entries, verbatim from PERF.md."""
    with open(os.path.join(root, "PERF.md")) as f:
        text = f.read()
    found = {}
    for blob in re.findall(r"`(\{\"name\": \"[^`]*\})`", text):
        entry = json.loads(blob)
        if entry["name"] in NAMES:
            found[entry["name"]] = entry
    assert sorted(found) == sorted(NAMES), sorted(found)
    return [found[n] for n in NAMES]


@pytest.fixture(scope="module")
def accepted(root):
    with open(os.path.join(root, "tests", "benchmark", "data",
                           "accepted_pr25.json")) as f:
        return json.load(f)


# -- made-up counters ----------------------------------------------------------

def _made_up(monkeypatch, per_second: dict, slow: float = 1.0,
             slice_at=(24.0, 30.0)):
    """A program whose counters grow `per_second` from t = 0 to 60 on
    perf_counter, checkpointed every 0.1 s — and `slow` times as fast in
    `slice_at` for the seconds counters (the profiler's tracer).  Returns
    the ctx of a run whose window is [10, 50]."""
    from paddle_tpu.obs import metrics

    pc = metrics.ProcessCounters()
    base = time.perf_counter() - 100.0
    for i in range(601):
        t = 0.1 * i
        pc.checkpoint(now=base + t)
        k = slow if slice_at[0] <= t < slice_at[1] else 1.0
        pc.add_many({name: v * 0.1 * (k if "seconds" in name else 1.0)
                     for name, v in per_second.items()})
    monkeypatch.setattr(metrics, "process_counters", lambda: pc)
    offset = time.time() - time.perf_counter()
    return types.SimpleNamespace(
        t_process=base, e2e={"setup_s": 10.0}, seconds=40.0, spans={},
        traffic={"trace_s": 4.0}, trace_window_s=4.0,
        counters={"decode_steps": 4000,
                  "trace_span": {"t0": base + slice_at[0] + offset,
                                 "t1": base + slice_at[1] + offset}})


#: a pump that lands 100 steps a second: 80 decode, 20 mixed
RATES = {
    SEC % "pt.pump.commands": 0.02, N % "pt.pump.commands": 100,
    SEC % "pt.engine.step": 0.90, N % "pt.engine.step": 100,
    SEC % "pt.pump.wait": 0.05, N % "pt.pump.wait": 1,
    SEC % "pt.step.admit": 0.05, N % "pt.step.admit": 100,
    SEC % "pt.step.plan": 0.20, N % "pt.step.plan": 100,
    SEC % "pt.step.dispatch": 0.08, N % "pt.step.dispatch": 100,
    SEC % "pt.step.readback": 0.40, N % "pt.step.readback": 100,
    SEC % "pt.step.emit": 0.10, N % "pt.step.emit": 100,
    SEC % "pt.kv.evict": 0.03, N % "pt.kv.evict": 10,
    step_clock.FLIGHT % "decode": 0.80, step_clock.LANDED % "decode": 80,
    step_clock.FLIGHT % "mixed": 0.36, step_clock.LANDED % "mixed": 20,
    "serving_loop_send_seconds_total": 0.07, "serving_loop_sends_total": 100,
}
WANT = {"host_ms_per_step.serve": 5.2,          # (0.02 + 0.90 - 0.40) / 100
        "readback_wait_share.serve": 40.0,
        "evict_ms_per_step.serve": 0.3,
        "decode_flight_ms.serve": 10.0,
        "mixed_flight_ms.serve": 18.0,
        "no_work_share.serve": 5.0,
        "loop_send_ms_per_step.serve": 0.7}


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_made_up_counters(root, monkeypatch, name):
    """Whatever the tracer does to the seconds inside the slice (here:
    three times as many), the reading is the window's outside it."""
    ctx = _made_up(monkeypatch, RATES, slow=3.0)
    assert _reader(root, name).read(ctx) == pytest.approx(WANT[name],
                                                          rel=1e-6)


def test_window_arithmetic_and_the_slices_exclusion(monkeypatch, capsys):
    ctx = _made_up(monkeypatch, RATES, slow=3.0)
    t0, t1, exclude = step_clock.stretch(ctx)
    assert t1 - t0 == 40.0
    (a, b), = exclude
    assert a - t0 == pytest.approx(14.0, abs=1e-3)      # 24 s into the run
    assert b - a == pytest.approx(6.0, abs=1e-3)
    w = step_clock.window(ctx)
    assert step_clock.window(ctx) is w                  # read once
    # [10, 24] and [30, 50] less at most a checkpoint an edge
    assert 33.6 <= w.seconds <= 34.0 + 1e-6
    assert w.landed() == pytest.approx(100 * w.seconds)
    assert w.landed("mixed") == pytest.approx(20 * w.seconds)
    assert w.outside_s() == pytest.approx(0.03 * w.seconds)
    assert w.host_s() == pytest.approx(0.52 * w.seconds)
    out = capsys.readouterr().out
    books = json.loads(out.split("STEP_CLOCK ", 1)[1].splitlines()[0])
    assert books["asked_s"] == pytest.approx(34.0, abs=1e-3)
    assert books["period_ms"] == pytest.approx(10.0)
    assert books["decode_steps_in_window"] == 4000
    mult = json.loads(out.split("STEP_CLOCK_TRACER ", 1)[1].splitlines()[0])
    assert mult["pt.step.plan"]["times"] == pytest.approx(3.0)
    assert mult["pt.kv.evict"]["outside_ms"] == pytest.approx(3.0)
    assert 2.7 <= mult["inside_s"] <= 3.0 + 1e-6
    # without a slice the whole window counts
    ctx = _made_up(monkeypatch, RATES)
    ctx.counters["trace_span"] = {}
    assert 39.8 <= step_clock.window(ctx).seconds <= 40.0 + 1e-6


def test_a_window_without_a_kind_of_step_reads_nothing_for_it(root,
                                                              monkeypatch):
    rates = {k: v for k, v in RATES.items() if "mixed" not in k}
    ctx = _made_up(monkeypatch, rates)
    assert _reader(root, "mixed_flight_ms.serve").read(ctx) is None
    assert _reader(root, "decode_flight_ms.serve").read(ctx) == \
        pytest.approx(10.0)
    # the pool never filled: the walk's cost is 0, not nothing
    rates = {k: v for k, v in RATES.items() if "evict" not in k}
    ctx = _made_up(monkeypatch, rates)
    assert _reader(root, "evict_ms_per_step.serve").read(ctx) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_reads_nothing_from_a_program_without_the_clock(root, monkeypatch,
                                                        name):
    """A parent commit: counters without windows, other counters only, or
    no process counters at all."""
    from paddle_tpu.obs import metrics

    ctx = _made_up(monkeypatch, {"serving_token_frames_total": 64.0})
    assert _reader(root, name).read(ctx) is None
    ctx.spans.clear()
    monkeypatch.setattr(metrics, "process_counters",
                        lambda: types.SimpleNamespace(snapshot=dict))
    assert _reader(root, name).read(ctx) is None
    ctx.spans.clear()
    monkeypatch.delattr(metrics, "process_counters")
    assert _reader(root, name).read(ctx) is None


def test_a_window_the_checkpoints_do_not_cover_raises(monkeypatch):
    ctx = _made_up(monkeypatch, RATES)
    ctx.e2e["setup_s"] = 45.0            # the window would end at t = 85
    with pytest.raises(LookupError):
        step_clock.window(ctx)


# -- the entries, withheld -------------------------------------------------------

def test_readers_agree_with_the_entries_perf_md_holds(root, bench):
    entries = withheld_entries(root)
    assert [e["name"] for e in entries] == NAMES
    better = {"readback_wait_share.serve": "higher"}
    for e in entries:
        mod = _reader(root, e["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == \
            (e["layer"], e["unit"], e["moves"])
        assert e == {"name": e["name"], "unit": e["unit"],
                     "better": better.get(e["name"], "lower"),
                     "source": "program_counter", "layer": "serving engine",
                     "moves": "itl_p95_ms", "workloads": CELLS}
        assert e["name"] not in bench.per_layer      # the pin stands
    for c in CELLS:
        assert "itl_p95_ms" in {m["name"] for m in bench.end_to_end_for(c)}


def _copy_with_entries(root, dest) -> str:
    """BENCHMARK.json with the seven appended, beside the benchmark's files
    (and, for a run, the program they drive)."""
    shutil.copytree(os.path.join(root, "benchmark"), dest / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["per_layer"].extend(withheld_entries(root))
    with open(dest / "BENCHMARK.json", "w") as f:
        json.dump(doc, f)
    return str(dest)


def test_the_seven_appended_pass_the_only_grew_checks(root, tmp_path,
                                                      accepted):
    copy = _copy_with_entries(root, tmp_path / "repo")
    _only_grew(copy, accepted)
    b = Benchmark(copy)
    _pr26_in_place(b, accepted)
    for name in NAMES:
        b.reader(name)                 # LAYER, UNIT, MOVES agree, or raises
    for c in b.cells:
        got = {m["name"] for m in b.per_layer_for(c)}
        assert (set(NAMES) <= got) == (c in CELLS)
        assert (set(NAMES) & got == set()) == (c not in CELLS)
    assert len(json.dumps(b.doc)) < 64 * 1024


def test_traced_rehearsal_reads_all_seven(root, tmp_path):
    """One serve cell through `run.py --rehearse --trace 1` on a copy that
    has the entries: every reader reads a number from the run's own
    counters, outside its profiler's slice."""
    copy = _copy_with_entries(root, tmp_path / "repo")
    for d in ("paddle_tpu", "tools", "demo"):
        os.symlink(os.path.join(root, d), os.path.join(copy, d))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(copy, "benchmark", "run.py"),
         "--workload", "sc2-3b-serve.decode-saturated", "--seed",
         str(2 ** 31 + 37), "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] is True and out["correct"] is True, out["checks"]
    got = {n: out["metrics"][n] for n in NAMES}
    units = {e["name"]: e["unit"] for e in withheld_entries(root)}
    for n, m in got.items():
        assert m["unit"] == units[n] and m["value"] >= 0.0, (n, m)
    assert got["host_ms_per_step.serve"]["value"] > 0.0
    assert got["decode_flight_ms.serve"]["value"] > 0.0
    assert 0.0 < got["readback_wait_share.serve"]["value"] < 100.0
    assert got["no_work_share.serve"]["value"] < 100.0
    books = json.loads(
        p.stdout.split("STEP_CLOCK ", 1)[1].splitlines()[0])
    # the books balance: in no span under 2% of the pump's wall time
    assert 0.0 <= books["outside_s"] <= 0.02 * books["covered_s"], books
