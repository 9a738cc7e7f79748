"""benchmark/lib/phases.py: the program's `pt.` spans in a profiler trace and
the device's idle time split by them — on a hand-made trace (known gaps
under known spans), and on recorded cuts of a v5e serve trace and a v5e train
trace that carry the spans (data/*_pt_spans.json, cut from `--trace 1` runs
of this PR's program by benchmark/phase_probe.py --dump)."""

import json
import os
import types

import pytest

from benchmark.lib.phases import (GROUPS, NO_SPAN, PhaseError, Phases,
                                  innermost, kernel_ms_per_step, median_ms,
                                  span_events, split)
from benchmark.lib.trace import WINDOW_EVENT, Trace

MS = 1_000_000      # ns
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def step(t0, kind="decode"):
    """The spans of one engine step of 20 ms starting at t0 (ms): commands
    1, [engine.step: admit 1, plan 2, kind 12 {dispatch 2, readback 8, 1 of
    its own at the end}, emit 3, 1 of its own]."""
    e = [["pt.pump.commands", t0, 1],
         ["pt.engine.step", t0 + 1, 19],
         ["pt.step.admit", t0 + 1, 1],
         ["pt.step.plan", t0 + 2, 2],
         ["pt.step." + kind, t0 + 4, 12],
         ["pt.step.dispatch", t0 + 4, 2],
         ["pt.step.readback", t0 + 7, 8],
         ["pt.step.emit", t0 + 16, 3]]
    return [[n, int(s * MS), int(d * MS)] for n, s, d in e]


# device busy [5,15) and [26,36) and [46,50): idle of a 50 ms window is
# [0,5) + [15,26) + [36,46) = 26 ms
SERVE = {
    "/device:TPU:0": {"XLA Ops": [
        ["fusion.1", 5 * MS, 4 * MS],
        ["paged_attn.2 [tpu_custom_call]", 9 * MS, 6 * MS],
        ["fusion.1", 26 * MS, 10 * MS],
        ["fusion.1", 46 * MS, 4 * MS]]},
    "/host:CPU": {
        # both threads' lines are called python3 and get merged
        "python3": step(0) + step(20, "mixed") + step(40) + [
            ["pt.loop.send", 17 * MS, 1 * MS],
            ["pt.loop.send", 37 * MS, 2 * MS],
            ["$selector_events.py:1 send", 17 * MS, 1 * MS]],
    },
}


class Ctx(types.SimpleNamespace):
    pass


def ctx_of(planes, window=None):
    """A run's context as ProfilerWindow.reduce() leaves it: the trace, and
    the length of ITS window."""
    tr = planes if isinstance(planes, Trace) else Trace(planes, window)
    return Ctx(trace_data=tr, trace_window_s=tr.window_s)


def serve_ctx(window=(0, 50 * MS)):
    return ctx_of(SERVE, window)


def test_innermost_pieces_of_nested_spans():
    spans = [(0, 100, "a"), (10, 40, "b"), (20, 30, "c"), (50, 60, "b"),
             (120, 130, "a")]
    assert innermost(spans) == [
        (0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 40, "b"),
        (40, 50, "a"), (50, 60, "b"), (60, 100, "a"), (120, 130, "a")]
    # a child that outlives its parent by jitter is cut at the parent's end
    assert innermost([(0, 10, "a"), (5, 12, "b")]) == \
        [(0, 5, "a"), (5, 10, "b")]


def test_idle_gaps_are_the_gaps_and_the_windows_two_edges():
    tr = Trace(SERVE, window=(0, 50 * MS))
    assert tr.idle_intervals("/device:TPU:0") == [
        (0, 5 * MS), (15 * MS, 26 * MS), (36 * MS, 46 * MS)]
    # a window shorter than the busy span stays where it was stamped and
    # cuts the ops that cross it: nothing is moved to hold the span's end
    tr = Trace(SERVE, window=(10 * MS, 50 * MS))
    assert tr.idle_intervals("/device:TPU:0") == [
        (15 * MS, 26 * MS), (36 * MS, 46 * MS)]
    assert tr.busy_s() == pytest.approx(0.019)
    # ... and the window is the host's own event, where the trace has one
    stamped = {"/device:TPU:0": SERVE["/device:TPU:0"],
               "/host:CPU": {"python3": SERVE["/host:CPU"]["python3"],
                             "tracer": [[WINDOW_EVENT, 30 * MS, 25 * MS]]}}
    tr = Trace(stamped)
    assert tr.window == (30 * MS, 55 * MS)
    assert tr.idle_intervals("/device:TPU:0") == [
        (36 * MS, 46 * MS), (50 * MS, 55 * MS)]
    assert Phases(tr, "serve").idle_pct() == pytest.approx(60.0)
    assert split([(0, 10)], [(2, 4, "x"), (6, 20, "y")]) == \
        {"x": 2, "y": 4, NO_SPAN: 4}


def test_known_gaps_fall_under_known_spans():
    ph = Phases.of(serve_ctx(), "serve")
    by = {k: v / MS for k, v in ph.idle_by_span.items() if v}
    # [0,5): commands 1, admit 1, plan 2, dispatch 1
    # [15,26): decode's own 1 (15-16), emit 3, engine.step's own 1 (19-20),
    #          commands 1, admit 1, plan 2, dispatch 2
    # [36,46): emit 3, engine.step's own 1, commands 1, admit 1, plan 2,
    #          dispatch 2 (the mixed step's own last ms is busy)
    assert by == {
        "pt.pump.commands": 3, "pt.step.admit": 3, "pt.step.plan": 6,
        "pt.step.dispatch": 5, "pt.step.decode": 1, "pt.step.emit": 6,
        "pt.engine.step": 2}
    assert sum(by.values()) == 26
    assert ph.idle_pct() == pytest.approx(52.0)
    assert ph.idle_share("emit") == pytest.approx(12.0)
    assert ph.idle_share("schedule") == pytest.approx(18.0)
    assert ph.idle_share("pump") == pytest.approx(6.0)
    assert ph.idle_share("launch") == pytest.approx(12.0)     # 5 + 1
    # the rest: pt.engine.step's own time
    assert ph.idle_unattributed_share() == pytest.approx(4.0)


def test_the_shares_sum_to_the_idle_share():
    ctx = serve_ctx()
    ph = Phases.of(ctx, "serve")
    total = sum(ph.idle_share(g) for g in GROUPS["serve"]) + \
        ph.idle_unattributed_share()
    assert total == pytest.approx(ph.idle_pct())
    # ... which is what device_idle_share.serve reads from the same trace
    assert ph.idle_pct() == pytest.approx(
        100.0 * (1.0 - ctx.trace_data.busy_s() / ctx.trace_window_s))
    assert Phases.of(ctx, "serve") is ph          # made once a run


def test_steps_are_counted_by_the_spans_that_start_in_the_window():
    # [18,42): the first step's emit reaches in, the second step starts in
    # it, the third's commands and engine.step do and its decode span (44)
    # does not
    ph = Phases.of(serve_ctx((18 * MS, 42 * MS)), "serve")
    assert len(ph.durations("pt.step.mixed")) == 1
    assert "pt.step.decode" not in ph.names     # 4-16 and 44-56
    assert len(ph.durations("pt.step.emit")) == 1          # 36, not 16
    assert len(ph.durations("pt.engine.step")) == 2        # 21 and 41
    # idle [18,26) + [36,42) of 24 ms; the first step's emit holds 18-19
    assert ph.idle_pct() == pytest.approx(100 * 14 / 24)
    assert ph.idle_by_span["pt.step.emit"] == 4 * MS
    # a window that no span of the family reaches has nothing to read
    assert Phases.of(serve_ctx((70 * MS, 80 * MS)), "serve") is None


def test_spans_of_other_threads_are_read_beside_the_split():
    ctx = serve_ctx()
    ph = Phases.of(ctx, "serve")
    assert "pt.loop.send" not in ph.names           # not in the family
    sends = [(s, e) for s, e, n in span_events(ctx.trace_data,
                                               ("pt.loop.",))]
    assert sends == [(17 * MS, 18 * MS), (37 * MS, 39 * MS)]
    # the python tracer's own frames are no `pt.` span
    assert all(n.startswith("pt.") for _, _, n in span_events(ctx.trace_data))


def test_step_medians_by_the_kind_in_the_name():
    ctx = serve_ctx()
    assert median_ms(ctx, "serve", "pt.step.decode") == pytest.approx(12.0)
    assert median_ms(ctx, "serve", "pt.step.mixed") == pytest.approx(12.0)
    assert median_ms(ctx, "serve", "pt.step.emit") == pytest.approx(3.0)


def test_an_unknown_name_raises_never_a_zero():
    ctx = serve_ctx()
    with pytest.raises(PhaseError, match="no span 'pt.step.scan'"):
        median_ms(ctx, "serve", "pt.step.scan")
    no_emit = {"/device:TPU:0": SERVE["/device:TPU:0"],
               "/host:CPU": {"python3": [
                   e for e in SERVE["/host:CPU"]["python3"]
                   if e[0] != "pt.step.emit"]}}
    ph = Phases.of(ctx_of(no_emit, (0, 50 * MS)), "serve")
    with pytest.raises(PhaseError, match="none of the spans"):
        ph.idle_share("emit")
    with pytest.raises(PhaseError):
        ph.idle_unattributed_share()
    with pytest.raises(KeyError):
        ph.idle_share("no-such-group")
    # an optional span (pt.kv.evict, pt.pump.wait) may be absent
    assert ph.idle_share("schedule") == pytest.approx(18.0)


def test_a_share_above_105_percent_raises():
    ph = Phases.of(serve_ctx((0, 10 * MS)), "serve")
    assert ph.idle_pct() == pytest.approx(50.0)
    # idle time is the window less the busy time in it, so it cannot pass
    # the window; a split that did would be an error, not 100%
    ph.idle_ns = 26 * MS
    with pytest.raises(PhaseError, match="of the traced window"):
        ph.idle_pct()


def test_a_program_without_spans_has_nothing_to_read():
    """The parent commit: the readers return None and the line leaves the
    metric out; nothing raises."""
    bare = {"/device:TPU:0": SERVE["/device:TPU:0"],
            "/host:CPU": {"python3": [["$engine.py:1056 step", 0, 5 * MS],
                                      ["bench.engine_step", 0, 5 * MS]]}}
    ctx = ctx_of(bare, (0, 50 * MS))
    assert Phases.of(ctx, "serve") is None
    assert Phases.of(ctx, "train") is None
    assert median_ms(ctx, "serve", "pt.step.decode") is None
    assert kernel_ms_per_step(ctx, r"flash_fwd") is None
    assert Phases.of(Ctx(trace_data=None, trace_window_s=None),
                     "serve") is None
    # a serve trace has no trainer spans, and the other way round
    assert Phases.of(serve_ctx(), "train") is None


TRAIN = {
    "/device:TPU:0": {"XLA Ops": [
        ["jvp_flash_fwd_.4 [tpu_custom_call]", 10 * MS, 10 * MS],
        ["transpose_jvp_flash_bwd_dq__.5 [tpu_custom_call]", 20 * MS, 6 * MS],
        ["transpose_jvp_flash_bwd_dkv__.6 [tpu_custom_call]", 26 * MS,
         8 * MS],
        ["fusion.3", 34 * MS, 6 * MS],
        ["jvp_flash_fwd_.4 [tpu_custom_call]", 42 * MS, 10 * MS],
        ["transpose_jvp_flash_bwd_dq__.5 [tpu_custom_call]", 52 * MS, 6 * MS],
        ["transpose_jvp_flash_bwd_dkv__.6 [tpu_custom_call]", 58 * MS,
         8 * MS],
        ["fusion.3", 66 * MS, 14 * MS]]},
    "/device:TPU:1": {"XLA Ops": [
        ["flash_fwd.1 [tpu_custom_call]", 10 * MS, 12 * MS],
        ["flash_bwd_dq.1 [tpu_custom_call]", 22 * MS, 6 * MS],
        ["flash_bwd_dkv.1 [tpu_custom_call]", 28 * MS, 8 * MS],
        ["flash_fwd.1 [tpu_custom_call]", 42 * MS, 12 * MS],
        ["flash_bwd_dq.1 [tpu_custom_call]", 54 * MS, 6 * MS],
        ["flash_bwd_dkv.1 [tpu_custom_call]", 60 * MS, 8 * MS]]},
    "/host:CPU": {"python3": [
        [n, int(s * MS), int(d * MS)] for n, s, d in [
            ["pt.train.next_batch", 2, 3],
            ["pt.feeder.make_batch", 2, 2.5],      # another thread's
            ["pt.train.step", 5, 6], ["pt.train.stage", 5, 2],
            ["pt.train.dispatch", 7, 4],
            ["pt.train.next_batch", 11, 1],
            ["pt.train.step", 12, 3], ["pt.train.stage", 12, 1],
            ["pt.train.dispatch", 13, 2],
            ["pt.train.next_batch", 15, 1],
            ["pt.train.drain", 40.5, 50]]]},
}


def test_the_train_split_and_the_kernels_per_step():
    ctx = ctx_of(TRAIN, (0, 100 * MS))
    ph = Phases.of(ctx, "train")
    # chip 0 is idle [0,10) + [40,42) + [80,100) = 32 ms of 100:
    # [0,10): no span 2, next_batch 3, stage 2, dispatch 3
    # [40,42): no span 0.5, drain 1.5;  [80,100): drain 10.5, no span 9.5
    assert ph.idle_pct() == pytest.approx(32.0)
    assert ph.idle_share("input") == pytest.approx(5.0)
    assert ph.idle_share("drain") == pytest.approx(12.0)
    assert ph.idle_unattributed_share() == pytest.approx(15.0)
    # two pt.train.step spans; kernel time is averaged over the chips
    assert kernel_ms_per_step(
        ctx, r"flash_fwd.*\[tpu_custom_call\]") == pytest.approx(11.0)
    assert kernel_ms_per_step(
        ctx, r"flash_bwd_(dq|dkv).*\[tpu_custom_call\]") == \
        pytest.approx(14.0)
    with pytest.raises(Exception, match="matches no device op"):
        kernel_ms_per_step(ctx, r"paged_attn")


def test_every_new_reader_reads_the_hand_made_traces(bench):
    """The twelve readers of this PR, through spec.py's own loader: each
    declares the layer, unit and end-to-end metric BENCHMARK.json gives it,
    reads a number from the family's trace and None from a bare one."""
    serve, train = serve_ctx(), ctx_of(TRAIN, (0, 100 * MS))
    bare = Ctx(trace_data=None, trace_window_s=None)
    got = {}
    for m in bench.per_layer.values():
        if m["source"] != "program_span" and "_ms_per_step" not in m["name"]:
            continue
        reader = bench.reader(m["name"])
        ctx = serve if m["name"].endswith(".serve") else train
        got[m["name"]] = reader.read(ctx)
        assert reader.read(bare) is None
    assert len(got) == 12 and all(v is not None for v in got.values())
    assert sum(v for k, v in got.items()
               if k.startswith("idle_") and k.endswith(".serve")) == \
        pytest.approx(52.0)
    assert sum(v for k, v in got.items()
               if k.startswith("idle_") and k.endswith(".train")) == \
        pytest.approx(32.0)
    assert got["decode_step_ms.serve"] == pytest.approx(12.0)
    assert got["flash_bwd_ms_per_step.train"] == pytest.approx(14.0)


# ---------------------------------------------------------------------------
# recorded cuts of v5e traces that carry the spans (my chip runs, PR 26:
# sc2-3b-serve.decode-saturated, two decode steps and the mixed step that
# follows an admission; and
# sc2-3b-train.seq4k, the tail of the fifth traced step, the sixth, the
# loss drain and the pass's end)
# ---------------------------------------------------------------------------

SERVE_CUT_S = 0.165790226
TRAIN_CUT_S = 0.62717131


def cut_ctx(name, window_s):
    """The cuts were recorded before the window was stamped: theirs ran from
    the trace's 0 for the host's `window_s`."""
    return ctx_of(Trace.from_json(os.path.join(DATA, name),
                                  window=(0, round(window_s * 1e9))))


def test_recorded_serve_cut_splits_the_idle_share():
    ctx = cut_ctx("v5e_serve_pt_spans.json", SERVE_CUT_S)
    ph = Phases.of(ctx, "serve")
    assert {"pt.pump.commands", "pt.engine.step", "pt.step.admit",
            "pt.step.plan", "pt.step.decode", "pt.step.mixed",
            "pt.step.dispatch", "pt.step.readback",
            "pt.step.emit"} == ph.names
    idle = 100.0 * (1.0 - ctx.trace_data.busy_s() / SERVE_CUT_S)
    assert ph.idle_pct() == pytest.approx(idle, abs=0.01)
    assert idle == pytest.approx(37.894, abs=0.01)
    shares = {g: ph.idle_share(g) for g in GROUPS["serve"]}
    assert shares == pytest.approx({"emit": 16.220, "schedule": 14.516,
                                    "pump": 0.068, "launch": 6.925},
                                   abs=0.01)
    rest = ph.idle_unattributed_share()
    assert rest == pytest.approx(0.165, abs=0.01)
    assert sum(shares.values()) + rest == pytest.approx(idle, abs=0.01)
    # next to nothing is dark; the chip waits for emit most, and in the
    # step after an admission for its plan (22 ms of this cut's 24)
    assert rest < idle / 100 and shares["emit"] == max(shares.values())
    # the step's kind is in the span's name: two decode steps, one mixed
    assert len(ph.durations("pt.step.decode")) == 2
    assert len(ph.durations("pt.step.mixed")) == 1
    assert median_ms(ctx, "serve", "pt.step.decode") == \
        pytest.approx(30.66, abs=0.01)
    assert median_ms(ctx, "serve", "pt.step.mixed") == \
        pytest.approx(51.10, abs=0.01)
    # 64 token frames a step on the loop thread, beside the split
    sends = span_events(ctx.trace_data, ("pt.loop.",))
    assert len(sends) == 211
    # the paged kernel under its own name, and the old pattern still holds
    assert ctx.trace_data.kernel(r"paged_attn")["calls"] == \
        ctx.trace_data.kernel(r"\[tpu_custom_call\]")["calls"] > 0


def test_recorded_train_cut_puts_the_idle_under_the_drain():
    ctx = cut_ctx("v5e_train_pt_spans.json", TRAIN_CUT_S)
    ph = Phases.of(ctx, "train")
    assert ph.names == {"pt.train.next_batch", "pt.train.step",
                        "pt.train.stage", "pt.train.dispatch",
                        "pt.train.drain"}
    idle = 100.0 * (1.0 - ctx.trace_data.busy_s() / TRAIN_CUT_S)
    assert ph.idle_pct() == pytest.approx(idle, abs=0.01)
    assert idle == pytest.approx(11.990, abs=0.01)
    assert ph.idle_share("input") == pytest.approx(0.396, abs=0.01)
    # the device had finished when the drain began: its jnp.stack compiles
    # (57 ms) with the chip idle, then runs for 338 ns
    assert ph.idle_share("drain") == pytest.approx(8.880, abs=0.01)
    assert ph.durations("pt.train.drain") == pytest.approx([0.05698],
                                                           abs=1e-4)
    assert ph.idle_unattributed_share() == pytest.approx(2.714, abs=0.01)
    # the three flash kernels apart (they were `jvp__` / `transpose_jvp__`);
    # the cut holds the sixth step and the tail of the fifth, so the
    # per-step readings are of this cut only
    tr = ctx.trace_data
    assert tr.kernel(r"flash_fwd")["calls"] == 4          # one a layer
    assert tr.kernel(r"flash_bwd_dq")["calls"] >= 4
    assert tr.kernel(r"flash_bwd_dkv")["calls"] >= 4
    three = sum(tr.kernel(p)["seconds"] for p in
                (r"flash_fwd", r"flash_bwd_dq", r"flash_bwd_dkv"))
    assert three == pytest.approx(
        tr.kernel(r"\[tpu_custom_call\]")["seconds"])
    # ONE step starts in the cut's window (the fifth began 411 ms before
    # it), so a step's count is 1 where the whole file's spans gave 2
    assert len(ph.durations("pt.train.step")) == 1
    assert kernel_ms_per_step(ctx, r"flash_fwd.*\[tpu_custom_call\]") == \
        pytest.approx(2 * 56.217, abs=0.01)
    assert kernel_ms_per_step(
        ctx, r"flash_bwd_(dq|dkv).*\[tpu_custom_call\]") == \
        pytest.approx(2 * 88.251, abs=0.01)


def test_the_new_readers_read_the_recorded_cuts(bench):
    serve = cut_ctx("v5e_serve_pt_spans.json", SERVE_CUT_S)
    train = cut_ctx("v5e_train_pt_spans.json", TRAIN_CUT_S)
    got = {}
    for m in bench.per_layer.values():
        if m["source"] == "program_span" or "_ms_per_step" in m["name"]:
            ctx = serve if m["name"].endswith(".serve") else train
            got[m["name"]] = bench.reader(m["name"]).read(ctx)
    assert len(got) == 12 and all(v is not None for v in got.values())
    for family, ctx in (("serve", serve), ("train", train)):
        total = sum(v for k, v in got.items()
                    if k.startswith("idle_") and k.endswith("." + family))
        idle = bench.reader(f"device_idle_share.{family}").read(ctx)
        assert total == pytest.approx(idle, abs=0.01)


# ---------------------------------------------------------------------------
# one window, one idle time: device_idle_share.* and the split read the same
# interval wherever the window lies against the busy span
# ---------------------------------------------------------------------------

def _cut(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


TRACES = {"SERVE": (lambda: SERVE, "serve"),
          "TRAIN": (lambda: TRAIN, "train"),
          "serve_cut": (lambda: _cut("v5e_serve_pt_spans.json"), "serve"),
          "train_cut": (lambda: _cut("v5e_train_pt_spans.json"), "train")}
# the window's two ends, as shares of the busy span (first op to last op)
WHERE = {"at": (0.0, 1.0), "before": (-0.3, 0.5), "after": (0.5, 1.3),
         "around": (-0.1, 1.1), "inside": (0.2, 0.7)}


@pytest.mark.parametrize("where", WHERE)
@pytest.mark.parametrize("name", TRACES)
def test_the_idle_share_and_the_split_are_one_number(bench, name, where):
    make, family = TRACES[name]
    # the split reads the first device; the share averages the chips
    one = {p: ls for p, ls in make().items() if p != "/device:TPU:1"}
    lo, hi = Trace(one).window
    a, b = WHERE[where]
    ctx = ctx_of(one, (round(lo + a * (hi - lo)), round(lo + b * (hi - lo))))
    idle = bench.reader(f"device_idle_share.{family}").read(ctx)
    ph = Phases.of(ctx, family)
    assert 0.0 <= idle <= 100.0
    assert ph.idle_pct() == pytest.approx(idle, abs=1e-9)
    assert sum(ph.idle_by_span.values()) == ph.idle_ns
    assert ctx.trace_data.busy_s() <= ctx.trace_window_s
    if where in ("at", "around"):           # every group's span is there
        total = sum(ph.idle_share(g) for g in GROUPS[family]) + \
            ph.idle_unattributed_share()
        assert total == pytest.approx(idle, abs=1e-9)


def test_across_chips_the_share_is_the_mean_and_the_split_the_first(bench):
    ctx = ctx_of(TRAIN, (5 * MS, 95 * MS))
    # chip 0 idle [5,10) + [40,42) + [80,95) = 22 ms; chip 1 [5,10) +
    # [36,42) + [68,95) = 38 ms, of 90
    assert bench.reader("device_idle_share.train").read(ctx) == \
        pytest.approx(100 * 30 / 90)
    assert Phases.of(ctx, "train").idle_pct() == pytest.approx(100 * 22 / 90)
