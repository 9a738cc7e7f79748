"""The trace reduction (.xplane.pb -> busy/idle, kernel time, collectives,
breakdown) on a small hand-made trace, on the xplane a CPU run records, and
on the small recorded cut of a TPU v5e trace kept in data/."""

import json
import os

import pytest

from benchmark.lib.trace import (WINDOW_EVENT, Trace, TraceError,
                                 find_xplane)

MS = 1_000_000      # ns

HAND = {
    "/device:TPU:0": {"XLA Ops": [
        ["fusion.1", 0 * MS, 4 * MS],
        ["_fwd_kernel.2", 4 * MS, 2 * MS],
        ["all-reduce-start.1", 6 * MS, 0],
        ["_fwd_kernel.7", 10 * MS, 2 * MS],          # 4 ms gap before it
        ["all-reduce-done.1", 12 * MS, 3 * MS],
        ["while.3", 20 * MS, 10 * MS],               # nests the next one
        ["fusion.9", 22 * MS, 2 * MS]],
        "Steps": [["step 1", 0, 30 * MS]]},
    "/device:TPU:1": {"XLA Ops": [
        ["fusion.1", 0 * MS, 8 * MS],
        ["all-reduce-done.1", 8 * MS, 1 * MS]]},
    "/host:CPU": {"python3": [
        ["bench.engine_step", 5 * MS, 6 * MS],
        ["$engine.py:1056 step", 6 * MS, 3.5 * MS],
        ["bench.input_wait", 15 * MS, 5 * MS]]},
}


def test_busy_is_the_union_of_op_intervals_averaged_over_chips():
    tr = Trace(HAND)
    assert tr.device_planes() == ["/device:TPU:0", "/device:TPU:1"]
    # chip 0: [0,6) + [10,15) + [20,30) = 21 ms (the nested op adds none);
    # chip 1: 9 ms
    assert tr.busy_s() == pytest.approx((0.021 + 0.009) / 2)
    assert tr.span_s() == pytest.approx(0.030)


def test_kernel_time_and_calls():
    k = Trace(HAND).kernel(r"_fwd_kernel")
    assert k["seconds"] == pytest.approx(0.004 / 2)
    assert k["calls"] == 1.0


def test_a_pattern_that_matches_nothing_is_an_error_never_a_zero():
    with pytest.raises(TraceError, match="matches no device op"):
        Trace(HAND).kernel(r"paged_attention")


def test_no_device_plane_is_an_error():
    with pytest.raises(TraceError, match="no operation"):
        Trace({"/host:CPU": HAND["/host:CPU"]}).busy_s()


def test_collective_seconds():
    assert Trace(HAND).collective_s() == pytest.approx((0.003 + 0.001) / 2)


def test_breakdown_names_ops_and_attributes_gaps():
    b = Trace(HAND).breakdown()
    ops = dict(b["device_ops"])
    assert ops["fusion"] == pytest.approx((0.004 + 0.002 + 0.008) / 2)
    assert ops["while"] == pytest.approx(0.010 / 2)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    gaps = dict(b["idle_gaps"])
    # [6,10) goes to the innermost host event covering most of it; [15,20)
    # to the input wait
    assert gaps["$engine.py:1056 step"] == pytest.approx(0.004)
    assert gaps["bench.input_wait"] == pytest.approx(0.005)


# ops before w0, across w0, inside, across w1 and after w1; chip 1 busy all
# through.  The window is [10, 30) ms.
EDGES = {
    "/device:TPU:0": {"XLA Ops": [
        ["fusion.1", 2 * MS, 3 * MS],                      # before
        ["_fwd_kernel.2", 8 * MS, 4 * MS],                 # across w0
        ["fusion.1", 14 * MS, 2 * MS],                     # inside
        ["all-reduce-done.1", 18 * MS, 2 * MS],            # inside
        ["_fwd_kernel.2", 27 * MS, 6 * MS],                # across w1
        ["all-reduce-done.1", 29 * MS, 9 * MS],            # across w1
        ["fusion.9", 40 * MS, 5 * MS]]},                   # after
    "/device:TPU:1": {"XLA Ops": [["while.3", 0, 60 * MS]]},
    "/host:CPU": {"python3": [
        ["bench.engine_step", 0, 14 * MS],
        ["bench.input_wait", 20 * MS, 8 * MS]]},
}
W = (10 * MS, 30 * MS)


def stamped(planes, w):
    """`planes` with the window as ProfilerWindow stamps it: a host event."""
    host = dict(planes["/host:CPU"], tracer=[[WINDOW_EVENT, w[0],
                                              w[1] - w[0]]])
    return dict(planes, **{"/host:CPU": host})


@pytest.mark.parametrize("make", [
    lambda: Trace(EDGES, window=W), lambda: Trace(stamped(EDGES, W))],
    ids=["window=", "event"])
def test_every_reduction_is_of_the_window(make):
    tr = make()
    assert tr.window == W and tr.window_s == pytest.approx(0.020)
    # chip 0: [10,12) + [14,16) + [18,20) + [27,30) = 9 ms; chip 1: 20 ms
    assert tr.busy_intervals("/device:TPU:0") == [
        [10 * MS, 12 * MS], [14 * MS, 16 * MS], [18 * MS, 20 * MS],
        [27 * MS, 30 * MS]]
    assert tr.busy_s() == pytest.approx((0.009 + 0.020) / 2)
    assert tr.busy_s() <= tr.window_s
    assert tr.span_s() == pytest.approx(0.020)
    assert tr.idle_intervals("/device:TPU:0") == [
        (12 * MS, 14 * MS), (16 * MS, 18 * MS), (20 * MS, 27 * MS)]
    assert tr.idle_intervals("/device:TPU:1") == []
    # collectives clipped too: [18,20) + [29,30) on chip 0
    assert tr.collective_s() == pytest.approx(0.003 / 2)
    # a kernel's calls are the ops that START in the window, whole: the one
    # across w1 counts all its 6 ms, the one across w0 none
    k = tr.kernel(r"_fwd_kernel")
    assert k == {"seconds": pytest.approx(0.006 / 2), "calls": 0.5}
    with pytest.raises(TraceError, match="starts in the window"):
        tr.kernel(r"fusion\.9")
    with pytest.raises(TraceError, match="starts in the window"):
        tr.kernel(r"while")                 # chip 1's began before w0
    ops = dict(tr.top_ops())
    assert ops == {"all-reduce-done": pytest.approx(0.011 / 2),
                   "_fwd_kernel": pytest.approx(0.006 / 2),
                   "fusion": pytest.approx(0.002 / 2)}
    gaps = dict(tr.idle_gaps())
    assert gaps == {"bench.engine_step": pytest.approx(0.002),
                    "(no host event)": pytest.approx(0.002),
                    "bench.input_wait": pytest.approx(0.007)}
    assert sum(gaps.values()) == pytest.approx(tr.window_s - 0.009)


@pytest.mark.parametrize("w,busy_ms", [
    ((0, 60 * MS), 27),             # holds everything: the whole union
    ((0, 2 * MS), 0),               # before the first op
    ((50 * MS, 60 * MS), 0),        # after the last
    ((9 * MS, 11 * MS), 2),         # inside one op
    ((44 * MS, 50 * MS), 1)])       # over the last op's end
def test_busy_is_never_above_the_window(w, busy_ms):
    one = {"/device:TPU:0": EDGES["/device:TPU:0"]}
    tr = Trace(one, window=w)
    assert tr.busy_s() == pytest.approx(busy_ms / 1e3)
    assert 0 <= tr.busy_s() <= tr.window_s
    idle = sum(b - a for a, b in tr.idle_intervals("/device:TPU:0"))
    assert idle / 1e9 == pytest.approx(tr.window_s - tr.busy_s())


def test_without_a_window_the_trace_reads_first_op_to_last_op():
    tr = Trace(EDGES)
    assert tr.window_event() is None
    assert tr.window == (0, 60 * MS)
    # chip 0's whole union: [2,5) + [8,12) + [14,16) + [18,20) + [27,38) +
    # [40,45) = 27 ms, as before there was a window
    assert tr.busy_s() == pytest.approx((0.027 + 0.060) / 2)
    assert tr.kernel(r"_fwd_kernel")["calls"] == 1.0
    assert Trace(HAND).window == (0, 30 * MS)
    # an explicit window wins over the event; two events are an error
    assert Trace(stamped(EDGES, W), window=(0, 5 * MS)).window == (0, 5 * MS)
    twice = stamped(EDGES, W)
    twice["/host:CPU"]["python3"] = twice["/host:CPU"]["python3"] + [
        [WINDOW_EVENT, 40 * MS, 5 * MS]]
    with pytest.raises(TraceError, match="2 'bench.window' events"):
        Trace(twice).window


def test_reads_the_xplane_a_cpu_run_records(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.marker"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = Trace.from_xplane(find_xplane(str(tmp_path)), cpu_as_device=True)
    host = [n for evs in tr.planes["/host:CPU"].values() for n, _, _ in evs]
    assert "bench.marker" in host
    assert tr.busy_s() > 0
    assert tr.window_event() is None        # no ProfilerWindow stamped it
    assert Trace.from_xplane(find_xplane(str(tmp_path))).planes.get(
        "/device:TPU:0") is None          # a CPU is not a device plane


RECORDED = os.path.join(os.path.dirname(__file__), "data")


@pytest.mark.parametrize("name,pattern", [
    ("v5e_train_trace_sample.json", "flash_attn_roofline.train"),
    ("v5e_serve_trace_sample.json", "paged_attn_roofline.serve")])
def test_recorded_v5e_trace(bench, name, pattern):
    path = os.path.join(RECORDED, name)
    if not os.path.exists(path):
        pytest.skip(f"{name} is recorded by the first traced chip run")
    tr = Trace.from_json(path)
    assert tr.device_planes()
    assert 0 < tr.busy_s() <= tr.span_s()
    k = tr.kernel(bench.reader(pattern).PATTERN)
    assert k["calls"] >= 1 and k["seconds"] > 0
    b = tr.breakdown()
    assert b["device_ops"] and json.dumps(b)
