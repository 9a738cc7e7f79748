"""The trace reduction (.xplane.pb -> busy/idle, kernel time, collectives,
breakdown) on a small hand-made trace, on the xplane a CPU run records, and
on the small recorded cut of a TPU v5e trace kept in data/."""

import json
import os

import pytest

from benchmark.lib.trace import Trace, TraceError, find_xplane

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
