"""The serving step clock over the measured window (docs/observability.md
"The step clock"): the pump thread's spans feed always-on second counters
through their own sinks, a compiled step carries its launch time to its
land, and the pump checkpoints the process's counters every 0.1 s
(paddle_tpu/obs/metrics.py: ProcessCounters.between).  So these readers
take a counter's growth over the window the end-to-end metrics are measured
in, on the ring's clock, and OUTSIDE the profiler's slice of the very run
that is traced: the one stretch of it where the profiler's Python tracer
multiplies the host's phases.

A program without the counters or the checkpoints (a parent commit) reads
None, as benchmark/lib/token_frames.py does."""

from __future__ import annotations

import json
import time

from .common import log

SECONDS = 'serving_pump_seconds_total{span="%s"}'
SPANS = 'serving_pump_spans_total{span="%s"}'
FLIGHT = 'serving_step_flight_seconds_total{kind="%s"}'
LANDED = 'serving_steps_landed_total{kind="%s"}'
KINDS = ("decode", "mixed", "scan", "spec")
#: the pump's three top-level spans: what is in none of them is `outside`
TOP = ("pt.pump.commands", "pt.engine.step", "pt.pump.wait")
PHASES = ("pt.pump.commands", "pt.step.admit", "pt.step.plan",
          "pt.step.dispatch", "pt.step.readback", "pt.step.emit",
          "pt.kv.evict", "pt.engine.step")


def _counters():
    """The program's ProcessCounters, if it has one that keeps windows."""
    try:
        from paddle_tpu.obs.metrics import process_counters
    except ImportError:
        return None
    pc = process_counters()
    return pc if hasattr(pc, "between") else None


def stretch(ctx) -> tuple:
    """(t0, t1, exclude) on `time.perf_counter()`: the measured window —
    `setup_s` after the process's start, for `--seconds` — and the
    profiler's slice inside it (`trace_span`: `time.time()` stamps taken
    around the profiler's start and stop, moved onto perf_counter by the
    offset of the two clocks now)."""
    t0 = ctx.t_process + ctx.e2e["setup_s"]
    span = ctx.counters.get("trace_span") or {}
    exclude = []
    if "t0" in span and "t1" in span:
        offset = time.perf_counter() - time.time()
        exclude.append((span["t0"] + offset, span["t1"] + offset))
    return t0, t0 + ctx.seconds, exclude


class Window:
    """The counters' growth over the window outside the slice."""

    def __init__(self, growth: dict, seconds: float):
        self.growth = growth
        self.seconds = seconds          # between the checkpoints used

    def span_s(self, name: str) -> float:
        return self.growth.get(SECONDS % name, 0.0)

    def span_n(self, name: str) -> float:
        return self.growth.get(SPANS % name, 0)

    def landed(self, kind: str = "") -> float:
        """Compiled steps whose tokens reached the host (of one kind)."""
        return sum(self.growth.get(LANDED % k, 0)
                   for k in ((kind,) if kind else KINDS))

    def flight_s(self, kind: str) -> float:
        return self.growth.get(FLIGHT % kind, 0.0)

    def host_s(self) -> float:
        """The pump thread's seconds that are the host's own work: its
        command drain and `engine.step()` — admit, plan (drafts and the
        eviction walk inside), the launch (dispatch inside), emit and the
        step's own time — less the read-back, where it waits for the
        device.  Read-back is subtracted from the sum because a land forced
        early nests inside `pt.pump.commands` or `pt.step.admit`."""
        return (self.span_s("pt.pump.commands")
                + self.span_s("pt.engine.step")
                - self.span_s("pt.step.readback"))

    def outside_s(self) -> float:
        """Seconds of the pump thread in none of its spans."""
        return self.seconds - sum(self.span_s(n) for n in TOP)

    def per_step_ms(self, seconds: float):
        n = self.landed()
        return 1e3 * seconds / n if n else None

    def share(self, seconds: float):
        return 100.0 * seconds / self.seconds if self.seconds else None


def window(ctx):
    """The run's Window (read once, kept on `ctx`), or None where the
    program keeps no such counters.  Logs the books it rests on."""
    if "step_clock" in ctx.spans:
        return ctx.spans["step_clock"]
    pc = _counters()
    w = None
    if pc is not None:
        t0, t1, exclude = stretch(ctx)
        growth, seconds = pc.between(t0, t1, exclude)
        if any(k.startswith("serving_pump_seconds_total") for k in growth):
            w = Window(growth, seconds)
            _log_books(ctx, pc, w, t0, t1, exclude)
    ctx.spans["step_clock"] = w
    return w


def _log_books(ctx, pc, w: Window, t0, t1, exclude) -> None:
    """One STEP_CLOCK line: the window's books; one STEP_CLOCK_TRACER line:
    each phase's mean inside the profiler's slice over its mean outside."""
    landed = w.landed()
    books = {
        "asked_s": (t1 - t0) - sum(max(0.0, min(b, t1) - max(a, t0))
                                   for a, b in exclude),
        "covered_s": w.seconds,
        "top_s": {n: w.span_s(n) for n in TOP},
        "outside_s": w.outside_s(),
        "landed": {k: w.landed(k) for k in KINDS if w.landed(k)},
        "decode_steps_in_window": ctx.counters.get("decode_steps"),
        "host_ms_per_step": w.per_step_ms(w.host_s()),
        "readback_ms_per_step": w.per_step_ms(w.span_s("pt.step.readback")),
        "wait_ms_per_step": w.per_step_ms(w.span_s("pt.pump.wait")),
        "period_ms": 1e3 * w.seconds / landed if landed else None,
        "phase_s": {n: w.span_s(n) for n in PHASES},
        "loop_send_s": w.growth.get("serving_loop_send_seconds_total", 0.0),
        "loop_sends": w.growth.get("serving_loop_sends_total", 0),
    }
    log("STEP_CLOCK " + json.dumps(books))
    if not exclude:
        return
    # the tracer runs from the end of the profiler's start to the start of
    # its stop, `trace_s` seconds of sleep between them: leave the start a
    # second and read up to `trace_s` after the slice's first stamp
    a, b = exclude[0]
    b = min(b, a + float(ctx.traffic.get("trace_s", 0.0)))
    a += 1.0
    try:
        inside, in_s = pc.between(a, b)
    except LookupError as e:
        log(f"STEP_CLOCK_TRACER nothing to read: {e}")
        return
    out = {"inside_s": in_s, "slice_s": exclude[0][1] - exclude[0][0],
           "trace_window_s": ctx.trace_window_s}
    for name in PHASES:
        n_in, n_out = inside.get(SPANS % name, 0), w.span_n(name)
        if n_in and n_out:
            m_in = inside.get(SECONDS % name, 0.0) / n_in
            m_out = w.span_s(name) / n_out
            out[name] = {"inside_ms": 1e3 * m_in, "outside_ms": 1e3 * m_out,
                         "n_inside": n_in, "n_outside": n_out,
                         "times": m_in / m_out if m_out else None}
    log("STEP_CLOCK_TRACER " + json.dumps(out))
