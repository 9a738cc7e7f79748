"""Per-step barrier/straggler statistics for mesh runs.

TPU-native analog of the reference's BarrierStat machinery (ref:
paddle/utils/BarrierStat.h:198-389 BarrierStatBase/BarrierEndStat +
REGISTER_BARRIER_TIMER_SERVER): the pserver printed, per trainer, how
unevenly workers arrived at each gradient barrier.  Under XLA there is no
explicit barrier to instrument — collectives are compiled into the step —
so the observable quantities become:

- **dispatch wait**: host time to enqueue the compiled step (grows when the
  device queue is full, i.e. the host is ahead of the device);
- **sync wait**: host time blocked fetching buffered losses (the drain is
  the real device barrier — it completes only when every chip has finished
  its steps, so it carries the straggler signal);
- **cross-process skew**: each process's mean step wall-time allgathered and
  compared, the per-trainer table of the reference's BarrierEndStat LOG.

A `BarrierTimer` keeps rolling windows and renders a one-line summary every
log_period (see Trainer.train_one_pass).
"""

from __future__ import annotations

from collections import deque
from typing import Optional

import numpy as np


def _pct(xs, unit_scale: float = 1e3) -> dict[str, float]:
    a = np.asarray(xs, np.float64) * unit_scale
    return {
        "p50": float(np.percentile(a, 50)),
        "p95": float(np.percentile(a, 95)),
        "p99": float(np.percentile(a, 99)),
        "max": float(a.max()),
    }


def _fmt_pct(name: str, p: dict[str, float]) -> str:
    return (f"{name} p50={p['p50']:.2f}ms p95={p['p95']:.2f}ms "
            f"p99={p['p99']:.2f}ms max={p['max']:.2f}ms")


class BarrierTimer:
    """Rolling per-step timing windows + cross-process straggler report.

    Every window IS a tracer span (paddle_tpu/obs/trace.py) whose `sink`
    is the window's deque, so a site is timed once: the trainer's
    per-dispatch phases land in the ring (while enabled) and on the
    profiler's timeline under the `pt.train.*` names — `pt.train.dispatch`
    (dispatch), `pt.train.drain` (sync), `pt.train.scan` (scan); h2d spans
    come from the prefetch thread, so they are `pt.feeder.stage` on their
    own track and the staging-vs-scan overlap shows as parallel lanes."""

    def __init__(self, window: int = 500, tracer=None,
                 track: str = "trainer"):
        from paddle_tpu.obs.trace import Tracer
        self.tracer = tracer if tracer is not None else Tracer(capacity=1)
        self.track = track
        self.dispatch_s: deque[float] = deque(maxlen=window)
        self.sync_s: deque[float] = deque(maxlen=window)
        # fused-dispatch (--steps_per_dispatch > 1) windows: h2d is the
        # background thread's stack+device_put of one k-group, scan the
        # host time to enqueue one k-step lax.scan.  Overlap is observable
        # as h2d percentiles staying flat while scan windows absorb the
        # whole step budget (see PERF.md "steps_per_dispatch").
        self.h2d_s: deque[float] = deque(maxlen=window)
        self.scan_s: deque[float] = deque(maxlen=window)
        self._t_enter: Optional[float] = None

    # -- recording --------------------------------------------------------
    def time_dispatch(self, windowed: bool = True):
        """Context manager timing one step dispatch.  `windowed=False`
        keeps a dispatch that compiles (seconds of XLA work, not queue
        backpressure) out of the rolling window; the span is the same."""
        return self.tracer.span(
            "pt.train.dispatch", self.track,
            sink=self.dispatch_s.append if windowed else None)

    def time_sync(self):
        """Context manager timing one host<-device drain (the barrier)."""
        return self.tracer.span("pt.train.drain", self.track,
                                sink=self.sync_s.append)

    def time_h2d(self):
        """Context manager timing one k-group host->device staging (runs on
        the prefetch thread — overlaps the current scan)."""
        return self.tracer.span("pt.feeder.stage", self.track + ":h2d",
                                sink=self.h2d_s.append)

    def time_scan(self, windowed: bool = True):
        """Context manager timing one fused k-step scan dispatch."""
        return self.tracer.span(
            "pt.train.scan", self.track,
            sink=self.scan_s.append if windowed else None)

    # -- reporting --------------------------------------------------------
    def local_summary(self) -> dict[str, dict[str, float]]:
        out = {}
        if self.dispatch_s:
            out["dispatch"] = _pct(self.dispatch_s)
        if self.sync_s:
            out["sync"] = _pct(self.sync_s)
        if self.h2d_s:
            out["h2d"] = _pct(self.h2d_s)
        if self.scan_s:
            out["scan"] = _pct(self.scan_s)
        return out

    def straggler_summary(self) -> Optional[dict[str, float]]:
        """Cross-process mean step-time table (multi-host only): allgather
        each process's mean dispatch+sync and report the skew — the
        reference's per-trainer avgGap table collapsed to its actionable
        numbers (slowest process and slow/mean ratio)."""
        import jax
        if jax.process_count() <= 1 or not (self.dispatch_s or self.sync_s):
            return None
        from jax.experimental import multihost_utils
        mine = np.asarray([
            float(np.mean(self.dispatch_s)) if self.dispatch_s else 0.0,
            float(np.mean(self.sync_s)) if self.sync_s else 0.0,
        ])
        table = np.asarray(multihost_utils.process_allgather(mine))  # [P, 2]
        per_proc = table.sum(axis=1)
        mean = float(per_proc.mean()) or 1e-12
        slowest = int(per_proc.argmax())
        return {
            "slowest_process": slowest,
            "slowest_ms": float(per_proc[slowest]) * 1e3,
            "mean_ms": mean * 1e3,
            "skew": float(per_proc[slowest]) / mean,
        }

    def render(self) -> str:
        """One log line, emitted every log_period on mesh runs."""
        parts = [_fmt_pct(k, v) for k, v in self.local_summary().items()]
        strag = self.straggler_summary()
        if strag is not None:
            parts.append(
                f"straggler: process {strag['slowest_process']} "
                f"{strag['slowest_ms']:.2f}ms vs mean {strag['mean_ms']:.2f}ms "
                f"(skew {strag['skew']:.2f}x)")
        return "; ".join(parts) if parts else "no samples"
