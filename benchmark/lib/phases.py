"""The program's own phase spans in a profiler trace, and the device's idle
time split by them.

paddle_tpu/obs/trace.py enters a jax.profiler.TraceAnnotation for every
`Tracer.span()`, so a traced run's host planes carry events named `pt.<thread
or layer>.<phase>` on the same clock as the device planes.  lib/trace.py
merges the host lines of one name and keeps names only, which is why thread
and kind are in the span's NAME.

The split: the first device's idle time — the trace's window (lib/trace.py)
less its busy intervals, `Trace.idle_intervals`, the very time
`device_idle_share.*` counts — goes, piece by piece, to the innermost span
covering it among ONE thread's families: the serving pump's (`pt.pump.*`,
`pt.engine.*`, `pt.step.*`, `pt.kv.*`) or the trainer loop's (`pt.train.*`).
Those nest on their thread; `pt.loop.*` and `pt.feeder.*` run on other
threads and are read beside the split (`durations`), never in it.

A program without spans (a parent commit) has nothing to read: `of()` returns
None and the metric is left out.  With spans in the trace, a name that
matches none of them is an error, never a zero."""

from __future__ import annotations

from .common import log
from .trace import HOST_PLANE, Trace, TraceError

PREFIX = "pt."
NO_SPAN = "(no span)"
FAMILIES = {
    "serve": ("pt.pump.", "pt.engine.", "pt.step.", "pt.kv."),
    "train": ("pt.train.",),
}

# What each share sums: (names of which at least one must be in the trace,
# names that may be).  The rest of the idle time is `unattributed`.
GROUPS = {
    "serve": {
        "emit": (("pt.step.emit",), ()),
        "schedule": (("pt.step.admit", "pt.step.plan"),
                     ("pt.kv.evict", "pt.step.draft")),
        # the pump thread outside pt.engine.step: the heartbeat and the
        # command drain are one span, the idle wait another
        "pump": (("pt.pump.commands",), ("pt.pump.wait",)),
        # launch latency, transfers and the device's own gaps between ops
        # while the host waits for the tokens; the step span's own time is
        # the bookkeeping between its two children
        "launch": (("pt.step.dispatch", "pt.step.readback"),
                   ("pt.step.decode", "pt.step.mixed", "pt.step.spec")),
    },
    "train": {
        "input": (("pt.train.next_batch", "pt.train.stage"), ()),
        "drain": (("pt.train.drain",), ()),
    },
}


class PhaseError(TraceError):
    pass


def span_events(trace: Trace, prefixes=(PREFIX,)) -> list:
    """(start_ns, end_ns, name) of every host event whose name starts with
    one of `prefixes`, outermost first where starts tie."""
    evs = []
    for plane, lines in trace.planes.items():
        if HOST_PLANE.match(plane):
            for es in lines.values():
                evs.extend((s, s + d, name) for name, s, d in es
                           if d > 0 and name.startswith(tuple(prefixes)))
    evs.sort(key=lambda e: (e[0], -e[1]))
    return evs


def innermost(spans: list) -> list:
    """Properly nested (start, end, name) spans of one thread, as disjoint
    (start, end, name) pieces each named by the innermost span covering it.
    A child that outlives its parent by clock jitter is cut at the parent's
    end."""
    out, stack, t = [], [], 0

    def piece(t0, t1, name):
        if t1 > t0:
            out.append((t0, t1, name))

    for s, e, name in spans:
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            piece(t, end, top)
            t = max(t, end)
        if stack:
            piece(t, s, stack[-1][1])
            e = min(e, stack[-1][0])
        stack.append((e, name))
        t = s
    while stack:
        end, top = stack.pop()
        piece(t, end, top)
        t = max(t, end)
    return out


def split(gaps: list, pieces: list) -> dict:
    """Nanoseconds of `gaps` under each name of `pieces` (both sorted and
    disjoint); what no piece covers goes to NO_SPAN."""
    agg = {}
    i = 0
    for g0, g1 in gaps:
        covered = 0
        while i < len(pieces) and pieces[i][1] <= g0:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < g1:
            p0, p1, name = pieces[j]
            d = min(p1, g1) - max(p0, g0)
            if d > 0:
                agg[name] = agg.get(name, 0) + d
                covered += d
            j += 1
        agg[NO_SPAN] = agg.get(NO_SPAN, 0) + (g1 - g0) - covered
    return agg


class Phases:
    """One family's spans in one trace, and the idle split by them."""

    def __init__(self, trace: Trace, family: str):
        self.family = family
        self.window_s = trace.window_s
        self.window = w0, w1 = trace.window
        # the spans that reach into the window; what the file holds before
        # the stamp or behind it belongs to no reading
        self.spans = [sp for sp in span_events(trace, FAMILIES[family])
                      if sp[0] < w1 and sp[1] > w0]
        self.names = {name for _, _, name in self.spans}
        gaps = trace.idle_intervals(trace.device_planes()[0])
        self.idle_ns = sum(b - a for a, b in gaps)
        self.idle_by_span = split(gaps, innermost(self.spans))

    @classmethod
    def of(cls, ctx, family: str):
        """The run's Phases (made once a run), or None where there is
        nothing to read: no trace, or a program that enters no `pt.` span
        of this family."""
        if ctx.trace_data is None:
            return None
        cache = ctx.__dict__.setdefault("_phases", {})
        if family not in cache:
            ph = cls(ctx.trace_data, family)
            cache[family] = ph if ph.spans else None
            if ph.spans:
                rows = sorted(ph.idle_by_span.items(), key=lambda kv: -kv[1])
                log(f"PHASES {family}: idle {ph.idle_ns / 1e9:.4f}s of "
                    f"{ph.window_s:.3f}s by innermost span "
                    f"{[[k, round(v / 1e9, 4)] for k, v in rows]}")
        return cache[family]

    def _known(self, must: tuple) -> None:
        if not any(n in self.names for n in must):
            raise PhaseError(
                f"none of the spans {list(must)} is in the trace; the "
                f"{self.family} family has {sorted(self.names)}")

    def _pct(self, ns: float, what: str) -> float:
        pct = 100.0 * ns / 1e9 / self.window_s
        if pct > 105.0:
            raise PhaseError(f"{what}: {pct:.1f}% of the traced window")
        return pct

    def idle_pct(self) -> float:
        """The first device's idle time, % of the traced window."""
        return self._pct(self.idle_ns, "idle")

    def idle_share(self, group: str) -> float:
        """Device idle time under the spans of one GROUPS entry, % of the
        traced window."""
        must, may = GROUPS[self.family][group]
        self._known(must)
        ns = sum(self.idle_by_span.get(n, 0) for n in must + may)
        return self._pct(ns, f"idle under {group}")

    def idle_unattributed_share(self) -> float:
        """The rest: idle time under no span of this family's groups."""
        return self.idle_pct() - sum(self.idle_share(g)
                                     for g in GROUPS[self.family])

    def durations(self, name: str) -> list:
        """Seconds of every span called `name` (any thread's) that starts
        in the window, whole."""
        if name not in self.names:
            raise PhaseError(f"no span {name!r} in the trace; the "
                             f"{self.family} family has {sorted(self.names)}")
        w0, w1 = self.window
        return [(e - s) / 1e9 for s, e, n in self.spans
                if n == name and w0 <= s < w1]


def median_ms(ctx, family: str, name: str):
    """Median duration of the spans called `name`, ms (None: no spans)."""
    ph = Phases.of(ctx, family)
    if ph is None:
        return None
    xs = sorted(ph.durations(name))
    mid = len(xs) // 2
    return 1e3 * (xs[mid] if len(xs) % 2 else (xs[mid - 1] + xs[mid]) / 2)


def kernel_ms_per_step(ctx, pattern: str):
    """Summed device time of the ops matching `pattern` over the
    `pt.train.step` spans in the trace, ms a step and chip (None: no
    spans)."""
    ph = Phases.of(ctx, "train")
    if ph is None:
        return None
    steps = len(ph.durations("pt.train.step"))
    return 1e3 * ctx.trace_data.kernel(pattern)["seconds"] / steps
