"""The reduction from a profiler trace to numbers: device busy and idle time,
a kernel's time, the time collectives run alone, the operations that took
most time and the longest idle gaps by what the host was doing.

jax.profiler writes <dir>/plugins/profile/<time>/*.xplane.pb;
jax.profiler.ProfileData reads it with nothing but JAX.  The reduction works
on a plain structure {plane: {line: [[name, start_ns, dur_ns], ...]}} so a
small recorded trace can be kept as JSON and checked on the CPU.

Every reduction is of ONE interval on the trace's own clock, the window: the
host event WINDOW_EVENT that lib/common.py:ProfilerWindow opens once the
profiler has started and closes before it is stopped (the profiler's stop
runs for a minute with the device still stepping, and the file holds the
first tenths of a second of those ops, with no host span beside them), or
the `window=` a hand-made trace is given, or with neither the first op's
start to the last op's end.  Busy, idle and collective time are
clipped to it; a kernel's time, the top operations and a step's count are of
the ops that START in it, whole, so that no per-call time is cut at an edge.

A name pattern that matches no event is an error, never a zero."""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = re.compile(r"^/host:")
WINDOW_EVENT = "bench.window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.I)
_SUFFIX = re.compile(r"[.\-_]\d+$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """A device op's event name is its whole HLO instruction
    (`%fusion.12 = f32[...] fusion(...), kind=kLoop, ...`).  Keep the
    instruction's own name, and for a custom call its target in brackets:
    `_mixed_impl.10 [tpu_custom_call]` is a Pallas kernel."""
    head = name.split(" = ", 1)[0].lstrip("%")
    m = _TARGET.search(name)
    return f"{head} [{m.group(1)}]" if m else head


class TraceError(RuntimeError):
    pass


def _op_family(name: str) -> str:
    """`fusion.12` -> `fusion`; `_mixed_impl.10 [tpu_custom_call]` ->
    `_mixed_impl [tpu_custom_call]`."""
    head, _, tail = name.partition(" [")
    head = _SUFFIX.sub("", head)
    return (f"{head} [{tail}" if tail else head)[:96]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class Trace:
    def __init__(self, planes: dict, window: tuple | None = None):
        self.planes = planes
        self._window = window
        self._busy: dict = {}
        self._started: dict = {}

    # -- loading ---------------------------------------------------------
    @classmethod
    def from_xplane(cls, path: str, keep_host: bool = True,
                    cpu_as_device: bool = False) -> "Trace":
        """`cpu_as_device` is for the CPU rehearsal alone: XLA:CPU's worker
        threads stand in for a device plane so the control flow can run."""
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        planes: dict = {}
        for plane in data.planes:
            name = plane.name
            if not (DEVICE_PLANE.match(name)
                    or (keep_host and HOST_PLANE.match(name))):
                continue
            lines = planes.setdefault(name, {})
            for line in plane.lines:
                evs = lines.setdefault(line.name, [])
                for ev in line.events:
                    evs.append([short_name(ev.name), int(ev.start_ns),
                                int(ev.duration_ns)])
        if cpu_as_device:
            ops = [e for ln, evs in planes.get("/host:CPU", {}).items()
                   if ln.startswith("tf_XLA") for e in evs]
            planes["/device:TPU:0"] = {OPS_LINE: sorted(ops, key=lambda e: e[1])}
        return cls(planes)

    @classmethod
    def from_json(cls, path: str, window: tuple | None = None) -> "Trace":
        with open(path) as f:
            return cls(json.load(f), window)

    def sample(self, max_events: int = 400) -> dict:
        """A small cut of this trace (the first events of every line), for
        a recorded test trace."""
        return {p: {ln: evs[:max_events] for ln, evs in lines.items()}
                for p, lines in self.planes.items()}

    def describe(self) -> dict:
        return {p: {ln: len(evs) for ln, evs in lines.items()}
                for p, lines in self.planes.items()}

    # -- device ----------------------------------------------------------
    def device_planes(self) -> list[str]:
        names = sorted(p for p in self.planes if DEVICE_PLANE.match(p)
                       and self.planes[p].get(OPS_LINE))
        if not names:
            raise TraceError(
                f"no device plane with an {OPS_LINE!r} line: no operation "
                f"ran on the device in the traced window "
                f"(planes: {self.describe()})")
        return names

    def ops(self, plane: str) -> list:
        return self.planes[plane][OPS_LINE]

    # -- the window ------------------------------------------------------
    def window_event(self):
        """(start_ns, end_ns) of the host's WINDOW_EVENT, None without one;
        more than one is an error."""
        evs = [(s, s + d) for p, lines in self.planes.items()
               if HOST_PLANE.match(p) for es in lines.values()
               for name, s, d in es if name == WINDOW_EVENT]
        if len(evs) > 1:
            raise TraceError(f"{len(evs)} {WINDOW_EVENT!r} events in one "
                             f"trace: {evs[:4]}")
        return evs[0] if evs else None

    @property
    def window(self) -> tuple:
        """(w0, w1) in the trace's ns: as given, else the window event,
        else the first op's start to the last op's end."""
        if self._window is None:
            self._window = self.window_event()
        if self._window is None:
            ops = [(s, s + d) for p in self.device_planes()
                   for _, s, d in self.ops(p) if d > 0]
            self._window = (min(s for s, _ in ops), max(e for _, e in ops))
        return self._window

    @property
    def window_s(self) -> float:
        w0, w1 = self.window
        return (w1 - w0) / 1e9

    def ops_in_window(self, plane: str) -> list:
        """The plane's ops that START inside the window."""
        if plane not in self._started:
            w0, w1 = self.window
            self._started[plane] = [e for e in self.ops(plane)
                                    if w0 <= e[1] < w1]
        return self._started[plane]

    def busy_intervals(self, plane: str, pattern=None) -> list:
        """The union of the plane's op intervals (of the ops `pattern`
        finds), clipped to the window."""
        if (plane, pattern) not in self._busy:
            w0, w1 = self.window
            self._busy[plane, pattern] = _union(
                (max(s, w0), min(s + d, w1)) for n, s, d in self.ops(plane)
                if d > 0 and s < w1 and s + d > w0
                and (pattern is None or pattern.search(n)))
        return self._busy[plane, pattern]

    def idle_intervals(self, plane: str) -> list:
        """The window less the plane's busy intervals: the gaps between
        them and the window's two edges, as (start, end)."""
        w0, w1 = self.window
        edges = [w0] + [t for iv in self.busy_intervals(plane)
                        for t in iv] + [w1]
        return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]

    def _seconds(self, pattern=None) -> float:
        planes = self.device_planes()
        return sum(e - s for p in planes
                   for s, e in self.busy_intervals(p, pattern)) \
            / 1e9 / len(planes)

    def busy_s(self) -> float:
        """Seconds of the window in which an operation ran on the device,
        averaged over the chips used: never above `window_s`."""
        return self._seconds()

    def span_s(self) -> float:
        """First op start to last op end inside the window, seconds."""
        ivs = [iv for iv in map(self.busy_intervals, self.device_planes())
               if iv]
        return (max(iv[-1][1] for iv in ivs)
                - min(iv[0][0] for iv in ivs)) / 1e9

    def kernel(self, pattern: str) -> dict:
        """Summed device time and call count of the ops that start in the
        window and whose name matches `pattern`, averaged over the chips
        used."""
        rx = re.compile(pattern)
        planes = self.device_planes()
        total_ns = calls = 0
        for p in planes:
            for name, _, d in self.ops_in_window(p):
                if rx.search(name):
                    total_ns += d
                    calls += 1
        if calls == 0:
            seen = sorted({_op_family(n) for p in planes
                           for n, _, _ in self.ops_in_window(p)})
            raise TraceError(f"pattern {pattern!r} matches no device op "
                             f"that starts in the window; op names there: "
                             f"{seen[:60]}")
        return {"seconds": total_ns / 1e9 / len(planes),
                "calls": calls / len(planes)}

    def collective_s(self) -> float:
        """Seconds of the window a collective holds the core's op line (on
        a TPU core ops run one at a time, so compute does not run beside
        it): the exposed part of the collectives, averaged over the chips."""
        return self._seconds(COLLECTIVE)

    # -- breakdown -------------------------------------------------------
    def top_ops(self, n: int = 10) -> list:
        agg: dict = {}
        planes = self.device_planes()
        for p in planes:
            for name, _, d in self.ops_in_window(p):
                key = _op_family(name)
                agg[key] = agg.get(key, 0) + d
        rows = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9 / len(planes)] for k, v in rows]

    def _host_events(self) -> list:
        evs = []
        for p, lines in self.planes.items():
            if HOST_PLANE.match(p):
                for ln, es in lines.items():
                    evs.extend((s, s + d, name) for name, s, d in es
                               if d > 0 and name != WINDOW_EVENT)
        evs.sort()
        return evs

    def idle_gaps(self, n: int = 10) -> list:
        """The idle time of the first device in the window, by what the
        host was doing: each idle interval goes to the innermost host event
        (the shortest one) that covers its middle."""
        import bisect

        gaps = self.idle_intervals(self.device_planes()[0])
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:2000]
        host = self._host_events()
        starts = [h[0] for h in host]
        agg: dict = {}
        for g0, g1 in gaps:
            mid = (g0 + g1) / 2
            best, best_len = "(no host event)", None
            hi = bisect.bisect_right(starts, mid)
            for s, e, name in host[max(0, hi - 600):hi]:
                if s <= mid < e and (best_len is None or e - s < best_len):
                    best, best_len = name, e - s
            key = _SUFFIX.sub("", best)[:96]
            agg[key] = agg.get(key, 0) + (g1 - g0)
        rows = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in rows]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(10), "idle_gaps": self.idle_gaps(10)}
