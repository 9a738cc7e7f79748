"""Span tracer: request-lifecycle and trainer-phase timing spans.

The TensorFlow-timeline analog for this stack (arXiv:1605.08695 ships
timeline tracing as a first-class subsystem; the TPU serving literature
diagnoses tail latency via per-phase request spans, arXiv:2605.25645):
lightweight begin/end spans with attributes, recorded into a BOUNDED ring
by the one thread that owns the instrumented state — the serving pump or
the trainer loop — so recording needs no locks and a week-old process
holds the last `capacity` spans, not its lifetime.

Design constraints, in order:

  1. **Off means off.**  `tracer.enabled` is False by default and every
     ring write checks it first.  `span()` / `begin()` additionally enter
     a `jax.profiler.TraceAnnotation` of the same name — the SECOND sink,
     built only while a profiler session runs (`is_enabled()`, 0.05 us
     to ask) — so one call site feeds the operator's ring and the
     profiler's timeline alike (see "Two sinks" below).  With both off a
     span is its object and the thread's open-span stack: about 0.9 us;
     one given a `sink=` also reads the clock twice and calls the sink
     (the serving pump's spans all do: the always-on second counters of
     docs/observability.md "The step clock"), and still writes no ring
     record and builds no annotation.
  2. **Single-writer ring.**  Spans are appended by the owning thread
     only; `snapshot()` may run on another thread (drain, a test) and
     copies the list under the GIL, using each record's monotonic `seq`
     to restore order.  No cross-thread mutation, matching the serving
     command-queue architecture.
  3. **Two export shapes.**  Structured JSONL (one span per line — the
     greppable archival form) and Chrome `trace_event` JSON (the
     `tools/trace_dump.py` product, loadable in Perfetto/chrome://tracing).

Span model: a span is (seq, name, track, ts, dur, attrs).  `track` is the
horizontal lane the viewer shows — one per request (`req:<id>`), one for
the engine (`engine`), one for the trainer (`trainer`).  `dur` 0.0 with
`instant=True` renders as an instant marker (preempt, done).  Times are
`time.perf_counter()` seconds; exports convert to microseconds.

Two sinks (docs/observability.md "The span model"): `span()` and
`begin()`/`end()` time a PHASE of the thread that runs it — the `pt.`
vocabulary, thread then phase (`pt.step.dispatch`, `pt.train.drain`) —
and feed (a) the ring, while `enabled`, and (b) the profiler trace, while
a `jax.profiler` session runs, where the event lands on the host plane on
the same clock as the device planes (the ring's perf_counter stamps cannot
be lined up with a profiler trace after the fact).  `add()`/`instant()`
stay ring-only: the per-request lanes (`req:<id>`) overlap and so cannot
nest on a thread; `annotation()` is the profiler sink alone, for a site
that runs once a token (`pt.loop.send`).  JAX is looked up lazily and only
once the process has imported it (a profiler session needs JAX
in-process), so this module — and the client/router import path — stays
stdlib-only.  The names of the spans open on the calling thread are kept
(`current_span()`), which is how obs/compile_watch.py names the phase an
eager compile happened in.

Distributed tracing (docs/observability.md "Distributed tracing"): a
request that crosses processes (client → fleet router → replica) carries
a wire-level trace context — `trace_id` (one per request, minted at the
router's ingress unless the client supplied one) and `parent` (the
sending side's span id) — which every process records as span ATTRS, so
stitching needs no tracer-core change.  `merge_chrome()` stitches span
sets pulled from several processes (the `trace` RPC, or `--trace-out`
files) into ONE Chrome trace with a named process group per source,
applying each source's clock offset (perf_counter epochs are
per-process; the puller measures the offset via ping-RTT midpointing).
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time
from typing import Optional

# -- the profiler sink and the per-thread stack of open spans ---------------
_annotation = None       # jax.profiler.TraceAnnotation; False = cannot have it
_open = threading.local()


def _annotation_cls():
    """jax.profiler.TraceAnnotation once this process has imported JAX,
    else None: ring-only (a profiler session needs JAX in-process, so no
    event is lost, and a JAX-free client never pays the import)."""
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        try:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        except ImportError:
            _annotation = False
    return _annotation or None


def _open_spans() -> list:
    try:
        return _open.names
    except AttributeError:
        names = _open.names = []
        return names


def current_span(prefix: str = "") -> Optional[str]:
    """Name of the innermost span open on the CALLING thread whose name
    starts with `prefix`, or None."""
    for name in reversed(_open_spans()):
        if name.startswith(prefix):
            return name
    return None


def new_trace_id() -> str:
    """One id per cross-process request — 16 hex chars, collision-safe at
    fleet request rates (os.urandom, no seeding to leak)."""
    return os.urandom(8).hex()


def new_span_id() -> str:
    """Parent-pointer currency for cross-process span stitching."""
    return os.urandom(4).hex()


def process_info(role: str, host: Optional[str] = None,
                 port: Optional[int] = None) -> dict:
    """The process-identity stamp a `trace` RPC reply (and a --trace-out
    file's meta line) carries, so a merged trace can name its tracks:
    role (replica/router/...), pid, hostname, and the bind address."""
    out = {"role": role, "pid": os.getpid(),
           "hostname": socket.gethostname()}
    if host is not None:
        out["addr"] = f"{host}:{port}"
    return out


def trace_reply(tracer: "Tracer", msg: dict, role: str,
                host: Optional[str] = None, port: Optional[int] = None,
                **ident) -> dict:
    """The `trace` RPC reply shared by the serving replica, the fleet
    router, and the pserver shard — trace_dump --pull depends on the
    three agreeing.  Applies a live `enable` flip BEFORE the snapshot
    (so enable:false returns the spans it just froze), stamps process
    identity (extra keyword fields like shard= ride along) plus a
    perf_counter/unix clock sample for ping-RTT alignment, and ships
    the retained ring with its accounting."""
    if isinstance(msg.get("enable"), bool):
        tracer.enabled = msg["enable"]
    proc = process_info(role, host, port)
    proc.update(ident)
    return {"type": "trace", "id": msg.get("id"),
            "process": proc,
            "clock": {"perf_counter": time.perf_counter(),
                      "unix": time.time()},
            "enabled": tracer.enabled,
            "recorded": tracer.recorded,
            "dropped": tracer.dropped,
            "spans": tracer.snapshot()}


def flush_trace_file(tracer: "Tracer", path: str, role: str,
                     host: Optional[str] = None,
                     port: Optional[int] = None, **ident) -> int:
    """Write `tracer`'s retained ring to `path` as JSONL with the
    leading `{"meta": {"process": ...}}` identity line, and note the
    count on stderr — the flush-on-every-exit-path discipline shared by
    serve.py, fleet_router.py, pserver.py, and train_dist.py.  Extra
    keyword fields (rank=, shard=) ride in the identity record so
    trace_dump --merge can name the track."""
    proc = process_info(role, host, port)
    proc.update(ident)
    n = tracer.export_jsonl(path, meta={"process": proc})
    print(f"wrote {n} spans to {path} ({tracer.dropped} dropped by "
          f"ring wrap); stitch with tools/trace_dump.py --merge",
          file=sys.stderr, flush=True)
    return n


class _NullSpan:
    """Shared no-op context manager: `annotation()` outside a session."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


def annotation(name: str):
    """The profiler sink alone: a `jax.profiler.TraceAnnotation` while a
    session runs, else a no-op.  For a per-token site (`pt.loop.send`),
    where a ring record a frame would wrap the bounded ring in seconds."""
    cls = _annotation or _annotation_cls()
    return cls(name) if cls is not None and cls.is_enabled() else _NULL


class _Span:
    """Context manager for one phase span: enters the profiler annotation,
    keeps the thread's open-span stack, and on exit feeds the ring (while
    the tracer is enabled) and `sink` (a callable taking the seconds — a
    Stat's add, a BarrierTimer window's append) from ONE clock pair."""

    __slots__ = ("tracer", "name", "track", "attrs", "sink", "t0", "ann")

    def __init__(self, tracer, name, track, attrs, sink=None):
        self.tracer = tracer
        self.name = name
        self.track = track
        self.attrs = attrs
        self.sink = sink

    def __enter__(self):
        try:
            _open.names.append(self.name)
        except AttributeError:
            _open.names = [self.name]
        cls = _annotation or _annotation_cls()
        if cls is not None and cls.is_enabled():     # a session runs
            self.ann = cls(self.name, **self.attrs) if self.attrs \
                else cls(self.name)
            self.ann.__enter__()
        else:
            self.ann = None
        self.t0 = time.perf_counter() \
            if self.sink is not None or self.tracer.enabled else 0.0
        return self

    def __exit__(self, *exc):
        if self.t0:
            dur = time.perf_counter() - self.t0
            if self.sink is not None:
                self.sink(dur)
            self.tracer.add(self.name, self.t0, dur, track=self.track,
                            attrs=self.attrs)
        if self.ann is not None:
            self.ann.__exit__(*exc)
        names = _open.names
        if names[-1] is self.name:
            names.pop()
        else:                    # a begin()/end() pair closed out of order
            _close(self.name)
        return False


def _close(name: str) -> None:
    """Drop the innermost `name` from the thread's open spans."""
    names = _open_spans()
    if name in names:
        del names[len(names) - 1 - names[::-1].index(name)]


class Tracer:
    """Bounded-ring span recorder.  One writer thread; see module note."""

    def __init__(self, capacity: int = 16384):
        self.capacity = int(capacity)
        assert self.capacity > 0
        self.enabled = False
        self._ring: list = []          # grows to capacity, then wraps
        self._n = 0                    # spans ever recorded (monotonic)

    # -- recording (owner thread) -----------------------------------------
    def add(self, name: str, ts: float, dur: float, track: str = "main",
            attrs: Optional[dict] = None, instant: bool = False) -> None:
        """Record one completed span (ts/dur in perf_counter seconds).

        Designed single-writer (the pump/trainer thread).  An occasional
        add from a sibling thread (the trainer's h2d prefetch lane) is
        GIL-safe — list ops never tear — but a racing pair may overwrite
        one span; tracing tolerates a lost sample, so no lock is paid on
        the per-step hot path."""
        if not self.enabled:
            return
        rec = (self._n, name, track, ts, dur, attrs,
               True if instant else False)
        if len(self._ring) < self.capacity:
            self._ring.append(rec)
        else:
            self._ring[self._n % self.capacity] = rec
        self._n += 1

    def span(self, name: str, track: str = "main", sink=None, **attrs):
        """``with tracer.span("pt.step.dispatch", track="engine", rows=64):``
        — one timed phase of the calling thread, fed to both sinks: the
        profiler annotation while a session runs, the ring while enabled.
        `sink(seconds)`, when given, is fed from the same clock pair (how
        global_stat / BarrierTimer sites are timed once)."""
        return _Span(self, name, track, attrs or None, sink)

    def begin(self, name: str, track: str = "main", sink=None, **attrs):
        """Open a span that a LATER call on the SAME thread (possibly in
        another method) closes via end(); spans opened in between must be
        closed first.  Returns an opaque handle."""
        sp = _Span(self, name, track, attrs or None, sink)
        sp.__enter__()
        return sp

    def end(self, handle, **extra_attrs) -> None:
        if handle is None:
            return
        if extra_attrs:
            # the ring's record only: the profiler took its attrs at begin
            handle.attrs = dict(handle.attrs or (), **extra_attrs)
        handle.__exit__(None, None, None)

    def instant(self, name: str, track: str = "main", **attrs) -> None:
        """Zero-duration marker (preempt, done, cancelled)."""
        if not self.enabled:
            return
        self.add(name, time.perf_counter(), 0.0, track=track,
                 attrs=attrs or None, instant=True)

    # -- reading / export (any thread) ------------------------------------
    @property
    def recorded(self) -> int:
        """Spans ever recorded (monotonic, includes overwritten)."""
        return self._n

    @property
    def dropped(self) -> int:
        """Spans overwritten by ring wrap-around."""
        return max(0, self._n - self.capacity)

    def clear(self) -> None:
        self._ring = []
        self._n = 0

    def snapshot(self) -> list[dict]:
        """Retained spans, oldest first, as dicts — the JSONL record
        shape.  Copies under the GIL; safe concurrent with recording
        (a span landing mid-copy may or may not appear, never torn)."""
        recs = sorted(list(self._ring))          # seq-first tuples
        return [{"seq": r[0], "name": r[1], "track": r[2],
                 "ts": r[3], "dur": r[4],
                 **({"attrs": r[5]} if r[5] else {}),
                 **({"instant": True} if r[6] else {})}
                for r in recs]

    def export_jsonl(self, path: str, meta: Optional[dict] = None) -> int:
        """Write retained spans as JSON-lines; returns the span count.
        `meta` (e.g. {"process": process_info(...)}) prepends one
        identity record — tools/trace_dump.py skips it when summarizing
        and uses it to label the process track when merging."""
        spans = self.snapshot()
        with open(path, "w") as f:
            if meta:
                f.write(json.dumps({"meta": meta},
                                   separators=(",", ":")) + "\n")
            for s in spans:
                f.write(json.dumps(s, separators=(",", ":")) + "\n")
        return len(spans)

    def chrome_trace(self) -> dict:
        """Chrome trace_event JSON object (Perfetto-loadable)."""
        return spans_to_chrome(self.snapshot())

    def export_chrome(self, path: str) -> int:
        spans = self.snapshot()
        with open(path, "w") as f:
            json.dump(spans_to_chrome(spans), f)
        return len(spans)


def spans_to_chrome(spans: list[dict]) -> dict:
    """JSONL-shaped span records -> Chrome trace_event JSON.

    Each track becomes a tid with a thread_name metadata event; complete
    spans are "X" events, instants are "i" (thread-scoped).  Times convert
    from perf_counter seconds to integer-friendly microseconds, rebased to
    the earliest span so the viewer opens at t=0."""
    events = _chrome_events(spans, pid=os.getpid(),
                            t_base=min((s["ts"] for s in spans),
                                       default=0.0))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def _chrome_events(spans: list[dict], pid: int, t_base: float,
                   offset_s: float = 0.0,
                   process_name: Optional[str] = None) -> list[dict]:
    """One source's spans as Chrome events under process `pid`, with its
    clock offset applied (local = source ts + offset) and all times
    rebased to `t_base` (already in the merged/local timebase)."""
    tids: dict[str, int] = {}
    events: list[dict] = []
    if process_name:
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": process_name}})
    for s in spans:
        track = s.get("track", "main")
        tid = tids.get(track)
        if tid is None:
            tid = tids[track] = len(tids) + 1
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": track}})
        ev = {"name": s["name"], "pid": pid, "tid": tid,
              "ts": round((s["ts"] + offset_s - t_base) * 1e6, 3),
              "cat": track.split(":", 1)[0]}
        if s.get("attrs"):
            ev["args"] = s["attrs"]
        if s.get("instant"):
            ev["ph"] = "i"
            ev["s"] = "t"
        else:
            ev["ph"] = "X"
            ev["dur"] = round(s["dur"] * 1e6, 3)
        events.append(ev)
    return events


def merge_chrome(sources: list[dict]) -> dict:
    """Stitch span sets from SEVERAL processes into one Chrome trace.

    Each source is {"spans": [...], "process": {...}|None,
    "offset_s": float} — spans in that process's perf_counter timebase,
    `offset_s` mapping them onto the merger's timebase (local ≈ remote +
    offset; 0.0 for local files).  Every source becomes its own process
    track group (synthetic pids — two replicas on one host, or an
    in-process test fleet, must not collapse into one group), named from
    its process identity; all events rebase to the earliest aligned span
    so the merged trace opens at t=0 with the processes side by side."""
    t_base = min((s["ts"] + src.get("offset_s", 0.0)
                  for src in sources for s in src.get("spans", ())),
                 default=0.0)
    events: list[dict] = []
    for i, src in enumerate(sources):
        proc = src.get("process") or {}
        name = " ".join(
            str(x) for x in (proc.get("role"), proc.get("addr"),
                             f"pid={proc['pid']}" if "pid" in proc
                             else None, src.get("label"))
            if x) or f"process-{i + 1}"
        events.extend(_chrome_events(
            src.get("spans", []), pid=i + 1, t_base=t_base,
            offset_s=float(src.get("offset_s", 0.0)), process_name=name))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


#: the process-global tracer every subsystem records into by default —
#: serving engine spans, trainer barrier windows, pass/eval spans.  Off
#: until something (tools/serve.py --trace-out, the benchmark's phase probe,
#: a test) flips `.enabled`.
_tracer = Tracer()


def get_tracer() -> Tracer:
    return _tracer
