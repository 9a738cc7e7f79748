"""Compile observability: per-signature jit compile events + storm detector.

On TPU, compile time is a first-class operational signal (the serving
comparisons in arXiv:2605.25645 treat it on par with throughput): a decode
step that stays at ONE signature is the whole point of the slot engine, and
per-bucket prefill means a handful of deliberate compiles — but a workload
that churns buckets (or a shape bug in a new graph path) turns "a handful"
into a RECOMPILE STORM where the chip spends its time in XLA instead of
serving.  Today that is invisible until tokens/sec craters.  This module
makes every compile an event:

  * `wrap_jit(site, fn)` wraps a jitted callable.  After each call it
    checks the jit cache size — growth means THIS call compiled — and
    records: a span on the `compile` tracer lane (name = site, dur =
    compile + first-run wall time, attrs = signature), a flight-recorder
    event, and `jit_compiles_total` / `jit_compile_seconds` /
    `jit_signatures` samples via `compile_collector()`.  The non-compile
    fast path costs two `_cache_size()` reads and two clock reads — noise
    against a real dispatch.  Attribute access proxies to the wrapped fn,
    so `.lower()` / `._cache_size()` introspection (the HLO
    checks, the serving signature oracles) keeps working.
  * `watch(site, key)` is the context-manager form for compiled paths that
    are not a single jit object (lm_generate's per-(B,P,max_new) scans):
    the first call with a new `key` records a compile event timed over the
    whole call (trace + compile + first run — the honest measurable).
  * the STORM DETECTOR: >= `storm_n` distinct signatures for one site
    inside `storm_window_s` seconds fires a warning once — a
    `recompile_storm` instant on the compile lane, a flight event, and
    `jit_recompile_storms_total` — then stays quiet until the window
    drains (so a sustained storm is one alert, not an alert storm).

  * EVERY backend compile has a site.  Once the process has JAX, the
    watcher listens to `jax.monitoring`'s `backend_compile_duration` (one
    event per executable XLA builds or loads from the cache, on the
    thread that asked).  An event inside a wrapped call belongs to that
    site and sets its count; one outside any — an eager op with a new
    shape: the admission-time `jax.random.split`, the loss drain's
    `jnp.stack` — is counted as `unwrapped` under the innermost open
    `pt.` span of that thread (obs/trace.py), else `(unattributed)`.  So
    `jit_compiles_total` summed over sites accounts for every backend
    compile of the process, and a compile inside a serving window names
    the phase that paid for it.  A site's `compiles` stays what it was — new signatures
    of a step program (each at least 1, more if the call built several
    executables), what the storm detector watches and what a benchmark
    holds to 0 inside its window.

Like the tracer and flight recorder this is a process-global singleton
(`get_compile_watch()`), stdlib-only, and always on: compile events are
rare enough that there is no flag to forget.
"""

from __future__ import annotations

import hashlib
import sys
import threading
import time
from collections import deque

from paddle_tpu.obs.flight import get_flight_recorder
from paddle_tpu.obs.trace import current_span, get_tracer

#: site of a backend compile no wrapped call and no open `pt.` span claims
UNATTRIBUTED = "(unattributed)"

# per thread: the wrapped calls in flight, innermost last, each a
# [site, backend compiles seen so far] frame the listener counts into
_calls = threading.local()
_listening = False


def _frames() -> list:
    try:
        return _calls.frames
    except AttributeError:
        frames = _calls.frames = []
        return frames


def _on_duration(event: str, seconds: float, **_kw) -> None:
    """jax.monitoring listener (runs on the compiling thread)."""
    if not event.endswith("/backend_compile_duration"):
        return
    frames = _frames()
    if frames:
        frames[-1][1] += 1               # the wrapped call accounts for it
    else:
        _watch.record_unwrapped(current_span("pt.") or UNATTRIBUTED, seconds)


def listen() -> bool:
    """Start counting every backend compile (idempotent).  Needs JAX in the
    process already — a wrapped jit or a trainer brings it; the JAX-free
    client path never calls this."""
    global _listening
    if not _listening and "jax" in sys.modules:
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True
        except Exception:                  # noqa: BLE001 — no monitoring API
            pass
    return _listening


def signature_of(args: tuple, kwargs: dict) -> str:
    """A stable short signature for a call's abstract shapes: walks the
    args pytree duck-typed (no jax import — this module loads on the
    dependency-light client path), describing array-ish leaves as
    dtype[shape].  Big pytrees (a params dict) hash down to a digest so
    the signature stays log-line sized."""
    parts: list[str] = []

    def walk(x):
        if isinstance(x, (list, tuple)):
            for v in x:
                walk(v)
        elif isinstance(x, dict):
            for k in sorted(x, key=str):
                walk(x[k])
        elif hasattr(x, "shape") and hasattr(x, "dtype"):
            parts.append(f"{x.dtype}[{','.join(map(str, x.shape))}]")
        elif isinstance(x, (bool, int, float, str)) or x is None:
            parts.append(repr(x))
        else:
            parts.append(type(x).__name__)

    walk(args)
    walk(kwargs)
    full = ";".join(parts)
    if len(full) <= 96:
        return full
    digest = hashlib.md5(full.encode()).hexdigest()[:10]
    return f"{len(parts)} leaves:{digest}:{full[:64]}…"


class _Watch:
    """Context manager for watch(): records on exit iff the key was new,
    or the call built an executable all the same."""

    __slots__ = ("cw", "site", "key", "known", "t0")

    def __init__(self, cw, site, key, known):
        self.cw = cw
        self.site = site
        self.key = key
        self.known = known

    def __enter__(self):
        listen()
        _frames().append([self.site, 0])
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *exc):
        n = _frames().pop()[1]
        if exc_type is None and (n or not self.known):
            self.cw.note(self.site, self.key,
                         time.perf_counter() - self.t0, t0=self.t0,
                         backend=n)
        return False


class _WatchedJit:
    """Callable proxy over one jitted function (see wrap_jit)."""

    __slots__ = ("_fn", "_site", "_cw")

    def __init__(self, fn, site, cw):
        object.__setattr__(self, "_fn", fn)
        object.__setattr__(self, "_site", site)
        object.__setattr__(self, "_cw", cw)

    def __call__(self, *args, **kwargs):
        fn = self._fn
        try:
            n0 = fn._cache_size()
        except Exception:                  # noqa: BLE001 — no cache probe
            n0 = None
        frames = _frames()
        frame = [self._site, 0]
        frames.append(frame)
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kwargs)
        finally:
            frames.pop()
        compiled = False
        if n0 is not None:
            try:
                compiled = fn._cache_size() > n0
            except Exception:              # noqa: BLE001
                pass
        if compiled or frame[1]:
            self._cw.record(self._site, signature_of(args, kwargs),
                            time.perf_counter() - t0, t0=t0,
                            backend=frame[1])
        return out

    def __getattr__(self, name):           # .lower(), ._cache_size(), ...
        return getattr(self._fn, name)


class CompileWatch:
    """Per-site compile accounting + the recompile-storm detector."""

    def __init__(self, storm_n: int = 6, storm_window_s: float = 60.0):
        self.storm_n = int(storm_n)
        self.storm_window_s = float(storm_window_s)
        self._lock = threading.Lock()
        self.compiles: dict[str, int] = {}        # site -> compile count
        self.unwrapped: dict[str, int] = {}       # span -> eager compiles
        self.seconds: dict[str, float] = {}       # site -> wall seconds
        self.storms: dict[str, int] = {}          # site -> storms fired
        self._sigs: dict[str, set] = {}           # site -> distinct sigs
        self._recent: dict[str, deque] = {}       # site -> (t, sig) window
        self._armed: dict[str, bool] = {}         # storm re-arm per site

    def clear(self) -> None:
        with self._lock:
            self.compiles.clear()
            self.unwrapped.clear()
            self.seconds.clear()
            self.storms.clear()
            self._sigs.clear()
            self._recent.clear()
            self._armed.clear()

    # -- instrumentation entry points --------------------------------------
    def wrap_jit(self, site: str, fn) -> _WatchedJit:
        """Wrap a jitted callable; compile events detected by jit-cache
        growth, so repeat signatures cost no signature computation."""
        listen()
        return _WatchedJit(fn, site, self)

    def watch(self, site: str, key) -> _Watch:
        """``with cw.watch("lm_decode.generate", (B, P, max_new)): ...`` —
        records a compile event on exit if `key` is new for the site."""
        with self._lock:
            known = key in self._sigs.get(site, ())
        return _Watch(self, site, key, known)

    def note(self, site: str, key, seconds: float, t0: float = 0.0,
             backend: int = 0) -> None:
        """Record a first-call-for-key event unless the key raced in."""
        with self._lock:
            if key in self._sigs.get(site, ()) and not backend:
                return
        self.record(site, str(key), seconds, t0=t0, raw_key=key,
                    backend=backend)

    # -- the event ---------------------------------------------------------
    def record_unwrapped(self, span: str, seconds: float) -> None:
        """One backend compile outside every wrapped site, in phase `span`
        (an eager op with a new shape).  Counted apart from `compiles`: it
        is no new signature of a step program."""
        with self._lock:
            self.unwrapped[span] = self.unwrapped.get(span, 0) + 1
            self.seconds[span] = self.seconds.get(span, 0.0) + seconds
        get_flight_recorder().record("compile", site=span, sig="(eager)",
                                     seconds=round(seconds, 4))

    def record(self, site: str, sig: str, seconds: float,
               t0: float = 0.0, raw_key=None, backend: int = 0) -> None:
        """A call at `site` with signature `sig` compiled, costing
        `seconds` of wall time (compile + first run).  `backend` is how
        many backend compiles the listener saw inside the call; the site
        counts them all, and at least 1 (a new signature is a compile
        event even where XLA had the executable already)."""
        now = time.perf_counter()
        storm = None
        key = raw_key if raw_key is not None else sig
        with self._lock:
            self.compiles[site] = self.compiles.get(site, 0) + \
                max(1, backend)
            self.seconds[site] = self.seconds.get(site, 0.0) + seconds
            self._sigs.setdefault(site, set()).add(key)
            dq = self._recent.setdefault(site, deque())
            while dq and dq[0][0] < now - self.storm_window_s:
                dq.popleft()
            if not dq:
                self._armed[site] = True   # window drained: re-arm
            dq.append((now, key))
            distinct = len({s for _, s in dq})
            if distinct >= self.storm_n and self._armed.get(site, True):
                self._armed[site] = False
                self.storms[site] = self.storms.get(site, 0) + 1
                storm = distinct
        tracer = get_tracer()
        if tracer.enabled:
            tracer.add(site, t0 or (now - seconds), seconds,
                       track="compile", attrs={"sig": sig})
        flight = get_flight_recorder()
        flight.record("compile", site=site, sig=sig,
                      seconds=round(seconds, 4))
        if storm is not None:
            if tracer.enabled:
                tracer.instant("recompile_storm", track="compile",
                               site=site, signatures=storm,
                               window_s=self.storm_window_s)
            flight.record("recompile_storm", site=site,
                          signatures=storm,
                          window_s=self.storm_window_s)

    # -- reading -----------------------------------------------------------
    def signature_count(self, site: str) -> int:
        with self._lock:
            return len(self._sigs.get(site, ()))

    def snapshot(self) -> dict:
        """{site: {"compiles", "unwrapped", "seconds", "signatures",
        "storms"}} — the postmortem-bundle shape.  A wrapped site has
        `compiles`; a `pt.` span (or `(unattributed)`) has `unwrapped`, the
        eager compiles that happened in that phase; the two summed over
        all rows are the process's backend compiles."""
        with self._lock:
            sites = set(self.compiles) | set(self._sigs) | set(self.unwrapped)
            return {site: {
                "compiles": self.compiles.get(site, 0),
                "unwrapped": self.unwrapped.get(site, 0),
                "seconds": round(self.seconds.get(site, 0.0), 4),
                "signatures": len(self._sigs.get(site, ())),
                "storms": self.storms.get(site, 0),
            } for site in sorted(sites)}


def compile_collector(cw: "CompileWatch" = None):
    """obs.metrics collector: per-site compile counters + signature
    gauges.  One collector instance serves both the serving server's and
    the trainer's registries (the watcher is process-global)."""

    def collect():
        w = cw or _watch
        out = []
        for site, st in w.snapshot().items():
            labels = {"site": site}
            out.append(("jit_compiles_total", "counter", labels,
                        float(st["compiles"] + st["unwrapped"])))
            out.append(("jit_compile_seconds", "counter", labels,
                        float(st["seconds"])))
            out.append(("jit_signatures", "gauge", labels,
                        float(st["signatures"])))
            out.append(("jit_recompile_storms_total", "counter", labels,
                        float(st["storms"])))
        return out

    return collect


#: process-global watcher — every instrumented jit entry point (trainer
#: train/eval steps, serving decode/prefill/pack, lm_generate) records here
_watch = CompileWatch()


def get_compile_watch() -> CompileWatch:
    return _watch
