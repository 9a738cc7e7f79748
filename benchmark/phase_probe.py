#!/usr/bin/env python3
"""By hand, through the chip tool: one run of a cell as benchmark/run.py
makes it, with the program's ring tracer on and/or the profiler on, then the
duration of each `pt.` phase span from either sink — what the host does in a
step with and without the profiler (PERF.md section 6, PR 26).

  python3 benchmark/phase_probe.py --ring 1 --workload <cell> --seed <n> \
      --seconds 40 --trace 0        # ring on, profiler off
  python3 benchmark/phase_probe.py --ring 0 --workload <cell> --seed <n> \
      --seconds 40 --trace 1        # the spans of the profiler trace

Prints run.py's lines, then `COMPILES` (obs/compile_watch.py's snapshot: the
run's compiles by wrapped site and by the span an eager one happened in) and
one `PHASE_RING` and/or `PHASE_PROFILER` line: {span: {"n", "p50_ms",
"p95_ms", "sum_s"}}, a serving step's phases apart by the kind of their step
(`pt.step.emit@decode`).  The ring's spans are those of the whole run
(warm-up and ramp included), the profiler's those of the traced slice.  `--dump <file.json>` also writes the traced slice as
lib/trace.py's plain structure — the device planes whole, of the host planes
the `pt.` and `bench.` events — from which the recorded cuts of
tests/benchmark/data/*_pt_spans.json were taken.  The benchmark's own runs
do not run it."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


KINDS = ("pt.step.decode", "pt.step.mixed", "pt.step.scan", "pt.step.spec")


def summarize(durations: dict) -> dict:
    from benchmark.lib import arith

    return {name: {"n": len(ds),
                   "p50_ms": 1e3 * arith.percentile(ds, 50),
                   "p95_ms": 1e3 * arith.percentile(ds, 95),
                   "sum_s": sum(ds)}
            for name, ds in sorted(durations.items())}


def by_kind(spans: list) -> dict:
    """(start_s, dur_s, name) spans of one sink -> {span name: summary},
    the serving step's phases apart by the kind of their step
    (`pt.step.emit@decode`): each phase belongs to the `pt.engine.step` that
    holds it, `pt.pump.commands` to the step that follows it; a step that
    ran no compiled program is `@idle`."""
    spans = sorted(spans)
    steps = [(s, s + d) for s, d, n in spans if n == "pt.engine.step"]
    kind_of = {}
    for s, d, n in spans:
        if n in KINDS:
            i = _holder(steps, s)
            if i is not None:
                kind_of[i] = n.rsplit(".", 1)[1]
    out: dict = {}
    for s, d, n in spans:
        key = n
        if n.startswith(("pt.step.", "pt.engine.", "pt.pump.commands",
                         "pt.kv.")):
            i = _holder(steps, s, following=n.startswith("pt.pump."))
            key = f"{n}@{kind_of.get(i, 'idle')}"
        out.setdefault(key, []).append(d)
    return summarize(out)


def _holder(steps: list, t: float, following: bool = False):
    """Index of the step interval that holds `t` (or, `following`, of the
    first one that starts after it)."""
    import bisect

    i = bisect.bisect_right(steps, (t, float("inf")))
    if following:
        return i if i < len(steps) else None
    return i - 1 if i and t < steps[i - 1][1] else None


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ring", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default="")
    own, rest = ap.parse_known_args(argv[1:])      # the rest is run.py's
    ring, dump = own.ring, own.dump
    cell = rest[rest.index("--workload") + 1]

    from benchmark import run
    from paddle_tpu.obs.trace import get_tracer

    tracer = get_tracer()
    if ring:
        tracer.capacity = 1 << 19          # the whole run, nothing wrapped
        tracer.enabled = True
    rc = run.main(rest)
    from paddle_tpu.obs.compile_watch import get_compile_watch
    print("COMPILES " + json.dumps(get_compile_watch().snapshot()),
          flush=True)
    if ring:
        spans = [(s["ts"], s["dur"], s["name"]) for s in tracer.snapshot()
                 if s["name"].startswith("pt.") and not s.get("instant")]
        print("PHASE_RING " + json.dumps(
            {"dropped": tracer.dropped, "spans": by_kind(spans)}),
            flush=True)
    if "--trace" in rest and rest[rest.index("--trace") + 1] == "1":
        from benchmark.lib.phases import span_events
        from benchmark.lib.trace import Trace, find_xplane

        tr = Trace.from_xplane(find_xplane(
            os.path.join(ROOT, ".bench_out", cell, "trace")),
            cpu_as_device="--rehearse" in rest)
        spans = [(s / 1e9, (e - s) / 1e9, name)
                 for s, e, name in span_events(tr)]
        print("PHASE_PROFILER " + json.dumps({"spans": by_kind(spans)}),
              flush=True)
        if dump:
            from benchmark.lib.trace import HOST_PLANE
            planes = {
                p: {ln: [e for e in evs
                         if e[0].startswith(("pt.", "bench."))]
                    for ln, evs in lines.items()} if HOST_PLANE.match(p)
                else lines for p, lines in tr.planes.items()}
            os.makedirs(os.path.dirname(os.path.abspath(dump)), exist_ok=True)
            with open(dump, "w") as f:
                json.dump(planes, f)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv))
