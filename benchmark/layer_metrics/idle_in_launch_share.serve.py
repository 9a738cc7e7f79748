"""Device idle time while the pump thread was in `pt.step.dispatch` or
`pt.step.readback` (or between them, inside the step's own span): launch
latency, transfers and the device's own gaps between ops — the part no
change to the host loop removes — % of the traced window:
benchmark/lib/phases.py.

A traced run's reading, and it reads a little HIGH: the profiler's Python
tracer, on in every `--trace 1` run, slows the host phases it times
(`pt.step.dispatch` 0.94 ms traced against 0.75 ms in the ring with no
profiler; the read-back is the device's own time; decode-saturated, PERF.md
section 6 PR 26 (b)/(c)).  Rank phases by this share; size a repair from the
ring (`benchmark/phase_probe.py --ring 1`): there the host holds the chip
8.2 of a 36.8 ms decode step, 22% against the 30.9% traced."""
from benchmark.lib.phases import Phases

LAYER = "device"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    ph = Phases.of(ctx, "serve")
    return None if ph is None else ph.idle_share("launch")
