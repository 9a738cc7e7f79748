"""Seconds of `pt.kv.evict` — the prefix tree's walk when the allocator runs
dry — over the steps landed, in the window outside the profiler's slice
(benchmark/lib/step_clock.py): 0 in a cell whose pool never fills.  Untraced:
inside the slice the profiler's Python tracer multiplies this pure-Python walk.
A program without the step clock's counters reads nothing."""
from benchmark.lib import step_clock

LAYER = "serving engine"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(ctx):
    w = step_clock.window(ctx)
    return None if w is None else w.per_step_ms(w.span_s("pt.kv.evict"))
