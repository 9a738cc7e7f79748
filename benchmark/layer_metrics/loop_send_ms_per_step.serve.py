"""The LOOP thread's seconds encoding and writing frames
(`serving_loop_send_seconds_total`: what `pt.loop.send` annotates) over the
steps landed, in the window outside the profiler's slice
(benchmark/lib/step_clock.py): what shares the interpreter with the pump's
planner.  A program without the step clock's counters reads nothing."""
from benchmark.lib import step_clock

LAYER = "serving engine"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(ctx):
    w = step_clock.window(ctx)
    return None if w is None else w.per_step_ms(
        w.growth.get("serving_loop_send_seconds_total", 0.0))
