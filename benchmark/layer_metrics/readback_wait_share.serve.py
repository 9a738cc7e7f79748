"""Share of the window, outside the profiler's slice, that the pump thread
spent in `pt.step.readback`: waiting for the device to hand a step's tokens
over (benchmark/lib/step_clock.py).  High means the chip is the limit; low
with work to do means the host is.  A program without the step clock's
counters reads nothing."""
from benchmark.lib import step_clock

LAYER = "serving engine"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    w = step_clock.window(ctx)
    return None if w is None else w.share(w.span_s("pt.step.readback"))
