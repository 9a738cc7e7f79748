"""Share of the window, outside the profiler's slice, that the pump thread
spent in `pt.pump.wait`: idle because no request was there to serve
(benchmark/lib/step_clock.py).  Near the idle share in an open-loop cell under
its knee, near 0 in a saturated one.  A program without the step clock's
counters reads nothing."""
from benchmark.lib import step_clock

LAYER = "serving engine"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    w = step_clock.window(ctx)
    return None if w is None else w.share(w.span_s("pt.pump.wait"))
