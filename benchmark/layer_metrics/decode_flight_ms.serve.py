"""Mean time of a compiled decode step from its launch (its `pt.step.decode`
span's start) to its tokens on the host (its read-back's end):
`serving_step_flight_seconds_total` over `serving_steps_landed_total` of the
kind, in the window outside the profiler's slice (benchmark/lib/step_clock.py).
With one step in flight it runs between one and two step periods: the step
waits behind the one before it, then runs.  A program without the step
clock's counters, or a window without such a step, reads nothing."""
from benchmark.lib import step_clock

LAYER = "serving engine"
UNIT = "ms"
MOVES = "itl_p95_ms"
KIND = "decode"


def read(ctx):
    w = step_clock.window(ctx)
    if w is None or not w.landed(KIND):
        return None
    return 1e3 * w.flight_s(KIND) / w.landed(KIND)
