"""The host's own work a compiled step, over the window outside the profiler's
slice (benchmark/lib/step_clock.py): the pump thread's seconds in its command
drain and in `engine.step()` — admit, plan, the launch, emit, the step's own
time — less the read-back, over the steps landed.  What the host must hide
under a device step: where it is longer than the step's flight, the host is
the limit.  A program without the step clock's counters reads nothing."""
from benchmark.lib import step_clock

LAYER = "serving engine"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(ctx):
    w = step_clock.window(ctx)
    return None if w is None else w.per_step_ms(w.host_s())
