"""Device idle time while the pump thread was in `pt.step.emit` (banking the
step's tokens, the on_token / on_finish callbacks, retiring), % of the
traced window: benchmark/lib/phases.py.

A traced run's reading, and it reads HIGH: the profiler's Python tracer, on
in every `--trace 1` run, slows the host phases it times (`pt.step.emit`
10.0 ms traced against 4.0 ms in the ring with no profiler; decode-
saturated, PERF.md section 6 PR 26 (b)/(c)), so this share overstates the
untraced one about 2x.  Rank phases by this share; size a repair from the
ring (`benchmark/phase_probe.py --ring 1`): there the host holds the chip
8.2 of a 36.8 ms decode step, 22% against the 30.9% traced."""
from benchmark.lib.phases import Phases

LAYER = "serving engine"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    ph = Phases.of(ctx, "serve")
    return None if ph is None else ph.idle_share("emit")
