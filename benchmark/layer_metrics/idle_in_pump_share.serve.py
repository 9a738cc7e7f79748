"""Device idle time while the pump thread was outside `pt.engine.step`: the
heartbeat and command drain (`pt.pump.commands`) and the idle wait
(`pt.pump.wait`), % of the traced window: benchmark/lib/phases.py."""
from benchmark.lib.phases import Phases

LAYER = "serving engine"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    ph = Phases.of(ctx, "serve")
    return None if ph is None else ph.idle_share("pump")
