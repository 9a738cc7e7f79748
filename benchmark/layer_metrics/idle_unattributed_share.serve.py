"""The rest of the device's idle time: under no span of the emit, schedule,
pump and launch shares (another thread held the pump, or the window's edge),
% of the traced window.  With the four it sums to the first device's idle
share: benchmark/lib/phases.py."""
from benchmark.lib.phases import Phases

LAYER = "serving engine"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    ph = Phases.of(ctx, "serve")
    return None if ph is None else ph.idle_unattributed_share()
