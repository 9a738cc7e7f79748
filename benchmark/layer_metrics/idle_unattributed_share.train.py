"""The rest of the device's idle time: under no span of the input and drain
shares (the dispatch itself, the pass's start and end, the window's edges),
% of the traced window.  With the two it sums to the first device's idle
share: benchmark/lib/phases.py."""
from benchmark.lib.phases import Phases

LAYER = "trainer loop"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    ph = Phases.of(ctx, "train")
    return None if ph is None else ph.idle_unattributed_share()
