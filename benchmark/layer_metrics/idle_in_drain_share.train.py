"""Device idle time while the trainer loop drained its buffered losses
(`pt.train.drain`: the stack, its compile, the host sync), % of the traced
window: benchmark/lib/phases.py."""
from benchmark.lib.phases import Phases

LAYER = "trainer loop"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    ph = Phases.of(ctx, "train")
    return None if ph is None else ph.idle_share("drain")
