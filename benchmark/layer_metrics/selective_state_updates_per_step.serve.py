"""Slot states ONE Mamba-1 layer read and wrote in ONE compiled step, on
average: the growth of the engine's recurrent counters over the measured
window (benchmark/lib/ssm_dense.py:window_growth),
serving_recurrent_slot_updates_total / (serving_recurrent_steps_total x
Mamba layers) — the rows that really moved a
slot's state (a paused or empty slot moves none, a prompt chunk's run moves
one whatever its length).  Near the cell's 256 slots when every slot
decodes; each is 2 x 327,680 B through HBM a layer.  A program without the
counters or their checkpoints has nothing to read."""
from benchmark.lib import ssm_dense

LAYER = "serving engine"
UNIT = "count"
MOVES = "output_tokens_per_s"


def read(ctx):
    return ssm_dense.updates_per_step(ctx)
