"""Slot states ONE Mamba-2 layer read and wrote in ONE compiled step, on
average: the engine's process-wide recurrent counters
serving_recurrent_slot_updates_total / (serving_recurrent_steps_total x
Mamba-2 layers), benchmark/lib/ssm_moe.py — the rows that really moved a
slot's state (a paused or empty slot moves none, a prompt chunk's segment
moves one whatever its length).  Near the cell's 256 slots when every slot
decodes; each is 2 x 2 MiB through HBM a layer.  Cumulative over the
process: warm-up and ramp are in it.  A program without the counters has
nothing to read."""
from benchmark.lib import ssm_moe

LAYER = "serving engine"
UNIT = "count"
MOVES = "output_tokens_per_s"


def read(ctx):
    return ssm_moe.updates_per_step(ctx.cfg)
