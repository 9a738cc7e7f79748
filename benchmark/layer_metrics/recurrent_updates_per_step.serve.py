"""Slot states ONE recurrent layer read and wrote in ONE compiled step, on
average: the engine's process-wide counters
serving_recurrent_slot_updates_total / (serving_recurrent_steps_total x KDA
layers), benchmark/lib/hybrid_linear.py — the rows that really moved a
state (a paused or empty slot moves none, a prompt chunk's segment moves
one whatever its length).  Near the cell's 128 slots when every slot
decodes.  Cumulative over the process: warm-up and ramp are in it.  A
program without the counters has nothing to read."""
from benchmark.lib import hybrid_linear

LAYER = "serving engine"
UNIT = "count"
MOVES = "output_tokens_per_s"


def read(ctx):
    return hybrid_linear.updates_per_step(ctx.cfg)
