"""Convolution tails ONE short-conv layer wrote in ONE compiled step, on
average: the engine's process-wide recurrent counters
serving_recurrent_slot_updates_total / (serving_recurrent_steps_total x conv
layers), benchmark/lib/conv_moe.py — the rows that really moved a slot's
tail (a paused or empty slot moves none, a prompt chunk's segment writes
one whatever its length).  Near the cell's 256 slots when every slot
decodes.  Cumulative over the process: warm-up and ramp are in it.  A
program without the counters has nothing to read."""
from benchmark.lib import conv_moe

LAYER = "serving engine"
UNIT = "count"
MOVES = "output_tokens_per_s"


def read(ctx):
    return conv_moe.updates_per_step(ctx.cfg)
