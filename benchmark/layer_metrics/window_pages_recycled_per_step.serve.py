"""Ring pages ONE window layer wrote over in ONE compiled step, on average:
the engine's process-wide counters serving_window_pages_recycled_total /
(serving_window_steps_total x window layers), benchmark/lib/window_moe.py —
a logical page past the ring's size landing on the page of the one
ring_pages before it (serving/paged_kv.py "WINDOW LAYERS").  With every
slot decoding past its ring's first lap it nears slots / page_size (64 / 16
= 4); a prompt chunk adds a page every page_size rows.  0 while no context
has outgrown its ring: the mechanism idle.  Cumulative over the process:
warm-up and ramp are in it.  A program without the counters has nothing to
read."""
from benchmark.lib import window_moe

LAYER = "serving engine"
UNIT = "count"
MOVES = "output_tokens_per_s"


def read(ctx):
    return window_moe.pages_recycled_per_step(ctx.cfg)
