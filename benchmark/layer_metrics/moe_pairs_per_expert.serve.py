"""Mean routed (token, expert) pairs ONE held expert draws in ONE MoE
layer's call: the engine's process-wide counters
serving_moe_pairs_total / (serving_moe_steps_total x experts held x MoE
layers), benchmark/lib/latent_moe.py.  How near the cell's per-expert load
is to the deployment's (64 pairs at 64 rows a chip over 32 chips; 2 here).
Cumulative over the process: warm-up and ramp are in it, drawn from the
same mix.  A program without the counters has nothing to read."""
from benchmark.lib import latent_moe

LAYER = "graph and ops"
UNIT = "count"
MOVES = "output_tokens_per_s"


def read(ctx):
    return latent_moe.pairs_per_expert(ctx.cfg)
