"""Mean routed (token, expert) pairs ONE held expert draws in ONE MoE
layer's call, in the cell that holds every expert: the engine's
process-wide counters serving_moe_pairs_total / (serving_moe_steps_total x
experts held x MoE layers), benchmark/lib/latent_moe.py — the quantity of
moe_pairs_per_expert.serve under a name of its own, because that metric's
list is held to the GigaChat cell by its test.  16 when all 256 slots
decode (256 rows x 4 picks / 64 experts: the deployment's own load); a
mixed step's chunk rows add theirs.  Cumulative over the process: warm-up
and ramp are in it, drawn from the same mix.  A program without the
counters has nothing to read."""
from benchmark.lib import latent_moe

LAYER = "graph and ops"
UNIT = "count"
MOVES = "output_tokens_per_s"


def read(ctx):
    return latent_moe.pairs_per_expert(ctx.cfg)
