"""Load imbalance over the held experts in the cell that holds every
expert: each step's busiest expert's routed pairs over the mean expert's,
both summed over the MoE layers and the steps
(serving_moe_pairs_max_total / (serving_moe_pairs_total / experts held)) —
the quantity of moe_load_imbalance.serve under a name of its own, as
moe_pairs_per_expert.serve-wide.  1 = even; a sorted or ragged expert
product waits for its fullest group.  Cumulative over the process."""
from benchmark.lib import latent_moe

LAYER = "graph and ops"
UNIT = "ratio"
MOVES = "output_tokens_per_s"


def read(ctx):
    c = latent_moe.moe_counters()
    if not c.get("serving_moe_pairs_total"):
        return None
    return c["serving_moe_pairs_max_total"] * ctx.cfg["experts_held"] \
        / c["serving_moe_pairs_total"]
