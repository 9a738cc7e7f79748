"""Load imbalance over the held experts: each step's busiest expert's routed
pairs over the mean expert's, both summed over the MoE layers and the steps
(serving_moe_pairs_max_total / (serving_moe_pairs_total / experts held)).
1 = even; the expert product waits for its fullest group.  Cumulative over
the process, as moe_pairs_per_expert.serve."""
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
