"""Mean share of the slots that were live per decode step in the window:
the engine's occupancy_sum / n_decode_steps, after minus before."""
LAYER = "serving engine"
UNIT = "%"
MOVES = "output_tokens_per_s"


def read(ctx):
    occ = ctx.counters.get("occupancy")
    return None if occ is None else 100.0 * occ
