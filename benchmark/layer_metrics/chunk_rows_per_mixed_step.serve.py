"""Prompt rows ONE mixed step of the measured window carried, on average:
its rows less padding less decode rows — the growth of the engine's
process-wide counters serving_chunk_rows_total / serving_mixed_steps_total
between the pump's checkpoints at the window's ends, warm-up and ramp left
out (benchmark/lib/mhc_latent_moe.py).  At most max_step_tokens less the
slots (1,088 - 48 = 1,040 in the long-prompt cell): the nearer, the fewer
steps a prompt waits through and the fuller the experts' groups.  A window
without a mixed step, or a program that keeps no such counters or no
checkpoints, has nothing to read."""
from benchmark.lib import mhc_latent_moe

LAYER = "serving engine"
UNIT = "count"
MOVES = "output_tokens_per_s"


def read(ctx):
    return mhc_latent_moe.chunk_rows_per_mixed_step(ctx)
