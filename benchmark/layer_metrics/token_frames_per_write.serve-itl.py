"""token_frames_per_write.serve in the cells judged on the inter-token gap
alone (benchmark/lib/token_frames.py).  Below the knee a step banks a
handful of tokens, so the writes have little to coalesce: the control for
the saturated cells' reading."""
from benchmark.lib import token_frames

LAYER = "serving engine"
UNIT = "count"
MOVES = "itl_p95_ms"


def read(ctx):
    return token_frames.per_write()
