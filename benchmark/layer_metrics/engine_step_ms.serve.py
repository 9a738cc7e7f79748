"""Median duration of a busy engine.step() in the window: the benchmark's
own span around the instance's step (host clock, read-back included)."""
from benchmark.lib import arith

LAYER = "serving engine"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(ctx):
    steps = ctx.spans.get("engine_step_s")
    if not steps:
        return None
    return 1e3 * arith.percentile(steps, 50)
