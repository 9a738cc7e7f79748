"""Median duration of the engine's own `pt.step.mixed` spans in the traced slice
(the call into the compiled mixed prefill/decode step until the host has read its tokens;
the engine names the span by the step it chose, so nothing is guessed from
counters): benchmark/lib/phases.py."""
from benchmark.lib.phases import median_ms

LAYER = "serving engine"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(ctx):
    return median_ms(ctx, "serve", "pt.step.mixed")
