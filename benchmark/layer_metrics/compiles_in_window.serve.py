"""Compilations inside the measured window: every backend compile of the
process (jax.monitoring's backend_compile_duration events, after minus
before), which includes what obs/compile_watch.py's jit_compiles_total counts
at the step programs' sites and also eager ops with a new shape.  Should be
0; `correct` holds the step programs' sites to 0."""
LAYER = "entry points"
UNIT = "count"
MOVES = "itl_p95_ms"


def read(ctx):
    return ctx.counters.get("backend_compiles_in_window",
                            ctx.counters.get("compiles_in_window"))
