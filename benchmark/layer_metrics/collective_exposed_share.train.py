"""Share of the traced window in which a collective holds the core and no
compute runs (only a cell across chips has something to read)."""
LAYER = "parallelism"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    if ctx.trace_data is None or ctx.chips < 2:
        return None
    return 100.0 * ctx.trace_data.collective_s() / ctx.trace_window_s
