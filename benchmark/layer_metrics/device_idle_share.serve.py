"""1 - (union of the device's op intervals) / traced window."""
LAYER = "device"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    if ctx.trace_data is None:
        return None
    return 100.0 * (1.0 - ctx.trace_data.busy_s() / ctx.trace_window_s)
