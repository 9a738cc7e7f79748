"""The serving server's token-delivery counters (paddle_tpu/obs/metrics.py
process_counters): serving_token_frames_total, the streamed token frames
written to client connections, and serving_frame_writes_total, the transport
writes that carried them.  Process-wide and cumulative (warm-up and ramp are
in them), so they are read after the server has stopped."""

from __future__ import annotations


def per_write():
    """Token frames a transport write carried, on average, or None where
    the program has no such counters (a parent commit) or wrote nothing."""
    try:
        from paddle_tpu.obs.metrics import process_counters
    except ImportError:
        return None
    c = process_counters().snapshot()
    writes = c.get("serving_frame_writes_total")
    if not writes:
        return None
    return c.get("serving_token_frames_total", 0) / writes
