"""Streamed token frames a transport write carried, on average
(benchmark/lib/token_frames.py: serving_token_frames_total /
serving_frame_writes_total, read after the server has stopped).  A step's
tokens for one connection leave as one write and the load generator speaks
over one connection, so a saturated cell reads near its 64 slots; a server
that sends a token a write reads 1.  A program without the counters has
nothing to read."""
from benchmark.lib import token_frames

LAYER = "serving engine"
UNIT = "count"
MOVES = "output_tokens_per_s"


def read(ctx):
    return token_frames.per_write()
