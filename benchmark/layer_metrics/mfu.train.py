"""Model FLOP/s utilisation: the operations a training token needs (6 per
matmul parameter plus causal attention, nothing recomputed counted —
benchmark/lib/arith.py) times tokens/s/chip of the untraced steps, over the
chip's published bf16 peak."""
from benchmark.lib import arith

LAYER = "graph and ops"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    rate = ctx.e2e.get("train_tokens_per_s_per_chip")
    if rate is None:
        return None
    flops = arith.train_flops_per_token(ctx.cfg, ctx.traffic["seq_len"])
    return arith.check_share(
        "mfu.train", 100.0 * flops * rate / ctx.peaks["bf16_flops"])
