"""Share of the flash kernels' grid steps that do a tile's matmuls: the
program's flash_live_tiles_total over flash_grid_steps_total, summed over
the three kernels (paddle_tpu/obs/metrics.py process_counters; counted once
for each kernel call traced into a program, so warm-up and window weigh the
same).  A causal T 4,096 reads 51.6% at 128 x 128 blocks (528 of 1,024
tiles), 56.3% at 512 x 512 (36 of 64); a bounded inner axis would read 100.
A program without the counters has nothing to read."""

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    try:
        from paddle_tpu.obs.metrics import process_counters
    except ImportError:
        return None
    c = process_counters().snapshot()
    steps = sum(v for k, v in c.items()
                if k.startswith("flash_grid_steps_total"))
    if not steps:
        return None
    live = sum(v for k, v in c.items()
               if k.startswith("flash_live_tiles_total"))
    return 100.0 * live / steps
