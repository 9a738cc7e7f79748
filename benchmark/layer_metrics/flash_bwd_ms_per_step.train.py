"""Summed device time of the two flash backward kernels (`flash_bwd_dq`,
`flash_bwd_dkv`) over the `pt.train.step` spans in the trace: ms a train
step and chip.  The kernels carry their own names (ops/pallas_attention.py `name=`), under shard_map
too: benchmark/lib/phases.py."""
from benchmark.lib.phases import kernel_ms_per_step

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s_per_chip"
PATTERN = r"flash_bwd_(dq|dkv).*\[tpu_custom_call\]"


def read(ctx):
    return kernel_ms_per_step(ctx, PATTERN)
