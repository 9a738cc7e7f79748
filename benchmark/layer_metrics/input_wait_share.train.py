"""Share of the window the trainer loop spent waiting for its next batch:
the benchmark's own timer around the iterator it hands to train_one_pass."""
LAYER = "trainer loop"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    if "input_wait_s" not in ctx.spans:
        return None
    return 100.0 * ctx.spans["input_wait_s"] / ctx.spans["window_s"]
