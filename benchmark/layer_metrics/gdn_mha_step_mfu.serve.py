"""Share of the chip's peak the WHOLE step of the Gated DeltaNet /
full-attention model reaches: the least time the traced slice's steps
could take — the larger of the bytes they must move over the HBM's rate
and the operations they must do over the MXU's bf16 peak — over the first
device's busy time in the slice.  The steps are the trace's own
(`pt.step.decode` and `pt.step.mixed` spans); what a step carries — rows,
prompt rows, attended contexts, fetched tokens, states moved, the share of
steps that are mixed — is the engine's counters' growth around the slice,
scaled to the trace's steps (benchmark/lib/gdn_mha_dense.py:slice_cost):
the weights read once a step, the K and V the paged kernel fetched in each
full layer (a tile's shared walk once), the linear layers' published state
read and written a row that advanced; two operations a weight a row (the
head on the sampled rows alone), attention's 4 H head a token attended.
The delta rule's own arithmetic is in neither (VPU work: `step_cost` says
why), so the share is a lower bound.  The larger is taken of the slice's
totals, as mhc_moe_step_roofline.serve does.  Padding rows are work the
chip did and the share does not count.  A program without the counters has
nothing to read."""
from benchmark.lib import arith, gdn_mha_dense
from benchmark.lib.common import log
from benchmark.lib.phases import Phases

LAYER = "graph and ops"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    ph = Phases.of(ctx, "serve")
    if ph is None:
        return None
    steps = sum(len(ph.durations(n)) for n in ("pt.step.decode",
                                               "pt.step.mixed")
                if n in ph.names)
    c = gdn_mha_dense.slice_cost(ctx, steps)
    if c is None:
        return None
    by_hbm = c["bytes"] / ctx.peaks["hbm_bytes_per_s"]
    by_mxu = c["flops"] / ctx.peaks["bf16_flops"]
    busy = ctx.trace_data.busy_s()
    log(f"GDN MHA STEP {steps} steps traced ({c['steps_counted']:.0f} "
        f"counted, {100 * c['mixed_share']:.0f}% mixed of "
        f"{c['chunk_rows']:.0f} prompt rows beside {c['decode_rows']:.1f} "
        f"decode rows; a step attends {c['attended']:.0f} and fetches "
        f"{c['fetched']:.0f} tokens a full layer and moves "
        f"{c['state_rows']:.1f} states a linear layer); least "
        f"{by_hbm:.3f}s by the HBM, {by_mxu:.3f}s by the MXU; busy "
        f"{busy:.3f}s")
    return arith.check_share("gdn_mha_step_mfu.serve",
                             100.0 * max(by_hbm, by_mxu) / busy)
