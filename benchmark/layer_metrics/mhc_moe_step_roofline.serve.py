"""Share of the chip's roofline the WHOLE step of the hyper-connected
latent-attention MoE model reaches: the least time the traced slice's
steps could take — the larger of the bytes they must move over the HBM's
rate and the operations they must do over the MXU's — over the first
device's busy time in the slice.  The steps are the trace's own
(`pt.step.decode` and `pt.step.mixed` spans); what a step carries — rows,
prompt rows, attended contexts, fetched tokens, the share of steps that
are mixed — is the engine's counters' growth around the slice
(benchmark/lib/mhc_latent_moe.py:slice_cost, which says why it is scaled
to the trace's steps: the weights read once a step — the routed experts
that drew a pair at each kind's rows —, the latent rows the kernel fetched
in every layer, a tile's shared walk once; two operations a weight a row,
latent attention's absorbed form on the contexts the rows attended; the
stream passes in neither, `step_cost` says why).  The larger is taken of
the slice's totals: the counters do not split contexts by step kind, and
the totals' larger is at most the sum of each kind's (equal where both
kinds are bound by the same peak, as this cell's are by the HBM).  Padding
rows are work the chip did and the share does not count.  A program
without the counters has nothing to read."""
from benchmark.lib import arith, mhc_latent_moe
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
    c = mhc_latent_moe.slice_cost(ctx, steps)
    if c is None:
        return None
    by_hbm = c["bytes"] / ctx.peaks["hbm_bytes_per_s"]
    by_mxu = c["flops"] / ctx.peaks["bf16_flops"]
    busy = ctx.trace_data.busy_s()
    log(f"MHC MOE STEP {steps} steps traced ({c['steps_counted']:.0f} "
        f"counted, {100 * c['mixed_share']:.0f}% mixed of "
        f"{c['chunk_rows']:.0f} prompt rows beside {c['decode_rows']:.1f} "
        f"decode rows; a step attends {c['attended']:.0f} and fetches "
        f"{c['fetched']:.0f} tokens a layer); least {by_hbm:.3f}s by the "
        f"HBM, {by_mxu:.3f}s by the MXU; busy {busy:.3f}s")
    return arith.check_share("mhc_moe_step_roofline.serve",
                             100.0 * max(by_hbm, by_mxu) / busy)
