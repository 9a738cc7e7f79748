"""Roofline share of the Gated DeltaNet prompt-chunk kernel (`gdn_seg`,
ops/pallas_kda_seg.py: the chunkwise form with a decay a head, one call a
layer for all of a mixed step's runs): the least time the chip could take
for the chunks the calls folded — a chunk's products and solve at 96 x 192
a head at ONE MXU pass each, its rows in and out, a run's state read and
written once (benchmark/lib/gdn_mha_dense.py:gdn_seg_cost) — over the
kernel's summed device time in the traced slice.  The chunks and runs are
the engine's own counts over the slice's stretch
(`serving_recurrent_segment_chunks_total`, and the states moved that no
decode row moved), scaled to the calls the trace holds.  The kernel
runs its products at full float32 precision (six passes) and its solve on
the VPU, so the share is small by construction: it is the distance a
faster form would close.  The pattern is the kernel's own name.  A trace
without the kernel, or a program without the counters, has nothing to
read."""
from benchmark.lib import arith, gdn_mha_dense
from benchmark.lib.common import log
from benchmark.lib.trace import TraceError

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
PATTERN = r"gdn_seg.*\[tpu_custom_call\]"


def read(ctx):
    if ctx.trace_data is None:
        return None
    n = gdn_mha_dense.seg_counts(ctx)
    n_gdn, _ = gdn_mha_dense.mixer_layers(ctx.cfg)
    if n is None or not n_gdn:
        return None
    try:
        k = ctx.trace_data.kernel(PATTERN)
    except TraceError as e:
        log(f"KERNEL gdn_seg: {str(e)[:200]}")
        return None
    # a call a layer a mixed step: the trace's calls over the counted steps
    steps = k["calls"] / n_gdn
    chunks = n["chunks"] / n["mixed"] * steps
    cost = gdn_mha_dense.gdn_seg_cost(ctx.cfg, chunks,
                                      n["runs"] / n["mixed"] * steps)
    r = arith.roofline_share(n_gdn * cost["flops"], n_gdn * cost["bytes"],
                             k["seconds"], ctx.peaks)
    heads = gdn_mha_dense.gdn_dims(ctx.cfg)[0]
    log(f"KERNEL gdn_seg: {k['calls']:.0f} calls, {k['seconds']:.4f}s, "
        f"{n['chunks'] / n['mixed']:.1f} chunks in "
        f"{n['runs'] / n['mixed']:.2f} runs a call, "
        f"{1e6 * k['seconds'] / (n_gdn * chunks * heads):.2f} us a head a "
        f"chunk, {r['bound']}-bound")
    return arith.check_share("gdn_seg_roofline.serve", r["share_pct"])
