"""Roofline share of the hyper-connections' stream pass (`mhc_mix`,
ops/pallas_hyper_conn.py: X' = H_res X + H_post^T y, one call a sublayer a
step): the least time the chip could take to move what a call must — a
row's n streams and the sublayer's output in, the n new streams out, (2 n +
1) x 3,584 x 2 B a row, and the row's 24 float32 maps
(benchmark/lib/mhc_latent_moe.py:mix_call_bytes; its n (n + 1) multiply-adds
a column are VPU work and never bound it) — over the kernel's summed device
time in the traced slice.  The rows of a call are the engine's own count
over the slice, padding included since the kernel moves padding rows too
(`serving_mhc_rows_total` over `serving_mhc_calls_total`).  The pattern is
the kernel's own name.  A trace without the kernel, or a program without
the counters, has nothing to read.

The yardstick is benchmark/peaks.json's HBM rate, the only memory that
file knows, and the share goes through `arith.check_share` as every share
does.  **In its own cell it RAISES there**: the calls of a 708-row slice
take 32.8 us where the HBM's 819 GB/s would need 55.9 us (170%; my chip
runs, PR 57).  tools/bench_mhc.py's size sweep — 1,153 GB/s at 1,088 rows,
362-368 GB/s from 4,352 rows on — fits the hypothesis that streams a
sublayer has just written are served from an on-chip memory; no public
rate or size of such a memory is in peaks.json, so the entry stays OWED
(PERF.md section 7 row 20) until a `benchmark` PR brings that peak, and
is not laid into BENCHMARK.json before."""
from benchmark.lib import arith, mhc_latent_moe
from benchmark.lib.common import log
from benchmark.lib.trace import TraceError

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
PATTERN = r"mhc_mix.*\[tpu_custom_call\]"


def read(ctx):
    if ctx.trace_data is None:
        return None
    rows = mhc_latent_moe.rows_per_mix_call(ctx)
    if not rows:
        return None
    try:
        k = ctx.trace_data.kernel(PATTERN)
    except TraceError as e:
        log(f"KERNEL mhc_mix: {str(e)[:200]}")
        return None
    moved = mhc_latent_moe.mix_call_bytes(ctx.cfg, rows) * k["calls"]
    r = arith.roofline_share(
        mhc_latent_moe.mix_call_flops(ctx.cfg, rows) * k["calls"], moved,
        k["seconds"], ctx.peaks)
    log(f"KERNEL mhc_mix: {k['calls']:.0f} calls, {k['seconds']:.4f}s, "
        f"{rows:.1f} rows a call, {moved / k['seconds'] / 1e9:.0f} GB/s, "
        f"{r['bound']}-bound")
    return arith.check_share("mhc_mix_roofline.serve", r["share_pct"])
