"""Metric arithmetic kept with the benchmark: percentiles, burst-shared
inter-token gaps, spreads, and the operations and bytes a kernel or a model
step needs, computed from shapes.  Pure Python / numpy-free on purpose: the
load generator imports it without touching JAX."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (q in 0..100) of a non-empty list."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with Python's statistics.quantiles(n=4) (the driver's rule)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def burst_shared_gaps(times, burst_eps_s: float = 0.0005) -> list[float]:
    """Inter-token gaps of ONE request as its client saw them.

    `times` are the arrival times of its tokens, in order.  Tokens that
    arrive within `burst_eps_s` of the previous one came in the same volley
    (a multi-step decode flush): the volley's leading gap is shared equally
    by its tokens, so a server that emits k tokens every k steps does not
    read k times burstier than one that emits 1 every step (the idea of
    tools/bench_serving.py:run_workload, taken at the client)."""
    gaps: list[float] = []
    i, n = 1, len(times)
    while i < n:
        j = i
        while j + 1 < n and times[j + 1] - times[j] <= burst_eps_s:
            j += 1
        burst = j - i + 1
        share = (times[i] - times[i - 1]) / burst
        # the volley's own (tiny) spread belongs to it too
        share += (times[j] - times[i]) / burst
        gaps.extend([share] * burst)
        i = j + 1
    return gaps


def window_metrics(requests, t0: float, t1: float, eps_s: float) -> dict:
    """What the client saw in [t0, t1).  `requests` are dicts with `due_at`
    and `times` (the arrival time of each token): the output tokens that
    arrived in the window over its length; the 95th percentile of ALL
    burst-shared gaps that ended in it; the 95th percentile (and median) of
    due time to first token over the requests due in it."""
    in_win = lambda t: t0 <= t < t1
    tokens = sum(1 for r in requests for t in r["times"] if in_win(t))
    gaps = []
    for r in requests:
        g = burst_shared_gaps(r["times"], eps_s)
        gaps.extend(x * 1e3 for x, t in zip(g, r["times"][1:]) if in_win(t))
    ttft = [(r["times"][0] - r["due_at"]) * 1e3 for r in requests
            if r["times"] and in_win(r["due_at"])]
    pct = lambda xs, q: percentile(xs, q) if xs else None
    return {"output_tokens_per_s": tokens / (t1 - t0),
            "output_tokens": tokens, "n_gaps": len(gaps),
            "itl_p50_ms": pct(gaps, 50), "itl_p95_ms": pct(gaps, 95),
            "n_ttft": len(ttft),
            "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95)}


# ---------------------------------------------------------------------------
# operations and bytes from shapes
# ---------------------------------------------------------------------------

def lm_matmul_params(cfg: dict) -> dict:
    """Parameters that sit in matrix multiplications, per layer and in the
    head (the embedding is a gather, not a matmul)."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * d + 2 * d * kv + d * d + 2 * d * cfg["intermediate_size"]
    return {"per_layer": per_layer, "head": d * cfg["vocab_size"],
            "total": per_layer * cfg["num_hidden_layers"]
            + d * cfg["vocab_size"]}


def lm_param_count(cfg: dict) -> int:
    """All parameters of the model as the program builds it (untied head,
    one attention bias, LayerNorm scale+bias, biased MLP)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    mm = lm_matmul_params(cfg)
    per_layer = mm["per_layer"] + d + f + d + 4 * d   # biases + two norms
    return (cfg["vocab_size"] * d + mm["head"] + 2 * d
            + per_layer * cfg["num_hidden_layers"])


def attention_flops_per_token(cfg: dict, seq_len: int, causal: bool = True,
                              backward: bool = True) -> float:
    """QK^T and PV of one token against its context, per model: 4*H*D*T
    per layer non-causal, half that on average under a causal mask; the
    backward pass needs 2.5x the forward (dS recompute not counted)."""
    d = cfg["hidden_size"]
    fwd = 4.0 * d * seq_len * (0.5 if causal else 1.0)
    return fwd * (3.5 if backward else 1.0) * cfg["num_hidden_layers"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs a training token needs: 6 per matmul parameter (forward
    2, backward 4) plus causal attention; nothing recomputed is counted."""
    return 6.0 * lm_matmul_params(cfg)["total"] \
        + attention_flops_per_token(cfg, seq_len)


def flash_train_cost(cfg: dict, batch: int, seq_len: int) -> dict:
    """Operations and bytes of the flash forward + backward kernels of ONE
    layer on [batch, seq_len] causal sequences: 2 matmuls forward and 5
    backward over the lower triangle; q, k, v, o, do read and dq, dk, dv, o
    written once each in the compute type (2 bytes)."""
    h = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // h
    hkv = cfg["num_key_value_heads"]
    tri = 0.5 * batch * h * seq_len * seq_len * hd
    flops = (2 + 5) * 2.0 * tri
    q_bytes = batch * seq_len * h * hd * 2
    kv_bytes = batch * seq_len * hkv * hd * 2
    bytes_ = (q_bytes * 2 + kv_bytes * 2) + (q_bytes * 4 + kv_bytes * 4)
    return {"flops": flops, "bytes": float(bytes_)}


def paged_decode_cost(cfg: dict, live_tokens: float, rows: float,
                      kv_bytes: int = 2) -> dict:
    """Operations and bytes ONE paged-attention call (one layer, one engine
    step) needs: every live K and V row read once, each query row against
    its own context (`live_tokens` is the sum of the rows' contexts)."""
    h = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // h
    hkv = cfg["num_key_value_heads"]
    bytes_ = live_tokens * 2 * hkv * hd * kv_bytes + rows * h * hd * 2 * 2
    flops = 4.0 * h * hd * live_tokens
    return {"flops": flops, "bytes": float(bytes_)}


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peaks: dict) -> dict:
    """Least time the chip could take over the time it took, in percent,
    and which of the two bounds it."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    return {"share_pct": 100.0 * least / seconds,
            "bound": "compute" if t_flops >= t_bytes else "memory"}


def check_share(name: str, value: float) -> float:
    """A share of a peak cannot pass 100%: above 105% the operations or
    bytes are counted too high or the time leaves work out — fail loudly."""
    if value > 105.0:
        raise RuntimeError(
            f"{name} reads {value:.2f}% — above what the chip can give; the "
            f"operation/byte count or the time is wrong")
    return value
