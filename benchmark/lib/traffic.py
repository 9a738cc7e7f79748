"""The one general traffic generator.  A traffic mix is a data file of
parameters (benchmark/traffic/<name>.json); this module turns it and a seed
into requests.  No JAX, no numpy: the load generator child imports it.

The traffic is STRATIFIED, not sampled: every seed gets the SAME multiset of
sizes and the same multiset of arrival gaps — the (i + 0.5) / n quantiles of
the stated distributions — in another order, and its own token ids.  So the
offered work of a window does not change with the seed, only which request
meets which.  With `shuffle_block` the order is shuffled inside blocks only,
each spanning its whole distribution, which also takes out the bursts that a
random draw has at the scale of a block: a mix that wants bursts asks for
them in its arrival distribution (`"arrival": {"dist": "gamma", "cv": 3}`)
and leaves `shuffle_block` out.

What a mix can say, as data alone: loop open (rate, arrival distribution) or
closed (clients), prompt and output lengths (any distribution below, one of
them a table of values in a file), the step of output lengths, and shared
prefixes (a pool of prompts' heads with Zipf popularity)."""

from __future__ import annotations

import math
import os
import random

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")

# -- distributions: quantile functions --------------------------------------


def _norm_ppf(p: float) -> float:
    """Inverse standard-normal CDF (Acklam's rational approximation,
    relative error < 1.2e-9)."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    lo = 0.02425
    if p < lo:
        q = math.sqrt(-2 * math.log(p))
        return (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    if p > 1 - lo:
        return -_norm_ppf(1 - p)
    q = p - 0.5
    r = q * q
    return (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
            + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r
                            + b[4]) * r + 1)


def quantile(dist: dict, p: float) -> float:
    """The p-quantile (0 < p < 1) of a distribution given as data:
    {"dist": "lognormal", "median": m, "sigma": s, "lo": a, "hi": b}
    {"dist": "uniform", "lo": a, "hi": b}
    {"dist": "constant", "value": v}
    {"dist": "exponential", "mean": m}
    {"dist": "gamma", "mean": m, "cv": c}      (cv 1 is the exponential)
    {"dist": "table", "values": [...]} or {"dist": "table", "file": f}: the
        empirical distribution of the values (f: one number a line, beside
        the mixes in benchmark/traffic/)"""
    kind = dist["dist"]
    if kind == "constant":
        x = float(dist["value"])
    elif kind == "uniform":
        x = dist["lo"] + (dist["hi"] - dist["lo"]) * p
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _norm_ppf(p))
    elif kind == "exponential":
        x = -dist["mean"] * math.log1p(-p)
    elif kind == "gamma":
        from scipy.special import gammaincinv     # only a gamma mix pays it
        shape = 1.0 / float(dist["cv"]) ** 2
        x = float(gammaincinv(shape, p)) * dist.get("mean", 1.0) / shape
    elif kind == "table":
        xs = _table(dist)
        x = xs[min(len(xs) - 1, int(p * len(xs)))]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "lo" in dist:
        x = max(x, dist["lo"])
    if "hi" in dist:
        x = min(x, dist["hi"])
    return x


def _table(dist: dict) -> list:
    if "values" not in dist:
        name = dist["file"]
        if os.path.basename(name) != name:
            raise ValueError(f"table file {name!r}: a file name, no path")
        with open(os.path.join(TRAFFIC_DIR, name)) as f:
            dist["values"] = [float(v) for v in f.read().split()]
    return sorted(dist["values"])


def stratified(dist: dict, n: int, rng: random.Random,
               integer: bool = True, block: int = 0) -> list:
    """n values at the quantiles (i + 0.5) / n, shuffled by `rng`.

    With `block` > 0 the order is shuffled inside consecutive blocks of that
    many values only, and every block spans the whole distribution (block j
    of m holds the quantiles j, j + m, j + 2m, ...): whatever stretch of the
    sequence a window happens to use, it sees nearly the same sizes."""
    xs = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    if integer:
        xs = [int(round(x)) for x in xs]
    if block <= 0 or block >= n:
        rng.shuffle(xs)
        return xs
    m = -(-n // block)                       # number of blocks
    out = []
    for j in range(m):
        part = xs[j::m]
        rng.shuffle(part)
        out.extend(part)
    return out


# -- requests ----------------------------------------------------------------


def _tokens(rng: random.Random, n: int, vocab: int) -> list[int]:
    return [rng.randrange(2, vocab) for _ in range(n)]


def _shared_heads(sp, n: int, vocab: int, rng: random.Random, block: int):
    """For each of n requests the shared head of its prompt, or None when
    the mix shares nothing (and then the seed's stream is not touched)."""
    if not sp:
        return None
    k = int(sp["pool"])
    pool = [_tokens(rng, length, vocab)
            for length in stratified(sp["len"], k, rng)]
    w = [1.0 / (j + 1) ** float(sp.get("zipf_s", 1.0)) for j in range(k)]
    cum = [sum(w[:j + 1]) / sum(w) for j in range(k)]
    # which head: the quantiles of the popularity law, as every size is
    picks = stratified({"dist": "table", "values": [
        next(j for j in range(k) if (i + 0.5) / n <= cum[j])
        for i in range(n)]}, n, rng, block=block)
    return [pool[j] for j in picks]


def serve_requests(traffic: dict, vocab: int, seed: int, seconds: float):
    """Requests of one serve window: a list of dicts with `due` (seconds
    from the window's start; None in a closed loop), `prompt`, `max_new`,
    `client` (closed loop: which client sends it, in order).

    open loop:   n = round(rate * horizon) requests; the gaps are the
                 quantiles of `arrival` (an exponential unless the mix says
                 otherwise: a Poisson process's gaps, stratified), scaled so
                 that they sum to the horizon (ramp + window)
    closed loop: per client, enough requests to outlast the horizon at any
                 plausible speed (`requests_per_client`)
    shared_prefix: {"pool": k, "zipf_s": s, "len": <distribution>} puts one
                 of k seeded heads before each prompt (`prompt_len` is then
                 the length of the tail), head j+1 with weight 1/(j+1)**s."""
    rng = random.Random(seed * 1000003 + 17)
    horizon = float(seconds) + float(traffic.get("ramp_s", 0.0))
    if traffic["loop"] == "open":
        n = max(1, int(round(traffic["rate_per_s"] * horizon)))
        block = int(traffic.get("shuffle_block", 0))
        gaps = stratified(traffic.get("arrival", {"dist": "exponential",
                                                  "mean": 1.0}),
                          n, rng, integer=False, block=block)
        scale = horizon / sum(gaps)
        due, t = [], 0.0
        for g in gaps:
            t += g * scale
            due.append(t - gaps[0] * scale)      # the first is due at 0
        clients = [None] * n
    elif traffic["loop"] == "closed":
        k = int(traffic["clients"])
        per = int(traffic["requests_per_client"])
        n = k * per
        block = k            # one round of the clients holds every size
        due = [None] * n
        clients = [i % k for i in range(n)]
    else:
        raise ValueError(f"loop {traffic['loop']!r}")
    prompts = stratified(traffic["prompt_len"], n, rng, block=block)
    outs = stratified(traffic["output_len"], n, rng, block=block)
    heads = _shared_heads(traffic.get("shared_prefix"), n, vocab, rng, block)
    limit = int(traffic["max_context"])
    # output lengths take a few values (multiples of `output_len_step`), as a
    # deployment's max_new does; the warm-up covers each (serve warm-up)
    step = int(traffic.get("output_len_step", 1))
    reqs = []
    for i in range(n):
        head = heads[i] if heads else []
        p = max(1, min(prompts[i], limit - step - 1 - len(head)))
        o = max(step, int(round(outs[i] / step)) * step)
        o = max(1, min(o, (limit - len(head) - p - 1) // step * step))
        reqs.append({"id": f"r{i}", "due": due[i], "client": clients[i],
                     "prompt": head + _tokens(rng, p, vocab), "max_new": o})
    if traffic["loop"] == "closed":
        # a closed loop started cold would end every first request together;
        # client c's first output is cut to the (c+1)/k share of its length,
        # so completions are spread from the first second
        k = int(traffic["clients"])
        for c in range(k):
            r = reqs[c]
            cut = r["max_new"] * (c + 1) / k
            r["max_new"] = max(step, int(math.ceil(cut / step)) * step)
    return reqs


def distinct_max_new(reqs: list[dict]) -> list[int]:
    """The output lengths a window will ask for.  The engine splits one
    sampling key per output token at admission, a program per distinct
    length: the warm-up admits each length once."""
    return sorted({r["max_new"] for r in reqs})


def warm_requests(traffic: dict, vocab: int, seed: int) -> list[list[dict]]:
    """The serve warm-up, in two waves, so that every program the window
    will use is compiled before it.  Wave 1: the cell's own shortest, median
    and longest prompt, concurrent, each decoding a few tokens (decode and
    mixed steps).  Wave 2, after wave 1 is done: its median prompt again
    with another tail, which shares cached pages up to the middle of one
    (the prefix cache's copy-on-write program — two random prompts share a
    first token often enough that the window would otherwise compile it)."""
    rng = random.Random(seed * 7919 + 5)
    limit = int(traffic["max_context"])
    head = traffic.get("shared_prefix", {}).get("len", {"dist": "constant",
                                                        "value": 0})
    lens = sorted({int(quantile(traffic["prompt_len"], p) + quantile(head, p))
                   for p in (0.001, 0.5, 0.999)})
    wave1 = [{"id": f"w{i}", "prompt": _tokens(rng, min(p, limit - 10), vocab),
              "max_new": 6} for i, p in enumerate(lens)]
    base = wave1[len(wave1) // 2]["prompt"]
    cut = max(1, len(base) - 3)
    wave2 = [{"id": "w_shared", "prompt": base[:cut] + _tokens(rng, 5, vocab),
              "max_new": 4}]
    return [wave1, wave2]
