"""Continuous-batching serving benchmark — mixed-length Poisson-arrival
workload through serving/engine.py.

Timing discipline (tools/_scan_bench.py's lessons applied to a host-driven
engine): the scheduler IS a host loop and every decode step already ends in
a host read of the sampled tokens — a device->host fetch is a completion
barrier on any backend, so per-step timing can
never report beyond-hardware numbers the way an unsynced dispatch loop
does.  What DOES need guarding is compile time: a full warmup pass drives
the same request mix through the engine first, so every prefill bucket and
the ONE decode signature are compiled before the timed region (asserted:
the decode jit cache must not grow during measurement).

Two modes per row:
  * --rate 0 (default): all requests arrive at t=0 — closed loop, peak
    tokens/sec at full slot pressure;
  * --rate R: open-loop Poisson arrivals at R requests/sec — tokens/sec at
    that offered load plus the mean slot occupancy (the capacity-planning
    curve PERF.md's serving section reads).

One JSON line per measurement, MEASURE/-compatible.

Usage:
  python tools/bench_serving.py                       # TPU-sized defaults
  python tools/bench_serving.py --rate 2,8,32         # occupancy curve
  python tools/bench_serving.py --num-requests 6 --slots 2 ... (rehearse)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_requests(n: int, prompt_lo: int, prompt_hi: int, max_new: int,
                  vocab: int, seed: int = 0, eos_id: int = -1):
    """Mixed-length request set: prompt lengths uniform in
    [prompt_lo, prompt_hi] (spanning several feeder buckets), greedy
    decode (throughput does not depend on token values)."""
    import numpy as np

    from paddle_tpu.serving import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        p = int(rng.integers(prompt_lo, prompt_hi + 1))
        prompt = rng.integers(2, vocab, p).astype(np.int32)
        reqs.append(Request(f"r{seed}_{i}", prompt, max_new=max_new,
                            eos_id=eos_id))
    return reqs


def make_prefix_prompts(n: int, prefix_pool: int, prefix_len: int,
                        prefix_skew: float, suffix_lo: int, suffix_hi: int,
                        vocab: int, pool_seed: int = 0, seed: int = 0):
    """Raw prompts for the prefix-skew workload: each draws one of
    `prefix_pool` shared system-prompt prefixes (Zipf-distributed
    popularity, exponent `prefix_skew` — rank k with probability
    ∝ 1/(k+1)^skew) and appends a per-request unique suffix.  The POOL is
    seeded by `pool_seed` alone so every rep shares the same prefixes
    (that sharing IS the workload); draws and suffixes vary with `seed`.
    Shared by the engine-level A/B (Request objects) and the fleet bench
    (client prompts over the wire)."""
    import numpy as np

    pool_rng = np.random.default_rng(pool_seed)
    prefixes = [pool_rng.integers(2, vocab, prefix_len).astype(np.int32)
                for _ in range(prefix_pool)]
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, prefix_pool + 1, dtype=np.float64) ** prefix_skew
    w /= w.sum()
    prompts = []
    for _ in range(n):
        k = int(rng.choice(prefix_pool, p=w))
        s = int(rng.integers(suffix_lo, suffix_hi + 1))
        prompts.append(np.concatenate(
            [prefixes[k], rng.integers(2, vocab, s).astype(np.int32)]))
    return prompts


def make_prefix_requests(n: int, prefix_pool: int, prefix_len: int,
                         prefix_skew: float, suffix_lo: int, suffix_hi: int,
                         max_new: int, vocab: int, pool_seed: int = 0,
                         seed: int = 0, eos_id: int = -1):
    """make_prefix_prompts wrapped as engine Request objects."""
    from paddle_tpu.serving import Request

    prompts = make_prefix_prompts(n, prefix_pool, prefix_len, prefix_skew,
                                  suffix_lo, suffix_hi, vocab,
                                  pool_seed=pool_seed, seed=seed)
    return [Request(f"p{seed}_{i}", prompt, max_new=max_new, eos_id=eos_id)
            for i, prompt in enumerate(prompts)]


def make_heavytail_requests(n: int, prompt_lo: int, prompt_hi: int,
                            max_new: int, vocab: int, seed: int = 0,
                            eos_id: int = -1, tail_frac: float = 0.1):
    """Heavy-tail prompt-length workload (the head-of-line-blocking
    adversary chunked prefill exists for): most prompts are short —
    lognormal body around `prompt_lo` — but `tail_frac` of them draw a
    Pareto tail reaching `prompt_hi` (a few multi-thousand-token prompts
    amid short ones at production shapes).  Greedy decode; lengths clamp
    to [2, prompt_hi] so every request fits the configured pool."""
    import numpy as np

    from paddle_tpu.serving import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        if rng.random() < tail_frac:
            p = prompt_lo * (1.0 + rng.pareto(1.1))      # heavy tail
        else:
            p = rng.lognormal(np.log(max(prompt_lo, 2)), 0.5)
        p = int(np.clip(p, 2, prompt_hi))
        prompt = rng.integers(2, vocab, p).astype(np.int32)
        reqs.append(Request(f"h{seed}_{i}", prompt, max_new=max_new,
                            eos_id=eos_id))
    return reqs


def make_repetitive_requests(n: int, prompt_lo: int, prompt_hi: int,
                             max_new: int, vocab: int, seed: int = 0,
                             motif_lo: int = 4, motif_hi: int = 12,
                             eos_id: int = -1):
    """Locally-repetitive prompts — the workload speculative decoding
    targets: each prompt tiles a short random motif to a mixed length
    (the structure of templated text, code, and retrieval contexts,
    where the next tokens often repeat an earlier span).  Greedy decode
    (spec changes steps-per-token, never the tokens), eos off so every
    request emits exactly max_new and the drafted/accepted/emitted
    reconciliation is exact."""
    import numpy as np

    from paddle_tpu.serving import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        m = int(rng.integers(motif_lo, motif_hi + 1))
        p = int(rng.integers(prompt_lo, prompt_hi + 1))
        motif = rng.integers(2, vocab, m).astype(np.int32)
        prompt = np.tile(motif, -(-p // m))[:p]
        reqs.append(Request(f"s{seed}_{i}", prompt, max_new=max_new,
                            eos_id=eos_id))
    return reqs


def poisson_arrivals(n: int, rate: float, seed: int = 0):
    """Arrival offsets (seconds from t0): exponential gaps at `rate`
    req/s; rate <= 0 -> everything at t=0 (closed loop)."""
    import numpy as np

    if rate <= 0:
        return np.zeros(n)
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate, n))


def run_workload(engine, requests, arrivals=None) -> dict:
    """Drive one workload to completion; returns wall seconds, generated
    tokens, mean occupancy over the steps of THIS run, decode steps,
    preemptions — plus the raw latency samples: `step_seconds` (duration
    of every busy decode step = the inter-token latency each live request
    observed on it) and `req_seconds` (admission -> finish per request,
    via the engine's on_finish hook).  The per-step host token read is the
    sync barrier."""
    import numpy as np

    arrivals = np.zeros(len(requests)) if arrivals is None else arrivals
    order = np.argsort(arrivals, kind="stable")
    requests = [requests[i] for i in order]
    arrivals = arrivals[order]
    tok0 = engine.tokens_generated
    step0 = engine.n_decode_steps
    occ0 = engine.occupancy_sum
    pre0 = engine.n_preemptions
    hit0, miss0 = engine.n_prefix_hits, engine.n_prefix_misses
    saved0 = engine.prefill_tokens_saved
    evict0 = engine.prefix.n_evictions if engine.prefix else 0
    cow0 = engine.kv.n_cow
    t_add: dict = {}
    req_seconds: list = []
    step_seconds: list = []
    first_tok_seconds: list = []
    prev_finish = engine.on_finish
    prev_token = engine.on_token

    def _on_finish(rid, toks, reason):
        if rid in t_add:
            req_seconds.append(time.perf_counter() - t_add.pop(rid))
        if prev_finish is not None:
            prev_finish(rid, toks, reason)

    seen_first: set = set()
    itl_seconds: list = []
    last_t: dict = {}
    last_idx: dict = {}
    bleft: dict = {}
    bshare: dict = {}

    def _on_token(rid, tok, idx):
        now = time.perf_counter()
        # index 0 = the prefill-sampled token: admission -> first token is
        # the latency prefix caching exists to cut.  A preempted request's
        # re-admission REPLAYS idx 0 (the engine re-fires on_token for the
        # deterministic restart) — only the first occurrence is the
        # request's real first-token latency, so dedup by rid.
        if idx == 0 and rid in t_add and rid not in seen_first:
            seen_first.add(rid)
            first_tok_seconds.append(now - t_add[rid])
        # burst bookkeeping counts EVERY banked token (replays included —
        # within one burst replayed indexes precede fresh ones), the same
        # discipline the server's token_latency stat uses: at
        # decode_steps=k one scan flush banks up to k tokens per slot in
        # one on_token volley, so each fresh token in the burst owns an
        # equal 1/burst share of the gap since the request's previous
        # fresh token — without it the ITL percentiles of a k>1 run
        # would read k-times bursty against a k=1 run
        if bleft.get(rid, 0) > 0:
            bleft[rid] -= 1
        else:                                  # first token of a new burst
            bleft[rid] = max(1, int(getattr(engine, "cur_burst", 1))) - 1
            bshare[rid] = -1.0
        # inter-token latency as the CLIENT sees it: the gap between a
        # request's consecutive FRESH tokens — the p99 of this is what
        # chunked prefill bounds.  Replayed tokens (idx <= last seen) are
        # dropped and do not advance the clock, so a preempt+replay stall
        # charges one honest big gap at the first fresh token (the same
        # t_last discipline the server's stats use).
        prev = last_idx.get(rid, -1)
        if idx > prev:
            if prev >= 0:
                if bshare[rid] < 0.0:
                    # first FRESH token since last_t: the gap covers this
                    # token plus the bleft still to come (all fresh —
                    # replays sort first within a burst)
                    bshare[rid] = (now - last_t[rid]) / (bleft[rid] + 1)
                itl_seconds.append(bshare[rid])
            last_t[rid] = now
            last_idx[rid] = idx
        if prev_token is not None:
            prev_token(rid, tok, idx)

    engine.on_finish = _on_finish
    engine.on_token = _on_token
    i, n = 0, len(requests)
    t0 = time.perf_counter()
    try:
        while True:
            now = time.perf_counter() - t0
            while i < n and arrivals[i] <= now:
                t_add[requests[i].req_id] = time.perf_counter()
                engine.add_request(requests[i])
                i += 1
            ts = time.perf_counter()
            busy = engine.step()
            if busy:
                step_seconds.append(time.perf_counter() - ts)
            else:
                if i >= n:
                    break
                time.sleep(min(max(arrivals[i] - (time.perf_counter() - t0),
                                   0.0), 0.05))
    finally:
        engine.on_finish = prev_finish
        engine.on_token = prev_token
    dt = time.perf_counter() - t0
    steps = engine.n_decode_steps - step0
    hits = engine.n_prefix_hits - hit0
    misses = engine.n_prefix_misses - miss0
    return {
        "seconds": dt,
        "tokens": engine.tokens_generated - tok0,
        "decode_steps": steps,
        "occupancy": (engine.occupancy_sum - occ0) / steps if steps else 0.0,
        "preemptions": engine.n_preemptions - pre0,
        "step_seconds": step_seconds,
        "req_seconds": req_seconds,
        "first_tok_seconds": first_tok_seconds,
        "itl_seconds": itl_seconds,
        "prefix_hits": hits,
        "prefix_misses": misses,
        "prefix_hit_rate": hits / (hits + misses) if hits + misses else 0.0,
        "prefill_tokens_saved": engine.prefill_tokens_saved - saved0,
        "prefix_evictions": (engine.prefix.n_evictions if engine.prefix
                             else 0) - evict0,
        "prefix_cow": engine.kv.n_cow - cow0,
    }


def warm_workload(engine, request_sets) -> None:
    """Compile everything the measured reps will touch BEFORE the timed
    region: run the first set end-to-end (decode signature + its buckets),
    then prefill one 1-token request per bucket any OTHER set needs —
    otherwise a rep whose seed draws a bucket the warmup seed missed pays
    a multi-second jit compile inside its timing window."""
    import numpy as np

    from paddle_tpu.serving import Request

    engine.run(request_sets[0])
    if engine.prefill_chunk is not None:
        # chunked mode has NO length-dependent prefill programs: one
        # workload compiles both signatures (the mixed step while chunks
        # are in flight, the [S,1] decode step once prefill drains)
        return
    seen = set(engine._prefill_cache)
    for reqs in request_sets[1:]:
        for r in reqs:
            b = engine.bucket_for(r.prompt_ids.size)
            if b not in seen:
                seen.add(b)
                engine.run([Request(f"_warm{b}",
                                    np.full(min(b, r.prompt_ids.size), 2,
                                            np.int32), max_new=1)])


def measure_prefix_skew(eng, wl: dict, reps: int, seed: int) -> dict:
    """A/B prefix-cache measurement on ONE engine: the identical
    prefix-skew workload (fresh Request objects each pass, same seeds)
    with the cache OFF, then ON — the off pass is the no-cache baseline
    the acceptance comparison reads.  Closed loop (all requests at t=0):
    arrival jitter would blur the first-token delta the cache exists to
    cut.

    Warmup discipline: the baseline side compiles the cold prefill
    buckets (warm_workload); the cached side then runs every rep set once
    against a warming tree BEFORE its timed reps — that pass compiles the
    suffix-prefill/pack signatures a warm-tree run touches and leaves the
    tree in the steady state production sees.  The decode step must stay
    at ONE signature throughout (reported as `decode_sig_stable`);
    suffix-prefill signature counts are reported, not asserted — which
    (pages, bucket) pairs occur is tree-state dependent by design."""
    import numpy as np

    def sets():
        return [make_prefix_requests(seed=seed + 1 + r, **wl)
                for r in range(reps)]

    eng.set_prefix_cache(False)
    warm_workload(eng, [make_prefix_requests(seed=seed, **wl)] + sets())
    sig0 = eng._decode_step._cache_size()
    base_vals, base_ftok = [], []
    for reqs in sets():
        rec = run_workload(eng, reqs)
        base_vals.append(rec["tokens"] / rec["seconds"])
        base_ftok += rec["first_tok_seconds"]

    eng.set_prefix_cache(True)
    # two warming passes (not timed): the first runs every rep set from a
    # cold tree (mostly misses — donations build the tree), the second
    # runs them again at steady state, compiling the suffix-prefill/pack
    # and COW-copy signatures a WARM-tree rep actually touches — without
    # it the first timed rep pays those compiles inside its window (a
    # cold-start warmup sees misses where the timed rep sees hits)
    for _ in range(2):
        for reqs in sets():
            eng.run(reqs)
    vals, ftok = [], []
    hits = misses = saved = evs = cows = 0
    for reqs in sets():
        rec = run_workload(eng, reqs)
        vals.append(rec["tokens"] / rec["seconds"])
        ftok += rec["first_tok_seconds"]
        hits += rec["prefix_hits"]
        misses += rec["prefix_misses"]
        saved += rec["prefill_tokens_saved"]
        evs += rec["prefix_evictions"]
        cows += rec["prefix_cow"]
    eng.kv.check()
    pct = lambda xs: float(np.percentile(xs, 50)) * 1e3 if xs else 0.0
    return {
        "decode_sig_stable": eng._decode_step._cache_size() == sig0,
        "baseline_tok_per_sec": float(np.median(base_vals)),
        "cached_tok_per_sec": float(np.median(vals)),
        "baseline_first_tok_ms_p50": round(pct(base_ftok), 3),
        "first_tok_ms_p50": round(pct(ftok), 3),
        "hits": hits, "misses": misses,
        "hit_rate": hits / (hits + misses) if hits + misses else 0.0,
        "tokens_saved": saved, "evictions": evs, "cow": cows,
        "suffix_prefill_sigs": len(eng._prefix_prefill_cache),
    }


def measure_spill(eng, wl: dict, reps: int, seed: int,
                  budget: int) -> dict:
    """Host-spill A/B on ONE engine: the identical prefix-skew workload
    (fresh Request objects each pass, same seeds) with the prefix cache ON
    both arms and the spill tier OFF, then ON at `budget` bytes.  The
    caller sizes the page pool BELOW the Zipf working set (--num-pages),
    so the off arm destroys cold prefixes under pressure and re-pays
    their prefill, while the on arm parks them in host RAM and restores
    on the next hit — the hit-rate delta is the number the tier exists
    for.  reset_prefix_cache between arms (drains the host tier too) so
    the on arm starts from the same cold allocator state.

    Warmup discipline matches measure_prefix_skew: warm_workload compiles
    the prefill buckets, then each arm runs every rep set twice untimed —
    the on arm's warming passes populate the host tier and compile the
    per-bucket restore scatter before the timed region.  The decode and
    mixed steps must hold their signatures across BOTH arms (spill work
    is admission-boundary host code, never a new jit) — reported as
    `sig_stable`, the bench's pass/fail verdict together with the
    restored-pages-vs-tokens-saved reconciliation."""
    import numpy as np

    def sets():
        return [make_prefix_requests(seed=seed + 1 + r, **wl)
                for r in range(reps)]

    pct = lambda xs: float(np.percentile(xs, 50)) * 1e3 if xs else 0.0

    eng.set_spill_budget(0)
    warm_workload(eng, [make_prefix_requests(seed=seed, **wl)] + sets())
    sig0 = eng._decode_step._cache_size()
    mixed0 = eng._mixed_step._cache_size()

    arms = {}
    for label, bytes_budget in (("off", 0), ("on", int(budget))):
        eng.reset_prefix_cache()
        eng.set_spill_budget(bytes_budget)
        for _ in range(2):                     # untimed steady-state warmup
            for reqs in sets():
                eng.run(reqs)
        spilled0 = eng.kv.n_spilled
        restored0 = eng.kv.n_restored
        rhit0 = eng.n_restore_hits
        rsaved0 = eng.restore_tokens_saved
        vals, ftok = [], []
        hits = misses = saved = evs = 0
        for reqs in sets():
            rec = run_workload(eng, reqs)
            vals.append(rec["tokens"] / rec["seconds"])
            ftok += rec["first_tok_seconds"]
            hits += rec["prefix_hits"]
            misses += rec["prefix_misses"]
            saved += rec["prefill_tokens_saved"]
            evs += rec["prefix_evictions"]
        eng.kv.check()
        arms[label] = {
            "tok_per_sec": float(np.median(vals)),
            "first_tok_ms_p50": round(pct(ftok), 3),
            "hits": hits, "misses": misses,
            "hit_rate": hits / (hits + misses) if hits + misses else 0.0,
            "tokens_saved": saved, "evictions": evs,
            "spilled_pages": eng.kv.n_spilled - spilled0,
            "restored_pages": eng.kv.n_restored - restored0,
            "restore_hits": eng.n_restore_hits - rhit0,
            "restore_tokens_saved": eng.restore_tokens_saved - rsaved0,
        }
    off, on = arms["off"], arms["on"]
    # every token a restore saved must be backed by a restored page (a
    # restored hit can save at most page_size tokens per restored page)
    reconcile_ok = (on["restored_pages"] > 0
                    and 0 < on["restore_tokens_saved"]
                    <= on["restored_pages"] * eng.kv.page_size)
    return {
        "spill_budget": int(budget),
        "num_pages": int(eng.kv.num_pages),
        "host_pages": int(eng.kv.host_page_count),
        "host_bytes": int(eng.kv.host_bytes),
        "page_nbytes": int(eng.kv.page_nbytes),
        "tok_per_sec": on["tok_per_sec"],
        "off_tok_per_sec": off["tok_per_sec"],
        "first_tok_ms_p50": on["first_tok_ms_p50"],
        "off_first_tok_ms_p50": off["first_tok_ms_p50"],
        "hit_rate": on["hit_rate"], "off_hit_rate": off["hit_rate"],
        "hit_rate_improved": on["hit_rate"] > off["hit_rate"],
        "tokens_saved": on["tokens_saved"],
        "off_tokens_saved": off["tokens_saved"],
        "evictions": on["evictions"], "off_evictions": off["evictions"],
        "spilled_pages": on["spilled_pages"],
        "restored_pages": on["restored_pages"],
        "restore_hits": on["restore_hits"],
        "restore_tokens_saved": on["restore_tokens_saved"],
        "off_spilled_pages": off["spilled_pages"],
        "restore_fn_sigs": len(eng.kv._restore_fns),
        "reconcile_ok": reconcile_ok,
        "sig_stable": (eng._decode_step._cache_size() == sig0
                       and eng._mixed_step._cache_size() == mixed0),
    }


def measure_chunked(eng, wl: dict, reps: int, seed: int,
                    prefill_chunk: int, max_step_tokens=None) -> dict:
    """Chunked-prefill A/B on ONE engine: the identical heavy-tail
    workload (fresh Request objects each pass, same seeds) with chunking
    OFF — legacy whole-prompt bucketed prefill, the head-of-line-blocking
    baseline — then ON.  Closed loop: arrival jitter would blur the
    inter-token tail the chunking exists to bound.

    Reports first-token AND inter-token p50/p99 for both sides (the
    acceptance comparison reads the p99s: a long cold prompt's prefill
    stalls every decoding slot's inter-token latency on the baseline,
    and the budgeted mixed step bounds it), plus tokens/s and the
    signature-stability verdict (the mixed step must hold ONE signature
    and the decode step its one across the timed region)."""
    import numpy as np

    def sets():
        return [make_heavytail_requests(seed=seed + 1 + r, **wl)
                for r in range(reps)]

    def run_reps():
        vals, ftok, itl = [], [], []
        for reqs in sets():
            rec = run_workload(eng, reqs)
            vals.append(rec["tokens"] / rec["seconds"])
            ftok += rec["first_tok_seconds"]
            itl += rec["itl_seconds"]
        return vals, ftok, itl

    def pcts(xs):
        return ([round(float(v) * 1e3, 3)
                 for v in np.percentile(xs, [50, 99])]
                if xs else [0.0, 0.0])

    eng.set_chunking(None)
    warm_workload(eng, [make_heavytail_requests(seed=seed, **wl)] + sets())
    base_vals, base_ftok, base_itl = run_reps()

    eng.set_chunking(prefill_chunk, max_step_tokens)
    warm_workload(eng, [make_heavytail_requests(seed=seed, **wl)])
    decode_sigs = eng._decode_step._cache_size()
    mixed_sigs = eng._mixed_step._cache_size()
    chunks0 = eng.n_prefill_chunks
    vals, ftok, itl = run_reps()
    eng.kv.check()
    b_ft, b_itl = pcts(base_ftok), pcts(base_itl)
    c_ft, c_itl = pcts(ftok), pcts(itl)
    return {
        "sig_stable": (eng._decode_step._cache_size() == decode_sigs
                       and eng._mixed_step._cache_size() == mixed_sigs
                       and mixed_sigs == 1),
        "prefill_chunk": int(eng.prefill_chunk),
        "max_step_tokens": int(eng.max_step_tokens),
        "prefill_chunks": eng.n_prefill_chunks - chunks0,
        "baseline_tok_per_sec": float(np.median(base_vals)),
        "chunked_tok_per_sec": float(np.median(vals)),
        "baseline_first_tok_ms_p50": b_ft[0],
        "baseline_first_tok_ms_p99": b_ft[1],
        "first_tok_ms_p50": c_ft[0], "first_tok_ms_p99": c_ft[1],
        "baseline_itl_ms_p50": b_itl[0], "baseline_itl_ms_p99": b_itl[1],
        "itl_ms_p50": c_itl[0], "itl_ms_p99": c_itl[1],
        "p99_itl_improved": c_itl[1] < b_itl[1],
        "p99_first_tok_improved": c_ft[1] < b_ft[1],
    }


def measure_spec(eng, wl: dict, reps: int, seed: int, spec_k: int) -> dict:
    """Speculative-decoding A/B on ONE engine: the identical
    locally-repetitive workload (fresh Request objects each pass, same
    seeds) with speculation OFF (the sequential baseline) then ON at
    `--spec-k` via set_speculation — emitted tokens are identical by
    construction (tests/test_spec_decode.py's oracle), so the ONLY
    deltas are steps-per-token and wall time.  Closed loop: spec's win
    is raw decode throughput, arrival jitter would only blur it.

    The token budget is pinned ONCE before both arms (chunk + one full
    chain per slot) so the signature sets stay fixed across the A/B.
    Reports tok/s both sides, the accept rate, the raw drafted/accepted
    counters, compiled steps both sides, and `reconcile_ok` — the
    counters must reconcile exactly to tokens emitted: with eos off no
    chain ever truncates, so every chain banks its accepted drafts plus
    one sampled token — `spec_tokens == accepted + chains` — and both
    arms emit the identical n * max_new total."""
    import numpy as np

    def sets():
        return [make_repetitive_requests(seed=seed + 1 + r, **wl)
                for r in range(reps)]

    S = len(eng.slots)
    if eng.prefill_chunk is not None:
        eng.set_chunking(eng.prefill_chunk,
                         eng.prefill_chunk + S * (spec_k + 1))
    eng.set_speculation(0)
    warm_workload(eng, [make_repetitive_requests(seed=seed, **wl)]
                  + sets())
    base_vals, base_steps = [], 0
    for reqs in sets():
        rec = run_workload(eng, reqs)
        base_vals.append(rec["tokens"] / rec["seconds"])
        base_steps += rec["decode_steps"]

    eng.set_speculation(spec_k)
    eng.run(make_repetitive_requests(seed=seed, **wl))  # verify-sig warm
    decode_sigs = eng._decode_step._cache_size()
    spec_sigs = eng._spec_step._cache_size()
    d0, a0 = eng.n_spec_drafted, eng.n_spec_accepted
    c0, t0 = eng.n_spec_chains, eng.n_spec_tokens
    vals, toks, steps = [], 0, 0
    for reqs in sets():
        rec = run_workload(eng, reqs)
        vals.append(rec["tokens"] / rec["seconds"])
        toks += rec["tokens"]
        steps += rec["decode_steps"]
    eng.kv.check()
    drafted = eng.n_spec_drafted - d0
    accepted = eng.n_spec_accepted - a0
    chains = eng.n_spec_chains - c0
    spec_tokens = eng.n_spec_tokens - t0
    base_med, spec_med = float(np.median(base_vals)), float(np.median(vals))
    return {
        "sig_stable": (eng._decode_step._cache_size() == decode_sigs
                       and eng._spec_step._cache_size() == spec_sigs
                       and spec_sigs == 1),
        "spec_k": int(spec_k),
        "max_step_tokens": int(eng.max_step_tokens),
        "baseline_tok_per_sec": base_med,
        "spec_tok_per_sec": spec_med,
        "speedup_vs_baseline": spec_med / base_med if base_med else 0.0,
        "accept_rate": accepted / drafted if drafted else 0.0,
        "drafted": int(drafted),
        "accepted": int(accepted),
        "chains": int(chains),
        "spec_tokens": int(spec_tokens),
        "tokens": int(toks),
        "baseline_decode_steps": int(base_steps),
        "spec_decode_steps": int(steps),
        "reconcile_ok": (spec_tokens == accepted + chains
                         and toks == reps * wl["n"] * wl["max_new"]),
    }


def measure_spec_modes(eng, wl: dict, hwl: dict, reps: int, seed: int,
                       spec_k: int, scan_k: int = 2,
                       tol: float = 0.85) -> dict:
    """Adaptive-speculation A/B on ONE engine: every drafter/depth/mode
    configuration over the SAME two workloads (fresh Request objects per
    pass, same seeds), all through idle-engine knob flips so the
    signature sets stay fixed.  Emitted tokens are identical in every
    arm by construction (greedy, exact verification), so the deltas are
    accept rate, steps-per-token and wall time.

    Workloads: `wl` is the locally-repetitive motif workload speculation
    targets; `hwl` is the heavy-tail NON-repetitive workload where a
    prompt-lookup drafter finds nothing — the separation the
    model-vs-ngram accept A/B exists to show (a draft MODEL still agrees
    with the target there; self-speculation maximally so).

    Arms (median tok/s over `reps` passes each):
      off_rep     spec 0, steps 1        — sequential baseline
      ngram_rep   spec K, ngram, static  — the PR-12 configuration
      model_rep   spec K, model, static  — batched draft-model dispatch
      scan_heavy  spec 0, steps scan_k   — multi-step baseline
      ngram_heavy / model_heavy          — the accept-rate A/B
      auto_rep / auto_heavy              — spec K model + dynamic k +
                                           decode_steps scan_k, mode auto

    Gates: `accept_model_gt_ngram` (strict, heavy-tail — the drafter
    upgrade's existence proof), `auto_ok_rep` / `auto_ok_heavy` (auto >=
    `tol` x `decode_mode=static` with the SAME spec/scan knobs — the
    pre-choice auto removes must never have been the better choice; tol
    absorbs CPU-host timing noise — at small rehearse scales the
    same-knob ratio sits near 0.9 with several-percent jitter, so the
    default leaves real margin), `sig_stable` (ONE draft signature
    across every model arm, verify/scan signatures unmoved by
    dynamic/auto) and `reconcile_ok` (every arm emitted exactly
    reps * n * max_new tokens).  The spec-OFF medians ride along
    unguarded: on a CPU host the draft rollout costs as much as the
    target step it saves, so spec-on wall time trails spec-off there —
    the same dispatch-bound caveat as the multi-step bench (PERF.md
    'Reading the multi-step bench'); the hardware queue carries the
    real comparison."""
    import numpy as np

    from paddle_tpu.serving.drafter import ModelDrafter, NgramDrafter

    def rep_sets():
        return [make_repetitive_requests(seed=seed + 1 + r, **wl)
                for r in range(reps)]

    def heavy_sets():
        return [make_heavytail_requests(seed=seed + 101 + r, **hwl)
                for r in range(reps)]

    S = len(eng.slots)
    if eng.prefill_chunk is not None:
        eng.set_chunking(eng.prefill_chunk,
                         eng.prefill_chunk + S * (spec_k + 1))
    # self-speculation from the ENGINE's own executor/params: the
    # strongest drafter available without a training run, and exactly
    # what `--drafter model` deploys
    model = ModelDrafter.from_target(eng.executor, eng.params)
    ngram = NgramDrafter()

    def arm(sets_fn, k, drafter, dynamic, steps, mode):
        eng.set_speculation(k, drafter=drafter, dynamic=dynamic)
        eng.set_decode_steps(steps)
        eng.set_decode_mode(mode)
        warm_workload(eng, sets_fn()[:1])
        d0, a0 = eng.n_spec_drafted, eng.n_spec_accepted
        c0 = eng.n_spec_chains
        vals, toks = [], 0
        for reqs in sets_fn():
            rec = run_workload(eng, reqs)
            vals.append(rec["tokens"] / rec["seconds"])
            toks += rec["tokens"]
        drafted = eng.n_spec_drafted - d0
        chains = eng.n_spec_chains - c0
        return {
            "tok_per_sec": float(np.median(vals)),
            "accept_rate": ((eng.n_spec_accepted - a0) / drafted
                            if drafted else 0.0),
            # mean drafted per chain = the depth the policy actually
            # ran at (k=0 windows draft nothing and open no chain)
            "effective_k": drafted / chains if chains else 0.0,
            "tokens": int(toks),
        }

    arms = {
        "off_rep": arm(rep_sets, 0, None, False, 1, "static"),
        "ngram_rep": arm(rep_sets, spec_k, ngram, False, 1, "static"),
        "model_rep": arm(rep_sets, spec_k, model, False, 1, "static"),
        "scan_heavy": arm(heavy_sets, 0, None, False, scan_k, "static"),
        "ngram_heavy": arm(heavy_sets, spec_k, ngram, False, 1, "static"),
        "model_heavy": arm(heavy_sets, spec_k, model, False, 1, "static"),
        "static_rep": arm(rep_sets, spec_k, model, True, scan_k,
                          "static"),
        "static_heavy": arm(heavy_sets, spec_k, model, True, scan_k,
                            "static"),
        "auto_rep": arm(rep_sets, spec_k, model, True, scan_k, "auto"),
        "auto_heavy": arm(heavy_sets, spec_k, model, True, scan_k,
                          "auto"),
    }
    eng.kv.check()
    from paddle_tpu.obs.compile_watch import get_compile_watch
    draft_sigs = get_compile_watch().signature_count("serving.draft_step")
    best_rep = max(arms[a]["tok_per_sec"]
                   for a in ("off_rep", "ngram_rep", "model_rep"))
    best_heavy = max(arms[a]["tok_per_sec"]
                     for a in ("scan_heavy", "ngram_heavy",
                               "model_heavy"))
    out = {
        "spec_k": int(spec_k), "scan_k": int(scan_k),
        "max_step_tokens": int(eng.max_step_tokens),
        "accept_model_gt_ngram": (arms["model_heavy"]["accept_rate"]
                                  > arms["ngram_heavy"]["accept_rate"]),
        "auto_ok_rep": (arms["auto_rep"]["tok_per_sec"]
                        >= tol * arms["static_rep"]["tok_per_sec"]),
        "auto_ok_heavy": (arms["auto_heavy"]["tok_per_sec"]
                          >= tol * arms["static_heavy"]["tok_per_sec"]),
        "best_static_rep_tok_per_sec": best_rep,
        "best_static_heavy_tok_per_sec": best_heavy,
        # ONE batched draft program serves every model arm — dynamic k
        # and auto mode slice host-side, they never re-lower
        "sig_stable": (draft_sigs == 1
                       and eng._spec_step._cache_size() == 1
                       and eng._decode_step._cache_size() == 1),
        "reconcile_ok": all(
            a["tokens"] == reps * w["n"] * w["max_new"]
            for a, w in ((arms[n], wl) for n in
                         ("off_rep", "ngram_rep", "model_rep",
                          "auto_rep"))) and all(
            arms[n]["tokens"] == reps * hwl["n"] * hwl["max_new"]
            for n in ("scan_heavy", "ngram_heavy", "model_heavy",
                      "auto_heavy")),
    }
    for name, a in arms.items():
        out[f"{name}_tok_per_sec"] = a["tok_per_sec"]
        out[f"{name}_accept_rate"] = round(a["accept_rate"], 4)
        out[f"{name}_effective_k"] = round(a["effective_k"], 3)
    out["ok"] = (out["accept_model_gt_ngram"] and out["auto_ok_rep"]
                 and out["auto_ok_heavy"] and out["sig_stable"]
                 and out["reconcile_ok"])
    return out


def measure_scan(eng, wl: dict, reps: int, seed: int, k: int) -> dict:
    """Multi-step decode A/B on ONE engine: the identical mixed-length
    workload (fresh Request objects each pass, same seeds) at
    decode_steps=1 (one dispatch per token) then decode_steps=k (ONE
    jitted lax.scan of k decode bodies per dispatch whenever every live
    slot is pure-decode) — emitted tokens are identical by construction
    (tests/test_multi_step.py's oracle), so the only deltas are
    dispatches-per-token and wall time.  Closed loop: the scan's win is
    host-dispatch amortization, arrival jitter would only blur it.

    set_decode_steps requires an idle engine — both flips happen between
    run_workload calls, when every slot has drained.  sig_stable pins
    the compiled-program story: the k=1 decode step stays at ONE
    signature across both arms and the scan arm compiles exactly ONE
    scanned program (the body appears ONCE in its HLO, as a while loop).
    reconcile_ok is the ceil(n/k) dispatch evidence: greedy with eos off
    means both arms emit exactly n * max_new tokens, and every scan
    flush advances its slots k steps — `scan_steps == k * scan_flushes`
    with `scan_flushes > 0` (steps where admission/prefill interleaves
    fall back to k=1 and touch neither counter)."""
    import numpy as np

    def sets():
        return [make_requests(seed=seed + 1 + r, **wl)
                for r in range(reps)]

    eng.set_decode_steps(1)
    warm_workload(eng, [make_requests(seed=seed, **wl)] + sets())
    base_vals, base_disp = [], 0
    for reqs in sets():
        rec = run_workload(eng, reqs)
        base_vals.append(rec["tokens"] / rec["seconds"])
        base_disp += rec["decode_steps"]

    eng.set_decode_steps(k)
    eng.run(make_requests(seed=seed, **wl))      # scan-signature warm
    decode_sigs = eng._decode_step._cache_size()
    scan_sigs = eng._scan_step._cache_size() if eng._scan_step else 0
    f0, s0 = eng.n_scan_flushes, eng.n_scan_steps
    vals, toks, disp = [], 0, 0
    for reqs in sets():
        rec = run_workload(eng, reqs)
        vals.append(rec["tokens"] / rec["seconds"])
        toks += rec["tokens"]
        disp += rec["decode_steps"]
    eng.kv.check()
    flushes = eng.n_scan_flushes - f0
    steps = eng.n_scan_steps - s0
    base_med, scan_med = float(np.median(base_vals)), float(np.median(vals))
    return {
        "sig_stable": (eng._decode_step._cache_size() == decode_sigs
                       and eng._scan_step is not None
                       and eng._scan_step._cache_size() == scan_sigs
                       and scan_sigs == 1),
        "decode_steps": int(k),
        "baseline_tok_per_sec": base_med,
        "scan_tok_per_sec": scan_med,
        "speedup_vs_baseline": scan_med / base_med if base_med else 0.0,
        "scan_flushes": int(flushes),
        "scan_steps": int(steps),
        "tokens": int(toks),
        "baseline_decode_steps": int(base_disp),
        "scan_decode_steps": int(disp),
        "reconcile_ok": (flushes > 0 and steps == k * flushes
                         and toks == reps * wl["n"] * wl["max_new"]),
    }


# ---------------------------------------------------------------------------
# fleet bench: one router + N replica SUBPROCESSES (tools/serve.py) vs one
# replica, on the prefix-skew workload, affinity vs random placement
# ---------------------------------------------------------------------------

def _spawn_replica(args, seed: int = 1, role: str = None):
    """One tools/serve.py subprocess built from the SAME model recipe as
    build_engine (identical params across replicas: same config, same
    seed); returns (proc, host, port) once its SERVE_JSON line prints."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    argv = [sys.executable, os.path.join(repo, "tools", "serve.py"),
            "--config", "demo/model_zoo/transformer_lm.py",
            "--config-args",
            f"vocab={args.vocab},dim={args.dim},layers={args.layers},"
            f"heads={args.heads},batch_size={args.slots},"
            f"compute_dtype={args.dtype}",
            "--slots", str(args.slots), "--page-size", str(args.page_size),
            "--max-context", str(args.max_context),
            "--max-queue", "64", "--seed", str(seed), "--port", "0"]
    if role:
        argv += ["--role", role]
    env = dict(os.environ, PYTHONPATH=repo)
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True, cwd=repo,
                            env=env)
    import select

    t0 = time.time()
    while time.time() - t0 < 600:
        # select-gate the pipe: a replica wedged BEFORE printing its bind
        # line (stuck compile, hung backend init) must trip this watchdog,
        # not block readline() until the caller's outer timeout kills the
        # whole bench with no diagnosis
        ready, _, _ = select.select([proc.stdout], [], [], 5.0)
        if not ready:
            if proc.poll() is not None:
                raise RuntimeError(f"replica died before binding (rc="
                                   f"{proc.returncode})")
            continue
        line = proc.stdout.readline()
        if not line and proc.poll() is not None:
            raise RuntimeError(f"replica died before binding (rc="
                               f"{proc.returncode})")
        if line.startswith("SERVE_JSON:"):
            addr = json.loads(line[len("SERVE_JSON:"):])
            return proc, addr["host"], addr["port"]
    proc.kill()
    raise RuntimeError("replica never printed SERVE_JSON within 600s")


def _stop_procs(procs) -> None:
    for proc in procs:
        if proc.poll() is None:
            proc.terminate()           # serve.py's SIGTERM drain path
    for proc in procs:
        try:
            proc.wait(timeout=60)
        except Exception:              # noqa: BLE001 — wedged child
            proc.kill()
            proc.wait(timeout=10)


def run_client_workload(host: str, port: int, prompts, max_new: int,
                        concurrency: int) -> dict:
    """Closed-loop client-side drive: `concurrency` threads, each with
    its own ServingClient connection, pulling prompts off one shared
    list.  Returns wall seconds, generated tokens, first-token p50 (ms),
    and the failure list (must be empty for a valid measurement)."""
    import queue as _queue
    import threading

    from paddle_tpu.serving.client import ServingClient

    work: _queue.Queue = _queue.Queue()
    for i, p in enumerate(prompts):
        work.put((i, [int(t) for t in p]))
    tokens = [0] * max(1, concurrency)
    first_tok: list = []
    failures: list = []
    lock = threading.Lock()

    def worker(wid: int):
        try:
            with ServingClient(host, port, timeout=600) as c:
                while True:
                    try:
                        i, p = work.get_nowait()
                    except _queue.Empty:
                        return
                    t0 = time.perf_counter()
                    seen = []

                    def on_tok(rid, tok, idx, _t0=t0, _seen=seen):
                        if idx == 0:
                            _seen.append(time.perf_counter() - _t0)

                    toks, reason = c.generate(p, max_new=max_new,
                                              on_token=on_tok)
                    tokens[wid] += len(toks) - len(p)
                    with lock:
                        first_tok.extend(seen)
                        if reason not in ("length", "stop"):
                            failures.append(f"req {i}: reason={reason}")
        except Exception as e:             # noqa: BLE001 — a failed
            with lock:                     # worker is a failed bench
                failures.append(f"worker {wid}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    import numpy as np

    return {"seconds": dt, "tokens": int(sum(tokens)),
            "tok_per_sec": sum(tokens) / dt if dt else 0.0,
            "first_tok_ms_p50": round(float(
                np.percentile(first_tok, 50)) * 1e3, 3) if first_tok
            else 0.0,
            "first_tok_ms_p99": round(float(
                np.percentile(first_tok, 99)) * 1e3, 3) if first_tok
            else 0.0,
            "failures": failures}


def _replica_prefix_counts(addrs) -> tuple[int, int]:
    """Aggregate (prefix_hits, prefix_misses) polled DIRECTLY from each
    replica (the router's stats are fleet-shaped)."""
    from paddle_tpu.serving.client import ServingClient

    hits = misses = 0
    for host, port in addrs:
        with ServingClient(host, port, timeout=60) as c:
            s = c.stats(stale_ok=True)
        hits += int(s.get("prefix_hits") or 0)
        misses += int(s.get("prefix_misses") or 0)
    return hits, misses


def measure_fleet(args) -> dict:
    """The fleet A/B (ISSUE 10): the SAME prefix-skew workload through
    (a) ONE replica, connected directly — the no-router baseline;
    (b) a router + N replica subprocesses, policy=random — fan-out with
        the prefix cache sharded blindly (the placement strawman);
    (c) a router + N replicas, policy=affinity — the KV-aware placement.

    Every arm gets FRESH replica processes (a warm prefix tree from the
    previous arm would corrupt the hit-rate comparison) and an untimed
    warmup pass over a DIFFERENT prefix pool (same shapes: compiles the
    mixed/decode signatures and settles the engines without pre-seeding
    the measured prefixes).  Reported: tokens/s per arm, aggregate
    prefix-cache hit rate per arm (polled from the replicas directly),
    and `affinity_hit_gt_random` — the acceptance comparison: affinity
    routing must beat random routing's hit rate on the same workload."""
    wl = dict(n=args.num_requests, prefix_pool=args.prefix_pool,
              prefix_len=args.prefix_len, prefix_skew=args.prefix_skew,
              suffix_lo=args.suffix_lo, suffix_hi=args.suffix_hi,
              vocab=args.vocab)
    timed_prompts = make_prefix_prompts(pool_seed=args.seed,
                                        seed=args.seed + 1, **wl)
    warm_prompts = make_prefix_prompts(pool_seed=args.seed + 1000,
                                       seed=args.seed + 1001, **wl)

    def one_arm(n_replicas: int, policy, trace_probe: bool = False):
        from paddle_tpu.fleet import FleetRouter

        procs, addrs = [], []
        rt = None
        try:
            for _ in range(n_replicas):
                proc, host, port = _spawn_replica(args)
                procs.append(proc)
                addrs.append((host, port))
            if policy is None:
                host, port = addrs[0]
            else:
                rkw = {}
                if trace_probe:
                    # the probe arm gets a PRIVATE router tracer ring so
                    # flipping it cannot touch the bench process's
                    # global tracer state
                    from paddle_tpu.obs import Tracer

                    rkw["tracer"] = Tracer()
                rt = FleetRouter(port=0, replicas=addrs, policy=policy,
                                 **rkw)
                host, port = rt.start_background()
            warm = run_client_workload(host, port, warm_prompts,
                                       args.max_new, args.concurrency)
            if warm["failures"]:
                raise RuntimeError(f"warmup failed: {warm['failures'][:3]}")
            h0, m0 = _replica_prefix_counts(addrs)
            rec = run_client_workload(host, port, timed_prompts,
                                      args.max_new, args.concurrency)
            h1, m1 = _replica_prefix_counts(addrs)
            dh, dm = h1 - h0, m1 - m0
            rec["prefix_hits"] = dh
            rec["prefix_misses"] = dm
            rec["hit_rate"] = dh / (dh + dm) if dh + dm else 0.0
            if rt is not None:
                from paddle_tpu.serving.client import ServingClient

                with ServingClient(host, port, timeout=60) as c:
                    s = c.stats()
                rec["sheds"] = s["sheds"]
                rec["retries"] = s["retries"]
            if trace_probe and rt is not None:
                # the fleet trace-overhead probe, through the ROUTER
                # path on the SAME fleet (fresh replicas per pass would
                # drown the signal in process jitter — the lesson of
                # bench.py's single-engine probe, which reuses one
                # engine): an off pass and an on pass back to back on
                # the warmed fleet, tracing flipped LIVE between them —
                # the trace RPC's `enable` switch on every replica plus
                # the router's private ring.  Each pass draws a FRESH
                # prefix pool so both see cold measured prefixes.
                # Budget: <= 2% tok/s cost (negative = noise).
                import numpy as np

                from paddle_tpu.serving.client import ServingClient

                def set_tracing(on: bool):
                    for h_, p_ in addrs:
                        with ServingClient(h_, p_, timeout=60) as c:
                            c.trace(pings=1, enable=on)
                    rt.tracer.enabled = on

                # interleaved cycles with ALTERNATING order (off,on then
                # on,off): the fleet keeps warming monotonically across
                # passes (allocator, trees, host JIT), so a fixed order
                # reads the warming trend as tracing cost — alternation
                # cancels a linear drift exactly out of the means
                offs, ons, cycle_pcts = [], [], []
                # probe passes are sized UP from the arm workload (4x,
                # floor 128): the off/on delta is a couple percent at
                # most, so each pass must be long enough that client/
                # thread setup jitter sits well under it
                pwl = dict(wl, n=max(int(wl["n"]) * 4, 128))
                # probe passes SATURATE the fleet (closed loop, enough
                # client threads to keep every slot busy): an
                # underutilized fleet measures OS thread scheduling, not
                # serving throughput — saturation is where a tracing
                # cost would show and where the rate is stable
                pconc = max(args.concurrency, 8)
                # one DISCARDED pass at probe scale first: the arm's
                # warmup ran at workload scale, and the first probe-
                # scale pass is itself a warmup (fuller pools, new
                # allocation pattern) — its transient would otherwise
                # land entirely on whichever side runs first
                run_client_workload(
                    host, port, make_prefix_prompts(
                        pool_seed=args.seed + 1900,
                        seed=args.seed + 1901, **pwl),
                    args.max_new, pconc)
                for cyc in range(max(1, int(getattr(
                        args, "trace_overhead_cycles", 5)))):
                    order = (False, True) if cyc % 2 == 0 \
                        else (True, False)
                    pair = {}
                    for on_pass in order:
                        prompts = make_prefix_prompts(
                            pool_seed=args.seed + 2000 + 10 * cyc
                            + int(on_pass),
                            seed=args.seed + 2500 + 10 * cyc
                            + int(on_pass), **pwl)
                        set_tracing(on_pass)
                        r = run_client_workload(host, port, prompts,
                                                args.max_new, pconc)
                        rec["failures"] = rec["failures"] + r["failures"]
                        pair[on_pass] = r["tok_per_sec"]
                        (ons if on_pass else offs).append(
                            r["tok_per_sec"])
                    if pair.get(False):
                        # per-cycle pairwise overhead: the two passes of
                        # a cycle are adjacent in time, so slow machine
                        # drift cancels within each pair; the MEDIAN
                        # over cycles then discards a contended outlier
                        cycle_pcts.append(
                            100.0 * (pair[False] - pair[True])
                            / pair[False])
                set_tracing(False)
                rec["trace_off_tok_per_sec"] = round(
                    float(np.mean(offs)), 1)
                rec["trace_on_tok_per_sec"] = round(
                    float(np.mean(ons)), 1)
                rec["trace_overhead_pct"] = round(
                    float(np.median(cycle_pcts)), 2) \
                    if cycle_pcts else 0.0
                # per-cycle spread, so a reader can tell a real cost
                # from machine noise (the CPU-rehearse caveat PERF.md
                # applies to every serving number)
                rec["trace_overhead_spread_pct"] = round(
                    float(np.max(cycle_pcts) - np.min(cycle_pcts)), 2) \
                    if cycle_pcts else 0.0
            return rec
        finally:
            if rt is not None:
                rt.stop_background(drain=True)
            _stop_procs(procs)

    single = one_arm(1, None)
    random_arm = one_arm(args.fleet, "random")
    affinity = one_arm(args.fleet, "affinity",
                       trace_probe=getattr(args, "trace_overhead", True))
    ok = not (single["failures"] or random_arm["failures"]
              or affinity["failures"])
    return {
        "fleet": args.fleet,
        "concurrency": args.concurrency,
        "ok": ok,
        "failures": (single["failures"] + random_arm["failures"]
                     + affinity["failures"])[:5],
        "trace_off_tok_per_sec": affinity.get("trace_off_tok_per_sec"),
        "trace_on_tok_per_sec": affinity.get("trace_on_tok_per_sec"),
        "trace_overhead_pct": affinity.get("trace_overhead_pct"),
        "trace_overhead_spread_pct":
            affinity.get("trace_overhead_spread_pct"),
        "tok_per_sec": round(affinity["tok_per_sec"], 1),
        "single_tok_per_sec": round(single["tok_per_sec"], 1),
        "random_tok_per_sec": round(random_arm["tok_per_sec"], 1),
        "speedup_vs_single": round(
            affinity["tok_per_sec"] / single["tok_per_sec"], 3)
        if single["tok_per_sec"] else 0.0,
        "hit_rate_affinity": round(affinity["hit_rate"], 4),
        "hit_rate_random": round(random_arm["hit_rate"], 4),
        "hit_rate_single": round(single["hit_rate"], 4),
        "affinity_hit_gt_random":
            affinity["hit_rate"] > random_arm["hit_rate"],
        "first_tok_ms_p50": affinity["first_tok_ms_p50"],
        "random_first_tok_ms_p50": random_arm["first_tok_ms_p50"],
        "router_sheds": affinity.get("sheds", 0.0),
        "router_retries": affinity.get("retries", 0.0),
    }


# ---------------------------------------------------------------------------
# disaggregated prefill/decode bench: router + 2 colocated role=both
# replicas vs router + 1 prefill-role + 1 decode-role replica, the SAME
# long-prompt workload (docs/serving.md "Disaggregated prefill/decode")
# ---------------------------------------------------------------------------

def measure_disagg(args) -> dict:
    """The disaggregation A/B (ISSUE 19): the SAME prefix-skew workload
    (same seeds, same request budget) through
      (a) colocated — a router over 2 role=both replicas (each request
          prefills AND decodes where it lands);
      (b) disagg — a router over 1 prefill-role + 1 decode-role replica:
          long prompts prefill on one, kv_push their committed pages,
          and decode on the other.
    Every arm gets FRESH replica subprocesses and an untimed warmup over
    a different prefix pool.  Reported: tokens/s + first-token p50/p99
    per arm, and the transfer ledger polled from the disagg router.
    Reconcile gate: zero failed requests in either arm, and the disagg
    arm genuinely shipped pages with zero push failures (a fallback-only
    run would silently measure colocated serving twice)."""
    wl = dict(n=args.num_requests, prefix_pool=args.prefix_pool,
              prefix_len=args.prefix_len, prefix_skew=args.prefix_skew,
              suffix_lo=args.suffix_lo, suffix_hi=args.suffix_hi,
              vocab=args.vocab)
    timed_prompts = make_prefix_prompts(pool_seed=args.seed,
                                        seed=args.seed + 1, **wl)
    warm_prompts = make_prefix_prompts(pool_seed=args.seed + 1000,
                                       seed=args.seed + 1001, **wl)

    def one_arm(roles):
        from paddle_tpu.fleet import FleetRouter
        from paddle_tpu.serving.client import ServingClient

        procs, addrs = [], []
        rt = None
        try:
            for role in roles:
                proc, host, port = _spawn_replica(args, role=role)
                procs.append(proc)
                addrs.append((host, port))
            rt = FleetRouter(port=0, replicas=addrs, policy="affinity")
            host, port = rt.start_background()
            warm = run_client_workload(host, port, warm_prompts,
                                       args.max_new, args.concurrency)
            if warm["failures"]:
                raise RuntimeError(f"warmup failed: {warm['failures'][:3]}")
            rec = run_client_workload(host, port, timed_prompts,
                                      args.max_new, args.concurrency)
            with ServingClient(host, port, timeout=60) as c:
                s = c.stats()
            for k in ("kv_pushes", "kv_push_failures", "kv_fallbacks",
                      "kv_pages_shipped", "sheds", "retries"):
                rec[k] = s[k]
            return rec
        finally:
            if rt is not None:
                rt.stop_background(drain=True)
            _stop_procs(procs)

    coloc = one_arm(["both", "both"])
    disagg = one_arm(["prefill", "decode"])
    ok = (not coloc["failures"] and not disagg["failures"]
          and disagg["kv_pages_shipped"] > 0
          and disagg["kv_push_failures"] == 0
          and disagg["kv_fallbacks"] == 0)
    return {
        "concurrency": args.concurrency,
        "ok": ok,
        "failures": (coloc["failures"] + disagg["failures"])[:5],
        "tok_per_sec": round(disagg["tok_per_sec"], 1),
        "coloc_tok_per_sec": round(coloc["tok_per_sec"], 1),
        "speedup_vs_coloc": round(
            disagg["tok_per_sec"] / coloc["tok_per_sec"], 3)
        if coloc["tok_per_sec"] else 0.0,
        "first_tok_ms_p50": disagg["first_tok_ms_p50"],
        "first_tok_ms_p99": disagg["first_tok_ms_p99"],
        "coloc_first_tok_ms_p50": coloc["first_tok_ms_p50"],
        "coloc_first_tok_ms_p99": coloc["first_tok_ms_p99"],
        "kv_pushes": disagg["kv_pushes"],
        "kv_push_failures": disagg["kv_push_failures"],
        "kv_fallbacks": disagg["kv_fallbacks"],
        "pages_shipped": disagg["kv_pages_shipped"],
        "router_sheds": disagg["sheds"],
        "router_retries": disagg["retries"],
    }


def build_engine(args, mesh=None):
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.trainer.trainer import Trainer

    cfg = parse_config(
        "demo/model_zoo/transformer_lm.py",
        f"vocab={args.vocab},dim={args.dim},layers={args.layers},"
        f"heads={args.heads},batch_size={args.slots},"
        f"compute_dtype={args.dtype}")
    tr = Trainer(cfg, seed=1)
    eng = ServingEngine(
        tr.executor, tr.params, num_slots=args.slots,
        page_size=args.page_size, max_context=args.max_context,
        num_pages=(getattr(args, "num_pages", 0) or None),
        spill_bytes_budget=(getattr(args, "spill_budget", 0) or 0),
        prefill_chunk=(getattr(args, "prefill_chunk", 0) or -1),
        max_step_tokens=(getattr(args, "max_step_tokens", 0) or None),
        mesh=mesh)
    return eng


# ---------------------------------------------------------------------------
# tensor-parallel bench: the SAME closed-loop workload on a single-device
# engine vs a mesh model=N sharded engine (docs/serving.md "Sharded decode")
# ---------------------------------------------------------------------------

def measure_tp(args) -> dict:
    """1-vs-N-shard A/B: identical request sets (same seeds) through a
    single-device engine and a tensor-parallel engine over `--mesh-model`
    devices, closed loop.  Reports tokens/s both arms plus the number
    sharding exists for — KV pool bytes resident PER SHARD (the sharded
    arm's per-chip HBM is 1/N of the single-chip pool) — and the
    signature-stability verdict (ONE decode + ONE mixed signature on the
    sharded engine too).  Token exactness across shard counts is
    tests/test_serving_tp.py's job.  On a CPU host run under
    XLA_FLAGS=--xla_force_host_platform_device_count=N (rehearse mode
    sets it); real speedups need real chips."""
    import jax
    import numpy as np

    from paddle_tpu.parallel.mesh import model_mesh

    n = int(args.mesh_model)
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"--mesh-model {n} needs {n} devices, have "
            f"{len(jax.devices())} — on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")
    base = dict(n=args.num_requests, prompt_lo=args.prompt_lo,
                prompt_hi=min(args.prompt_hi,
                              args.max_context - args.max_new - 1),
                max_new=args.max_new, vocab=args.vocab)

    def rep_sets():
        return [make_requests(seed=args.seed + 1 + r, **base)
                for r in range(args.reps)]

    arms = {}
    for label, shards in (("single", 1), ("tp", n)):
        eng = build_engine(args,
                           mesh=model_mesh(n) if shards > 1 else None)
        warm_workload(eng, [make_requests(seed=args.seed, **base)]
                      + rep_sets())
        sigs = eng._decode_step._cache_size()
        mixed = eng._mixed_step._cache_size()
        vals = []
        for reqs in rep_sets():
            rec = run_workload(eng, reqs)
            vals.append(rec["tokens"] / rec["seconds"])
        arms[label] = {
            "tok_per_sec": float(np.median(vals)),
            "pool_bytes_per_shard": int(eng.kv.pool_bytes_per_shard),
            "sig_stable": (eng._decode_step._cache_size() == sigs == 1
                           and eng._mixed_step._cache_size() == mixed),
            "tp_shards": eng.tp,
        }
        eng.executor.mesh = None       # arms must not inherit the mesh
    single, tp = arms["single"], arms["tp"]
    return {
        "mesh_model": n,
        "tok_per_sec": tp["tok_per_sec"],
        "single_tok_per_sec": single["tok_per_sec"],
        "speedup_vs_single": (tp["tok_per_sec"] / single["tok_per_sec"]
                              if single["tok_per_sec"] else 0.0),
        "pool_bytes_per_shard": tp["pool_bytes_per_shard"],
        "single_pool_bytes": single["pool_bytes_per_shard"],
        "pool_shrink_vs_single": (
            single["pool_bytes_per_shard"] / tp["pool_bytes_per_shard"]
            if tp["pool_bytes_per_shard"] else 0.0),
        "sig_stable": single["sig_stable"] and tp["sig_stable"],
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-requests", type=int, default=64)
    ap.add_argument("--rate", default="0",
                    help="comma list of offered req/s (0 = closed loop)")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-context", type=int, default=768)
    ap.add_argument("--prompt-lo", type=int, default=32)
    ap.add_argument("--prompt-hi", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    # prefix-skew workload (docs/serving.md "Prefix caching"): Zipf draws
    # over a pool of shared system-prompt prefixes + unique suffixes,
    # measured cache-off then cache-on (closed loop; --rate is ignored)
    ap.add_argument("--prefix-skew", type=float, default=None,
                    metavar="EXP",
                    help="run the prefix-skew A/B workload with this Zipf "
                         "exponent (reports hit rate, prefill tokens "
                         "saved, first-token p50 vs no-cache baseline)")
    ap.add_argument("--prefix-pool", type=int, default=8,
                    help="number of distinct shared prefixes")
    ap.add_argument("--prefix-len", type=int, default=128,
                    help="shared prefix length in tokens")
    ap.add_argument("--suffix-lo", type=int, default=16)
    ap.add_argument("--suffix-hi", type=int, default=64)
    # host KV spill tier (docs/serving.md "KV spill tier"): A/B the same
    # prefix-skew workload with the spill tier off then on — pair with
    # --num-pages sized BELOW the Zipf working set so the off arm is
    # forced to destroy cold prefixes under pool pressure
    ap.add_argument("--spill-budget", type=int, default=0, metavar="BYTES",
                    help="run the host-spill A/B: prefix cache on both "
                         "arms, spill tier off then on at BYTES of host "
                         "RAM (reports hit rate, restored pages, prefill "
                         "tokens saved, first-token p50 both arms)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV page-pool size override incl. trash page "
                         "(0 = engine default; the spill A/B wants this "
                         "below the workload's working set)")
    # chunked prefill (docs/serving.md "Chunked prefill"): --prompt-dist
    # heavy-tail runs the A/B (legacy whole-prompt prefill vs budgeted
    # mixed steps) on a Pareto/lognormal prompt-length workload
    # fleet (docs/serving.md "Fleet"): --fleet N runs the router A/B —
    # one replica direct vs router+N replica subprocesses, prefix-skew
    # workload, affinity vs random placement hit rates
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="run the fleet A/B with N replica subprocesses "
                         "(reports tok/s vs one replica and affinity-vs-"
                         "random prefix hit rates)")
    ap.add_argument("--concurrency", type=int, default=8,
                    help="client threads driving the fleet workload")
    # disaggregated prefill/decode (docs/serving.md "Disaggregated
    # prefill/decode"): --disagg runs the role-split A/B — router + 2
    # colocated role=both replicas vs router + 1 prefill + 1 decode
    # replica with the kv_push page-transfer plane, same seeds/budget
    ap.add_argument("--disagg", action="store_true",
                    help="run the disaggregated prefill/decode A/B "
                         "(reports tok/s + first-token p50/p99 per arm "
                         "and the kv_xfer ledger: pushes, pages shipped, "
                         "failures, fallbacks)")
    ap.add_argument("--no-trace-overhead", dest="trace_overhead",
                    action="store_false", default=True,
                    help="skip the fleet trace-overhead arm (a fourth "
                         "affinity arm with router + replica tracing ON "
                         "through the router path; <= 2%% tok/s budget)")
    ap.add_argument("--prompt-dist", choices=["uniform", "heavy-tail"],
                    default="uniform",
                    help="heavy-tail: lognormal body + Pareto tail prompt "
                         "lengths, measured chunking off vs on (first-"
                         "token and inter-token p50/p99 both sides)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunk size in tokens (0 = engine default, "
                         "4*page_size)")
    ap.add_argument("--max-step-tokens", type=int, default=0,
                    help="per-step token budget (0 = engine default, "
                         "prefill_chunk + slots)")
    # tensor-parallel A/B (docs/serving.md "Sharded decode"): the same
    # closed-loop workload on one device vs a mesh model=N sharded engine
    ap.add_argument("--mesh-model", type=int, default=0, metavar="N",
                    help="run the 1-vs-N-shard A/B: tokens/s + KV pool "
                         "bytes per shard, single-device engine vs "
                         "attention-head/KV-pool sharding over N devices")
    # speculative decoding A/B (docs/serving.md "Speculative decoding"):
    # spec-off vs spec-on at k on ONE engine, locally-repetitive prompts
    ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                    help="run the speculative-decoding A/B: the same "
                         "locally-repetitive workload with speculation "
                         "off then on at K drafts/slot/step (reports "
                         "tok/s both arms, accept rate, drafted/"
                         "accepted counters reconciled to tokens)")
    ap.add_argument("--drafter", choices=["ngram", "model"],
                    default="ngram",
                    help="with --spec-k: 'model' runs the adaptive-"
                         "speculation matrix instead of the plain A/B — "
                         "ngram vs batched draft-model (self-speculation)"
                         " vs decode_mode=auto arms on repetitive AND "
                         "heavy-tail workloads, with the model-vs-ngram "
                         "accept-rate gate")
    ap.add_argument("--spec-dynamic", action="store_true",
                    help="with --spec-k: enable the per-slot dynamic-k "
                         "policy in the auto arms (implies the adaptive "
                         "matrix, like --drafter model)")
    # multi-step decode A/B (docs/serving.md "Multi-step decode"):
    # decode_steps=1 vs ONE scanned dispatch of K decode bodies
    ap.add_argument("--decode-steps", type=int, default=0, metavar="K",
                    help="run the multi-step decode A/B: the same "
                         "closed-loop workload at decode_steps=1 then "
                         "with K scanned decode bodies per dispatch "
                         "(reports tok/s both arms, scan flush/step "
                         "counters reconciled to tokens; on CPU expect "
                         "<=1x — PERF.md 'Reading the multi-step bench')")
    args = ap.parse_args()

    import numpy as np

    if args.mesh_model > 1:
        m = measure_tp(args)
        print(json.dumps({
            "bench": "serving_tp",
            "num_requests": args.num_requests, "slots": args.slots,
            "page_size": args.page_size, "max_context": args.max_context,
            "prompt_lens": [args.prompt_lo, args.prompt_hi],
            "max_new": args.max_new, "dim": args.dim,
            "layers": args.layers, "heads": args.heads,
            "dtype": args.dtype, "reps": args.reps,
            "lm_serving_tp_tok_per_sec": m["tok_per_sec"],
            **{k: m[k] for k in (
                "mesh_model", "single_tok_per_sec", "speedup_vs_single",
                "pool_bytes_per_shard", "single_pool_bytes",
                "pool_shrink_vs_single", "sig_stable")},
        }), flush=True)
        return 0 if m["sig_stable"] else 1

    if args.disagg:
        if args.prefix_skew is None:
            args.prefix_skew = 1.0     # the disagg A/B rides the prefix-
                                       # skew workload too (long shared
                                       # prompts are what disagg splits)
        m = measure_disagg(args)
        print(json.dumps({
            "bench": "serving_disagg",
            "num_requests": args.num_requests, "slots": args.slots,
            "page_size": args.page_size, "max_context": args.max_context,
            "prefix_pool": args.prefix_pool, "prefix_len": args.prefix_len,
            "prefix_skew": args.prefix_skew,
            "suffix_lens": [args.suffix_lo, args.suffix_hi],
            "max_new": args.max_new, "dim": args.dim,
            "layers": args.layers, "dtype": args.dtype,
            "lm_serving_disagg_tok_per_sec": m["tok_per_sec"],
            **{k: m[k] for k in (
                "concurrency", "coloc_tok_per_sec", "speedup_vs_coloc",
                "first_tok_ms_p50", "first_tok_ms_p99",
                "coloc_first_tok_ms_p50", "coloc_first_tok_ms_p99",
                "kv_pushes", "kv_push_failures", "kv_fallbacks",
                "pages_shipped", "router_sheds", "router_retries",
                "ok", "failures")},
        }), flush=True)
        return 0 if m["ok"] else 1

    if args.fleet > 0:
        if args.prefix_skew is None:
            args.prefix_skew = 1.0     # --prefix-skew doubles as the
        m = measure_fleet(args)        # engine-A/B trigger; fleet mode
                                       # just needs a Zipf exponent
        print(json.dumps({
            "bench": "serving_fleet",
            "num_requests": args.num_requests, "slots": args.slots,
            "page_size": args.page_size, "max_context": args.max_context,
            "prefix_pool": args.prefix_pool, "prefix_len": args.prefix_len,
            "prefix_skew": args.prefix_skew,
            "suffix_lens": [args.suffix_lo, args.suffix_hi],
            "max_new": args.max_new, "dim": args.dim,
            "layers": args.layers, "dtype": args.dtype,
            "lm_serving_fleet_tok_per_sec": m["tok_per_sec"],
            "lm_serving_fleet_trace_overhead_pct": m["trace_overhead_pct"],
            **{k: m[k] for k in (
                "fleet", "concurrency", "single_tok_per_sec",
                "random_tok_per_sec", "speedup_vs_single",
                "hit_rate_affinity", "hit_rate_random", "hit_rate_single",
                "affinity_hit_gt_random", "first_tok_ms_p50",
                "random_first_tok_ms_p50", "router_sheds",
                "router_retries", "trace_off_tok_per_sec",
                "trace_on_tok_per_sec", "trace_overhead_spread_pct",
                "ok", "failures")},
        }), flush=True)
        return 0 if m["ok"] else 1

    if args.spec_k > 0 and (args.drafter == "model" or args.spec_dynamic):
        eng = build_engine(args)
        hi = min(args.prompt_hi, args.max_context - args.max_new - 1)
        wl = dict(n=args.num_requests, prompt_lo=args.prompt_lo,
                  prompt_hi=hi, max_new=args.max_new, vocab=args.vocab)
        hwl = dict(wl)
        m = measure_spec_modes(eng, wl, hwl, args.reps, args.seed,
                               args.spec_k)
        print(json.dumps({
            "bench": "serving_spec_modes",
            "num_requests": args.num_requests, "slots": args.slots,
            "page_size": args.page_size, "max_context": args.max_context,
            "prompt_lens": [args.prompt_lo, hi], "max_new": args.max_new,
            "dim": args.dim, "layers": args.layers, "dtype": args.dtype,
            "reps": args.reps, "drafter": "model",
            "spec_dynamic": True,
            "lm_serving_spec_model_tok_per_sec":
                round(m["model_rep_tok_per_sec"], 1),
            "lm_serving_spec_auto_tok_per_sec":
                round(m["auto_rep_tok_per_sec"], 1),
            "lm_serving_spec_effective_k":
                round(m["auto_rep_effective_k"], 3),
            "lm_serving_spec_model_accept_rate_heavy":
                m["model_heavy_accept_rate"],
            "lm_serving_spec_ngram_accept_rate_heavy":
                m["ngram_heavy_accept_rate"],
            **{k: m[k] for k in sorted(m)},
        }), flush=True)
        return 0 if m["ok"] else 1

    if args.spec_k > 0:
        eng = build_engine(args)
        hi = min(args.prompt_hi, args.max_context - args.max_new - 1)
        wl = dict(n=args.num_requests, prompt_lo=args.prompt_lo,
                  prompt_hi=hi, max_new=args.max_new, vocab=args.vocab)
        m = measure_spec(eng, wl, args.reps, args.seed, args.spec_k)
        print(json.dumps({
            "bench": "serving_spec",
            "num_requests": args.num_requests, "slots": args.slots,
            "page_size": args.page_size, "max_context": args.max_context,
            "prompt_lens": [args.prompt_lo, hi], "max_new": args.max_new,
            "dim": args.dim, "layers": args.layers, "dtype": args.dtype,
            "reps": args.reps,
            "lm_serving_spec_tok_per_sec": round(m["spec_tok_per_sec"], 1),
            "lm_serving_spec_accept_rate": round(m["accept_rate"], 4),
            **{k: m[k] for k in (
                "spec_k", "max_step_tokens", "baseline_tok_per_sec",
                "speedup_vs_baseline", "drafted", "accepted", "chains",
                "spec_tokens", "tokens", "baseline_decode_steps",
                "spec_decode_steps", "reconcile_ok", "sig_stable")},
        }), flush=True)
        return 0 if m["sig_stable"] and m["reconcile_ok"] else 1

    if args.decode_steps > 1:
        eng = build_engine(args)
        hi = min(args.prompt_hi, args.max_context - args.max_new - 1)
        wl = dict(n=args.num_requests, prompt_lo=args.prompt_lo,
                  prompt_hi=hi, max_new=args.max_new, vocab=args.vocab)
        m = measure_scan(eng, wl, args.reps, args.seed, args.decode_steps)
        print(json.dumps({
            "bench": "serving_scan",
            "num_requests": args.num_requests, "slots": args.slots,
            "page_size": args.page_size, "max_context": args.max_context,
            "prompt_lens": [args.prompt_lo, hi], "max_new": args.max_new,
            "dim": args.dim, "layers": args.layers, "dtype": args.dtype,
            "reps": args.reps,
            "lm_serving_scan_tok_per_sec": round(m["scan_tok_per_sec"], 1),
            **{k: m[k] for k in (
                "decode_steps", "baseline_tok_per_sec",
                "speedup_vs_baseline", "scan_flushes", "scan_steps",
                "tokens", "baseline_decode_steps", "scan_decode_steps",
                "reconcile_ok", "sig_stable")},
        }), flush=True)
        return 0 if m["sig_stable"] and m["reconcile_ok"] else 1

    if args.spill_budget > 0:
        if args.prefix_skew is None:
            args.prefix_skew = 1.0     # the spill A/B rides the prefix-
                                       # skew workload; default the Zipf
                                       # exponent when only --spill-budget
                                       # is given
        eng = build_engine(args)
        wl = dict(n=args.num_requests, prefix_pool=args.prefix_pool,
                  prefix_len=args.prefix_len, prefix_skew=args.prefix_skew,
                  suffix_lo=args.suffix_lo, suffix_hi=args.suffix_hi,
                  max_new=args.max_new, vocab=args.vocab)
        m = measure_spill(eng, wl, args.reps, args.seed, args.spill_budget)
        print(json.dumps({
            "bench": "serving_spill",
            "num_requests": args.num_requests, "slots": args.slots,
            "page_size": args.page_size, "max_context": args.max_context,
            "prefix_pool": args.prefix_pool, "prefix_len": args.prefix_len,
            "prefix_skew": args.prefix_skew,
            "suffix_lens": [args.suffix_lo, args.suffix_hi],
            "max_new": args.max_new, "dim": args.dim,
            "layers": args.layers, "dtype": args.dtype, "reps": args.reps,
            "lm_serving_spill_hit_rate": round(m["hit_rate"], 4),
            "lm_serving_spill_tok_per_sec": round(m["tok_per_sec"], 1),
            **{k: m[k] for k in (
                "spill_budget", "num_pages", "host_pages", "host_bytes",
                "page_nbytes", "off_tok_per_sec", "first_tok_ms_p50",
                "off_first_tok_ms_p50", "off_hit_rate",
                "hit_rate_improved", "tokens_saved", "off_tokens_saved",
                "evictions", "off_evictions", "spilled_pages",
                "restored_pages", "restore_hits", "restore_tokens_saved",
                "off_spilled_pages", "restore_fn_sigs", "reconcile_ok",
                "sig_stable")},
        }), flush=True)
        return 0 if (m["sig_stable"] and m["reconcile_ok"]
                     and m["hit_rate_improved"]) else 1

    eng = build_engine(args)
    if args.prompt_dist == "heavy-tail":
        # the tail must FIT the pool: clamp at slot capacity minus the
        # decode budget (validate() would reject anything bigger anyway)
        hi = min(args.prompt_hi, args.max_context - args.max_new - 1)
        wl = dict(n=args.num_requests, prompt_lo=args.prompt_lo,
                  prompt_hi=hi, max_new=args.max_new, vocab=args.vocab)
        m = measure_chunked(eng, wl, args.reps, args.seed,
                            args.prefill_chunk or 4 * args.page_size,
                            args.max_step_tokens or None)
        print(json.dumps({
            "bench": "serving_chunked",
            "num_requests": args.num_requests, "slots": args.slots,
            "page_size": args.page_size, "max_context": args.max_context,
            "prompt_lens": [args.prompt_lo, hi], "max_new": args.max_new,
            "dim": args.dim, "layers": args.layers, "dtype": args.dtype,
            "reps": args.reps,
            "lm_serving_p99_itl_chunked_ms": m["itl_ms_p99"],
            **{k: m[k] for k in (
                "prefill_chunk", "max_step_tokens", "prefill_chunks",
                "baseline_itl_ms_p50", "baseline_itl_ms_p99",
                "itl_ms_p50",
                "baseline_first_tok_ms_p50", "baseline_first_tok_ms_p99",
                "first_tok_ms_p50", "first_tok_ms_p99",
                "baseline_tok_per_sec", "chunked_tok_per_sec",
                "p99_itl_improved", "p99_first_tok_improved",
                "sig_stable")},
        }), flush=True)
        return 0 if m["sig_stable"] else 1
    if args.prefix_skew is not None:
        wl = dict(n=args.num_requests, prefix_pool=args.prefix_pool,
                  prefix_len=args.prefix_len, prefix_skew=args.prefix_skew,
                  suffix_lo=args.suffix_lo, suffix_hi=args.suffix_hi,
                  max_new=args.max_new, vocab=args.vocab)
        m = measure_prefix_skew(eng, wl, args.reps, args.seed)
        # configured prefix share of the prompt tokens — the number the
        # tokens-saved rate should track (PERF.md "reading the hit rate")
        share = args.prefix_len / (
            args.prefix_len + (args.suffix_lo + args.suffix_hi) / 2.0)
        print(json.dumps({
            "bench": "serving_prefix",
            "num_requests": args.num_requests, "slots": args.slots,
            "page_size": args.page_size, "max_context": args.max_context,
            "prefix_pool": args.prefix_pool, "prefix_len": args.prefix_len,
            "prefix_skew": args.prefix_skew,
            "suffix_lens": [args.suffix_lo, args.suffix_hi],
            "max_new": args.max_new, "dim": args.dim,
            "layers": args.layers, "dtype": args.dtype, "reps": args.reps,
            "prefix_share_configured": round(share, 3),
            "lm_serving_prefix_hit_rate": round(m["hit_rate"], 4),
            "lm_serving_prefill_tokens_saved_total": m["tokens_saved"],
            "first_tok_ms_p50": m["first_tok_ms_p50"],
            "baseline_first_tok_ms_p50": m["baseline_first_tok_ms_p50"],
            "tokens_per_sec_median": round(m["cached_tok_per_sec"], 1),
            "baseline_tokens_per_sec_median":
                round(m["baseline_tok_per_sec"], 1),
            "prefix_evictions": m["evictions"], "prefix_cow": m["cow"],
            "suffix_prefill_sigs": m["suffix_prefill_sigs"],
            "decode_sig_stable": m["decode_sig_stable"],
        }), flush=True)
        return 0 if m["decode_sig_stable"] else 1
    base = dict(n=args.num_requests, prompt_lo=args.prompt_lo,
                prompt_hi=args.prompt_hi, max_new=args.max_new,
                vocab=args.vocab)

    # every measured workload, generated up front so warmup can compile
    # exactly the buckets the timed reps will touch
    rep_sets = [make_requests(seed=args.seed + 1 + rep, **base)
                for rep in range(args.reps)]
    warm_workload(eng, [make_requests(seed=args.seed, **base)] + rep_sets)
    sigs = eng._decode_step._cache_size()
    mixed = eng._mixed_step._cache_size()
    buckets = len(eng._prefill_cache)

    ok = True
    for rate in [float(r) for r in str(args.rate).split(",") if r != ""]:
        vals, occs, pres = [], [], 0
        step_s, req_s = [], []
        rec = {}
        for rep in range(args.reps):
            reqs = make_requests(seed=args.seed + 1 + rep, **base)
            arr = poisson_arrivals(len(reqs), rate, seed=args.seed + rep)
            rec = run_workload(eng, reqs, arr)
            vals.append(rec["tokens"] / rec["seconds"])
            occs.append(rec["occupancy"])
            pres += rec["preemptions"]
            step_s += rec["step_seconds"]
            req_s += rec["req_seconds"]
        if eng._decode_step._cache_size() != sigs or \
                eng._mixed_step._cache_size() != mixed or \
                len(eng._prefill_cache) != buckets:
            ok = False
            print(json.dumps({"bench": "serving",
                              "error": "decode/mixed step or prefill "
                                       "bucket recompiled during the "
                                       "timed region"}), flush=True)
        q1, med, q3 = np.percentile(vals, [25, 50, 75])
        # per-token latency = busy decode-step duration (each live request
        # advances one token per step); per-request = admit -> finish.
        # p99 over all reps at this rate — the tail the capacity curve is
        # actually planned around, not the mean the throughput row shows.
        tok_p50, tok_p99 = (np.percentile(step_s, [50, 99]) * 1e3
                            if step_s else (0.0, 0.0))
        req_p50, req_p99 = (np.percentile(req_s, [50, 99]) * 1e3
                            if req_s else (0.0, 0.0))
        print(json.dumps({
            "bench": "serving", "rate_req_per_sec": rate,
            "num_requests": args.num_requests, "slots": args.slots,
            "page_size": args.page_size, "max_context": args.max_context,
            "prompt_lens": [args.prompt_lo, args.prompt_hi],
            "max_new": args.max_new,
            "dim": args.dim, "layers": args.layers, "dtype": args.dtype,
            "tokens_per_sec_median": round(float(med), 1),
            "tokens_per_sec_iqr": [round(float(q1), 1), round(float(q3), 1)],
            "occupancy": round(float(np.mean(occs)), 3),   # mean over reps —
            # stays consistent with the median throughput it sits next to
            "tok_latency_ms_p50": round(float(tok_p50), 3),
            "lm_serving_p99_tok_latency_ms": round(float(tok_p99), 3),
            "req_latency_ms_p50": round(float(req_p50), 3),
            "req_latency_ms_p99": round(float(req_p99), 3),
            "decode_steps": rec["decode_steps"],
            "preemptions": pres,
            "decode_signatures": eng._decode_step._cache_size(),
            "prefill_buckets": len(eng._prefill_cache),
            "reps": args.reps,
        }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
