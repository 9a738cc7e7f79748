"""Cell kind `serve`: the server's own code in the process that holds the
chip — tools/serve.py's flags and build_engine, ServingServer on loopback —
and the load generator as a child that speaks the wire protocol."""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import threading
import time

from benchmark.lib import arith
from benchmark.lib.common import (ProfilerWindow, check_weights_fit,
                                  compiles_by_site, compiles_total, log,
                                  memory_bytes, setup_jax)
from benchmark.lib.spec import load_module
from benchmark.lib import traffic as traffic_mod


def server_argv(cfg: dict, seed32: int) -> list[str]:
    """tools/serve.py's own command line for this configuration."""
    f = cfg["server_flags"]
    config_args = (
        f"vocab={cfg['vocab_size']},dim={cfg['hidden_size']},"
        f"layers={cfg['num_hidden_layers']},heads={cfg['num_attention_heads']},"
        f"kv_heads={cfg['num_key_value_heads']},ffn={cfg['intermediate_size']},"
        f"rope_theta={cfg['rope_theta']},batch_size=1,"
        f"compute_dtype={cfg['compute_dtype']},attn_impl={cfg['attn_impl']}")
    # every key of the configuration's `server_flags` is a flag of the tool
    # (`num_pages` -> `--num-pages`), so a later configuration sets one the
    # first did not by data alone
    flags = [w for k, v in f.items()
             for w in ("--" + k.replace("_", "-"), str(v))]
    return ["--config", cfg["dsl"], "--config-args", config_args, *flags,
            "--port", "0", "--seed", str(seed32)]


def parse_server_flags(serve_tool, argv: list[str]):
    """Let the tool's own main() parse its flags (so a flag a later PR adds
    gets its default), stopping where it would start the server."""
    got = {}

    async def capture(args):
        got["args"] = args
        return 0

    real = serve_tool.amain
    serve_tool.amain = capture
    try:
        serve_tool.main(argv)
    finally:
        serve_tool.amain = real
    return got["args"]


def seeded_weights(ref, cfg, engine, seed32: int):
    """The benchmark's own weights from the seed, in the engine's place."""
    w = ref.make_weights(cfg, seed32)
    check_weights_fit(engine.params, w)
    engine.params = w
    gc.collect()


def warm_up(c, tf: dict, vocab: int, seed: int, seconds: float) -> int:
    """Through the wire, before the window: the mix's extremes (decode and
    mixed steps), a shared prefix (copy-on-write), and one admission per
    distinct output length of the window's requests, cancelled at its first
    token (the per-length key-split program)."""
    n = 0
    for wave in traffic_mod.warm_requests(tf, vocab, seed % (2 ** 31 - 1)):
        c.collect([c.submit(r["prompt"], max_new=r["max_new"],
                            req_id=r["id"]) for r in wave])
        n += len(wave)
    lengths = traffic_mod.distinct_max_new(
        traffic_mod.serve_requests(tf, vocab, seed, seconds))
    open_ids = {c.submit([2, 3, 4], max_new=v, req_id=f"wl{v}"): False
                for v in lengths}
    while open_ids:
        msg = c.recv()
        rid = msg.get("id")
        if rid not in open_ids:
            continue
        if msg.get("type") == "token" and not open_ids[rid]:
            open_ids[rid] = True
            c.cancel(rid)
        elif msg.get("type") in ("done", "overload", "error"):
            del open_ids[rid]
    return n + len(lengths)


def start_engine(ctx, ref):
    """tools/serve.py's flags and build_engine; the seeded weights."""
    cfg = ctx.cfg
    serve_tool = load_module(os.path.join(ctx.bench.root, "tools", "serve.py"),
                             "tools_serve")
    args = parse_server_flags(serve_tool, server_argv(cfg, ctx.seed32))
    t = time.perf_counter()
    engine = serve_tool.build_engine(args)
    gc.collect()                 # the Trainer build_engine made, and its Adam
    log(f"ENGINE built in {time.perf_counter() - t:.1f}s: "
        f"{len(engine.slots)} slots, chunk {engine.prefill_chunk}, "
        f"step tokens {engine.max_step_tokens}")

    seeded_weights(ref, cfg, engine, ctx.seed32)
    return engine, args


def offer(ctx, host: str, port: int, tf: dict, seed: int, seconds: float,
          on_line=None, dump_times: str = "") -> dict:
    """One window of `tf` through the load generator, a child that never
    touches the chip.  Returns its RESULT; `on_line` sees its other lines."""
    spec_path = os.path.join(ctx.out_dir, "loadgen.json")
    with open(spec_path, "w") as f:
        json.dump({"host": host, "port": port, "seed": seed,
                   "seconds": seconds, "vocab": ctx.cfg["vocab_size"],
                   "traffic": tf, "dump_times": dump_times}, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    child = subprocess.Popen(
        [sys.executable, os.path.join(ctx.bench.dir, "lib", "loadgen.py"),
         spec_path], env=env, stdout=subprocess.PIPE, text=True,
        cwd=ctx.bench.root)
    result: dict = {}
    try:
        for line in child.stdout:
            line = line.strip()
            if line.startswith("RESULT "):
                result = json.loads(line[len("RESULT "):])
            elif line and on_line is not None:
                on_line(line)
        rc = child.wait()
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if rc != 0 or not result:
        raise RuntimeError(f"the load generator failed (exit {rc})")
    return result


def pool_state(engine) -> dict:
    """The KV pool now: pages no request or cached prefix holds, and how
    often the prefix cache has had to evict for the allocator."""
    return {"free_pages": engine.kv.free_page_count,
            "evictions": engine.prefix.n_evictions if engine.prefix else 0}


def late_limit_ms(tf: dict) -> float:
    """An open loop's sender may run late by 2% of the mix's mean gap."""
    return 0.02 * 1e3 / float(tf["rate_per_s"])


def run(ctx) -> dict:
    jax, device = setup_jax(ctx)
    from paddle_tpu.serving.client import ServingClient
    from paddle_tpu.serving.server import ServingServer

    cfg, tf = ctx.cfg, ctx.traffic
    ref = ctx.bench.reference(cfg["reference"])
    engine, args = start_engine(ctx, ref)

    # the benchmark's span around the instance's engine.step
    steps: list = []
    inner = engine.step
    annotate = jax.profiler.TraceAnnotation if ctx.trace else None

    def timed_step():
        t0 = time.time()
        if annotate is not None:
            with annotate("bench.engine_step"):
                busy = inner()
        else:
            busy = inner()
        if busy:
            steps.append((t0, time.time() - t0, engine.n_mixed_steps,
                          engine.n_prefill_chunks))
        return busy

    engine.step = timed_step
    srv = ServingServer(engine, host=args.host, port=0,
                        max_queue=args.max_queue,
                        wedge_threshold_s=args.wedge_threshold_s,
                        role=args.role)
    host, port = srv.start_background()
    try:
        # warm-up: the cell's own extremes, through the wire
        t = time.perf_counter()
        with ServingClient(host, port, timeout=1200.0) as c:
            n_warm = warm_up(c, tf, cfg["vocab_size"], ctx.seed, ctx.seconds)
        log(f"WARM {n_warm} requests in {time.perf_counter() - t:.1f}s; "
            f"compile cache {ctx.counters['compile_cache']}")

        marks: dict = {}
        prof = ProfilerWindow(ctx) if ctx.trace else None
        trace_span = {}

        def snapshot():
            return {"compiles": compiles_total(), "sites": compiles_by_site(),
                    "jit_work": dict(ctx.counters["jit_work"]),
                    "bytes_in_use": memory_bytes(jax, ctx.chips,
                                                 "bytes_in_use"),
                    "n_steps": len(steps),
                    "decode_steps": engine.n_decode_steps,
                    "occupancy_sum": engine.occupancy_sum,
                    "mixed_steps": engine.n_mixed_steps,
                    "prefill_chunks": engine.n_prefill_chunks,
                    "tokens_generated": engine.tokens_generated,
                    **pool_state(engine)}

        def traced_slice():
            time.sleep(min(2.0, ctx.seconds / 4))
            trace_span["t0"] = time.time()
            prof.start()
            time.sleep(min(float(tf["trace_s"]), ctx.seconds / 2))
            prof.stop()
            trace_span["t1"] = time.time()

        tracer = []

        def on_line(line):
            if line.startswith("WINDOW_START"):
                marks["start"] = snapshot()
                ctx.e2e["setup_s"] = time.perf_counter() - ctx.t_process
                if prof is not None:
                    tracer.append(threading.Thread(target=traced_slice))
                    tracer[0].start()
            elif line.startswith("WINDOW_END"):
                marks["end"] = snapshot()
            else:
                log("LOADGEN " + line[:500])

        try:
            result = offer(ctx, host, port, tf, ctx.seed, ctx.seconds, on_line)
        finally:
            for t in tracer:
                t.join()
        if "end" not in marks:
            raise RuntimeError("the load generator marked no window")
    finally:
        srv.stop_background(drain=False, timeout=120)
    peak = memory_bytes(jax, ctx.chips)

    a, b = marks["start"], marks["end"]
    dsteps = b["decode_steps"] - a["decode_steps"]
    w0, w1 = result["window"]
    in_window = [d for t0, d, _, _ in steps if w0 <= t0 < w1]
    # decode-only and mixed steps apart (the engine's own counters tell)
    kinds = {"decode": [], "mixed": []}
    for prev, cur in zip(steps, steps[1:]):
        if w0 <= cur[0] < w1:
            kinds["mixed" if cur[2] > prev[2] else "decode"].append(cur[1])
    ctx.spans["engine_step_by_kind_s"] = kinds
    ctx.counters.update({
        "compiles_in_window": b["compiles"] - a["compiles"],
        "decode_steps": dsteps,
        "occupancy": (b["occupancy_sum"] - a["occupancy_sum"]) / dsteps
        if dsteps else None,
        "mixed_steps": b["mixed_steps"] - a["mixed_steps"],
        "prefill_chunks": b["prefill_chunks"] - a["prefill_chunks"],
        "live_samples": result["live_samples"],
        "trace_span": trace_span,
    })
    ctx.spans["engine_step_s"] = in_window
    ctx.e2e["output_tokens_per_s"] = result["output_tokens_per_s"]
    if result["itl_ms"]["p95"] is not None:
        ctx.e2e["itl_p95_ms"] = result["itl_ms"]["p95"]
    # beside the metrics, unbounded: what is resident once start-up is over
    # (memory_peak_bytes is build_engine's transient Trainer), the share of
    # the KV pool's token slots the window's requests held (the client's
    # view, sampled every 0.1 s), and the first-token times
    pool_tokens = (engine.kv.num_pages - 1) * engine.kv.page_size
    held = [c for t, c, _ in result["live_samples"] if w0 <= t < w1]
    ctx.notes.update({
        "memory_resident_bytes": a["bytes_in_use"],
        "kv_pool_tokens": pool_tokens,
        "kv_pool_live_share": sum(held) / len(held) / pool_tokens
        if held else None,
        # a pool with no free page makes every new page an eviction: the
        # steady state of a server that has run for minutes, which a window
        # this short after a fresh start may never reach (PERF.md section 6)
        "kv_pool_free_pages_at_end": b["free_pages"],
        "prefix_evictions_in_window": b["evictions"] - a["evictions"],
        "ttft_p50_ms": result["ttft_ms"]["p50"],
        "ttft_p95_ms": result["ttft_ms"]["p95"],
        "ttft_requests": result["ttft_ms"]["n"]})
    short = {k: v for k, v in result.items()
             if k not in ("live_samples", "check_sample")}
    log(f"WINDOW {json.dumps(short)}")
    new = {k: v - a["sites"].get(k, 0) for k, v in b["sites"].items()
           if v != a["sites"].get(k, 0)}
    if new:
        log(f"COMPILED IN WINDOW {new}")
    jw = {k: b["jit_work"][k] - a["jit_work"][k] for k in b["jit_work"]}
    ctx.counters["backend_compiles_in_window"] = jw["backend_compiles"]
    log(f"JIT WORK IN WINDOW {jw}")
    for kind, ds in kinds.items():
        if ds:
            log(f"STEPS {kind}: {len(ds)} steps, {sum(ds):.2f}s, p50 "
                f"{1e3 * arith.percentile(ds, 50):.1f} ms, p95 "
                f"{1e3 * arith.percentile(ds, 95):.1f} ms, max "
                f"{1e3 * max(ds):.1f} ms")
    log(f"ENGINE steps in window {len(in_window)} (decode {dsteps}, mixed "
        f"{ctx.counters['mixed_steps']}, prefill chunks "
        f"{ctx.counters['prefill_chunks']}); compiles in window "
        f"{ctx.counters['compiles_in_window']}; generator late p50 "
        f"{result['late_ms']['p50']:.2f} ms p95 "
        f"{result['late_ms']['p95']:.2f} ms max "
        f"{result['late_ms']['max']:.2f} ms; notes {json.dumps(ctx.notes)}")
    if prof is not None:
        prof.reduce()

    # ---- correct: served greedy tokens against ONE full reference forward
    from benchmark.lib.check import served_margin
    limits = cfg["limits"]
    served = [(s["prompt"], s["new"]) for s in result["check_sample"]]
    ok = ctx.check("requests_failed", result["failed"], 0)
    ok &= ctx.check("check_sample_missing",
                    max(0, 2 - len(served)), 0)
    if served:
        m = served_margin(jax, ref, cfg, engine.params, served,
                          int(tf["check_max_tokens"]))
        log(f"SERVED {m}")
        ok &= ctx.check("serve_margin_nats", m["mean_nats"],
                        limits["serve_margin_nats"])
    ok &= ctx.check("compiles_in_window",
                    ctx.counters["compiles_in_window"], 0)
    if tf["loop"] == "open" and not ctx.rehearse:
        # a generator that ran late offered less than the cell says (on the
        # CPU the rehearsal's engine and sender share the cores: no reading)
        ok &= ctx.check("generator_late_p95_ms", result["late_ms"]["p95"],
                        late_limit_ms(tf))
    device["memory_peak_bytes"] = peak
    return {"correct": bool(ok), "attempted": result["attempted"],
            "failed": result["failed"], "device": device}


def _by_hand_server(ctx):
    """One engine and one server for a study run by hand."""
    setup_jax(ctx)
    from paddle_tpu.serving.server import ServingServer

    ref = ctx.bench.reference(ctx.cfg["reference"])
    engine, args = start_engine(ctx, ref)
    srv = ServingServer(engine, host=args.host, port=0,
                        max_queue=args.max_queue)
    host, port = srv.start_background()
    return srv, host, port, engine, ref


def calibrate(ctx, seeds: list[int]) -> None:
    """On the chip, at the cell's own size, no timed window: for each seed
    the program's number and the control's (the reference in fp8 deciding
    the tokens), read in one process.  Prints one CAL line a seed."""
    import random

    import jax

    from benchmark.lib.check import served_margin
    from paddle_tpu.serving.client import ServingClient

    cfg, tf = ctx.cfg, ctx.traffic
    srv, host, port, engine, ref = _by_hand_server(ctx)
    pad = int(tf["check_max_tokens"])
    try:
        for seed in seeds:
            seeded_weights(ref, cfg, engine, seed % (2 ** 31 - 1))
            reqs = traffic_mod.serve_requests(tf, cfg["vocab_size"], seed, 30)
            reqs = [r for r in reqs
                    if len(r["prompt"]) + r["max_new"] <= pad]
            random.Random(seed).shuffle(reqs)
            reqs = reqs[:int(tf["check_requests"])]
            with ServingClient(host, port, timeout=1200.0) as c:
                ids = [c.submit(r["prompt"], max_new=r["max_new"],
                                req_id=f"{seed}_{r['id']}") for r in reqs]
                got = c.collect(ids)
            served = [(r["prompt"], got[i]["tokens"][len(r["prompt"]):])
                      for r, i in zip(reqs, ids)]
            prog = served_margin(jax, ref, cfg, engine.params, served, pad)
            ctl = served_margin(jax, ref, cfg, engine.params, served, pad,
                                quant="fp8")
            bf = served_margin(jax, ref, cfg, engine.params, served, pad,
                               quant="bf16")
            log("CAL " + json.dumps({"seed": seed, "program": prog,
                                     "control_fp8": ctl,
                                     "reference_bf16": bf}))
    finally:
        srv.stop_background(drain=False, timeout=120)


def sweep(ctx, rates: list[float]) -> None:
    """Find the knee of an open-loop mix once, by hand, on the chip: one
    engine, one server, the mix offered at each rate in turn.  A rate is
    sustained when completed requests/s stay within 3% of offered and the
    requests in flight at the window's end are no more than at its middle."""
    from paddle_tpu.serving.client import ServingClient

    cfg, tf = ctx.cfg, dict(ctx.traffic)
    srv, host, port, _, _ = _by_hand_server(ctx)
    try:
        with ServingClient(host, port, timeout=1200.0) as c:
            for i, rate in enumerate(rates):      # every rate's lengths
                warm_up(c, dict(tf, rate_per_s=rate, drain_s=0.0),
                        cfg["vocab_size"], ctx.seed + i, ctx.seconds)
        for i, rate in enumerate(rates):
            tf.update(rate_per_s=rate, drain_s=0.0)
            depth, stop = [], threading.Event()

            def watch():
                with ServingClient(host, port) as c:
                    while not stop.wait(0.5):
                        st = c.stats(stale_ok=True)
                        depth.append((time.time(), st["inflight"],
                                      st["queue_depth"]))

            th = threading.Thread(target=watch)
            th.start()
            try:
                res = offer(ctx, host, port, tf, ctx.seed + i, ctx.seconds)
            finally:
                stop.set()
                th.join()
            w0, w1 = res["window"]
            mid = [d for t, d, _ in depth if abs(t - (w0 + w1) / 2) < 1.5]
            end = [d for t, d, _ in depth if w1 - 1.5 <= t <= w1]
            log("SWEEP " + json.dumps({
                "rate": rate,
                "completed_per_s": res["completed_in_window"] / ctx.seconds,
                "due_per_s": res["due_in_window"] / ctx.seconds,
                "output_tokens_per_s": res["output_tokens_per_s"],
                "ttft_ms": res["ttft_ms"], "itl_ms": res["itl_ms"],
                "failed": res["failed"], "late_ms": res["late_ms"],
                "inflight_mid": max(mid) if mid else None,
                "inflight_end": max(end) if end else None}))
            time.sleep(2.0)     # let the cancelled tail of this rate drain
    finally:
        srv.stop_background(drain=False, timeout=120)


def windows(ctx, seeds: list[int], lengths: list[float]) -> None:
    """How the spread of the client's metrics shrinks with the window's
    length, read once, by hand, on the chip: one engine, one server, the
    cell's mix offered for --seconds under each seed with every token's
    arrival kept; each metric is then taken over every disjoint stretch of
    each length (PERF.md section 2).  Prints one WINDOWS line a seed and a
    SPREAD line a length.  The one server carries its state from seed to
    seed: its pool fills with cached pages (`pool_after`), and from then on
    it is a slower server, so seeds compare only while free pages remain."""
    from paddle_tpu.serving.client import ServingClient

    cfg, tf = ctx.cfg, dict(ctx.traffic, drain_s=0.0)
    srv, host, port, engine, _ = _by_hand_server(ctx)
    dump = os.path.join(ctx.out_dir, "token_times.json")
    values: dict = {}
    try:
        for seed in seeds:
            with ServingClient(host, port, timeout=1200.0) as c:
                warm_up(c, tf, cfg["vocab_size"], seed, ctx.seconds)
            res = offer(ctx, host, port, tf, seed, ctx.seconds,
                        dump_times=dump)
            with open(dump) as f:
                d = json.load(f)
            w0 = d["window"][0]
            row = {"seed": seed, "pool_after": pool_state(engine), "whole": {
                "output_tokens_per_s": res["output_tokens_per_s"],
                "itl_p95_ms": res["itl_ms"]["p95"],
                "ttft_p95_ms": res["ttft_ms"]["p95"]}}
            for length in lengths:
                for j in range(int(ctx.seconds // length)):
                    m = arith.window_metrics(d["requests"], w0 + j * length,
                                             w0 + (j + 1) * length,
                                             d["eps_s"])
                    row.setdefault(f"{length:g}s", []).append(m)
                    for k, v in m.items():
                        values.setdefault((length, k), []).append(v)
            log("WINDOWS " + json.dumps(row))
            time.sleep(2.0)
        for (length, k), vs in sorted(values.items()):
            vs = sorted(v for v in vs if v is not None)
            if len(vs) >= 2 and k.endswith(("_per_s", "_ms")):
                log("SPREAD " + json.dumps({
                    "seconds": length, "metric": k, "n": len(vs),
                    "median": arith.percentile(vs, 50),
                    "spread": arith.iqr_share(vs),
                    "range": (vs[-1] - vs[0]) / arith.percentile(vs, 50)}))
    finally:
        srv.stop_background(drain=False, timeout=120)
