#!/usr/bin/env python3
"""The load generator: a child process that speaks the wire protocol through
paddle_tpu.serving.client and never touches the chip (JAX_PLATFORMS=cpu is
set by its parent).  One connection, one reader (the main thread), one
sender, one sampler: few threads, so the load itself is steady.

  python3 benchmark/lib/loadgen.py <spec.json>

The spec holds host, port, seed, seconds, vocab and the traffic mix.  Every
request is timed from when it was DUE (open loop) — not from when it was
sent — and how late the sender ran is reported.  A spec with `dump_times`
(a path; benchmark/calibrate.py's study of window lengths sets it) also gets
every token's arrival time written there.  Lines on stdout:

  WINDOW_START <epoch>     the ramp is over, the measured window starts
  WINDOW_END <epoch>
  RESULT <json>            the last line
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import arith, traffic as traffic_mod  # noqa: E402


def say(line: str) -> None:
    sys.stdout.write(line + "\n")
    sys.stdout.flush()


def main(argv) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    from paddle_tpu.serving.client import ServingClient

    tf = spec["traffic"]
    seconds = float(spec["seconds"])
    ramp = float(tf.get("ramp_s", 0.0))
    drain = float(tf.get("drain_s", 0.0))
    eps = float(tf.get("burst_eps_ms", 0.5)) / 1e3
    reqs = traffic_mod.serve_requests(tf, spec["vocab"], spec["seed"], seconds)
    by_id = {r["id"]: r for r in reqs}
    for r in reqs:
        r.update(sent=None, times=[], done=None, reason=None, tokens=None,
                 error=None)
    closed = tf["loop"] == "closed"
    client = ServingClient(spec["host"], spec["port"], timeout=600.0)
    lock = threading.Lock()
    stop = threading.Event()

    def submit(r):
        r["sent"] = time.time()
        client.submit(r["prompt"], max_new=r["max_new"], req_id=r["id"])

    t_start = time.time() + 0.2
    t_win0 = t_start + ramp
    t_win1 = t_win0 + seconds
    t_end = t_win1 + drain

    # closed loop: each client's requests in order
    queues: dict = {}
    if closed:
        for r in reqs:
            queues.setdefault(r["client"], []).append(r)

    def sender():
        """Open loop: send each request when it is due, whatever the server
        is doing."""
        for r in reqs:
            due = t_start + r["due"]
            r["due_at"] = due
            wait = due - time.time()
            if wait > 0 and stop.wait(wait):
                return
            if stop.is_set() or time.time() >= t_win1:
                return
            with lock:
                submit(r)

    live_samples = []

    def sampler():
        while not stop.wait(0.1):
            now = time.time()
            ctx = n = 0
            for r in reqs:
                if r["sent"] is not None and r["done"] is None and r["times"]:
                    ctx += len(r["prompt"]) + len(r["times"])
                    n += 1
            live_samples.append([now, ctx, n])

    def marks():
        time.sleep(max(0.0, t_win0 - time.time()))
        say(f"WINDOW_START {time.time():.6f}")
        time.sleep(max(0.0, t_win1 - time.time()))
        say(f"WINDOW_END {time.time():.6f}")

    threads = [threading.Thread(target=sampler, daemon=True),
               threading.Thread(target=marks, daemon=True)]
    time.sleep(max(0.0, t_start - time.time()))
    if closed:
        with lock:
            for q in queues.values():
                r = q.pop(0)
                r["due_at"] = time.time()
                submit(r)
    else:
        threads.append(threading.Thread(target=sender, daemon=True))
    for t in threads:
        t.start()

    # the reader: every frame stamped as it arrives
    client.sock.settimeout(0.25)
    inflight = lambda: sum(1 for r in reqs
                           if r["sent"] is not None and r["done"] is None)
    while True:
        now = time.time()
        if closed and now >= t_win1:
            break
        if not closed and now >= t_win1 and (inflight() == 0 or now >= t_end):
            break
        try:
            msg = client.recv()
        except (TimeoutError, OSError) as e:
            if isinstance(e, TimeoutError) or "timed out" in str(e):
                continue
            raise
        now = time.time()
        r = by_id.get(msg.get("id"))
        if r is None:
            continue
        kind = msg.get("type")
        if kind == "token":
            r["times"].append(now)
        elif kind in ("done", "overload", "error"):
            r["done"] = now
            r["reason"] = msg.get("reason") if kind == "done" else kind
            if kind == "done":
                r["tokens"] = msg.get("tokens")
            else:
                r["error"] = json.dumps(msg)[:300]
            if closed and now < t_win1 and queues[r["client"]]:
                nxt = queues[r["client"]].pop(0)
                nxt["due_at"] = now
                with lock:
                    submit(nxt)
    stop.set()
    unfinished = [r for r in reqs if r["sent"] is not None
                  and r["done"] is None]
    for r in unfinished:
        try:
            with lock:
                client.cancel(r["id"])
        except OSError:
            pass
    time.sleep(0.2)
    client.close()

    # ---- reduce -------------------------------------------------------
    in_win = lambda t: t_win0 <= t < t_win1
    sent = [r for r in reqs if r["sent"] is not None]
    judged = [r for r in sent if in_win(r["due_at"])]
    failed = [r for r in judged if r["reason"] in ("overload", "error")
              or (not closed and r["done"] is None)
              or (r["reason"] == "length"
                  and len(r["tokens"]) != len(r["prompt"]) + r["max_new"])]
    m = arith.window_metrics(sent, t_win0, t_win1, eps)
    late = [(r["sent"] - r["due_at"]) * 1e3 for r in sent if not closed]
    completed = [r for r in sent if r["reason"] == "length" and r["tokens"]]
    rng = random.Random(spec["seed"])
    pool = [r for r in completed
            if len(r["tokens"]) <= int(tf["check_max_tokens"])]
    rng.shuffle(pool)
    sample = [{"prompt": r["prompt"],
               "new": r["tokens"][len(r["prompt"]):]}
              for r in pool[:int(tf["check_requests"])]]
    result = {
        "window": [t_win0, t_win1],
        "attempted": len(judged), "failed": len(failed),
        "failed_examples": [r["error"] or r["reason"] for r in failed[:3]],
        "sent": len(sent), "completed": len(completed),
        "completed_in_window": sum(1 for r in completed if in_win(r["done"])),
        "due_in_window": len(judged),
        "unfinished_at_end": len(unfinished),
        "output_tokens_in_window": m["output_tokens"],
        "output_tokens_per_s": m["output_tokens"] / seconds,
        "ttft_ms": {"n": m["n_ttft"], "p50": m["ttft_p50_ms"],
                    "p95": m["ttft_p95_ms"]},
        "itl_ms": {"n": m["n_gaps"], "p50": m["itl_p50_ms"],
                   "p95": m["itl_p95_ms"]},
        "late_ms": {"n": len(late),
                    "p50": arith.percentile(late, 50) if late else 0.0,
                    "p95": arith.percentile(late, 95) if late else 0.0,
                    "max": max(late) if late else 0.0},
        "live_samples": live_samples,
        "check_sample": sample,
    }
    if spec.get("dump_times"):
        with open(spec["dump_times"], "w") as f:
            json.dump({"window": [t_win0, t_win1], "eps_s": eps,
                       "requests": [{"due_at": r["due_at"],
                                     "times": r["times"]} for r in sent]}, f)
    say("RESULT " + json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
