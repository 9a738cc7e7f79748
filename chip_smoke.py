#!/usr/bin/env python3
"""First contact with the chip: drive the LM trainer and the serving engine
through their normal CLIs on one TPU, check what comes out, say so.

  python chip_smoke.py               # one chip: train, serve, kernels,
                                     # hybrid
  python chip_smoke.py --chips 4     # four chips: data-parallel train vs
                                     # the single-device run, nothing else
  python chip_smoke.py --rehearse    # CPU control-flow rehearsal at tiny
                                     # widths; exits 3, never prints ok:true

The model is demo/model_zoo/transformer_lm.py at the repo's documented LM
preset (vocab 32000, dim 512, 8 layers, 8 heads of 64, bf16 compute):
training at batch 64 x 512 tokens with the Pallas flash kernel; serving
with 16 slots, page 16, context 768 through the Pallas paged-decode
kernel.  Weights are random from a seed (the serve phase loads what the
train phase just trained, so greedy decoding has something to be sure of).

One process per chip: THIS process never imports JAX.  Every phase runs as
child processes, one after another, each owning the chip alone:

  train    `python -m paddle_tpu train ...` per-batch, then again with
           --steps_per_dispatch=4; loss finite and falling (metrics.jsonl);
           a check child finds tpu_custom_call in the compiled train step
           and round-trips a checkpoint bit-exactly
  serve    `tools/serve.py` as the server; `tools/serve.py --client`
           one-shots (held to the CPU platform — they only open a socket)
           send greedy/streamed, sampled, shared-prefix and long-prompt
           requests; stats consistent; SIGTERM drains to exit 0; a check
           child finds tpu_custom_call in the decode step and holds every
           greedy token against a dense fp32 forward of the same weights
  kernels  `tools/tpu_parity.py`: each Pallas kernel against its jnp /
           lax.scan reference at the demo widths
  hybrid   `tools/serve.py` on benchmark/configs/kimi_linear.py at the
           benchmark's rehearsal size (hidden 64, 4 heads, 2 layers: one KDA
           layer and one NoPE latent layer at their PUBLISHED widths, 16 of
           256 experts): greedy twice alike, a long prompt chunked beside a
           decoding one, alone == batched, the recurrent counters in the
           stats, SIGTERM drains

No fallback: a child that finds no TPU exits non-zero at once, any failed
check aborts the run with exit 1, and only a run in which every phase
passed on a TPU prints the final `{"ok": true, "device": ...}` line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, "output", "chip_smoke")   # output/ is git-ignored
BUDGET_S = 1140.0           # the driver allows 1200 s, compiles included
LM_CONFIG = "demo/model_zoo/transformer_lm.py"
HYBRID_CONFIG = "benchmark/configs/kimi_linear.py"
HYBRID_ARGS = ("vocab=128,dim=64,layers=2,heads=4,kv_heads=4,ffn=128,"
               "rope_theta=10000,batch_size=1,compute_dtype=bfloat16,"
               "attn_impl=flash")

FULL = dict(vocab=32000, dim=512, layers=8, heads=8, batch=64, seq=512,
            passes=6, slots=16, page=16, context=768,
            prompt=12, max_new=32, shared=200, tail=8, long=300)
TINY = dict(vocab=64, dim=32, layers=1, heads=2, batch=8, seq=32,
            passes=3, slots=4, page=8, context=96,
            prompt=5, max_new=8, shared=40, tail=4, long=70)

# stated tolerances
FUSED_REL_TOL = 0.02    # per-pass cost, --steps_per_dispatch=4 vs per-batch
DP_REL_TOL = 0.03       # per-pass cost, data:4 vs one device (bf16 compute;
                        # the all-reduce changes the gradient's sum order)
GREEDY_MARGIN_NATS = 0.15   # a served greedy token may trail the fp32
                            # reference's argmax by at most this log-prob
                            # (bf16 logits near a tie), never by more


class SmokeFailure(Exception):
    pass


def need(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# parent side: child-process plumbing (stdlib only — no JAX in this process)
# ---------------------------------------------------------------------------

_T0 = time.time()
_LIVE: list[subprocess.Popen] = []


def _remaining() -> float:
    return BUDGET_S - (time.time() - _T0)


def _kill(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def _kill_all() -> None:
    for proc in _LIVE:
        _kill(proc)


def _env(rehearse: bool, chips: int, client: bool = False) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    if client:
        # a client one-shot only opens a socket; the server owns the chip
        env["JAX_PLATFORMS"] = "cpu"
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
        if chips > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count="
                                f"{chips}")
    return env


def _spawn(name: str, argv: list[str], env: dict) -> tuple:
    log = os.path.join(WORK, name + ".log")
    f = open(log, "w")
    proc = subprocess.Popen(argv, cwd=REPO, env=env, stdout=f,
                            stderr=subprocess.STDOUT, start_new_session=True)
    f.close()
    _LIVE.append(proc)
    return proc, log


def _read(path: str) -> str:
    with open(path, errors="replace") as f:
        return f.read()


def _run(name: str, argv: list[str], env: dict, timeout: float = 900.0) -> str:
    """Run one child to its end; return its output.  Non-zero exit or a
    timeout fails the smoke (the tail of the child's output says why)."""
    t0 = time.time()
    proc, log = _spawn(name, argv, env)
    try:
        rc = proc.wait(timeout=max(1.0, min(timeout, _remaining())))
    except subprocess.TimeoutExpired:
        _kill(proc)
        raise SmokeFailure(f"{name}: timed out after {time.time() - t0:.0f}s"
                           f"\n{_read(log)[-3000:]}")
    out = _read(log)
    need(rc == 0, f"{name}: exit {rc}\n{out[-3000:]}")
    say(f"  [{name}] exit 0 in {time.time() - t0:.1f}s")
    return out


def _report(out: str) -> str:
    """A check child's own report lines (see `_tell`)."""
    return "\n".join(ln for ln in out.splitlines() if ln.startswith("  > "))


def _tagged(out: str, tag: str) -> dict:
    lines = [ln for ln in out.splitlines() if ln.startswith(tag)]
    need(lines, f"no {tag} line in child output:\n{out[-2000:]}")
    return json.loads(lines[-1][len(tag):])


def _self(child: str, spec: dict) -> list[str]:
    path = os.path.join(WORK, f"{child}.spec.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    return [sys.executable, os.path.join(REPO, "chip_smoke.py"),
            "--child", child, "--spec", path]


def config_args(w: dict) -> str:
    return (f"vocab={w['vocab']},dim={w['dim']},layers={w['layers']},"
            f"heads={w['heads']},batch_size={w['batch']},"
            f"compute_dtype=bfloat16,attn_impl=flash,seq_len={w['seq'] + 1}")


# ---------------------------------------------------------------------------
# phases (parent side)
# ---------------------------------------------------------------------------

def _train_cli(name: str, w: dict, env: dict, rehearse: bool,
               extra: list[str]) -> list[float]:
    """One `python -m paddle_tpu train` run; returns the per-pass costs."""
    save_dir = os.path.join(WORK, name)
    argv = [sys.executable, "-m", "paddle_tpu", "train",
            f"--config={LM_CONFIG}", f"--config_args={config_args(w)}",
            f"--num_passes={w['passes']}", f"--saving_period={w['passes']}",
            f"--save_dir={save_dir}", "--log_period=4", "--seed=1"] + extra
    say(f"  $ {' '.join(argv[1:])}")
    out = _run(name, argv, env)
    need(rehearse or "platform=tpu" in out,
         f"{name}: the trainer did not report a TPU:\n{out[-1500:]}")
    with open(os.path.join(save_dir, "metrics.jsonl")) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    need(len(rows) == w["passes"], f"{name}: {len(rows)} metric rows")
    costs = [float(r["cost"]) for r in rows]
    steps = sum(int(r["batches"]) for r in rows)
    need(all(math.isfinite(c) for c in costs), f"{name}: non-finite {costs}")
    need(costs[-1] < costs[0],
         f"{name}: loss did not fall: first {costs[0]} last {costs[-1]}")
    say(f"  [{name}] {steps} steps of batch {w['batch']} x {w['seq']} tokens;"
        f" loss first {costs[0]:.4f} last {costs[-1]:.4f}; per pass "
        f"{[round(c, 4) for c in costs]}")
    return costs


def _rel_gap(a: list[float], b: list[float]) -> float:
    return max(abs(x - y) / abs(x) for x, y in zip(a, b))


def phase_train(w: dict, rehearse: bool) -> dict:
    say("== train: python -m paddle_tpu train (Trainer.train_one_pass)")
    env = _env(rehearse, 1)
    k1 = _train_cli("train_k1", w, env, rehearse, [])
    k4 = _train_cli("train_k4", w, env, rehearse, ["--steps_per_dispatch=4"])
    gap = _rel_gap(k1, k4)
    need(gap <= FUSED_REL_TOL,
         f"fused dispatch diverged from per-batch: rel gap {gap}")
    say(f"  per-batch vs --steps_per_dispatch=4: max per-pass rel gap "
        f"{gap:.2e} (tolerance {FUSED_REL_TOL})")
    return {"config_args": config_args(w),
            "k1": os.path.join(WORK, "train_k1"),
            "k4": os.path.join(WORK, "train_k4")}


def _prompt(rng: random.Random, n: int, vocab: int) -> list[int]:
    return [rng.randrange(2, vocab) for _ in range(n)]


def _client_argv(port: int, prompt: list[int], max_new: int,
                 extra: list[str]) -> list[str]:
    return [sys.executable, "tools/serve.py", "--client",
            f"127.0.0.1:{port}", "--prompt", ",".join(map(str, prompt)),
            "--max-new", str(max_new), "--timeout-s", "300"] + extra


def _result(name: str, out: str, prompt: list[int], max_new: int) -> list[int]:
    """The NEW tokens of a client one-shot's final JSON (prompt stripped)."""
    res = json.loads([ln for ln in out.splitlines()
                      if ln.startswith("{")][-1])
    toks = [int(t) for t in res["tokens"]]
    need(toks[:len(prompt)] == prompt and
         len(toks) == len(prompt) + max_new,
         f"{name}: {len(toks) - len(prompt)} new tokens of {max_new} "
         f"(reason {res['reason']!r})")
    return toks[len(prompt):]


def phase_serve(w: dict, rehearse: bool, checkpoint: str) -> dict:
    say("== serve: tools/serve.py (ServingEngine + wire protocol)")
    env = _env(rehearse, 1)
    cenv = _env(rehearse, 1, client=True)
    argv = [sys.executable, "tools/serve.py", "--config", LM_CONFIG,
            "--config-args", config_args(w), "--slots", str(w["slots"]),
            "--page-size", str(w["page"]), "--max-context",
            str(w["context"]), "--port", "0", "--seed", "1"]
    if checkpoint:
        argv += ["--checkpoint", checkpoint]
    srv, log, port = _serve_up("serve", argv, env, rehearse)

    rng = random.Random(0)
    V, N = w["vocab"], w["max_new"]
    greedy = []          # (prompt, tokens) for the reference check

    # 1. greedy, streamed; 2. the same again, not streamed
    p0 = _prompt(rng, w["prompt"], V)
    out = _run("client_greedy_stream",
               _client_argv(port, p0, N, ["--stream"]), cenv)
    t_stream = [int(ln.split("=")[1]) for ln in out.splitlines()
                if ln.startswith("token[")]
    t0_ = _result("greedy_stream", out, p0, N)
    need(t_stream == t0_, f"streamed {t_stream} != final {t0_}")
    again = _result("greedy_again", _run(
        "client_greedy_again", _client_argv(port, p0, N, []), cenv), p0, N)
    need(again == t0_, f"greedy not deterministic: {t0_} vs {again}")
    greedy.append((p0, t0_))
    say(f"  greedy streamed: {N} tokens, stream == final, second run equal")

    # 3-6. concurrently: sampled, two sharing a long prefix, one prompt
    # longer than the prefill chunk (4 * page) — decode rows and prompt
    # chunks share the engine's mixed steps
    shared = _prompt(rng, w["shared"], V)
    reqs = {
        "sampled": (_prompt(rng, w["prompt"] + 8, V), N,
                    ["--top-k", "8", "--temperature", "0.9", "--seed", "5"]),
        "prefix_a": (shared + _prompt(rng, w["tail"], V), N // 2, []),
        "prefix_b": (shared + _prompt(rng, w["tail"], V), N // 2, []),
        "long": (_prompt(rng, w["long"], V), N // 2, []),
    }
    need(w["long"] > 4 * w["page"], "long prompt must exceed the chunk")
    procs = {name: _spawn("client_" + name, _client_argv(port, p, n, x), cenv)
             for name, (p, n, x) in reqs.items()}
    tokens = {}
    for name, (proc, clog) in procs.items():
        try:
            rc = proc.wait(timeout=max(1.0, min(400.0, _remaining())))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"client {name} timed out:\n{_read(clog)}")
        need(rc == 0, f"client {name}: exit {rc}\n{_read(clog)[-2000:]}")
        tokens[name] = _result(name, _read(clog), *reqs[name][:2])
    for name in ("prefix_a", "prefix_b", "long"):
        greedy.append((reqs[name][0], tokens[name]))
    say(f"  concurrent: sampled (top-k 8, T 0.9) {N} tokens; two prompts "
        f"sharing {w['shared']} tokens; one {w['long']}-token prompt "
        f"(chunk {4 * w['page']}) — all finished")
    # the shared-prefix request again, alone: batching and the prefix cache
    # must not change its tokens
    alone = _result("prefix_a_alone", _run(
        "client_prefix_a_alone",
        _client_argv(port, reqs["prefix_a"][0], N // 2, []), cenv),
        reqs["prefix_a"][0], N // 2)
    need(alone == tokens["prefix_a"],
         f"batched {tokens['prefix_a']} != alone {alone}")

    out = _run("client_stats", [
        sys.executable, "tools/serve.py", "--client", f"127.0.0.1:{port}",
        "--stats"], cenv)
    stats = json.loads(out[out.index("\n{\n") if "\n{\n" in out else 0:])
    need(stats.get("consistent") is True, f"stats not consistent: {stats}")
    need(stats["prefix_hits"] >= 1, f"no prefix-cache hit: {stats}")
    say("  stats consistent: true; " + ", ".join(
        f"{k}={stats[k]}" for k in ("decode_steps", "mixed_steps",
                                    "prefill_chunks", "prefix_hits",
                                    "tokens_generated")))

    _drain(srv, log)
    return {"config_args": config_args(w), "checkpoint": checkpoint,
            "slots": w["slots"], "page": w["page"], "context": w["context"],
            "greedy": greedy}


def _serve_up(name: str, argv: list[str], env: dict, rehearse: bool):
    """Start a tools/serve.py server; wait for its SERVE_JSON line."""
    say(f"  $ {' '.join(argv[1:])}")
    t0 = time.time()
    srv, log = _spawn(name, argv, env)
    while "SERVE_JSON:" not in _read(log):
        need(srv.poll() is None,
             f"server died (exit {srv.returncode}):\n{_read(log)[-3000:]}")
        need(_remaining() > 0 and time.time() - t0 < 600,
             f"server never bound:\n{_read(log)[-3000:]}")
        time.sleep(0.5)
    hello = _tagged(_read(log), "SERVE_JSON:")
    need(rehearse or hello["device"]["platform"] == "tpu",
         f"server is not on a TPU: {hello}")
    say(f"  server up in {time.time() - t0:.1f}s on {hello['device']}")
    return srv, log, int(hello["port"])


def _drain(srv, log: str) -> None:
    """SIGTERM the server's group: it must drain and exit 0."""
    os.killpg(srv.pid, signal.SIGTERM)
    try:
        rc = srv.wait(timeout=max(1.0, min(120.0, _remaining())))
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"server ignored SIGTERM:\n{_read(log)[-2000:]}")
    need(rc == 0, f"server exit {rc} after SIGTERM:\n{_read(log)[-2000:]}")
    say("  SIGTERM: drained, exit 0")


def phase_hybrid(rehearse: bool) -> None:
    """The hybrid linear-attention model through the same server: slot
    state beside the latent pages, no prefix index."""
    say("== hybrid: tools/serve.py on benchmark/configs/kimi_linear.py")
    env = _env(rehearse, 1)
    cenv = _env(rehearse, 1, client=True)
    argv = [sys.executable, "tools/serve.py", "--config", HYBRID_CONFIG,
            "--config-args", HYBRID_ARGS, "--slots", "4", "--page-size", "8",
            "--max-context", "128", "--prefill-chunk", "16",
            "--param-dtype", "bfloat16", "--port", "0", "--seed", "1"]
    srv, log, port = _serve_up("hybrid", argv, env, rehearse)
    rng = random.Random(1)
    p0, n = _prompt(rng, 9, 128), 12
    first = _result("hybrid_greedy", _run(
        "client_hybrid_greedy", _client_argv(port, p0, n, []), cenv), p0, n)
    long = _prompt(rng, 70, 128)
    procs = {"again": _spawn("client_hybrid_again",
                             _client_argv(port, p0, n, []), cenv),
             "long": _spawn("client_hybrid_long",
                            _client_argv(port, long, n, []), cenv)}
    got = {}
    for name, (proc, clog) in procs.items():
        try:
            rc = proc.wait(timeout=max(1.0, min(400.0, _remaining())))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"client {name} timed out:\n{_read(clog)}")
        need(rc == 0, f"client {name}: exit {rc}\n{_read(clog)[-2000:]}")
        got[name] = _result(name, _read(clog),
                            p0 if name == "again" else long, n)
    need(got["again"] == first,
         f"a slot's state leaked between requests or batching changed the "
         f"tokens: {first} vs {got['again']}")
    alone = _result("hybrid_long_alone", _run(
        "client_hybrid_long_alone", _client_argv(port, long, n, []), cenv),
        long, n)
    need(alone == got["long"], f"batched {got['long']} != alone {alone}")
    out = _run("client_hybrid_stats", [
        sys.executable, "tools/serve.py", "--client", f"127.0.0.1:{port}",
        "--stats"], cenv)
    stats = json.loads(out[out.index("\n{\n") if "\n{\n" in out else 0:])
    need(stats.get("consistent") is True, f"stats not consistent: {stats}")
    need(stats["recurrent_steps"] > 0 and stats["recurrent_slot_updates"] > 0
         and stats["slot_state_bytes"] > 0 and stats["prefix_hits"] == 0,
         f"no recurrent state counted: {stats}")
    say("  greedy twice alike, batched == alone; " + ", ".join(
        f"{k}={stats[k]}" for k in ("decode_steps", "mixed_steps",
                                    "recurrent_steps",
                                    "recurrent_slot_updates",
                                    "slot_state_bytes", "moe_pairs_total")))
    _drain(srv, log)


def phase_kernels(rehearse: bool) -> None:
    say("== kernels: tools/tpu_parity.py (Pallas vs jnp / lax.scan)")
    argv = [sys.executable, "tools/tpu_parity.py"]
    if rehearse:
        argv += ["--interpret", "--only=flash_B1_,additive_B5,lstm_B4,gru_B5"]
    out = _run("kernels", argv, _env(rehearse, 1))
    for ln in out.splitlines():
        if ln.startswith('{"case"'):
            rec = json.loads(ln)
            say(f"  {rec['case']}: max abs err {rec['max_err']}")
    need(json.loads(out.splitlines()[-1]).get("all_ok") is True,
         f"kernel parity failed:\n{out[-2000:]}")


def phase_dp(w: dict, rehearse: bool, chips: int) -> dict:
    say(f"== train on {chips} chips: --mesh_shape=data:{chips} vs one device")
    env = _env(rehearse, chips)
    one = _train_cli("train_dp1", w, env, rehearse, [])
    dp = _train_cli(f"train_dp{chips}", w, env, rehearse,
                    [f"--mesh_shape=data:{chips}"])
    gap = _rel_gap(one, dp)
    need(gap <= DP_REL_TOL, f"data:{chips} diverged from one device: {gap}")
    say(f"  data:{chips} vs one device, same seed and batches: max per-pass "
        f"rel gap {gap:.2e} (tolerance {DP_REL_TOL}, bf16 compute)")
    return {"config_args": config_args(w), "chips": chips}


# ---------------------------------------------------------------------------
# children that own the chip (these import JAX; the parent never gets here)
# ---------------------------------------------------------------------------

def _child_setup(spec: dict):
    sys.path.insert(0, REPO)
    import jax

    from paddle_tpu.utils import enable_compile_cache

    enable_compile_cache()
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print("DEVICE_JSON:" + json.dumps(info), flush=True)
    if info["platform"] != "tpu" and not spec.get("rehearse"):
        print(f"chip_smoke: no TPU (platform={info['platform']!r})",
              file=sys.stderr)
        sys.exit(1)
    return jax


def _tell(msg: str) -> None:
    """One report line of a check child (the parent echoes these)."""
    print("  > " + msg, flush=True)


def _custom_calls(compiled, what: str, rehearse: bool) -> None:
    n = compiled.as_text().count("tpu_custom_call")
    _tell(f"{what}: tpu_custom_call x{n} in the compiled text")
    need(n > 0 or rehearse, f"{what}: no Pallas kernel in the compiled step")


def _host(tree):
    import jax
    import numpy as np
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)


def _assert_trees_identical(a, b, what: str) -> int:
    import jax
    import numpy as np
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    need(ta == tb, f"{what}: tree structure differs")
    for x, y in zip(la, lb):
        need(x.dtype == y.dtype and x.shape == y.shape and
             np.array_equal(x, y, equal_nan=True), f"{what}: a leaf differs")
    return len(la)


def child_probe(spec: dict) -> int:
    _child_setup(spec)
    return 0


def child_train_check(spec: dict) -> int:
    jax = _child_setup(spec)
    import numpy as np

    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.trainer.checkpoint import latest_checkpoint
    from paddle_tpu.trainer.trainer import Trainer

    cfg = parse_config(LM_CONFIG, spec["config_args"])
    tr = Trainer(cfg, seed=1)
    batch = next(iter(tr.train_batches()))
    compiled = tr._train_step.lower(tr.params, tr.opt_state, tr.net_state,
                                    batch, jax.random.PRNGKey(0)).compile()
    _custom_calls(compiled, "train step (attn_impl=flash)", spec["rehearse"])

    # checkpoint save -> load round trip, bit for bit
    tr.load(latest_checkpoint(spec["k1"]))
    want = _host((tr.params, tr.opt_state))
    path = tr.save(os.path.join(WORK, "roundtrip"))
    tr2 = Trainer(cfg, seed=2)      # another init: equality must come
    tr2.load(path)                  # from the file
    n = _assert_trees_identical(want, _host((tr2.params, tr2.opt_state)),
                                "checkpoint round trip")
    _tell(f"checkpoint save -> load: {n} arrays (parameters + optimizer "
          f"state) identical")
    tr2.load(latest_checkpoint(spec["k4"]))
    gap = max(float(np.max(np.abs(a.astype(np.float64) - b)))
              for a, b in zip(jax.tree.leaves(want[0]),
                              jax.tree.leaves(_host(tr2.params))))
    _tell(f"per-batch vs fused final parameters: max abs diff {gap:.3e}")
    return 0


def child_serve_check(spec: dict) -> int:
    jax = _child_setup(spec)
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.graph.builder import GraphExecutor
    from paddle_tpu.graph.context import TEST
    from paddle_tpu.graph.lm_decode import _resolve_io_names
    from paddle_tpu.parameter.argument import Argument
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.trainer.checkpoint import latest_checkpoint
    from paddle_tpu.trainer.trainer import Trainer

    cfg = parse_config(LM_CONFIG, spec["config_args"])
    tr = Trainer(cfg, seed=1)
    if spec["checkpoint"]:
        tr.load(latest_checkpoint(spec["checkpoint"]))
    eng = ServingEngine(tr.executor, tr.params, num_slots=spec["slots"],
                        page_size=spec["page"], max_context=spec["context"])
    eng._sync_run_mask(range(spec["slots"]))
    eng._sync_device_state()
    _custom_calls(eng._decode_step.lower(eng.params, eng._build_state(),
                                         eng._d_run).compile(),
                  "decode step (paged KV)", spec["rehearse"])

    # the reference: ONE dense fp32 forward of the same weights over each
    # prompt + served tokens (teacher forcing).  Every served greedy token
    # must be the reference's argmax, or trail it by a bf16-sized margin.
    dense = spec["config_args"].replace("attn_impl=flash", "attn_impl=dense") \
        .replace("compute_dtype=bfloat16", "compute_dtype=float32")
    ref = GraphExecutor(parse_config(LM_CONFIG, dense).model_config,
                        compute_dtype="float32")
    inp, out_name = _resolve_io_names(ref.model, None, None)
    seqs = [p + t for p, t in spec["greedy"]]
    L = max(map(len, seqs))
    ids = np.zeros((len(seqs), L), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    lens = np.asarray([len(s) for s in seqs], np.int32)
    outputs, _, _ = jax.jit(lambda p, a: ref.forward(p, a, None, TEST, None))(
        tr.params, {inp: Argument(ids=jnp.asarray(ids),
                                  lengths=jnp.asarray(lens))})
    probs = np.asarray(outputs[out_name].value, np.float32)
    need(np.isfinite(probs).all(), "reference forward is not finite")
    logp = np.log(np.maximum(probs, 1e-30))
    total = exact = 0
    worst = 0.0
    for i, (p, t) in enumerate(spec["greedy"]):
        for j, tok in enumerate(t):
            row = logp[i, len(p) + j - 1]
            worst = max(worst, float(row.max() - row[tok]))
            exact += int(row.argmax() == tok)
            total += 1
    _tell(f"greedy tokens vs dense fp32 reference: {exact}/{total} equal "
          f"its argmax; worst log-prob margin {worst:.4f} nats (tolerance "
          f"{GREEDY_MARGIN_NATS})")
    need(worst <= GREEDY_MARGIN_NATS, "served tokens left the reference")
    return 0


def child_dp_check(spec: dict) -> int:
    jax = _child_setup(spec)
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.parallel.dp import shard_batch
    from paddle_tpu.parallel.mesh import mesh_from_flag
    from paddle_tpu.trainer.trainer import Trainer

    n = spec["chips"]
    need(len(jax.devices()) == n, f"{len(jax.devices())} devices, not {n}")
    mesh = mesh_from_flag(f"data:{n}")
    tr = Trainer(parse_config(LM_CONFIG, spec["config_args"]), seed=1,
                 mesh=mesh)
    batch = next(iter(tr.train_batches()))
    sharded = shard_batch(mesh, batch)
    _custom_calls(tr._train_step.lower(tr.params, tr.opt_state, tr.net_state,
                                       sharded, jax.random.PRNGKey(0))
                  .compile(), f"data:{n} train step", spec["rehearse"])
    loss = float(tr.train_one_batch(batch))
    need(math.isfinite(loss), f"data:{n} step loss {loss}")

    def devices_of(x):
        return {s.device for s in x.addressable_shards}

    ids = sharded["tokens"].ids
    need(len(devices_of(ids)) == n, "batch is not spread over the chips")
    need(all(s.data.shape[0] == ids.shape[0] // n
             for s in ids.addressable_shards), "batch shards are not 1/n")
    slot = jax.tree.leaves(tr.opt_state["slots"])[0]
    need(len(devices_of(slot)) == n, "optimizer state sits on < n chips")
    _tell(f"batch {tuple(ids.shape)} in {n} shards of "
          f"{tuple(ids.addressable_shards[0].data.shape)} on {n} distinct "
          f"devices; optimizer state on {n} distinct devices")
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()]
    _tell(f"memory_stats bytes_in_use per device: {used}")
    need(spec["rehearse"] or all(u and u > 0 for u in used),
         "a chip holds nothing")
    return 0


CHILDREN = {"probe": child_probe, "train_check": child_train_check,
            "serve_check": child_serve_check, "dp_check": child_dp_check}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = ONLY the data-parallel train comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny widths (exit 3, never ok)")
    ap.add_argument("--phases", default="train,serve,kernels,hybrid",
                    help="one-chip phases to run (all are needed for ok)")
    ap.add_argument("--child", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    ap.add_argument("--spec", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child:
        with open(args.spec) as f:
            return CHILDREN[args.child](json.load(f))

    if "jax" in sys.modules:
        raise RuntimeError("the chip_smoke parent must stay off JAX")
    for rel in ("paddle_tpu/__main__.py", "tools/serve.py",
                "tools/tpu_parity.py", LM_CONFIG):
        if not os.path.exists(os.path.join(REPO, rel)):
            print(f"chip_smoke: {rel} is missing next to chip_smoke.py",
                  file=sys.stderr)
            return 2
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    w = TINY if args.rehearse else FULL
    phases = [p for p in args.phases.split(",") if p]
    all_phases = sorted(phases) == ["hybrid", "kernels", "serve", "train"]

    def check(child: str, spec: dict) -> None:
        spec["rehearse"] = args.rehearse
        say(_report(_run(child, _self(child, spec),
                         _env(args.rehearse, args.chips))))

    try:
        device = _tagged(_run("probe", _self("probe",
                                             {"rehearse": args.rehearse}),
                              _env(args.rehearse, args.chips)),
                         "DEVICE_JSON:")
        say(f"device: {device}")
        need(device["count"] >= args.chips,
             f"--chips {args.chips} but {device['count']} device(s)")
        if args.chips > 1:
            check("dp_check", phase_dp(w, args.rehearse, args.chips))
        else:
            trained = None
            if "train" in phases:
                trained = phase_train(w, args.rehearse)
                check("train_check", trained)
            if "serve" in phases:
                check("serve_check", phase_serve(
                    w, args.rehearse, trained["k1"] if trained else ""))
            if "kernels" in phases:
                phase_kernels(args.rehearse)
            if "hybrid" in phases:
                phase_hybrid(args.rehearse)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        _kill_all()

    say(f"wall time {time.time() - _T0:.0f}s")
    if args.rehearse or device["platform"] != "tpu":
        say(json.dumps({"ok": False, "rehearsal": True, "device": device}))
        return 3
    if args.chips == 1 and not all_phases:
        say(json.dumps({"ok": False, "partial": phases, "device": device}))
        return 3
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
