"""The cell lfm2-24b-serve.long-output-256 on the CPU: its rehearsal (hidden
64 / 4 heads over 2 KV heads / 2 layers / vocab 128 at the PUBLISHED expert
width, expert count and taps: a conv layer with the dense MLP and a
QK-normed attention layer with all 64 experts) prints the contract's line
untraced and traced with the cell's per-layer metrics; the fp8 control fails
the comparison that decides `correct` where the reference's own tokens pass
it; and the byte and operation counts of benchmark/lib/conv_moe.py against
numbers worked out by hand (ISSUE 35 section 1)."""

import json
import os
import subprocess
import sys

import pytest

CELL = "lfm2-24b-serve.long-output-256"
CONFIG = "lfm2-24b-a2b-serve"
# Four readers this PR brings as FILES and not yet as entries of
# BENCHMARK.json: tests/benchmark/test_dense_decode_roofline.py (PR 34's, not
# this PR's to edit) asserts that `dense_decode_hbm_roofline.serve` is the LAST
# per-layer metric, so nothing can be appended behind it, and an entry put
# in the middle reads as a change to what was there (PERF.md section 7 row
# 20 has the entries, for the `benchmark` PR that relaxes that assertion).
WITHHELD = {"conv_moe_decode_hbm_roofline.serve": ("graph and ops", "%",
                                                   "itl_p95_ms"),
            "moe_pairs_per_expert.serve-wide": ("graph and ops", "count",
                                                "output_tokens_per_s"),
            "moe_load_imbalance.serve-wide": ("graph and ops", "ratio",
                                              "output_tokens_per_s"),
            "short_conv_updates_per_step.serve": ("serving engine", "count",
                                                  "output_tokens_per_s")}
# the one accepted list the cell joins; the lists other cells' tests hold
# to their own cell (moe_pairs_per_expert.serve, moe_load_imbalance.serve,
# recurrent_updates_per_step.serve, token_frames_per_write.serve) it leaves
JOINED = {"paged_attn_roofline.serve"}


def _reader(bench, name):
    from benchmark.lib.spec import load_module
    return load_module(os.path.join(bench.dir, "layer_metrics", name + ".py"),
                       "metric_" + name)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(root, bench, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    # The window is the benchmark's own `run_seconds`, as the hybrid cell's
    # rehearsal takes it and for its reason: rehearse.json shrinks the slots
    # to 4 and not the configuration's 512 step tokens, and a mixed step of
    # 512 rows on this CPU is a quarter of a second alone and more beside
    # five other workers, so 3 s could hold one step and no inter-token
    # gap.  At the lowest priority: it takes the cores the other workers
    # leave idle.
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 81), "--seconds",
         str(bench.doc["run_seconds"]), "--trace", str(trace),
         "--rehearse"], cwd=root, env=env,
        capture_output=True, text=True, timeout=1200,
        preexec_fn=lambda: os.nice(19))
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] is True and out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["count"] == 1
    names = {c["name"] for c in out["checks"]}
    assert {"serve_margin_nats", "compiles_in_window"} <= names
    if trace:
        want = {m["name"] for m in bench.per_layer_for(CELL)}
        got = out["metrics"]
        # what the program counts and the host clocks always reads; what
        # comes from the spans and ops of a one-second traced slice reads
        # only if the slice held a whole step, and the CPU has no Mosaic
        # kernel to time
        sliced = {n for n in want if bench.per_layer[n]["source"] in
                  ("program_span", "device_trace")}
        assert want - set(got) <= sliced
        assert got["slot_occupancy.serve"]["value"] > 0
        if "decode_step_ms.serve" in got:       # the slice held steps
            assert want - set(got) <= {"paged_attn_roofline.serve",
                                       "mixed_step_ms.serve"}
    else:
        assert set(out["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                       "setup_s"}


def test_cell_and_its_metrics_are_declared_as_the_issue_names_them(bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "long-output-256", 1)
    tf = bench.traffic("long-output-256")
    assert (tf["loop"], tf["clients"], tf["output_len_step"]) == \
        ("closed", 256, 128)
    assert "rate_per_s" not in tf               # no rate is offered
    assert (tf["prompt_len"]["lo"], tf["prompt_len"]["hi"]) == (256, 1024)
    assert (tf["output_len"]["lo"], tf["output_len"]["hi"]) == (512, 2048)
    assert (tf["ramp_s"], tf["check_requests"], tf["check_max_tokens"],
            tf["trace_s"], tf["requests_per_client"], tf["max_context"]) == \
        (40.0, 6, 2048, 4.0, 8, 4096)
    # the long-output mix at four times the width, and a ramp that outlasts
    # the cold start's backlog at 256 chunk rows a step (27-29 s on the
    # chip: PERF.md section 6), so the traced slice holds both step kinds
    base = bench.traffic("long-output")
    assert {k for k in tf if tf[k] != base.get(k)} == \
        {"clients", "ramp_s", "note", "name"}
    cfg = bench.config(CONFIG)
    assert cfg["server_flags"]["slots"] == tf["clients"]
    assert cfg["server_flags"]["max_context"] == tf["max_context"]
    e2e = {m["name"] for m in bench.end_to_end_for(CELL)}
    assert e2e == {"output_tokens_per_s", "itl_p95_ms", "setup_s"}
    per = {m["name"] for m in bench.per_layer_for(CELL)}
    assert JOINED <= per
    for name in JOINED:
        assert CELL in bench.per_layer[name]["workloads"]
    for name, (layer, unit, moves) in WITHHELD.items():
        r = _reader(bench, name)        # the file is there and says what
        assert (r.LAYER, r.UNIT, r.MOVES) == (layer, unit, moves)
        assert layer in {m["layer"] for m in bench.per_layer.values()}
        assert moves in {m["name"] for m in bench.end_to_end_for(CELL)}
    # the other models' own shares are not this cell's to report
    assert not {"decode_hbm_roofline.serve", "mla_attn_roofline.serve",
                "hybrid_decode_hbm_roofline.serve", "kda_step_roofline.serve",
                "dense_decode_hbm_roofline.serve",
                "recurrent_updates_per_step.serve",
                "moe_pairs_per_expert.serve"} & per
    assert bench.configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "num_dense_layers"]


TINY = dict(hidden_size=64, intermediate_size=64, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=5, vocab_size=64,
            moe_intermediate_size=16, num_experts=16, experts_held=16,
            num_experts_per_tok=4, param_dtype="float32", init_std=0.3,
            select_bias_std=0.3)


def test_served_margin_passes_the_reference_and_fails_the_fp8_control(bench):
    """Teacher-forced greedy tokens at a tiny size: the reference's own
    argmax trails nothing, the bf16 control stays under the tiny limit, the
    fp8 control — the precision below the configuration's — does not."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.check import served_margin
    ref = bench.reference("lfm2_moe")
    cfg = dict(bench.config(CONFIG), **TINY)
    w = ref.make_weights(cfg, 3)
    lp = ref.jitted("log_probs", cfg)
    served = []
    rng = np.random.default_rng(0)
    for _ in range(4):
        prompt = rng.integers(2, 64, 8).tolist()
        seq = list(prompt)
        for _ in range(24):                # greedy decode with the reference
            ids = np.zeros(32, np.int32)
            ids[:len(seq)] = seq
            rows = np.zeros(32, np.int32)
            rows[0] = len(seq) - 1
            with jax.default_matmul_precision("highest"):
                seq.append(int(jnp.argmax(lp(w, jnp.asarray(ids),
                                             jnp.asarray(rows))[0])))
        served.append((prompt, seq[len(prompt):]))
    own = served_margin(jax, ref, cfg, w, served, 32)
    assert own["mean_nats"] == 0.0 and own["tokens"] == 96
    bf = served_margin(jax, ref, cfg, w, served, 32, quant="bf16")
    f8 = served_margin(jax, ref, cfg, w, served, 32, quant="fp8")
    limit = 0.02
    assert bf["mean_nats"] < limit < f8["mean_nats"], (bf, f8)


def test_decode_step_bytes_by_hand(bench):
    """The cell's decode step, worked out by hand (ISSUE 35 section 1): four
    conv mixers of 16.78 M parameters, one attention mixer of 10.49 M, 64
    experts of 9.437 M in each of four layers, 6.08 GB a step at 256 rows of
    1.3 k live tokens, 79% of it the experts."""
    from benchmark.lib import conv_moe as cm
    cfg = bench.config(CONFIG)
    assert cm.mixer_layers(cfg) == (4, 1)
    assert cm.mixer_layers(dict(cfg, num_hidden_layers=2)) == (1, 1)
    assert cm.conv_params(cfg) == 2048 * 6144 + 3 * 2048 + 2048 * 2048
    assert round(cm.conv_params(cfg) / 1e6, 2) == 16.78
    assert cm.attention_params(cfg) == (2 * 2048 * 2048 + 2 * 2048 * 512
                                        + 2 * 64)
    assert round(cm.attention_params(cfg) / 1e6, 2) == 10.49
    assert cm.expert_params(cfg) == 3 * 2048 * 1536 == 9_437_184
    assert cm.kv_row_bytes(cfg) == 2048          # 8 heads x 64 x K, V x 2 B
    assert cm.conv_tail_bytes(cfg) == 2 * 2048 * 2 == 8192
    assert cm.moe_layers(cfg) == 4
    parts = cm.decode_step_bytes(cfg, rows=256, live_tokens=256 * 1300,
                                 pairs_per_expert=16.0, tail_rows=256)
    assert parts["routed_experts"] == pytest.approx(
        4 * 64 * 9_437_184 * 2, rel=1e-6)        # every expert draws a pair
    assert round(parts["routed_experts"] / 1e9, 2) == 4.83
    assert parts["kv_rows"] == 256 * 1300 * 2048
    assert round(parts["kv_rows"] / 1e9, 2) == 0.68
    assert parts["head"] == 2048 * 65536 * 2
    assert round(parts["head"] / 1e9, 2) == 0.27
    assert parts["dense_mlp"] == 3 * 2048 * 11776 * 2
    assert round(parts["dense_mlp"] / 1e9, 3) == 0.145
    mixers = parts["conv_matrices"] + parts["attention_matrices"]
    assert round(mixers / 1e9, 3) == 0.155
    assert parts["conv_tails"] == 4 * 2 * 8192 * 256
    assert parts["router"] == 4 * 2048 * 64 * 2
    assert 6.05e9 < parts["total"] < 6.12e9
    assert 0.785 < parts["routed_experts"] / parts["total"] < 0.80
    # at 2 pairs an expert one in seven experts draws none
    few = cm.decode_step_bytes(cfg, 32, 32 * 1300, 2.0, 32)
    assert few["routed_experts"] == pytest.approx(
        parts["routed_experts"] * 0.8647, rel=1e-3)
    # what parallel/moe.py computes against what was routed
    f256, f512 = cm.expert_flops(cfg, 256), cm.expert_flops(cfg, 512)
    assert round(f256["rows_x_held"] / 1e12, 2) == 1.24
    assert round(f512["rows_x_held"] / 1e12, 2) == 2.47
    assert round(f256["routed_pairs"] / 1e12, 2) == 0.08
    assert f256["rows_x_held"] == 16 * f256["routed_pairs"]


def test_readers_read_nothing_from_a_program_without_the_counters(
        bench, monkeypatch):
    """Laid over a parent checkout the readers return None and do not
    raise: the program there counts nothing for this model (and
    obs.metrics may have no process_counters at all)."""
    import types

    import paddle_tpu.obs.metrics as metrics
    from benchmark.lib import conv_moe as cm
    ctx = types.SimpleNamespace(cfg=bench.config(CONFIG), trace_data=None,
                                counters={})
    readers = [_reader(bench, n) for n in sorted(WITHHELD)]
    real = metrics.process_counters
    monkeypatch.setattr(metrics, "process_counters",
                        lambda: types.SimpleNamespace(snapshot=lambda: {}))
    assert cm.updates_per_step(ctx.cfg) is None
    assert [r.read(ctx) for r in readers] == [None] * 4
    monkeypatch.setattr(metrics, "process_counters", real)
    monkeypatch.delattr(metrics, "process_counters")
    assert [r.read(ctx) for r in readers] == [None] * 4
