"""The cell gigachat3.1-702b-serve.long-output on the CPU: its rehearsal
(hidden 64 / 4 heads / 2 layers / vocab 128 at the PUBLISHED latent and
expert widths) prints the contract's line untraced and traced with every
per-layer metric of the cell; the fp8 control fails the comparison that
decides `correct` where the reference's own tokens pass it; and the byte
counts of the roofline metrics against numbers worked out by hand."""

import json
import os
import subprocess
import sys

import pytest

CELL = "gigachat3.1-702b-serve.long-output"
NEW = {"moe_pairs_per_expert.serve", "moe_load_imbalance.serve",
       "decode_hbm_roofline.serve", "mla_attn_roofline.serve"}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(root, bench, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 78), "--seconds", "3",
         "--trace", str(trace), "--rehearse"], cwd=root, env=env,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] is True and out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["count"] == 1
    names = {c["name"] for c in out["checks"]}
    assert {"serve_margin_nats", "compiles_in_window"} <= names
    if trace:
        want = {m["name"] for m in bench.per_layer_for(CELL)}
        # the CPU has no Mosaic kernel to time; everything else reads
        assert want - set(out["metrics"]) <= {"mla_attn_roofline.serve"}
        assert NEW & set(out["metrics"]) >= NEW - {"mla_attn_roofline.serve"}
        assert out["metrics"]["moe_pairs_per_expert.serve"]["value"] > 0
        assert out["metrics"]["moe_load_imbalance.serve"]["value"] >= 1
    else:
        assert set(out["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                       "setup_s"}


def test_cell_and_its_metrics_are_declared_as_the_issue_names_them(bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("gigachat3.1-702b-a36b-serve", "long-output", 1)
    tf = bench.traffic("long-output")
    assert (tf["loop"], tf["clients"], tf["output_len_step"]) == \
        ("closed", 64, 128)
    assert (tf["prompt_len"]["lo"], tf["prompt_len"]["hi"]) == (256, 1024)
    assert (tf["output_len"]["lo"], tf["output_len"]["hi"]) == (512, 2048)
    e2e = {m["name"] for m in bench.end_to_end_for(CELL)}
    assert e2e == {"output_tokens_per_s", "itl_p95_ms", "setup_s"}
    per = {m["name"] for m in bench.per_layer_for(CELL)}
    assert NEW <= per
    for name in NEW:
        assert bench.per_layer[name]["workloads"] == [CELL]
        bench.reader(name)              # LAYER / UNIT / MOVES agree
    # the StarCoder2 kernel's share is not this cell's to report
    assert "paged_attn_roofline.serve" not in per


TINY = dict(hidden_size=32, intermediate_size=64, num_attention_heads=4,
            num_hidden_layers=2, vocab_size=64, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=12, moe_intermediate_size=16, n_routed_experts=16,
            experts_held=4, ep_rank=1, n_group=4, topk_group=2,
            num_experts_per_tok=4, param_dtype="float32", init_std=0.3,
            select_bias_std=0.3)


def test_served_margin_passes_the_reference_and_fails_the_fp8_control(bench):
    """Teacher-forced greedy tokens at a tiny size: the reference's own
    argmax trails nothing, the bf16 control stays under the rehearsal's
    limit, the fp8 control — the precision below the configuration's —
    does not."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.check import served_margin
    ref = bench.reference("gigachat3")
    cfg = dict(bench.config("gigachat3.1-702b-a36b-serve"), **TINY)
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
    """The cell's decode step, worked out by hand (ISSUE 29's table):
    attention 132.6 M parameters a layer, 5.5 GB of weights a step with
    every held expert hit, 1,152 B a live token a layer."""
    from benchmark.lib import latent_moe
    cfg = bench.config("gigachat3.1-702b-a36b-serve")
    att = latent_moe.attention_params(cfg)
    assert att == (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
                   + 512 * 64 * 320 + 64 * 192 * 7168)
    assert round(att / 1e6, 1) == 132.6
    assert latent_moe.latent_row_bytes(cfg) == 1152
    parts = latent_moe.decode_step_bytes(cfg, rows=64, live_tokens=0,
                                         pairs_per_expert=50.0)
    expert = 3 * 7168 * 2048 * 2
    assert parts["routed_experts"] == pytest.approx(4 * 8 * expert, rel=1e-6)
    assert parts["shared_experts"] == 4 * expert
    assert parts["dense_mlp"] == 3 * 7168 * 18432 * 2
    assert parts["head"] == 7168 * 16032 * 2
    assert 5.4e9 < parts["total"] < 5.6e9
    # 2 pairs an expert a call: 86% of the held experts drew one
    assert latent_moe.experts_hit(2.0) == pytest.approx(0.8647, abs=1e-4)
    live = latent_moe.decode_step_bytes(cfg, 64, 64 * 1300, 50.0)
    assert live["latent_rows"] == 5 * 64 * 1300 * 1152
    # the kernel's call: the live rows read once, 2*64*(576+512) flops each
    cost = latent_moe.latent_attention_cost(cfg, 1000, 64)
    assert cost["flops"] == 2.0 * 64 * (576 + 512) * 1000
    assert cost["bytes"] == 1000 * 1152 + 64 * 64 * (576 + 512) * 2


def test_moe_readers_read_nothing_from_a_program_without_the_counters(
        bench, monkeypatch):
    """Laid over a parent checkout the readers return None and do not
    raise: obs.metrics there has no process_counters."""
    import types

    import paddle_tpu.obs.metrics as metrics
    ctx = types.SimpleNamespace(cfg=bench.config(
        "gigachat3.1-702b-a36b-serve"), trace_data=None, counters={})
    pairs = bench.reader("moe_pairs_per_expert.serve")
    imb = bench.reader("moe_load_imbalance.serve")
    monkeypatch.delattr(metrics, "process_counters")
    assert pairs.read(ctx) is None and imb.read(ctx) is None
    assert bench.reader("decode_hbm_roofline.serve").read(ctx) is None
    assert bench.reader("mla_attn_roofline.serve").read(ctx) is None
