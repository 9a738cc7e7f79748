"""The cell kimi-linear-48b-serve.long-output-128 on the CPU: its rehearsal
(hidden 64 / 4 heads / 2 layers / vocab 128 at the PUBLISHED KDA, latent and
expert widths: one KDA layer and one NoPE latent layer) prints the contract's
line untraced and traced with the cell's per-layer metrics; the fp8 control
fails the comparison that decides `correct` where the reference's own tokens
pass it; and the byte counts of the roofline metrics against numbers worked
out by hand."""

import json
import os
import subprocess
import sys

import pytest

CELL = "kimi-linear-48b-serve.long-output-128"
CONFIG = "kimi-linear-48b-a3b-serve"
NEW = {"kda_step_roofline.serve", "hybrid_decode_hbm_roofline.serve",
       "recurrent_updates_per_step.serve"}
# the one accepted list the cell joins; the GigaChat cell's own test
# (tests/benchmark/test_cell_gigachat3.py, not this PR's to edit) holds
# moe_pairs_per_expert.serve, moe_load_imbalance.serve and
# mla_attn_roofline.serve to that cell alone
JOINED = {"token_frames_per_write.serve"}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(root, bench, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    # The window is the benchmark's own `run_seconds`, not the 3 s the
    # other cells' rehearsals take: the configuration's 320 step tokens
    # are not among the sizes rehearse.json shrinks, and a mixed step of
    # 320 rows through the interpreted `mla_paged_attn` in bfloat16 is 4 ms
    # a row on this CPU (1.2 s a step alone, 2.2 s beside five other
    # workers), so 3 s held ONE step and no inter-token gap.  At the lowest
    # priority: this is the heaviest rehearsal of the suite, it takes the
    # cores the other workers leave idle, and their traced rehearsals keep
    # the one-second slice that has to hold a whole step of theirs.
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 78), "--seconds",
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
        # only if the slice held a whole step (the interpreted mixed step
        # of 320 rows is longer than the slice; a run of 20 ms decode
        # steps between two of them fits), and the CPU has no Mosaic
        # kernel to time
        sliced = {n for n in want if bench.per_layer[n]["source"] in
                  ("program_span", "device_trace")}
        assert want - set(got) <= sliced
        assert 0 < got["recurrent_updates_per_step.serve"]["value"] <= 4
        assert got["token_frames_per_write.serve"]["value"] >= 1
        if "decode_step_ms.serve" in got:       # the slice held steps
            assert want - set(got) <= {"kda_step_roofline.serve",
                                       "mixed_step_ms.serve"}
            assert 0 < got["hybrid_decode_hbm_roofline.serve"]["value"]
    else:
        assert set(out["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                       "setup_s"}


def test_cell_and_its_metrics_are_declared_as_the_issue_names_them(bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "long-output-128", 1)
    tf = bench.traffic("long-output-128")
    assert (tf["loop"], tf["clients"], tf["output_len_step"]) == \
        ("closed", 128, 128)
    assert "rate_per_s" not in tf               # no rate is offered
    assert (tf["prompt_len"]["lo"], tf["prompt_len"]["hi"]) == (256, 1024)
    assert (tf["output_len"]["lo"], tf["output_len"]["hi"]) == (512, 2048)
    assert (tf["ramp_s"], tf["check_requests"], tf["check_max_tokens"],
            tf["trace_s"], tf["requests_per_client"], tf["max_context"]) == \
        (25.0, 6, 2048, 4.0, 8, 4096)
    # the long-output mix at twice the width: nothing else differs
    base = bench.traffic("long-output")
    assert {k for k in tf if tf[k] != base.get(k)} == \
        {"clients", "ramp_s", "note", "name"}
    e2e = {m["name"] for m in bench.end_to_end_for(CELL)}
    assert e2e == {"output_tokens_per_s", "itl_p95_ms", "setup_s"}
    per = {m["name"] for m in bench.per_layer_for(CELL)}
    assert NEW | JOINED <= per
    for name in NEW:
        assert bench.per_layer[name]["workloads"] == [CELL]
        bench.reader(name)              # LAYER / UNIT / MOVES agree
    for name in JOINED:
        assert bench.per_layer[name]["workloads"][-1] == CELL
    # the other models' own shares are not this cell's to report
    assert not {"paged_attn_roofline.serve", "decode_hbm_roofline.serve",
                "mla_attn_roofline.serve"} & per
    assert bench.configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]


TINY = dict(hidden_size=32, intermediate_size=64, num_attention_heads=4,
            num_hidden_layers=4, vocab_size=64, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            moe_intermediate_size=16, num_experts=16, experts_held=4,
            ep_rank=1, num_experts_per_token=4, param_dtype="float32",
            init_std=0.3, select_bias_std=0.3)


def test_served_margin_passes_the_reference_and_fails_the_fp8_control(bench):
    """Teacher-forced greedy tokens at a tiny size: the reference's own
    argmax trails nothing, the bf16 control stays under the tiny limit, the
    fp8 control — the precision below the configuration's — does not."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.check import served_margin
    ref = bench.reference("kimi_linear")
    cfg = dict(bench.config(CONFIG), **TINY)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"], head_dim=8,
                                     num_heads=4)
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
    """The cell's decode step, worked out by hand (ISSUE 33 section 1): 10
    KDA layers of 39.5 M parameters and 2 MiB of state a row, 3 MLA layers
    of 29.1 M, 10.1 GB a step at 128 rows of 1.3 k live tokens."""
    from benchmark.lib import hybrid_linear as hl
    cfg = bench.config(CONFIG)
    assert hl.mixer_layers(cfg) == (10, 3)
    assert hl.mixer_layers(dict(cfg, num_hidden_layers=2)) == (1, 1)
    assert hl.kda_state_bytes(cfg) == 32 * 128 * 128 * 4 == 2 * 2 ** 20
    assert hl.kda_params(cfg) == (
        4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
        + 3 * 4 * 4096)
    assert round(hl.kda_params(cfg) / 1e6, 1) == 39.5
    assert hl.mla_params(cfg) == (2304 * 6144 + 2304 * 576 + 512 * 8192
                                  + 4096 * 2304)
    assert round(hl.mla_params(cfg) / 1e6, 1) == 29.1
    parts = hl.decode_step_bytes(cfg, rows=128, live_tokens=128 * 1300,
                                 pairs_per_expert=4.0, state_rows=128)
    assert parts["kda_state"] == 10 * 2 * 128 * 2 * 2 ** 20
    assert round(parts["kda_state"] / 1e9, 2) == 5.37
    assert round(parts["kda_matrices"] / 1e9, 2) == 0.79
    expert = 3 * 2304 * 1024 * 2
    assert parts["routed_experts"] == pytest.approx(
        12 * 16 * expert * 0.9817, rel=1e-3)
    assert parts["shared_experts"] == 12 * expert
    assert parts["dense_mlp"] == 3 * 2304 * 9216 * 2
    assert parts["head"] == 2304 * 20480 * 2
    assert parts["latent_rows"] == 3 * 128 * 1300 * 1152
    assert 9.9e9 < parts["total"] < 10.3e9
    share = (parts["kda_state"] + parts["kda_matrices"]) / parts["total"]
    assert 0.59 < share < 0.63          # "the KDA layers are 61% of it"
    # the kernel's call: a live row's state read once and written once,
    # six operations a state element
    cost = hl.kda_step_cost(cfg, 100)
    assert cost["bytes"] == 2 * 100 * 2 * 2 ** 20
    assert cost["flops"] == 6 * 100 * 32 * 128 * 128


def test_readers_read_nothing_from_a_program_without_the_counters(
        bench, monkeypatch):
    """Laid over a parent checkout the readers return None and do not
    raise: the program there counts no recurrent step (and obs.metrics may
    have no process_counters at all)."""
    import types

    import paddle_tpu.obs.metrics as metrics
    from benchmark.lib import hybrid_linear as hl
    ctx = types.SimpleNamespace(cfg=bench.config(CONFIG), trace_data=None,
                                counters={})
    readers = [bench.reader(n) for n in sorted(NEW)]
    real = metrics.process_counters
    monkeypatch.setattr(metrics, "process_counters",
                        lambda: types.SimpleNamespace(snapshot=lambda: {}))
    assert hl.updates_per_step(ctx.cfg) is None
    assert [r.read(ctx) for r in readers] == [None] * 3
    monkeypatch.setattr(metrics, "process_counters", real)
    monkeypatch.delattr(metrics, "process_counters")
    assert [r.read(ctx) for r in readers] == [None] * 3
