"""The cell jamba2-3b-serve.long-output-256 on the CPU: its rehearsal (hidden
64 / 4 heads over 1 KV head / 2 layers / vocab 128 at the PUBLISHED Mamba
sizes — state 16, time-step rank 160, 4 taps, expansion 2 — and head size:
two Mamba layers, a stack with no page-indexed part) prints the contract's
line untraced and, on a copy with this PR's three withheld entries laid in,
traced with the readers' metrics; the fp8 control fails the comparison that
decides `correct` where the reference's own tokens pass it; the
configuration file is the catalog row with nothing cut, and the DSL's
defaults are the file's; the three readers read nothing without a trace or
counters and the right number from a canned one; and the byte and operation
counts of benchmark/lib/ssm_dense.py against the tables of ISSUE 43
sections 3 and 4."""

import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

CELL = "jamba2-3b-serve.long-output-256"
CONFIG = "jamba2-3b-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "AI21-Jamba2-3B"
# Three readers this PR brings as FILES and not yet as entries of
# BENCHMARK.json: tests/benchmark/test_dense_decode_roofline.py (PR 34's,
# not this PR's to edit) asserts that `dense_decode_hbm_roofline.serve` is
# the LAST per-layer metric, so nothing can be appended behind it (PERF.md
# section 7 row 20 has the entries verbatim, for the `benchmark` PR that
# relaxes that assertion; this file reads them from there).
WITHHELD = {"selective_scan_roofline.serve": ("kernels", "%", "itl_p95_ms"),
            "ssm_dense_decode_hbm_roofline.serve": ("graph and ops", "%",
                                                    "itl_p95_ms"),
            "selective_state_updates_per_step.serve": (
                "serving engine", "count", "output_tokens_per_s")}
NAMES = list(WITHHELD)
# what BENCHMARK.json held before this cell, in its order
CELLS_BEFORE = ["sc2-3b-train.seq4k", "sc2-3b-serve.decode-saturated",
                "sc2-3b-serve.chat", "sc2-3b-train.seq4k-dp4",
                "gigachat3.1-702b-serve.long-output",
                "kimi-linear-48b-serve.long-output-128",
                "lfm2-24b-serve.long-output-256",
                "nemotron3-nano-30b-serve.long-output-256"]
CONFIGS_BEFORE = ["starcoder2-3b-train", "starcoder2-3b-serve",
                  "gigachat3.1-702b-a36b-serve", "kimi-linear-48b-a3b-serve",
                  "lfm2-24b-a2b-serve", "nemotron3-nano-30b-a3b-serve"]


def _reader(bench, name):
    from benchmark.lib.spec import load_module
    return load_module(os.path.join(bench.dir, "layer_metrics", name + ".py"),
                       "metric_" + name)


def withheld_entries(root) -> list:
    """This PR's three `per_layer` entries, verbatim from PERF.md."""
    with open(os.path.join(root, "PERF.md")) as f:
        text = f.read()
    found = {}
    for blob in re.findall(r"`(\{\"name\": \"[^`]*\})`", text):
        entry = json.loads(blob)
        if entry["name"] in NAMES:
            found[entry["name"]] = entry
    assert sorted(found) == sorted(NAMES), sorted(found)
    return [found[n] for n in NAMES]


def _rehearse(root, cwd, trace, seed):
    # a window of 3 s: the rehearsal's two tiny Mamba layers step in 3-4 ms,
    # so its 4 clients x 50 requests are served in about 4 s — a longer
    # window would end idle, and the profiler's one-second slice with it
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "3",
         "--trace", str(trace), "--rehearse"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=1200,
        preexec_fn=lambda: os.nice(19))
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] is True and out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["count"] == 1
    names = {c["name"] for c in out["checks"]}
    assert {"serve_margin_nats", "compiles_in_window"} <= names
    return out


def test_rehearsal_prints_the_contracts_last_line(root, bench):
    out = _rehearse(root, root, 0, 2 ** 31 + 143)
    assert set(out["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                   "setup_s"}


def test_traced_rehearsal_reads_the_withheld_readers(root, tmp_path):
    """`run.py --rehearse --trace 1` on a copy of the benchmark whose
    BENCHMARK.json has PERF.md's three entries appended: the counter's
    reader reads the rehearsal's own count, and the two that read the
    device's trace are asked (what comes from the ops of a one-second slice
    on the CPU, where the scan is no Mosaic call, may have nothing to
    read: then the line leaves them out and nothing raises)."""
    from benchmark.lib.spec import Benchmark
    copy = tmp_path / "repo"
    shutil.copytree(os.path.join(root, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["per_layer"].extend(withheld_entries(root))
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(doc, f)
    for d in ("paddle_tpu", "tools", "demo"):
        os.symlink(os.path.join(root, d), os.path.join(copy, d))
    b = Benchmark(str(copy))
    for name in NAMES:
        b.reader(name)                 # LAYER, UNIT, MOVES agree, or raises
    want = {m["name"] for m in b.per_layer_for(CELL)}
    assert set(NAMES) <= want
    for c in b.cells:
        if c != CELL:
            assert not set(NAMES) & {m["name"] for m in b.per_layer_for(c)}
    out = _rehearse(root, str(copy), 1, 2 ** 31 + 144)
    got = out["metrics"]
    sliced = {n for n in want if b.per_layer[n]["source"] in
              ("program_span", "device_trace")}
    assert want - set(got) <= sliced
    assert got["slot_occupancy.serve"]["value"] > 0
    # two Mamba layers at the rehearsal's 4 slots: a few states a step
    m = got["selective_state_updates_per_step.serve"]
    assert m["unit"] == "count" and 0 < m["value"] <= 5


def test_cell_and_its_metrics_are_declared_as_the_issue_names_them(root,
                                                                   bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "long-output-256", 1)
    # appended behind what was there, whose order stands (never "the last":
    # the next cell is appended behind this one)
    assert list(bench.cells)[:9] == CELLS_BEFORE + [CELL]
    assert list(bench.configs)[:7] == CONFIGS_BEFORE + [CONFIG]
    tf = bench.traffic("long-output-256")
    cfg = bench.config(CONFIG)
    assert cfg["server_flags"]["slots"] == tf["clients"] == 256
    assert cfg["server_flags"]["max_context"] == tf["max_context"] == 4096
    e2e = {m["name"] for m in bench.end_to_end_for(CELL)}
    assert e2e == {"output_tokens_per_s", "itl_p95_ms", "setup_s"}
    for name, n in (("output_tokens_per_s", 5), ("itl_p95_ms", 6)):
        assert bench.end_to_end[name]["workloads"].index(CELL) == n
    per = {m["name"] for m in bench.per_layer_for(CELL)}
    assert {"device_idle_share.serve", "slot_occupancy.serve",
            "compiles_in_window.serve"} <= per
    entries = {e["name"]: e for e in withheld_entries(root)}
    for name, (layer, unit, moves) in WITHHELD.items():
        r = _reader(bench, name)        # the file is there and says what
        assert (r.LAYER, r.UNIT, r.MOVES) == (layer, unit, moves)
        assert name not in bench.per_layer          # the pin stands
        assert layer in {m["layer"] for m in bench.per_layer.values()}
        assert moves in e2e
        e = entries[name]
        assert e == {"name": name, "unit": unit, "better": "higher",
                     "source": "program_counter" if unit == "count"
                     else "device_trace", "layer": layer, "moves": moves,
                     "workloads": [CELL]}
    # the other models' own shares are not this cell's, and the paged
    # kernel's reader sums EVERY custom call of a serve step (ROADMAP B17):
    # it would take the scan's calls for the paged kernel
    assert not {"paged_attn_roofline.serve", "decode_hbm_roofline.serve",
                "mla_attn_roofline.serve", "kda_step_roofline.serve",
                "hybrid_decode_hbm_roofline.serve",
                "dense_decode_hbm_roofline.serve",
                "recurrent_updates_per_step.serve",
                "moe_pairs_per_expert.serve"} & per
    assert bench.configs[CONFIG]["reduced"] == ["tie_word_embeddings"]
    assert bench.configs[CONFIG]["source"] == cfg["source"]


def test_the_cell_before_this_one_is_declared_as_its_own_test_says(bench):
    """PR 41's test of the Nemotron cell's declarations pins that cell as
    the LAST of BENCHMARK.json's lists; with this cell behind it that one
    assertion cannot hold, the file is a `benchmark` PR's to edit, and
    tests/conftest.py expects the test to fail until one does.  So that
    nothing it held is lost meanwhile: every assertion of it, from its own
    names, with "the last" replaced by the cell's index and the order of
    everything before it."""
    from benchmark.lib.spec import load_module
    nemo = load_module(os.path.join(os.path.dirname(__file__),
                                    "test_cell_nemotron_h.py"),
                       "the_nemotron_cells_test")
    cell = bench.cell(nemo.CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (nemo.CONFIG, "long-output-256", 1)
    assert CELLS_BEFORE[-1] == nemo.CELL and CONFIGS_BEFORE[-1] == nemo.CONFIG
    assert list(bench.cells)[:8] == CELLS_BEFORE
    assert list(bench.configs)[:6] == CONFIGS_BEFORE
    tf = bench.traffic("long-output-256")
    cfg = bench.config(nemo.CONFIG)
    assert cfg["server_flags"]["slots"] == tf["clients"] == 256
    assert cfg["server_flags"]["max_context"] == tf["max_context"] == 4096
    e2e = {m["name"] for m in bench.end_to_end_for(nemo.CELL)}
    assert e2e == {"output_tokens_per_s", "itl_p95_ms", "setup_s"}
    serve = CELLS_BEFORE[4:]
    assert bench.end_to_end["output_tokens_per_s"]["workloads"][:5] == \
        CELLS_BEFORE[1:2] + serve
    assert bench.end_to_end["itl_p95_ms"]["workloads"][:6] == \
        [CELLS_BEFORE[2], CELLS_BEFORE[1]] + serve
    per = {m["name"] for m in bench.per_layer_for(nemo.CELL)}
    assert {"device_idle_share.serve", "slot_occupancy.serve",
            "compiles_in_window.serve"} <= per
    for name, (layer, unit, moves) in nemo.WITHHELD.items():
        r = nemo._reader(bench, name)
        assert (r.LAYER, r.UNIT, r.MOVES) == (layer, unit, moves)
        assert name not in bench.per_layer
        assert layer in {m["layer"] for m in bench.per_layer.values()}
        assert moves in e2e
    assert not {"paged_attn_roofline.serve", "decode_hbm_roofline.serve",
                "mla_attn_roofline.serve", "kda_step_roofline.serve",
                "hybrid_decode_hbm_roofline.serve",
                "dense_decode_hbm_roofline.serve",
                "recurrent_updates_per_step.serve",
                "moe_pairs_per_expert.serve"} & per
    assert bench.configs[nemo.CONFIG]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert bench.configs[nemo.CONFIG]["source"] == cfg["source"]


# -- the configuration ------------------------------------------------------------

def test_configuration_file_is_the_catalog_row_with_nothing_cut(bench):
    import numpy as np
    cfg = bench.config(CONFIG)
    ref = bench.reference("jamba")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == ROW)
        assert cfg["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k == "tie_word_embeddings":      # the one departure
                assert v is True and cfg[k] is False
                assert cfg["published"][k] is True
            else:
                assert cfg[k] == v, k
        assert "order of the layer types" in row["not_given"]
    assert set(cfg["reduced"]) == {"tie_word_embeddings"}
    for key in ("published", "deployment", "assumed", "departures"):
        assert cfg[key], key
    # every published width, the full depth, the whole vocabulary
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"]) == (2560, 8192, 20, 1, 128)
    assert (cfg["mamba_expand"], cfg["mamba_d_state"], cfg["mamba_dt_rank"],
            cfg["mamba_d_conv"], cfg["mamba_conv_bias"],
            cfg["mamba_proj_bias"]) == (2, 16, 160, 4, True, False)
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == \
        (cfg["published"]["num_hidden_layers"],
         cfg["published"]["vocab_size"]) == (28, 65536)
    assert cfg["deployment"]["chips"] == 1
    assert [i for i in range(28) if ref.is_attention(cfg, i)] == [7, 21]
    # a rehearsal's two layers are two Mamba layers
    assert not any(ref.is_attention(cfg, i) for i in range(2))
    # what the row does not settle
    for key in ("layer order", "state_dtype", "mamba initializers",
                "attn_use_rope", "head_dim"):
        assert key in cfg["assumed"], key
    assert cfg["attn_use_rope"] is False and cfg["state_dtype"] == "float32"
    assert cfg["server_flags"] == {
        "slots": 256, "page_size": 16, "max_context": 4096,
        "prefill_chunk": 128, "max_step_tokens": 512, "max_queue": 1024,
        "decode_steps": 1, "spec_k": 0, "param_dtype": "bfloat16",
        "weights": "deferred"}
    assert cfg["param_dtype"] == cfg["compute_dtype"] == "bfloat16"
    # 3,197.1 M parameters, 6.39 GB in bf16 (ISSUE 43 section 3)
    n = sum(int(np.prod(s)) for s, _ in ref.param_shapes(cfg).values())
    assert round(n / 1e6, 1) == 3197.1 and round(2 * n / 1e9, 2) == 6.39


def test_dsl_defaults_equal_the_configuration_file(bench):
    """benchmark/kinds/serve.py sends ten sizes; every other one reaches
    the model as the DSL file's default — held to the JSON here."""
    cfg = bench.config(CONFIG)
    with open(os.path.join(bench.root, cfg["dsl"])) as f:
        src = f.read()
    defaults = {m.group(1): m.group(2).strip() for m in re.finditer(
        r'get_config_arg\(\s*"(\w+)",\s*\w+,\s*([^)]+)\)', src)}
    sent = {"vocab", "dim", "layers", "heads", "kv_heads", "ffn",
            "rope_theta", "batch_size", "compute_dtype", "attn_impl",
            "seq_len"}
    checked = 0
    for name, text in defaults.items():
        if name in sent:
            continue
        if name == "attn_use_rope":
            assert text == str(cfg[name])
        else:
            assert float(text) == float(cfg[name]), name
        checked += 1
    assert checked == 10
    assert float(defaults["rope_theta"]) == float(cfg["rope_theta"])
    assert defaults["kv_heads"] == str(cfg["num_key_value_heads"])


# -- the comparison that decides `correct` ----------------------------------------

TINY = dict(hidden_size=48, intermediate_size=96, num_attention_heads=4,
            num_key_value_heads=1, head_dim=16, num_hidden_layers=4,
            attn_layer_period=4, attn_layer_offset=1, vocab_size=64,
            mamba_dt_rank=8, param_dtype="float32", init_std=0.3)


def test_served_margin_passes_the_reference_and_fails_the_fp8_control(bench):
    """Teacher-forced greedy tokens at a tiny size: the reference's own
    argmax trails nothing, the bf16 control stays under the tiny limit, the
    fp8 control — the precision below the configuration's — does not."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.check import served_margin
    ref = bench.reference("jamba")
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


# -- the arithmetic ---------------------------------------------------------------

def test_weights_pools_and_decode_step_bytes_are_the_issues_tables(bench):
    """ISSUE 43 sections 3 and 4, at the cell's sizes: 41.24 M a Mamba
    mixer, 13.76 M an attention mixer, 62.92 M an MLP, 3,197 M parameters =
    6.39 GB; 9.86 GB resident; 11.2 GB a decode step at 256 rows of 1.3 k
    live tokens, 39% of it the state and 62% the Mamba mixers."""
    from benchmark.lib import ssm_dense as sd
    cfg = bench.config(CONFIG)
    assert sd.layer_counts(cfg) == {"mamba": 26, "attention": 2}
    assert sd.layer_counts(dict(cfg, num_hidden_layers=2)) == \
        {"mamba": 2, "attention": 0}
    assert sd.d_inner(cfg) == 5120
    assert sd.mamba_params(cfg) == (
        2560 * 10240 + 5 * 5120 + 5120 * 192 + 161 * 5120 + 16 * 5120 + 5120
        + 192 + 5120 * 2560)
    assert round(sd.mamba_params(cfg) / 1e6, 2) == 41.24
    assert sd.attention_params(cfg) == 2 * 2560 * 2560 + 2 * 2560 * 128
    assert round(sd.attention_params(cfg) / 1e6, 2) == 13.76
    assert sd.mlp_params(cfg) == 3 * 2560 * 8192 + 2 * 2560
    assert round(sd.mlp_params(cfg) / 1e6, 2) == 62.92
    wp = sd.weight_params(cfg)
    assert round(2 * wp["mamba"] / 1e9, 3) == 2.145
    assert round(2 * wp["attention"] / 1e9, 3) == 0.055
    assert round(2 * wp["mlp_and_norms"] / 1e9, 3) == 3.524
    assert round(2 * wp["embedding_and_head"] / 1e9, 3) == 0.671
    assert round(wp["total"] / 1e6, 1) == 3197.1
    assert round(2 * wp["total"] / 1e9, 2) == 6.39
    assert sd.state_bytes(cfg) == 16 * 5120 * 4 == 327_680
    assert sd.conv_tail_bytes(cfg) == 3 * 5120 * 2
    assert sd.kv_row_bytes(cfg) == 512
    res = sd.resident_bytes(cfg)
    assert res["state_pool"] == 26 * 257 * 327_680
    assert round(res["state_pool"] / 1e9, 3) == 2.190
    assert round(res["conv_tails"] / 1e9, 3) == 0.205
    assert res["kv_pool"] == 2 * (256 * 256 + 1) * 16 * 512
    assert round(res["kv_pool"] / 1e9, 3) == 1.074
    assert round(res["total"] / 1e9, 2) == 9.86
    assert 0.61 < res["total"] / 16e9 < 0.63
    parts = sd.decode_step_bytes(cfg, rows=256, live_tokens=256 * 1300,
                                 state_rows=256)
    assert parts["ssm_state"] == 26 * 256 * 2 * 327_680
    assert round(parts["ssm_state"] / 1e9, 2) == 4.36
    assert round(parts["conv_tails"] / 1e9, 2) == 0.41
    assert round(parts["mamba_matrices"] / 1e9, 3) == 2.145
    assert round(parts["kv_rows"] / 1e9, 2) == 0.34
    assert round(parts["head"] / 1e9, 3) == 0.336
    matrices = parts["mamba_matrices"] + parts["attention_matrices"] \
        + parts["mlp_and_norms"] + parts["head"]
    assert round(matrices / 1e9, 2) == 6.06
    assert 11.1e9 < parts["total"] < 11.25e9
    assert round(1e3 * parts["total"] / 819e9, 1) == 13.6
    assert 0.385 < parts["ssm_state"] / parts["total"] < 0.395
    mixers = parts["ssm_state"] + parts["conv_tails"] \
        + parts["mamba_matrices"]
    assert 0.615 < mixers / parts["total"] < 0.625
    # the matrices' products: 1.55 TFLOP at 256 rows, 3.1 at 512
    assert round(sd.step_matmul_flops(cfg, 256) / 1e12, 2) == 1.55
    assert round(sd.step_matmul_flops(cfg, 512) / 1e12, 2) == 3.10
    # one scan call: the state in and out a live run, five float32 vectors
    # a token, 8 operations a state element a token
    assert sd.scan_token_bytes(5120, 16) == (3 * 5120 + 32) * 4
    assert sd.scan_call_bytes(5120, 16, runs=256, tokens=256) == \
        256 * 2 * 327_680 + 256 * (3 * 5120 + 32) * 4
    assert sd.scan_call_flops(5120, 16, tokens=256) == 8 * 81_920 * 256


# -- the readers ------------------------------------------------------------------

def test_readers_read_nothing_from_a_program_without_the_counters(bench):
    """Laid over a parent checkout the readers return None and do not
    raise: the program there keeps no checkpoints (no growth at all), or
    its window counted nothing for this model."""
    from benchmark.lib import ssm_dense as sd
    ctx = types.SimpleNamespace(cfg=bench.config(CONFIG), trace_data=None,
                                counters={}, spans={"ssm_dense_growth": {}})
    readers = [_reader(bench, n) for n in NAMES]
    assert sd.updates_per_step(ctx) is None
    assert sd.scan_work_per_step(ctx) is None
    assert [r.read(ctx) for r in readers] == [None] * 3
    # the recurrent counters without the tokens by kind (a parent that
    # serves another recurrent model): still nothing
    ctx.spans["ssm_dense_growth"] = {
        "serving_recurrent_steps_total": 10,
        "serving_recurrent_slot_updates_total": 100}
    assert [r.read(ctx) for r in readers] == [None] * 3


def test_readers_take_the_windows_growth_not_the_process_totals(
        bench, monkeypatch):
    """What the process counted before the window (warm-up, ramp) is not
    in the reading: the whole measured window's growth where the pump's
    checkpoints cover it, the stretch outside the profiler's slice where
    they do not, nothing from a program that keeps none."""
    from benchmark.lib import ssm_dense as sd, step_clock
    from paddle_tpu.obs.metrics import counter_key
    assert sd.TOKENS % "step" == counter_key(
        "serving_recurrent_tokens_total", kind="step")

    def grown(steps, rows):
        return {"serving_recurrent_steps_total": steps,
                "serving_recurrent_slot_updates_total": steps * 26 * rows,
                sd.TOKENS % "step": steps * rows, sd.TOKENS % "segment": 0}

    asked = []

    class Counters:                     # totals: 1,000 steps of 3 rows
        covered = True

        def snapshot(self):
            return grown(1000, 3)

        def between(self, t0, t1, exclude=()):
            asked.append((t0, t1, tuple(exclude)))
            if not self.covered:
                raise LookupError("no checkpoints cover the window")
            return grown(100, 256), t1 - t0

    pc = Counters()
    monkeypatch.setattr(step_clock, "_counters", lambda: pc)

    def ctx():
        return types.SimpleNamespace(
            cfg=bench.config(CONFIG), trace_data=None, counters={}, spans={},
            t_process=5.0, e2e={"setup_s": 200.0}, seconds=40.0)

    c = ctx()
    assert sd.updates_per_step(c) == 256
    assert sd.scan_work_per_step(c)["step"]["tokens"] == 256
    assert asked == [(205.0, 245.0, ())]            # once a run, the window
    # the whole window not covered: the step clock's stretch, if it has one
    pc.covered = False
    c = ctx()
    c.spans["step_clock"] = step_clock.Window(grown(7, 250), 2.0)
    assert sd.updates_per_step(c) == 250
    c = ctx()
    c.spans["step_clock"] = None
    assert sd.updates_per_step(c) is None
    # a program without checkpoints
    monkeypatch.setattr(step_clock, "_counters", lambda: None)
    assert sd.updates_per_step(ctx()) is None


class _Trace:
    """A canned trace: each scan call 26 times a step (one a Mamba layer)
    over 10 steps, beside an `ssd_step`-named and a paged call the patterns
    must not take."""

    def __init__(self, step_seconds, seg_seconds, busy):
        self._ops = {
            "selective_scan_step.1[tpu_custom_call]": (step_seconds, 260.0),
            "selective_scan_seg.4[tpu_custom_call]": (seg_seconds, 260.0),
            "ssd_step.1[tpu_custom_call]": (9.0, 7.0),
            "paged_attn.1[tpu_custom_call]": (9.0, 20.0)}
        self._busy = busy

    def kernel(self, pattern):
        from benchmark.lib.trace import TraceError
        hit = [v for k, v in self._ops.items() if re.search(pattern, k)]
        if not hit:
            raise TraceError(f"pattern {pattern!r} matches no device op")
        return {"seconds": sum(s for s, _ in hit),
                "calls": sum(c for _, c in hit)}

    def busy_s(self):
        return self._busy


def _canned(bench, monkeypatch, step_seconds, seg_seconds, busy):
    from benchmark.lib import phases, ssm_dense as sd
    from benchmark.lib.spec import peaks_for
    cfg = bench.config(CONFIG)
    # 100 steps counted in the window; every slot's row advanced in each of
    # the 26 Mamba layers and two chunk runs of 128 tokens rode along
    growth = {"serving_recurrent_steps_total": 100,
              "serving_recurrent_slot_updates_total": 100 * 26 * 258,
              "serving_recurrent_rows_total": 100 * 26 * 512,
              sd.TOKENS % "step": 100 * 256,
              sd.TOKENS % "segment": 100 * 256}
    ph = types.SimpleNamespace(
        names={"pt.step.decode", "pt.step.mixed"},
        durations=lambda n: [0.001] * (8 if n.endswith("decode") else 2))
    monkeypatch.setattr(phases.Phases, "of",
                        staticmethod(lambda ctx, kind: ph))
    return types.SimpleNamespace(
        cfg=cfg, trace_data=_Trace(step_seconds, seg_seconds, busy),
        peaks=peaks_for("TPU v5 lite", bench.dir),
        spans={"ssm_dense_growth": growth},
        counters={"trace_span": {"t0": 0.0, "t1": 10.0},
                  "live_samples": [(1.0, 256 * 1300, 256),
                                   (2.0, 256 * 1300, 256)]})


def test_readers_read_a_canned_trace_and_counters(bench, monkeypatch):
    """260 calls of each kind over 10 steps: a layer's step call moves 256
    states (2 x 327,680 B) and 256 tokens' vectors, its segment calls 2
    states and 256 tokens' — 200.6 MB a layer a step, 63.7 ms over the
    slice at 819 GB/s, 49.0% of 0.13 s; a decode step's 11.21 GB (258 states
    moved a layer) is 13.69 ms, 68.4% of 20 ms busy a step.  The patterns take the scan's two
    names alone."""
    ctx = _canned(bench, monkeypatch, 0.09, 0.04, busy=0.2)
    assert _reader(bench, "selective_state_updates_per_step.serve") \
        .read(ctx) == 258
    hbm = ctx.peaks["hbm_bytes_per_s"]
    tok = (3 * 5120 + 32) * 4
    a_layer_step = (256 * 2 * 327_680 + 256 * tok) \
        + (2 * 2 * 327_680 + 256 * tok)
    share = _reader(bench, "selective_scan_roofline.serve").read(ctx)
    assert share == pytest.approx(100 * (260 * a_layer_step / hbm) / 0.13,
                                  rel=1e-6)
    assert 48 < share < 50
    step = _reader(bench, "ssm_dense_decode_hbm_roofline.serve").read(ctx)
    assert step == pytest.approx(100 * (11.2091e9 / hbm) / 0.02, rel=2e-3)
    # the decode rows through the jnp step (no `selective_scan_step` in the
    # trace): the segments' call alone, the steps from the engine's spans
    del ctx.trace_data._ops["selective_scan_step.1[tpu_custom_call]"]
    seg = _reader(bench, "selective_scan_roofline.serve").read(ctx)
    assert seg == pytest.approx(
        100 * (260 * (2 * 2 * 327_680 + 256 * tok) / hbm) / 0.04, rel=1e-6)


def test_readers_raise_on_a_share_above_what_the_chip_can_give(
        bench, monkeypatch):
    ctx = _canned(bench, monkeypatch, 0.04, 0.01, busy=0.12)
    for name in ("selective_scan_roofline.serve",
                 "ssm_dense_decode_hbm_roofline.serve"):
        with pytest.raises(RuntimeError, match="above what the chip"):
            _reader(bench, name).read(ctx)
    # a trace without either call has nothing to read
    ctx.trace_data._ops = {"paged_attn.1[tpu_custom_call]": (9.0, 20.0)}
    assert _reader(bench, "selective_scan_roofline.serve").read(ctx) is None
