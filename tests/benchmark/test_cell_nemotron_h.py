"""The cell nemotron3-nano-30b-serve.long-output-256 on the CPU: its rehearsal
(hidden 64 / 4 heads over 2 KV heads / 2 layers / vocab 128 at the PUBLISHED
Mamba-2 sizes, expert widths and expert count: `ME`, a Mamba-2 layer and an
expert layer, a stack with no page-indexed part) prints the contract's line
untraced and traced; the fp8 control fails the comparison that decides
`correct` where the reference's own tokens pass it; the configuration file
is the catalog row cut as it says, and the DSL's defaults are the file's; the
three readers this PR brings read nothing without a trace or counters and
the right number from a canned one; and the byte and operation counts of
benchmark/lib/ssm_moe.py against the table of ISSUE 41 section 1."""

import json
import os
import re
import subprocess
import sys
import types

import pytest

CELL = "nemotron3-nano-30b-serve.long-output-256"
CONFIG = "nemotron3-nano-30b-a3b-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
# Three readers this PR brings as FILES and not yet as entries of
# BENCHMARK.json, and two of PR 35's that read this configuration unedited:
# tests/benchmark/test_dense_decode_roofline.py (PR 34's, not this PR's to
# edit) asserts that `dense_decode_hbm_roofline.serve` is the LAST per-layer
# metric, so nothing can be appended behind it (PERF.md section 7 row 20 has
# the entries, for the `benchmark` PR that relaxes that assertion).
WITHHELD = {"ssd_step_roofline.serve": ("kernels", "%", "itl_p95_ms"),
            "ssm_moe_decode_hbm_roofline.serve": ("graph and ops", "%",
                                                  "itl_p95_ms"),
            "ssm_state_updates_per_step.serve": ("serving engine", "count",
                                                 "output_tokens_per_s"),
            "moe_pairs_per_expert.serve-wide": ("graph and ops", "count",
                                                "output_tokens_per_s"),
            "moe_load_imbalance.serve-wide": ("graph and ops", "ratio",
                                              "output_tokens_per_s")}
MINE = sorted(n for n in WITHHELD if n.startswith("ss"))


def _reader(bench, name):
    from benchmark.lib.spec import load_module
    return load_module(os.path.join(bench.dir, "layer_metrics", name + ".py"),
                       "metric_" + name)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(root, bench, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    # The window is the benchmark's own `run_seconds`, as the hybrid and
    # LFM2 cells' rehearsals take it and for their reason: rehearse.json
    # shrinks the slots to 4 and not the configuration's 512 step tokens
    # nor the Mamba-2 sizes, and a mixed step of 512 rows through two
    # chunkwise passes over 64 heads of 64 x 128 on this CPU is a good part
    # of a second alone and more beside five other workers, so 3 s could
    # hold one step and no inter-token gap.  At the lowest priority: it
    # takes the cores the other workers leave idle.
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
        # only if the slice held a whole step
        sliced = {n for n in want if bench.per_layer[n]["source"] in
                  ("program_span", "device_trace")}
        assert want - set(got) <= sliced
        assert got["slot_occupancy.serve"]["value"] > 0
    else:
        assert set(out["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                       "setup_s"}


def test_cell_and_its_metrics_are_declared_as_the_issue_names_them(bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "long-output-256", 1)
    assert list(bench.cells)[-1] == CELL and list(bench.configs)[-1] == CONFIG
    tf = bench.traffic("long-output-256")
    cfg = bench.config(CONFIG)
    assert cfg["server_flags"]["slots"] == tf["clients"] == 256
    assert cfg["server_flags"]["max_context"] == tf["max_context"] == 4096
    e2e = {m["name"] for m in bench.end_to_end_for(CELL)}
    assert e2e == {"output_tokens_per_s", "itl_p95_ms", "setup_s"}
    for name in ("output_tokens_per_s", "itl_p95_ms"):
        assert bench.end_to_end[name]["workloads"][-1] == CELL
    per = {m["name"] for m in bench.per_layer_for(CELL)}
    assert {"device_idle_share.serve", "slot_occupancy.serve",
            "compiles_in_window.serve"} <= per
    for name, (layer, unit, moves) in WITHHELD.items():
        r = _reader(bench, name)        # the file is there and says what
        assert (r.LAYER, r.UNIT, r.MOVES) == (layer, unit, moves)
        assert name not in bench.per_layer
        assert layer in {m["layer"] for m in bench.per_layer.values()}
        assert moves in e2e
    # the paged kernel's reader sums EVERY custom call of a serve step
    # (ROADMAP B17): it would take `ssd_step` for the paged kernel, so the
    # cell leaves its list; the other models' own shares are not its either
    assert not {"paged_attn_roofline.serve", "decode_hbm_roofline.serve",
                "mla_attn_roofline.serve", "kda_step_roofline.serve",
                "hybrid_decode_hbm_roofline.serve",
                "dense_decode_hbm_roofline.serve",
                "recurrent_updates_per_step.serve",
                "moe_pairs_per_expert.serve"} & per
    assert bench.configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert bench.configs[CONFIG]["source"] == cfg["source"]


# -- the configuration ------------------------------------------------------------

def test_configuration_file_is_the_catalog_row_cut_as_it_says(bench):
    import numpy as np
    cfg = bench.config(CONFIG)
    ref = bench.reference("nemotron_h")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == ROW)
        assert cfg["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k in ("num_hidden_layers", "vocab_size"):
                assert cfg[k] != v and cfg["published"][k] == v, k
            else:       # n_routed_experts stays the 128 the router scores
                assert cfg[k] == v, k
        assert cfg["published"]["hybrid_override_pattern"] == \
            row["config"]["hybrid_override_pattern"]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size"}
    for key in ("published", "deployment", "assumed", "departures"):
        assert cfg[key], key
    # the published widths, uncut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2688, 32, 2, 128)
    assert (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"],
            cfg["chunk_size"], cfg["use_conv_bias"]) == \
        (64, 64, 128, 8, 4, 128, True)
    assert (cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], cfg["mlp_hidden_act"],
            cfg["mlp_bias"]) == (1856, 3712, 128, 6, 2.5, "relu2", False)
    # the cut: layers 1-9, 32 of 128 experts, a quarter of the vocabulary
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 4 and "why_not_2_chips" in dep
    assert cfg["experts_held"] * dep["chips_sharing_a_layer"] == \
        cfg["n_routed_experts"] == cfg["published"]["n_routed_experts"]
    assert cfg["vocab_size"] * dep["chips_sharing_a_layer"] == \
        cfg["published"]["vocab_size"]
    assert cfg["ep_rank"] == 0 and cfg["first_layer"] == 1
    assert ref.layer_kinds(cfg) == "MEMEM*EME"
    assert ref.layer_kinds(dict(cfg, num_hidden_layers=2)) == "ME"
    # what the row does not settle
    assert cfg["attn_use_rope"] is False and "attn_use_rope" in cfg["assumed"]
    assert cfg["state_dtype"] == "float32" and "state_dtype" in cfg["assumed"]
    # the aliases the shared readers read: 4 expert layers of 9
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == \
        ref.layer_kinds(cfg).count("E") == 4
    assert cfg["server_flags"] == {
        "slots": 256, "page_size": 16, "max_context": 4096,
        "prefill_chunk": 128, "max_step_tokens": 512, "max_queue": 1024,
        "decode_steps": 1, "spec_k": 0, "param_dtype": "bfloat16"}
    assert cfg["param_dtype"] == cfg["compute_dtype"] == "bfloat16"
    # 1,712.9 M parameters, 3.43 GB in bf16 (ISSUE 41 section 1)
    n = sum(int(np.prod(s)) for s, _ in ref.param_shapes(cfg).values())
    assert round(n / 1e6, 1) == 1712.9 and round(2 * n / 1e9, 2) == 3.43


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
        if name == "pattern":
            first = cfg["first_layer"] - 1
            assert text.strip('"') == cfg["hybrid_override_pattern"][
                first:first + cfg["num_hidden_layers"]]
        elif name == "attn_use_rope":
            assert text == str(cfg[name])
        else:
            assert float(text) == float(cfg[name]), name
        checked += 1
    assert checked == 18
    assert float(defaults["rope_theta"]) == float(cfg["rope_theta"])
    # `ffn` is sent and unused: the model has no dense MLP
    assert len(re.findall(r"\bffn\b", src.split('"""', 2)[2])) == 2


# -- the comparison that decides `correct` ----------------------------------------

TINY = dict(hidden_size=48, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, num_hidden_layers=5, first_layer=3, vocab_size=64,
            mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
            n_groups=2, moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=40, n_routed_experts=16,
            experts_held=16, num_experts_per_tok=3, param_dtype="float32",
            init_std=0.3, select_bias_std=0.3)


def test_served_margin_passes_the_reference_and_fails_the_fp8_control(bench):
    """Teacher-forced greedy tokens at a tiny size: the reference's own
    argmax trails nothing, the bf16 control stays under the tiny limit, the
    fp8 control — the precision below the configuration's — does not."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.check import served_margin
    ref = bench.reference("nemotron_h")
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

def test_weights_and_decode_step_bytes_are_the_issues_table(bench):
    """ISSUE 41 section 1, at the cell's sizes: 38.74 M a Mamba-2 mixer,
    23.40 M the attention, 32 x 9.978 M routed experts a layer, 3.43 GB of
    weights; 7.96 GB a decode step at 256 rows of 1.3 k live tokens, 54% of
    it the state and 32% the experts."""
    from benchmark.lib import ssm_moe as sm
    cfg = bench.config(CONFIG)
    assert sm.layer_letters(cfg) == "MEMEM*EME"
    assert sm.layer_counts(cfg) == {"M": 4, "E": 4, "*": 1}
    assert sm.layer_counts(dict(cfg, num_hidden_layers=2)) == \
        {"M": 1, "E": 1, "*": 0}
    assert sm.mamba_sizes(cfg) == {"d_in": 4096, "conv": 6144, "in": 10304}
    assert sm.mamba_params(cfg) == (2688 * 10304 + 4096 * 2688 + 5 * 6144
                                    + 3 * 64 + 4096)
    assert round(sm.mamba_params(cfg) / 1e6, 2) == 38.74
    assert sm.attention_params(cfg) == 2 * 2688 * 4096 + 2 * 2688 * 256
    assert round(sm.attention_params(cfg) / 1e6, 2) == 23.40
    assert sm.expert_params(cfg) == 2 * 2688 * 1856 == 9_977_856
    assert sm.shared_expert_params(cfg) == 2 * 2688 * 3712
    assert sm.router_params(cfg) == 2688 * 128 + 128
    assert sm.ssm_state_bytes(cfg) == 64 * 64 * 128 * 4 == 2 * 2 ** 20
    assert sm.conv_tail_bytes(cfg) == 3 * 6144 * 2
    assert sm.kv_row_bytes(cfg) == 1024
    wp = sm.weight_params(cfg)
    assert round(2 * wp["mamba"] / 1e9, 3) == 0.310
    assert round(2 * wp["attention"] / 1e9, 3) == 0.047
    assert round(2 * wp["routed_experts"] / 1e9, 3) == 2.554
    assert round(2 * wp["shared_and_router"] / 1e9, 3) == 0.162
    assert round(2 * wp["embedding_head_norms"] / 1e9, 3) == 0.352
    assert round(wp["total"] / 1e6, 1) == 1712.9
    assert round(2 * wp["total"] / 1e9, 2) == 3.43
    # the pools: 4 layers x 257 rows of state and tails, 1 layer of K/V
    assert round(4 * 257 * sm.ssm_state_bytes(cfg) / 1e9, 3) == 2.156
    assert round(4 * 257 * sm.conv_tail_bytes(cfg) / 1e9, 3) == 0.038
    assert round((256 * 4096 + 16) * sm.kv_row_bytes(cfg) / 1e9, 3) == 1.074
    parts = sm.decode_step_bytes(cfg, rows=256, live_tokens=256 * 1300,
                                 pairs_per_expert=12.0, state_rows=256)
    assert parts["ssm_state"] == 4 * 2 * 2 * 2 ** 20 * 256
    assert round(parts["ssm_state"] / 1e9, 3) == 4.295
    assert round(parts["conv_tails"] / 1e9, 3) == 0.075
    assert parts["routed_experts"] == pytest.approx(
        4 * 32 * 9_977_856 * 2, rel=1e-5)      # 12 pairs: every expert hit
    assert round(parts["routed_experts"] / 1e9, 3) == 2.554
    assert round(parts["shared_experts"] / 1e9, 3) == 0.160
    assert round(parts["mamba_matrices"] / 1e9, 3) == 0.310
    assert round(parts["attention_matrices"] / 1e9, 3) == 0.047
    assert round(parts["head"] / 1e9, 3) == 0.176
    assert round(parts["kv_rows"] / 1e9, 3) == 0.341
    assert 7.93e9 < parts["total"] < 7.99e9
    assert 0.535 < parts["ssm_state"] / parts["total"] < 0.545
    assert 0.315 < parts["routed_experts"] / parts["total"] < 0.325
    # the experts' products: dense at decode is 256 rows x 32 held
    f = sm.expert_flops(cfg, 256)
    assert round(f["rows_x_held"] / 1e12, 2) == 0.65
    assert f["rows_x_held"] * 6 == f["routed_pairs"] * 128
    # one `ssd_step` call at 256 live rows: 2 x 2 MiB a row, 5 ops an element
    c = sm.ssd_step_cost(cfg, 256)
    assert c["bytes"] == 256 * 2 * 2 * 2 ** 20
    assert c["flops"] == 5 * 64 * 64 * 128 * 256


# -- the readers ------------------------------------------------------------------

def test_readers_read_nothing_from_a_program_without_the_counters(
        bench, monkeypatch):
    """Laid over a parent checkout the readers return None and do not
    raise: the program there counts nothing for this model (and
    obs.metrics may have no process_counters at all)."""
    import paddle_tpu.obs.metrics as metrics
    from benchmark.lib import ssm_moe as sm
    ctx = types.SimpleNamespace(cfg=bench.config(CONFIG), trace_data=None,
                                counters={})
    readers = [_reader(bench, n) for n in sorted(WITHHELD)]
    real = metrics.process_counters
    monkeypatch.setattr(metrics, "process_counters",
                        lambda: types.SimpleNamespace(snapshot=lambda: {}))
    assert sm.updates_per_step(ctx.cfg) is None
    assert [r.read(ctx) for r in readers] == [None] * 5
    monkeypatch.setattr(metrics, "process_counters", real)
    monkeypatch.delattr(metrics, "process_counters")
    assert [r.read(ctx) for r in readers] == [None] * 5


class _Trace:
    """A canned trace: `ssd_step` 4 calls a step (one a Mamba-2 layer) over
    10 steps, beside a `kda_step`-named and a paged call the pattern must
    not take."""

    def __init__(self, ssd_seconds, busy):
        self._ssd, self._busy = ssd_seconds, busy

    def kernel(self, pattern):
        from benchmark.lib.trace import TraceError
        ops = {"ssd_step.1[tpu_custom_call]": (self._ssd, 40.0),
               "kda_step.1[tpu_custom_call]": (9.0, 7.0),
               "paged_attn.1[tpu_custom_call]": (9.0, 10.0)}
        hit = [v for k, v in ops.items() if re.search(pattern, k)]
        if not hit:
            raise TraceError(f"pattern {pattern!r} matches no device op")
        return {"seconds": sum(s for s, _ in hit),
                "calls": sum(c for _, c in hit)}

    def busy_s(self):
        return self._busy


def _canned(bench, monkeypatch, ssd_seconds, busy):
    import paddle_tpu.obs.metrics as metrics
    from benchmark.lib import phases
    from benchmark.lib.spec import peaks_for
    cfg = bench.config(CONFIG)
    # 100 steps counted; every slot advanced in each of the 4 Mamba-2
    # layers; 12 pairs an expert in each of the 4 expert layers
    snap = {"serving_recurrent_steps_total": 100,
            "serving_recurrent_slot_updates_total": 100 * 4 * 256,
            "serving_recurrent_rows_total": 100 * 4 * 256,
            "serving_moe_steps_total": 100,
            "serving_moe_pairs_total": 100 * 4 * 32 * 12,
            "serving_moe_pairs_max_total": 100 * 4 * 18}
    monkeypatch.setattr(metrics, "process_counters",
                        lambda: types.SimpleNamespace(snapshot=lambda: snap))
    ph = types.SimpleNamespace(
        names={"pt.step.decode", "pt.step.mixed"},
        durations=lambda n: [0.001] * (8 if n.endswith("decode") else 2))
    monkeypatch.setattr(phases.Phases, "of",
                        staticmethod(lambda ctx, kind: ph))
    return types.SimpleNamespace(
        cfg=cfg, trace_data=_Trace(ssd_seconds, busy),
        peaks=peaks_for("TPU v5 lite", bench.dir),
        counters={"trace_span": {"t0": 0.0, "t1": 10.0},
                  "live_samples": [(1.0, 256 * 1300, 256),
                                   (2.0, 256 * 1300, 256)]})


def test_readers_read_a_canned_trace_and_counters(bench, monkeypatch):
    """40 `ssd_step` calls of 256 live rows in 0.1 s: 2 x 2 MiB x 256 rows a
    call at 819 GB/s is 1.311 ms, 52.4% of 2.5 ms a call; a decode step's
    7.96 GB is 9.71 ms, 64.8% of 15 ms busy a step.  The kernel's pattern
    takes `ssd_step` alone."""
    ctx = _canned(bench, monkeypatch, ssd_seconds=0.1, busy=0.15)
    assert _reader(bench, "ssm_state_updates_per_step.serve").read(ctx) == 256
    assert _reader(bench, "moe_pairs_per_expert.serve-wide").read(ctx) == 12
    assert _reader(bench, "moe_load_imbalance.serve-wide").read(ctx) == \
        pytest.approx(18 / 12)
    hbm = ctx.peaks["hbm_bytes_per_s"]
    share = _reader(bench, "ssd_step_roofline.serve").read(ctx)
    assert share == pytest.approx(
        100 * (256 * 2 * 2 * 2 ** 20 / hbm) / (0.1 / 40), rel=1e-6)
    assert 50 < share < 55
    step = _reader(bench, "ssm_moe_decode_hbm_roofline.serve").read(ctx)
    assert step == pytest.approx(100 * (7.9586e9 / hbm) / 0.015, rel=2e-3)


def test_readers_raise_on_a_share_above_what_the_chip_can_give(
        bench, monkeypatch):
    ctx = _canned(bench, monkeypatch, ssd_seconds=0.04, busy=0.09)
    for name in ("ssd_step_roofline.serve",
                 "ssm_moe_decode_hbm_roofline.serve"):
        with pytest.raises(RuntimeError, match="above what the chip"):
            _reader(bench, name).read(ctx)
    # a trace without the kernel has nothing to read
    ctx.trace_data.kernel = lambda pattern: (_ for _ in ()).throw(
        __import__("benchmark.lib.trace", fromlist=["TraceError"])
        .TraceError("no op"))
    assert _reader(bench, "ssd_step_roofline.serve").read(ctx) is None
