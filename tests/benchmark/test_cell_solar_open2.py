"""The cell solar-open2-250b-serve.long-output-128 on the CPU: its rehearsal
(hidden 64 / 4 heads over 2 KV heads / 2 layers / vocab 128 at the PUBLISHED
head size, KDA and expert widths: one gated GQA layer and one KDA layer,
320 experts scored and 40 held) prints the contract's line untraced and, on
a copy with this PR's two withheld entries laid in and the three accepted
lists it can join joined, traced with the readers' metrics; the fp8 control
fails the comparison that decides `correct` where the reference's own
tokens pass it;
every number of the configuration's table (ISSUE 48 section 2) from
benchmark/lib/kda_gqa_moe.py; the readers read nothing without a trace or
counters, the right number from a canned one, and raise above what the chip
can give; the traffic file is the one that was there."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

CELL = "solar-open2-250b-serve.long-output-128"
CONFIG = "solar-open2-250b-serve"
# Two readers this PR brings as FILES and not yet as entries of
# BENCHMARK.json: tests/benchmark/test_dense_decode_roofline.py (PR 34's,
# not this PR's to edit) asserts that `dense_decode_hbm_roofline.serve` is
# the LAST per-layer metric, so nothing can be appended behind it (PERF.md
# section 7 row 20 has the entries verbatim, for the `benchmark` PR that
# relaxes that assertion; this file reads them from there).
WITHHELD = {"kda_gqa_decode_hbm_roofline.serve": ("graph and ops", "%",
                                                  "itl_p95_ms"),
            "paged_attn_named_roofline.serve": ("kernels", "%", "itl_p95_ms")}
NAMES = list(WITHHELD)
# accepted metrics whose `workloads` the cell joins once the tests that pin
# those lists (test_cell_kimi_linear.py, test_cell_gigachat3.py) let it:
# their readers read this configuration's keys as they are.  Two more,
# `kda_step_roofline.serve` and `recurrent_updates_per_step.serve`, wait on
# benchmark/lib/hybrid_linear.py:mixer_layers besides: it reads
# `linear_attn_config.full_attn_layers`, which this family's published group
# does not have and the file may not add (PERF.md section 7 row 20)
TO_JOIN = ("moe_pairs_per_expert.serve", "moe_load_imbalance.serve",
           "token_frames_per_write.serve")
TRAFFIC_SHA256 = \
    "77fb9094837f008cf1ff82229657044f1b8ea0c1b117e009686c3b17b73f6a2c"


def _reader(bench, name):
    from benchmark.lib.spec import load_module
    return load_module(os.path.join(bench.dir, "layer_metrics", name + ".py"),
                       "metric_" + name)


def withheld_entries(root) -> list:
    """This PR's two `per_layer` entries, verbatim from PERF.md."""
    with open(os.path.join(root, "PERF.md")) as f:
        text = f.read()
    found = {}
    for blob in re.findall(r"`(\{\"name\": \"[^`]*\})`", text):
        entry = json.loads(blob)
        if entry["name"] in NAMES:
            found[entry["name"]] = entry
    assert sorted(found) == sorted(NAMES), sorted(found)
    return [found[n] for n in NAMES]


def _rehearse(root, cwd, trace, seed, seconds):
    # The window is the benchmark's own `run_seconds`, as the Kimi and
    # Nemotron cells' rehearsals take: the configuration's 320 step tokens
    # are not among the sizes rehearse.json shrinks, and a mixed step of 320
    # rows through the interpreted `paged_attn` and `kda_step` takes half a
    # second on this CPU alone and several beside five other workers — a
    # window of 5 s then ended before two requests had (no check sample).
    # At the lowest priority: it takes the cores the other workers leave.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds",
         str(seconds),
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
    out = _rehearse(root, root, 0, 2 ** 31 + 151, bench.doc["run_seconds"])
    assert set(out["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                   "setup_s"}


def test_traced_rehearsal_reads_the_withheld_and_the_joined_readers(
        root, tmp_path):
    """`run.py --rehearse --trace 1` on a copy of the benchmark whose
    BENCHMARK.json has PERF.md's two entries appended and the cell's name
    at the end of the three lists it can join: the counters' readers read
    the rehearsal's own counts from this configuration's keys as they are,
    and those that read the device's trace are asked (what comes from the
    ops of a one-second slice on the CPU, where no kernel is a Mosaic call,
    may have nothing to read: then the line leaves them out and nothing
    raises)."""
    from benchmark.lib.spec import Benchmark
    copy = tmp_path / "repo"
    shutil.copytree(os.path.join(root, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["per_layer"].extend(withheld_entries(root))
    for m in doc["per_layer"]:
        if m["name"] in TO_JOIN:
            m["workloads"].append(CELL)
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(doc, f)
    for d in ("paddle_tpu", "tools", "demo"):
        os.symlink(os.path.join(root, d), os.path.join(copy, d))
    b = Benchmark(str(copy))
    for name in NAMES:
        b.reader(name)                 # LAYER, UNIT, MOVES agree, or raises
    want = {m["name"] for m in b.per_layer_for(CELL)}
    assert set(NAMES) | set(TO_JOIN) <= want
    for c in b.cells:
        if c != CELL:
            assert not set(NAMES) & {m["name"] for m in b.per_layer_for(c)}
    out = _rehearse(root, str(copy), 1, 2 ** 31 + 152, doc["run_seconds"])
    got = out["metrics"]
    sliced = {n for n in want if b.per_layer[n]["source"] in
              ("program_span", "device_trace")}
    assert want - set(got) <= sliced
    assert got["slot_occupancy.serve"]["value"] > 0
    # 40 held of 320 scored, top-8: a fraction of a pair an expert a step
    assert 0 < got["moe_pairs_per_expert.serve"]["value"] < 8
    assert got["moe_load_imbalance.serve"]["value"] >= 1
    assert got["token_frames_per_write.serve"]["value"] >= 1


def test_cell_and_its_metrics_are_declared_as_the_issue_names_them(root,
                                                                   bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "long-output-128", 1)
    assert "1/8" in cell["why"] and "mixers full" in cell["why"]
    # appended behind what was there (never "the last": the next cell is
    # appended behind this one)
    assert list(bench.cells).index(CELL) == 9
    assert list(bench.configs).index(CONFIG) == 7
    with open(os.path.join(bench.dir, "traffic", "long-output-128.json"),
              "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == TRAFFIC_SHA256
    tf = bench.traffic("long-output-128")
    cfg = bench.config(CONFIG)
    assert (tf["loop"], tf["requests_per_client"]) == ("closed", 8)
    assert "rate_per_s" not in tf               # no rate is offered
    assert cfg["server_flags"]["slots"] == tf["clients"] == 128
    assert cfg["server_flags"]["max_context"] == tf["max_context"] == 4096
    e2e = {m["name"] for m in bench.end_to_end_for(CELL)}
    assert e2e == {"output_tokens_per_s", "itl_p95_ms", "setup_s"}
    for name, n in (("output_tokens_per_s", 6), ("itl_p95_ms", 7)):
        assert bench.end_to_end[name]["workloads"].index(CELL) == n
    per = {m["name"] for m in bench.per_layer_for(CELL)}
    assert {"device_idle_share.serve", "slot_occupancy.serve",
            "compiles_in_window.serve", "decode_step_ms.serve"} <= per
    entries = {e["name"]: e for e in withheld_entries(root)}
    for name, (layer, unit, moves) in WITHHELD.items():
        r = _reader(bench, name)        # the file is there and says what
        assert (r.LAYER, r.UNIT, r.MOVES) == (layer, unit, moves)
        assert name not in bench.per_layer          # the pin stands
        assert layer in {m["layer"] for m in bench.per_layer.values()}
        assert moves in e2e
        assert entries[name] == {
            "name": name, "unit": unit, "better": "higher",
            "source": "device_trace", "layer": layer, "moves": moves,
            "workloads": [CELL]}
    # the pins stand: the accepted lists are as they were, and the paged
    # kernel's reader that sums EVERY custom call (it would take `kda_step`
    # for the paged kernel) is not this cell's
    assert not (set(TO_JOIN) | {
        "paged_attn_roofline.serve", "decode_hbm_roofline.serve",
        "mla_attn_roofline.serve", "hybrid_decode_hbm_roofline.serve",
        "dense_decode_hbm_roofline.serve"}) & per
    assert bench.configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert bench.configs[CONFIG]["source"] == cfg["source"]
    assert bench.configs[CONFIG]["file"] == \
        "benchmark/configs/solar-open2-250b-serve.json"


TINY = dict(hidden_size=32, intermediate_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=4,
            vocab_size=64, moe_intermediate_size=16, n_routed_experts=16,
            experts_held=4, ep_rank=1, num_experts_per_tok=4,
            param_dtype="float32", init_std=0.3, select_bias_std=0.3)


def test_served_margin_passes_the_reference_and_fails_the_fp8_control(bench):
    """Teacher-forced greedy tokens at a tiny size: the reference's own
    argmax trails nothing, the bf16 control stays under the tiny limit, the
    fp8 control — the precision below the configuration's — does not."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.check import served_margin
    ref = bench.reference("solar_open2")
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


def test_weights_pools_and_decode_step_bytes_are_the_issues_table(bench):
    """Every number of ISSUE 48 section 2, from the shapes."""
    from benchmark.lib import arith, kda_gqa_moe as m
    cfg = bench.config(CONFIG)
    assert m.mixer_layers(cfg) == (3, 1)
    assert m.mixer_layers(dict(cfg, num_hidden_layers=2)) == (1, 1)
    assert m.mixer_layers(dict(cfg, num_hidden_layers=48)) == (36, 12)
    # a KDA layer: q k v o [4096, 8192], two low-rank pairs of rank 128,
    # beta [4096, 64], three 4-tap convolutions, A_log, dt_bias, the norm
    assert m.kda_params(cfg) == (
        4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
        + 3 * 4 * 8192 + 64 + 8192 + 128)
    assert round(m.kda_params(cfg) / 1e6, 2) == 137.73
    # a gated GQA layer: q, o and the gate [4096, 8192], k and v [4096, 1024]
    assert m.gqa_params(cfg) == 3 * 4096 * 8192 + 2 * 4096 * 1024
    assert round(m.gqa_params(cfg) / 1e6, 2) == 109.05
    assert m.gqa_params(dict(cfg, use_gqa_gate=False)) == \
        2 * 4096 * 8192 + 2 * 4096 * 1024
    assert m.expert_params(cfg) == 3 * 4096 * 1280
    assert round(m.expert_params(cfg) / 1e6, 3) == 15.729
    assert round((m.router_params(cfg) + m.expert_params(cfg)) / 1e6, 2) \
        == 17.04
    w = m.weight_params(cfg)
    assert round((w["kda"] + w["gqa"]) * 2 / 1e9, 3) == 1.044
    assert w["routed_experts"] == 4 * 40 * 15_728_640
    assert round(w["routed_experts"] * 2 / 1e9, 3) == 5.033
    assert round(w["router_shared_norms"] * 2 / 1e9, 3) == 0.136
    assert round(w["router_shared_norms"] / 4 / 1e6, 2) == 17.05
    assert w["embedding_head"] == 2 * 24576 * 4096
    assert round(w["embedding_head"] * 2 / 1e9, 3) == 0.403
    assert round(w["total"] / 1e6, 1) == 3308.4
    # the published model: "250B-A15B"
    pub = m.published_params(cfg)
    assert round(pub["total"] / 1e9, 2) == 250.29
    assert round(pub["active"] / 1e9, 1) == 14.7
    assert round(2 * 4096 * 196608 / 1e6, 1) == 1610.6
    # resident: 129 rows of 4 MiB a KDA layer, a [3, 24576] tail, 4,096 B
    # of K/V a token over 128 x 4096 + 16 tokens
    assert m.kda_state_bytes(cfg) == 64 * 128 * 128 * 4 == 4 * 2 ** 20
    assert m.conv_tail_bytes(cfg) == 3 * 24576 * 2
    assert m.kv_row_bytes(cfg) == 4096
    assert m.pool_tokens(cfg) == 524_304
    res = m.resident_bytes(cfg)
    assert round(res["weights"] / 1e9, 3) == 6.617
    assert res["kda_state"] == 3 * 129 * 4 * 2 ** 20
    assert round(res["kda_state"] / 1e9, 3) == 1.623
    assert round(res["conv_tails"] / 1e9, 3) == 0.057
    assert round(res["kv_pool"] / 1e9, 3) == 2.148
    assert round(res["total"] / 1e9, 2) == 10.44
    assert 0.65 < res["total"] / 16e9 < 0.66
    # two weight sets at start-up would not fit: `weights: deferred`
    assert round((2 * res["weights"] + res["total"] - res["weights"]) / 1e9,
                 1) == 17.1
    # a decode step at 128 rows: 3.2 pairs an expert, 96% of them read
    load = m.deployment_pairs_per_expert(cfg, 128)
    assert load == {"here": pytest.approx(3.2),
                    "deployment": pytest.approx(25.6)}
    parts = m.decode_step_bytes(cfg, rows=128, live_tokens=128 * 1600,
                                pairs_per_expert=3.2, state_rows=128)
    assert parts["kda_state"] == 3 * 128 * 2 * 4 * 2 ** 20
    assert round(parts["kda_state"] / 1e9, 2) == 3.22
    assert round(parts["routed_experts"] / 1e9, 2) == 4.83
    assert parts["routed_experts"] == pytest.approx(5.033e9 * 0.9592,
                                                    rel=1e-3)
    assert round((parts["kda_matrices"] + parts["gqa_matrices"]) / 1e9, 2) \
        == 1.04
    assert round(parts["kv_rows"] / 1e9, 1) == 0.8
    assert round((parts["head"] + parts["shared_experts"] + parts["router"])
                 / 1e9, 2) == 0.34
    assert 10.2e9 < parts["total"] < 10.4e9
    assert round(1e3 * parts["total"] / 819e9, 1) == 12.5
    share = (parts["kda_state"] + parts["routed_experts"]) / parts["total"]
    assert 0.775 < share < 0.79         # "78% of a decode step's bytes"
    # the kernels' calls: a live row's state read once and written once,
    # six operations a state element; a token's K and V read once, q in and
    # the result out at 64 heads of 128 — where arith.paged_decode_cost
    # would take a head for hidden_size / heads = 64 wide
    cost = m.kda_step_cost(cfg, 100)
    assert cost["bytes"] == 2 * 100 * 4 * 2 ** 20
    assert cost["flops"] == 6 * 100 * 64 * 128 * 128
    paged = m.paged_cost(cfg, live_tokens=1000, rows=10)
    assert paged["bytes"] == 1000 * 4096 + 10 * 8192 * 2 * 2
    assert paged["flops"] == 4 * 8192 * 1000
    assert arith.paged_decode_cost(cfg, 1000, 10)["bytes"] < paged["bytes"]


# -- the readers ------------------------------------------------------------------

class _Trace:
    """A canned trace of 10 steps: `paged_attn` once a step (one GQA layer),
    `kda_step` three times, and an `mla_paged_attn` call the paged pattern
    must not take."""

    def __init__(self, paged_seconds, busy):
        self._ops = {
            "paged_attn.1[tpu_custom_call]": (paged_seconds, 10.0),
            "kda_step.2[tpu_custom_call]": (0.05, 30.0),
            "mla_paged_attn.3[tpu_custom_call]": (9.0, 7.0)}
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


def _canned(bench, monkeypatch, paged_seconds, busy):
    import paddle_tpu.obs.metrics as metrics
    from benchmark.lib import phases
    from benchmark.lib.spec import peaks_for
    # 100 steps counted: every slot's row advanced in each of the 3 KDA
    # layers, 3.2 pairs an expert a layer
    counted = {"serving_recurrent_steps_total": 100,
               "serving_recurrent_slot_updates_total": 100 * 3 * 128,
               "serving_moe_steps_total": 100,
               "serving_moe_pairs_total": 100 * 4 * 40 * 3.2,
               "serving_moe_pairs_max_total": 100 * 4 * 9}
    monkeypatch.setattr(
        metrics, "process_counters",
        lambda: types.SimpleNamespace(snapshot=lambda: dict(counted)))
    ph = types.SimpleNamespace(
        names={"pt.step.decode", "pt.step.mixed"},
        durations=lambda n: [0.001] * (8 if n.endswith("decode") else 2))
    monkeypatch.setattr(phases.Phases, "of",
                        staticmethod(lambda ctx, kind: ph))
    return types.SimpleNamespace(
        cfg=bench.config(CONFIG), trace_data=_Trace(paged_seconds, busy),
        peaks=peaks_for("TPU v5 lite", bench.dir),
        counters={"trace_span": {"t0": 0.0, "t1": 10.0},
                  "live_samples": [(1.0, 128 * 1600, 128),
                                   (2.0, 128 * 1600, 128),
                                   (11.0, 5, 1)]})


def test_readers_read_nothing_from_a_program_without_the_counters(
        bench, monkeypatch):
    """Laid over a parent checkout the readers return None and do not
    raise: no trace; a trace and a program that counted no recurrent step;
    a program whose obs.metrics has no process_counters at all."""
    import paddle_tpu.obs.metrics as metrics
    from benchmark.lib import kda_gqa_moe as m
    readers = [_reader(bench, n) for n in NAMES]
    bare = types.SimpleNamespace(cfg=bench.config(CONFIG), trace_data=None,
                                 counters={})
    assert [r.read(bare) for r in readers] == [None, None]
    ctx = _canned(bench, monkeypatch, 0.01, 0.2)
    monkeypatch.setattr(metrics, "process_counters",
                        lambda: types.SimpleNamespace(snapshot=lambda: {}))
    assert m.updates_per_step(ctx.cfg) is None
    assert readers[0].read(ctx) is None
    monkeypatch.delattr(metrics, "process_counters")
    assert readers[0].read(ctx) is None
    # a trace without the paged kernel has nothing for its reader
    del ctx.trace_data._ops["paged_attn.1[tpu_custom_call]"]
    assert readers[1].read(ctx) is None


def test_readers_read_a_canned_trace_and_counters(bench, monkeypatch,
                                                  capsys):
    """10 steps in the slice, 128 rows of 1,600 live tokens: a decode step's
    10.30 GB is 12.57 ms at 819 GB/s, 62.9% of 20 ms busy a step; one
    `paged_attn` call reads 204,800 tokens x 4,096 B + q and o = 0.843 GB,
    1.03 ms, 10 calls 51.5% of 0.02 s — and the pattern takes neither
    `kda_step` nor `mla_paged_attn`."""
    from benchmark.lib import kda_gqa_moe as m
    ctx = _canned(bench, monkeypatch, 0.02, busy=0.2)
    hbm = ctx.peaks["hbm_bytes_per_s"]
    assert m.updates_per_step(ctx.cfg) == 128
    parts = m.decode_step_bytes(ctx.cfg, 128, 128 * 1600, 3.2, 128)
    step = _reader(bench, NAMES[0]).read(ctx)
    assert step == pytest.approx(100 * (parts["total"] / hbm) / 0.02,
                                 rel=1e-6)
    assert 62 < step < 64
    # the step's reader logs `kda_step`'s own share: 30 calls of 128 states
    # of 4 MiB read and written, 1.07 GB a call, 39.3 ms of 50 ms
    assert "KERNEL kda_step: 30 calls, 0.0500s, 128.0 live rows a call, " \
        "memory-bound, 78.66% of its roofline" in capsys.readouterr().out
    paged = _reader(bench, NAMES[1]).read(ctx)
    one = 128 * 1600 * 4096 + 128 * 8192 * 2 * 2
    assert paged == pytest.approx(100 * (10 * one / hbm) / 0.02, rel=1e-6)
    assert 51 < paged < 52


def test_readers_raise_on_a_share_above_what_the_chip_can_give(
        bench, monkeypatch):
    ctx = _canned(bench, monkeypatch, 0.005, busy=0.1)
    for name in NAMES:
        with pytest.raises(RuntimeError, match="above what the chip"):
            _reader(bench, name).read(ctx)
