"""A configuration, a traffic mix, a cell and a per-layer metric are each
added by NEW files plus entries in BENCHMARK.json, editing no file that is
there — shown in a temporary copy.  Joining an end-to-end metric that lists
its cells is one more entry: the new cell's name at the end of that metric's
`workloads` in BENCHMARK.json (the contract gives such a metric that list,
and what a cell reports is read from nowhere else, PERF.md section 3)."""

import hashlib
import json
import os
import shutil

from benchmark.lib import traffic
from benchmark.lib.spec import Benchmark


def _hashes(top):
    out = {}
    for d, _, files in os.walk(top):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, top)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_add_config_cell_and_metric_without_editing(root, tmp_path,
                                                    monkeypatch):
    copy = tmp_path / "repo"
    shutil.copytree(os.path.join(root, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), copy)
    before = _hashes(copy / "benchmark")

    # a new configuration of the same family: one new file of sizes
    cfg = json.load(open(copy / "benchmark/configs/starcoder2-3b-serve.json"))
    cfg.update(hidden_size=4608, intermediate_size=18432,
               num_attention_heads=36, num_key_value_heads=4,
               num_hidden_layers=4)
    json.dump(cfg, open(copy / "benchmark/configs/starcoder2-7b-serve.json",
                        "w"))
    # a new traffic mix: one new data file the general generator reads
    # (bursty arrivals, a pool of shared system prompts and a table of
    # lengths in a file of its own: parameters, not code)
    mix = json.load(open(copy / "benchmark/traffic/chat.json"))
    del mix["shuffle_block"]
    mix.update(rate_per_s=9.0, arrival={"dist": "gamma", "cv": 3.0},
               prompt_len={"dist": "table", "file": "long-prompt.lens.txt"},
               shared_prefix={"pool": 4, "zipf_s": 1.0,
                              "len": {"dist": "constant", "value": 1024}})
    json.dump(mix, open(copy / "benchmark/traffic/long-prompt.json", "w"))
    (copy / "benchmark/traffic/long-prompt.lens.txt").write_text(
        "\n".join(str(v) for v in range(1024, 2500, 64)))
    # a new per-layer metric: one new reader
    (copy / "benchmark/layer_metrics/prefill_chunks.serve.py").write_text(
        'LAYER = "serving engine"\nUNIT = "count"\n'
        'MOVES = "itl_p95_ms"\n\n\ndef read(ctx):\n'
        '    return ctx.counters.get("prefill_chunks")\n')
    doc = json.load(open(copy / "BENCHMARK.json"))
    doc["configs"].append({
        "name": "starcoder2-7b-serve",
        "source": "https://huggingface.co/bigcode/starcoder2-7b",
        "file": "benchmark/configs/starcoder2-7b-serve.json",
        "reduced": ["num_hidden_layers"], "why": "the 7B widths"})
    doc["workloads"].append({
        "name": "sc2-7b-serve.long-prompt", "config": "starcoder2-7b-serve",
        "traffic": "long-prompt", "chips": 1, "why": "long prompts"})
    old = json.loads(json.dumps(doc))
    for m in doc["end_to_end"]:
        if m["name"] in ("output_tokens_per_s", "itl_p95_ms"):
            m["workloads"].append("sc2-7b-serve.long-prompt")
    doc["per_layer"].append({
        "name": "prefill_chunks.serve", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "serving engine",
        "moves": "itl_p95_ms", "workloads": ["sc2-7b-serve.long-prompt"]})
    json.dump(doc, open(copy / "BENCHMARK.json", "w"))
    # BENCHMARK.json only grew: every entry that was there still is, with
    # every value it had, bounds and all; a list of cells is at most longer
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[key], doc[key]):
            for k, v in was.items():
                assert now[k] == v or (k == "workloads"
                                       and now[k][:len(v)] == v)

    b = Benchmark(str(copy))
    cell = b.cell("sc2-7b-serve.long-prompt")
    assert b.config(cell["config"])["hidden_size"] == 4608
    tf = b.traffic(cell["traffic"])
    assert tf["rate_per_s"] == 9.0 and hasattr(b.kind(tf["kind"]), "run")
    assert [m["name"] for m in b.end_to_end_for(cell["name"])] == [
        "output_tokens_per_s", "itl_p95_ms", "setup_s"]
    monkeypatch.setattr(traffic, "TRAFFIC_DIR",
                        str(copy / "benchmark" / "traffic"))
    reqs = traffic.serve_requests(tf, 49152, 7, 10)
    assert len(reqs) == 180 and len({tuple(r["prompt"][:1024])
                                     for r in reqs}) == 4
    assert {len(r["prompt"]) - 1024 for r in reqs} <= set(
        range(1024, 2500, 64))
    names = [m["name"] for m in b.per_layer_for(cell["name"])]
    assert "prefill_chunks.serve" in names and "slot_occupancy.serve" in names

    class Ctx:
        counters = {"prefill_chunks": 7}
    assert b.reader("prefill_chunks.serve").read(Ctx) == 7
    # the old cells do not report the new metric
    assert "prefill_chunks.serve" not in [
        m["name"] for m in b.per_layer_for("sc2-3b-serve.chat")]

    after = _hashes(copy / "benchmark")
    assert {k: after[k] for k in before} == before      # nothing edited
    assert set(after) - set(before) == {
        "configs/starcoder2-7b-serve.json", "traffic/long-prompt.json",
        "traffic/long-prompt.lens.txt",
        "layer_metrics/prefill_chunks.serve.py"}
