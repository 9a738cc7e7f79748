"""A PR that is no `benchmark` PR may only add to the benchmark: new files
under BENCHMARK.json's `paths` and new entries at the end of its lists.
Held against the record of what PR 25 left (data/accepted_pr25.json): no
file that was there differs, no entry that was there changed or moved."""

import hashlib
import json
import os
import shutil

import pytest


@pytest.fixture(scope="module")
def accepted():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "data", "accepted_pr25.json")) as f:
        return json.load(f)


def _git_blob_id(path: str) -> str:
    with open(path, "rb") as f:
        data = f.read()
    return hashlib.sha1(b"blob %d\0" % len(data) + data).hexdigest()


def test_no_file_the_benchmark_had_differs(root, accepted):
    changed = [p for p, blob in accepted["files"].items()
               if not os.path.exists(os.path.join(root, p))
               or _git_blob_id(os.path.join(root, p)) != blob]
    assert changed == []


def test_benchmark_json_only_grew(root, accepted):
    _only_grew(root, accepted)


def _only_grew(root, accepted):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        now = json.load(f)
    was = accepted["benchmark_json"]
    assert set(was) <= set(now)
    for key in ("command", "paths", "run_seconds"):
        assert now[key] == was[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        # what was there leads the list, in its order, value for value; a
        # metric's list of cells may be longer, by names put at its end
        for old, new in zip(was[key], now[key][:len(was[key])]):
            assert set(old) == set(new), (key, old["name"])
            for k, v in old.items():
                if k == "workloads":
                    assert new[k][:len(v)] == v, (key, old["name"])
                else:
                    assert new[k] == v, (key, old["name"], k)
        assert len(now[key]) >= len(was[key])


#: what PR 26 added; later PRs add theirs after these, as data alone (a
#: reader file plus an entry), and this file says nothing about those
PR26 = ["idle_in_emit_share.serve", "idle_in_schedule_share.serve",
        "idle_in_pump_share.serve", "idle_in_launch_share.serve",
        "idle_unattributed_share.serve", "decode_step_ms.serve",
        "mixed_step_ms.serve", "idle_in_input_share.train",
        "idle_in_drain_share.train", "idle_unattributed_share.train",
        "flash_fwd_ms_per_step.train", "flash_bwd_ms_per_step.train"]


def test_pr26_metrics_follow_pr25s_each_with_its_reader(bench, accepted):
    _pr26_in_place(bench, accepted)


def _pr26_in_place(bench, accepted):
    was = [m["name"] for m in accepted["benchmark_json"]["per_layer"]]
    names = list(bench.per_layer)
    assert names[len(was):len(was) + len(PR26)] == PR26
    layers = {m["layer"] for m in accepted["benchmark_json"]["per_layer"]}
    # each is reported in PR 25's cells of its family, through `moves`
    cells = {c: {m["name"] for m in bench.per_layer_for(c)}
             for c in bench.cells}
    for name in PR26:
        m = bench.per_layer[name]
        bench.reader(name)             # LAYER, UNIT, MOVES agree, or raises
        assert m["layer"] in layers    # a layer PERF.md section 3 names
        for c in (w["name"] for w in accepted["benchmark_json"]["workloads"]):
            assert (name in cells[c]) == \
                (("train" in c) == name.endswith(".train")), (name, c)


def test_a_later_metric_and_cell_added_as_data_pass_these_checks(
        root, tmp_path, accepted):
    """The next PR adds a cell, a mix and a metric (on a layer of its own,
    with its own list of cells) as files and entries: nothing here may
    need an edit for that."""
    from benchmark.lib.spec import Benchmark
    copy = tmp_path / "repo"
    shutil.copytree(os.path.join(root, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), copy)
    shutil.copy(copy / "benchmark/traffic/chat.json",
                copy / "benchmark/traffic/chat-later.json")
    (copy / "benchmark/layer_metrics/later_count.serve.py").write_text(
        'LAYER = "a later layer"\nUNIT = "count"\n'
        'MOVES = "itl_p95_ms"\n\n\ndef read(ctx):\n    return 1\n')
    doc = json.load(open(copy / "BENCHMARK.json"))
    doc["workloads"].append({
        "name": "sc2-3b-serve.chat-later", "config": "starcoder2-3b-serve",
        "traffic": "chat-later", "chips": 1, "why": "a later cell"})
    for m in doc["end_to_end"]:
        if m["name"] in ("output_tokens_per_s", "itl_p95_ms"):
            m["workloads"].append("sc2-3b-serve.chat-later")
    doc["per_layer"].append({
        "name": "later_count.serve", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "a later layer",
        "moves": "itl_p95_ms", "workloads": ["sc2-3b-serve.chat-later"]})
    json.dump(doc, open(copy / "BENCHMARK.json", "w"))
    _only_grew(str(copy), accepted)
    _pr26_in_place(Benchmark(str(copy)), accepted)
