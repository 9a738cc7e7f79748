"""The harness finds cells, configurations and metrics by name, refuses
names and units outside the allowed characters, and BENCHMARK.json keeps to
the contract's shape."""

import json
import os
import re

import pytest

from benchmark.lib import spec

CELLS = ["sc2-3b-train.seq4k", "sc2-3b-serve.decode-saturated",
         "sc2-3b-serve.chat", "sc2-3b-train.seq4k-dp4"]
PER_LAYER = ["compiles_in_window.train", "compiles_in_window.serve",
             "input_wait_share.train", "slot_occupancy.serve",
             "engine_step_ms.serve", "mfu.train",
             "flash_attn_roofline.train",
             "paged_attn_roofline.serve", "collective_exposed_share.train",
             "device_idle_share.train", "device_idle_share.serve"]


def test_top_level_keys(bench):
    assert sorted(bench.doc) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads",
         "end_to_end", "per_layer"])
    assert bench.doc["command"] == ["python3", "benchmark/run.py"]
    assert bench.doc["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= bench.doc["run_seconds"] <= 51
    assert len(json.dumps(bench.doc)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_found_by_name(bench, cell):
    c = bench.cell(cell)
    assert set(c) == {"name", "config", "traffic", "chips", "why"}
    assert c["chips"] in (1, 4) and 1 <= len(c["why"]) <= 200
    cfg = bench.config(c["config"])
    tf = bench.traffic(c["traffic"])
    assert hasattr(bench.kind(tf["kind"]), "run")
    assert hasattr(bench.reference(cfg["reference"]), "make_weights")
    assert os.path.exists(os.path.join(bench.root, cfg["dsl"]))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_what_the_contract_asks(bench, cell):
    e2e = [m["name"] for m in bench.end_to_end_for(cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = bench.per_layer_for(cell)
    assert layers
    for m in layers:        # every cell that reports it reports what it moves
        assert m["moves"] in e2e, (m["name"], cell)


def test_four_chip_share(bench):
    four = [c for c in bench.cells.values() if c["chips"] == 4]
    assert len(four) <= max(1, len(bench.cells) // 4)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric_has_its_reader(bench, metric):
    m = bench.per_layer[metric]
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    mod = bench.reader(metric)          # checks LAYER, UNIT, MOVES agree
    assert callable(mod.read)


def test_end_to_end_metrics(bench):
    assert set(bench.end_to_end) == {
        "train_tokens_per_s_per_chip", "output_tokens_per_s", "itl_p95_ms",
        "setup_s"}
    for m in bench.end_to_end.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert "workloads" not in bench.end_to_end["setup_s"]


@pytest.mark.parametrize("config", ["starcoder2-3b-train",
                                    "starcoder2-3b-serve"])
def test_config_keeps_every_published_width(bench, config):
    entry = bench.configs[config]
    cfg = bench.config(config)
    assert entry["source"].startswith("https://huggingface.co/bigcode/")
    assert cfg["hidden_size"] == 3072
    assert cfg["intermediate_size"] == 12288
    assert cfg["num_attention_heads"] == 24
    assert cfg["num_key_value_heads"] == 2
    assert cfg["vocab_size"] == 49152
    assert cfg["rope_theta"] == pytest.approx(999999.4420358813)
    assert set(entry["reduced"]) == set(cfg["reduced"])
    width = re.compile(r"hidden_size|intermediate|_dim$|_rank$|head_size|"
                       r"num_attention_heads|num_key_value_heads")
    assert not [k for k in entry["reduced"] if width.search(k)]
    for key in ("source", "assumed", "limits"):
        assert key in cfg
    for k, published in cfg["published"].items():
        assert k in entry["reduced"] and cfg[k] != published


@pytest.mark.parametrize("bad", ["", "a b", "a,b", "a/b", "µs", "x" * 65,
                                 "-lead", ".lead", "tab\tname"])
def test_names_outside_the_allowed_characters_are_refused(bad):
    with pytest.raises(spec.SpecError):
        spec.check_name(bad)


@pytest.mark.parametrize("good", ["a", "_x", "9lives", "sc2-3b-train.seq4k",
                                  "x" * 64])
def test_names_inside_the_allowed_characters_pass(good):
    assert spec.check_name(good) == good


@pytest.mark.parametrize("bad", ["", "tokens per second", "µs", "x" * 17,
                                 "a,b"])
def test_units_outside_the_allowed_characters_are_refused(bad):
    with pytest.raises(spec.SpecError):
        spec.check_unit(bad)


@pytest.mark.parametrize("good", ["tokens/s/chip", "%", "ms", "count", "s"])
def test_units_inside_the_allowed_characters_pass(good):
    assert spec.check_unit(good) == good


def test_unknown_names_are_errors(bench):
    for call in (bench.cell, bench.config, bench.traffic, bench.kind,
                 bench.reference):
        with pytest.raises(spec.SpecError):
            call("no-such-thing")


def test_peaks_known_device_and_unknown_device():
    row = spec.peaks_for("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in row["source"]
    with pytest.raises(spec.SpecError):
        spec.peaks_for("TPU v9 imaginary")
    with pytest.raises(spec.SpecError):
        spec.peaks_for("cpu")


def test_files_under_paths_are_named_from_allowed_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in bench.doc["paths"]:
        for d, _, files in os.walk(os.path.join(bench.root, path)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), bench.root)
                assert ok.match(rel), rel
