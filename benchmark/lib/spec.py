"""Find the benchmark's pieces by the names in BENCHMARK.json.

Everything that belongs to one configuration, one traffic mix, one cell kind
or one per-layer metric is a file of its own:

  benchmark/configs/<config>.json          the sizes as run (+ source, reduced,
                                           assumed, the DSL file, server flags)
  benchmark/traffic/<traffic>.json         parameters of one traffic mix; its
                                           "kind" names benchmark/kinds/<kind>.py
  benchmark/layer_metrics/<metric>.py      read(ctx) -> number or None
  benchmark/reference/<reference>.py       the plain float32 reference

so a later PR adds a cell, a configuration or a metric with new files plus
entries in BENCHMARK.json, and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    pass


def check_name(name: str, what: str = "name") -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what} {name!r}: a name is at most 64 of letters, "
                        f"digits, '_', '.', '-' and does not start with '.' "
                        f"or '-'")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"unit {unit!r}: 1 to 16 of letters, digits, "
                        f"'_', '/', '%', '.', '-'")
    return unit


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path (metric names carry dots, so no package)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + re.sub(r"[^A-Za-z0-9_]", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """BENCHMARK.json plus the files it names, rooted at `root`."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.dir = os.path.join(root, "benchmark")
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}
        for group in (self.cells, self.configs, self.end_to_end,
                      self.per_layer):
            for name in group:
                check_name(name)
        for m in list(self.end_to_end.values()) + list(self.per_layer.values()):
            check_unit(m["unit"])
            if m["source"] not in SOURCES:
                raise SpecError(f"{m['name']}: source {m['source']!r}")
            if m["better"] not in ("lower", "higher"):
                raise SpecError(f"{m['name']}: better {m['better']!r}")
        for m in self.per_layer.values():
            if m["moves"] not in self.end_to_end:
                raise SpecError(f"{m['name']} moves {m['moves']!r}, which is "
                                f"no end-to-end metric")

    # -- cells ---------------------------------------------------------------
    def cell(self, name: str) -> dict:
        check_name(name, "workload")
        if name not in self.cells:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                            f"(have: {sorted(self.cells)})")
        return self.cells[name]

    def config(self, name: str) -> dict:
        """The configuration's file of sizes (BENCHMARK.json names it)."""
        check_name(name, "config")
        if name not in self.configs:
            raise SpecError(f"no config {name!r} in BENCHMARK.json")
        cfg = _load_json(os.path.join(self.root, self.configs[name]["file"]))
        cfg["name"] = name
        return cfg

    def traffic(self, name: str) -> dict:
        check_name(name, "traffic")
        path = os.path.join(self.dir, "traffic", name + ".json")
        if not os.path.exists(path):
            raise SpecError(f"no traffic file {path}")
        tr = _load_json(path)
        tr["name"] = name
        check_name(tr.get("kind", ""), "traffic kind")
        return tr

    def kind(self, name: str):
        path = os.path.join(self.dir, "kinds", check_name(name, "kind") + ".py")
        if not os.path.exists(path):
            raise SpecError(f"no cell kind {name!r} ({path})")
        return load_module(path, "kind_" + name)

    def reference(self, name: str):
        path = os.path.join(self.dir, "reference",
                            check_name(name, "reference") + ".py")
        if not os.path.exists(path):
            raise SpecError(f"no reference {name!r} ({path})")
        return load_module(path, "reference_" + name)

    # -- metrics -------------------------------------------------------------
    def reports(self, metric: dict, cell: str) -> bool:
        """Does `cell` report `metric`?  (its `workloads` key, or — without
        one — every cell that reports the end-to-end metric it moves)."""
        if "workloads" in metric:
            return cell in metric["workloads"]
        moves = metric.get("moves")
        return True if moves is None else \
            self.reports(self.end_to_end[moves], cell)

    def end_to_end_for(self, cell: str) -> list[dict]:
        return [m for m in self.end_to_end.values() if self.reports(m, cell)]

    def per_layer_for(self, cell: str) -> list[dict]:
        return [m for m in self.per_layer.values() if self.reports(m, cell)]

    def reader(self, metric: str):
        """The per-layer metric's own reader: benchmark/layer_metrics/
        <metric>.py with read(ctx) and LAYER, UNIT, MOVES."""
        path = os.path.join(self.dir, "layer_metrics",
                            check_name(metric, "metric") + ".py")
        if not os.path.exists(path):
            raise SpecError(f"per-layer metric {metric!r} has no reader "
                            f"({path})")
        mod = load_module(path, "metric_" + metric)
        want = self.per_layer[metric]
        for key, attr in (("layer", "LAYER"), ("unit", "UNIT"),
                          ("moves", "MOVES")):
            if getattr(mod, attr) != want[key]:
                raise SpecError(
                    f"{metric}: reader says {attr}={getattr(mod, attr)!r}, "
                    f"BENCHMARK.json says {want[key]!r}")
        return mod


def peaks_for(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """Published peaks of one chip, by `device_kind`.  An unknown device is
    an error, never a default."""
    table = _load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    kind = device_kind.lower()
    for row in table:
        if any(k in kind for k in row["device_kind_contains"]):
            return row
    raise SpecError(f"no peaks on record for device_kind {device_kind!r}: "
                    f"add a row with its source to benchmark/peaks.json")
