"""run.py end to end: it refuses to run without a TPU, refuses a directory
that holds only BENCHMARK.json and the benchmark's paths, and one tiny-width
rehearsal of each cell kind (train, serve open and closed loop, dp4 on four
virtual devices) prints the contract's last line."""

import json
import os
import shutil
import subprocess
import sys

import pytest


def _run(root, *args, cwd=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd or root, "benchmark", "run.py"),
         *args], cwd=cwd or root, env=env, capture_output=True, text=True,
        timeout=timeout)


def test_exits_nonzero_and_prints_no_result_without_a_tpu(root):
    p = _run(root, "--workload", "sc2-3b-train.seq4k", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_exits_nonzero_for_an_unknown_cell(root):
    p = _run(root, "--workload", "no-such-cell", "--seed", "1", "--seconds",
             "1", "--trace", "0")
    assert p.returncode != 0 and "no workload" in p.stderr


def test_exits_nonzero_in_a_directory_with_only_the_benchmark(root, tmp_path):
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(root, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(root, "tests", "benchmark"),
                    tmp_path / "tests" / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(root, "--workload", "sc2-3b-train.seq4k", "--seed", "1",
             "--seconds", "1", "--trace", "0", "--rehearse",
             cwd=str(tmp_path))
    assert p.returncode not in (0, 3)
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


CASES = [("sc2-3b-train.seq4k", 0), ("sc2-3b-train.seq4k", 1),
         ("sc2-3b-serve.decode-saturated", 0), ("sc2-3b-serve.chat", 1),
         ("sc2-3b-train.seq4k-dp4", 1)]


@pytest.mark.parametrize("cell,trace", CASES)
def test_rehearsal_prints_the_contracts_last_line(root, bench, cell, trace):
    p = _run(root, "--workload", cell, "--seed", str(2 ** 31 + 77),
             "--seconds", "3", "--trace", str(trace), "--rehearse")
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] is True
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in out
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["device"]["count"] == bench.cell(cell)["chips"]
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    if trace:
        names = {m["name"] for m in bench.per_layer_for(cell)}
        assert set(out["metrics"]) <= names and out["metrics"]
        # busy time is of the stamped window: never above it
        assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
        assert [ln for ln in p.stdout.splitlines()
                if ln.startswith("TRACE window ")]
        assert len(out["breakdown"]["device_ops"]) <= 10
        assert len(out["breakdown"]["idle_gaps"]) <= 10
    else:
        want = {m["name"] for m in bench.end_to_end_for(cell)}
        assert set(out["metrics"]) == want
        assert out["metrics"]["setup_s"]["value"] > 0
    # every number compared is printed beside its limit
    assert [ln for ln in p.stdout.splitlines() if ln.startswith("CHECK ")]
