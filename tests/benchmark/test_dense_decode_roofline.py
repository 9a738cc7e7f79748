"""`dense_decode_hbm_roofline.serve` on the CPU: the bytes a StarCoder2
decode step must read at the stated compute dtype against numbers worked out
by hand, a share the chip cannot give raises, a run without a trace reads
nothing, the entry is declared as ISSUE 34 names it, and the traced
rehearsal of both StarCoder2 serve cells prints it."""

import json
import os
import subprocess
import sys
import types

import pytest

NAME = "dense_decode_hbm_roofline.serve"
CELLS = ["sc2-3b-serve.chat", "sc2-3b-serve.decode-saturated"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def test_decode_step_bytes_by_hand(bench):
    """8 layers of 3072 x (3072 + 2 x 256 + 3072 + 2 x 12288) and the head's
    3072 x 49152: 918.6 M matrix parameters, 1.837 GB in bfloat16 whatever
    the program holds them in; a live token is 2 x 2 x 128 x 2 B a layer."""
    from benchmark.lib import arith
    cfg = bench.config("starcoder2-3b-serve")
    mm = arith.lm_matmul_params(cfg)
    assert mm["total"] == 8 * 3072 * (2 * 3072 + 2 * 256 + 2 * 12288) \
        + 3072 * 49152 == 918_552_576
    assert (cfg["compute_dtype"], cfg["param_dtype"]) == ("bfloat16",
                                                          "float32")
    reader = bench.reader(NAME)
    idle = reader.decode_step_bytes(cfg, rows=0, tokens=0)
    assert idle == {"weights": 2.0 * mm["total"], "kv": 0.0,
                    "total": 2.0 * mm["total"]}
    parts = reader.decode_step_bytes(cfg, rows=64, tokens=64 * 600)
    kv_call = 64 * 600 * 2 * 2 * 128 * 2 + 64 * 24 * 128 * 2 * 2
    assert kv_call == arith.paged_decode_cost(cfg, 64 * 600, 64, 2)["bytes"]
    assert parts["kv"] == 8 * kv_call
    assert parts["total"] == 2 * mm["total"] + 8 * kv_call
    assert round(parts["total"] / 1e9, 3) == 2.158
    # the same model computed in float32 must read twice the matrices
    wide = reader.decode_step_bytes(dict(cfg, compute_dtype="float32"), 0, 0)
    assert wide["weights"] == 4.0 * mm["total"]
    with pytest.raises(KeyError):
        reader.decode_step_bytes(dict(cfg, compute_dtype="float8"), 0, 0)


def test_share_is_least_time_over_busy_time_and_cannot_pass_the_chip(bench):
    cfg = bench.config("starcoder2-3b-serve")
    reader = bench.reader(NAME)
    total = reader.decode_step_bytes(cfg, 64, 64 * 600)["total"]
    least = total / 819e9
    assert 2.6e-3 < least < 2.7e-3
    assert reader.share(total, 2 * least, PEAKS) == pytest.approx(50.0)
    assert reader.share(total, least / 1.04, PEAKS) == pytest.approx(104.0)
    with pytest.raises(RuntimeError, match=NAME):
        reader.share(total, least / 1.06, PEAKS)


def test_reads_nothing_without_a_trace(bench):
    ctx = types.SimpleNamespace(cfg=bench.config("starcoder2-3b-serve"),
                                trace_data=None, counters={}, peaks=PEAKS)
    assert bench.reader(NAME).read(ctx) is None


def test_declared_as_the_issue_names_it(bench):
    m = bench.per_layer[NAME]
    assert list(bench.per_layer)[-1] == NAME
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "device_trace", "layer": "graph and ops",
                 "moves": "itl_p95_ms", "workloads": CELLS}
    assert [c for c in bench.cells
            if NAME in {x["name"] for x in bench.per_layer_for(c)}] == \
        [c for c in bench.cells if c in CELLS]
    for c in CELLS:
        assert "itl_p95_ms" in {x["name"] for x in bench.end_to_end_for(c)}


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_prints_the_metric(root, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 79), "--seconds", "3",
         "--trace", "1", "--rehearse"], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] is True and out["correct"] is True, out["checks"]
    got = out["metrics"][NAME]
    assert got["unit"] == "%" and 0.0 < got["value"] < 105.0
