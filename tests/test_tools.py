"""Tools suite tests: merge-model round trip, dot diagram, cost parsing,
image augmentation, torch weight import (torch CPU is available in-image)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu.config.parser import parse_config_callable


def _config():
    from paddle_tpu import dsl

    def conf():
        dsl.settings(batch_size=8, learning_rate=0.1)
        x = dsl.data_layer(name="x", size=6)
        h = dsl.fc_layer(input=x, size=5, act=dsl.TanhActivation(), name="hidden")
        out = dsl.fc_layer(input=h, size=3, act=dsl.SoftmaxActivation(), name="out")
        dsl.classification_cost(input=out, label=dsl.data_layer(name="y", size=3))
    return parse_config_callable(conf)


def test_hlo_gather_detector_anchors_to_shapes():
    """ADVICE r5 regression for tools/hlo_sparse_check.py:113: the table
    all-gather verdict must anchor to parsed operand/result shapes and
    the gathered dimension — a row count appearing elsewhere in the line
    (replica_groups, channel ids, a feature-dim activation gather) must
    not trip the exit-2 verdict; real table materializations (direct or
    grouped [rows/n, n, D] form) must."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.hlo_sparse_check import gather_spans_table

    tables = [((3952, 64), 0), ((6040, 64), 0), ((512, 256), 0)]
    # feature-dim activation gather whose WIDTH equals a table row count
    act = ("%ag = f32[64,256]{0,1} all-gather(f32[64,32]{0,1} %c), "
           "channel_id=6, replica_groups=[1,8]<=[8], dimensions={1}")
    assert not gather_spans_table(act, [((256, 256), 0)] + tables)
    # row count only inside replica_groups / channel id
    noise = ("%ag2 = f32[64,10]{1,0} all-gather(f32[8,10]{1,0} %x), "
             "channel_id=3952, replica_groups=[1,3952]<=[3952], "
             "dimensions={0}")
    assert not gather_spans_table(noise, tables)
    # coincidentally table-shaped result gathered along the UNSHARDED dim
    other_dim = ("%ag3 = f32[512,256]{1,0} all-gather(f32[512,32]{1,0} %x), "
                 "replica_groups=[1,8]<=[8], dimensions={1}")
    assert not gather_spans_table(other_dim, tables)
    # genuine: the table reassembled directly...
    direct = ("%ag4 = f32[3952,64]{1,0} all-gather(f32[494,64]{1,0} %s), "
              "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}")
    assert gather_spans_table(direct, tables)
    # ...or in GSPMD's grouped [rows/n, n, D] lowering (bitcast follows)
    grouped = ("%ag5 = f32[64,8,256]{1,0,2} all-gather(f32[64,1,256]"
               "{1,0,2} %p), channel_id=9, replica_groups=[1,8]<=[8], "
               "dimensions={1}")
    assert gather_spans_table(grouped, tables)


def test_hlo_shard_check_decode_has_no_pool_allgather():
    """tools/hlo_shard_check.py on the real engine over a 2-shard host
    mesh: the tensor-parallel decode, mixed and spec-verify programs must
    contain zero all-gathers of the KV pools or attention projections,
    and exactly the per-layer post-attention all-reduce — the acceptance
    evidence for the sharded-decode HBM/FLOPs split (docs/serving.md).
    The report holds those three programs and the draft step, no other."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    from tools.hlo_shard_check import run_check

    if len(jax.devices()) < 2:
        import pytest

        pytest.skip("needs >= 2 devices (conftest provides 8 host devices)")
    out = run_check(model=2, save="")
    assert out["ok"], out["verdict"]
    assert set(out["steps"]) == {"decode", "mixed", "spec", "draft"}
    assert "scan_decode_steps" not in out
    for step in ("decode", "mixed", "spec"):
        rec = out["steps"][step]
        assert rec["table_all_gathers"] == [], (step, rec)
        assert rec["n_all_gathers"] == 0, \
            (step, "unexpected all-gather — sharded decode must keep ALL "
                   "activations head-local until the out-projection reduce")
        assert rec["n_all_reduces"] == rec["expected_all_reduces"], rec


@pytest.mark.parametrize("value,accepted", [("1", True), ("2", False)])
def test_serve_keeps_decode_steps_for_one_value(value, accepted, capsys):
    """`--decode-steps` stays a flag of tools/serve.py because every serve
    configuration's `server_flags` passes it (benchmark/kinds/serve.py turns
    each key into a flag): 1 parses, and reaches no argument of the engine;
    any other value is refused by the flag's name, exit code 2."""
    from tests.model_parity import serve_tool
    tool, parse = serve_tool()
    argv = ["--config", "demo/model_zoo/transformer_lm.py",
            "--decode-steps", value]
    if accepted:
        assert parse(argv).decode_steps == 1
        import inspect
        assert "decode_steps" not in inspect.getsource(tool.build_engine)
        return
    with pytest.raises(SystemExit) as refused:
        parse(argv)
    assert refused.value.code == 2
    assert "--decode-steps" in capsys.readouterr().err


def test_check_metrics_names_lint(tmp_path):
    """ISSUE 5 tier-1 lint: obs.metrics.CATALOG and docs/observability.md
    must agree both ways — plus the drift detectors actually detect."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.check_metrics_names import SECTION, check, doc_metric_names, main

    assert main() == 0, "CATALOG vs docs/observability.md drifted"

    # drift detection: a doc with one bogus row and none of the real names
    fake = tmp_path / "observability.md"
    fake.write_text(f"# x\n\n{SECTION}\n\n| Metric | Kind |\n|---|---|\n"
                    f"| `made_up_metric` | gauge |\n")
    undocumented, stale = check(str(fake))
    assert stale == {"made_up_metric"}
    assert "serving_queue_depth" in undocumented

    # a doc without the anchor section is a loud error, not a silent pass
    nosec = tmp_path / "empty.md"
    nosec.write_text("# nothing here\n")
    import pytest

    with pytest.raises(ValueError, match="Metric reference"):
        doc_metric_names(str(nosec))


def test_check_metrics_names_catches_dead_catalog_rows(tmp_path):
    """ISSUE 6: the third lint direction — every CATALOG name must be
    referenced somewhere under paddle_tpu/ OUTSIDE the CATALOG block
    itself, so a dead row (declared, documented, never emitted) cannot
    linger.  The current tree is clean; a planted bogus name is caught;
    the CATALOG assignment cannot vouch for itself."""
    from tools.check_metrics_names import _source_without_catalog, \
        unreferenced_names

    assert unreferenced_names() == set(), \
        "dead CATALOG rows (or the reference scan broke)"
    assert unreferenced_names({"totally_made_up_metric"}) == \
        {"totally_made_up_metric"}
    # a real name referenced ONLY by its own catalog row reads as dead:
    # the blanked source must not contain the rows the full source has
    metrics_py = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "paddle_tpu", "obs", "metrics.py")
    blanked = _source_without_catalog(metrics_py)
    with open(metrics_py) as f:
        full = f.read()
    assert "jit_compiles_total" in full
    assert "jit_compiles_total" not in blanked
    assert "CATALOG" in blanked                # only the assignment went


def test_check_metrics_names_event_table_lint(tmp_path):
    """ISSUE 13 satellite: the FOURTH lint direction — every flight-event
    kind emitted under paddle_tpu/ has a row in the doc's flight-event
    table and vice versa, with non-literal kinds themselves flagged (a
    computed kind could ship undocumented)."""
    from tools.check_metrics_names import (EVENT_SECTION, check_events,
                                           doc_event_kinds,
                                           emitted_event_kinds)

    # the current tree is clean in both directions
    undoc, stale, problems = check_events()
    assert undoc == set() and stale == set() and problems == []
    kinds, _ = emitted_event_kinds()
    assert {"queued", "route", "retry", "shed", "pump_death",
            "fleet_unhealthy", "replica_drain"} <= kinds

    # drift detection: a doc with one bogus row and none of the real ones
    fake = tmp_path / "observability.md"
    fake.write_text(f"# x\n\n{EVENT_SECTION}\n\n| Kind | Meaning |\n"
                    f"|---|---|\n| `made_up_event` | ? |\n")
    undoc, stale, _ = check_events(str(fake))
    assert stale == {"made_up_event"}
    assert "queued" in undoc

    # a computed kind is a lint error, not a silent gap
    root = tmp_path / "pkg"
    root.mkdir()
    (root / "mod.py").write_text(
        'flight.record("documented_kind", a=1)\n'
        'self.flight.record("undocumented_kind")\n'
        'flight.record("prefix_" + op)\n'
        'other.record("not_a_flight_event")\n')
    fake.write_text(f"# x\n\n{EVENT_SECTION}\n\n| Kind | Meaning |\n"
                    f"|---|---|\n| `documented_kind` | ok |\n")
    undoc, stale, problems = check_events(str(fake), str(root))
    assert undoc == {"undocumented_kind"}
    assert stale == set()
    assert len(problems) == 1 and "not a string literal" in problems[0]

    # a doc without the anchor section is a loud error
    nosec = tmp_path / "empty.md"
    nosec.write_text("# nothing\n")
    import pytest

    with pytest.raises(ValueError, match="Flight event reference"):
        doc_event_kinds(str(nosec))


def test_trace_dump_merge_stitches_processes_with_offsets(tmp_path,
                                                         capsys):
    """ISSUE 13: --merge stitches span FILES (meta identity line + clock
    offset applied) into one Chrome trace with a process group per file,
    and load_spans still reads a meta-bearing file transparently."""
    import json as _json

    from tools.trace_dump import load_spans, load_trace_file, main

    router = tmp_path / "router.jsonl"
    with open(router, "w") as f:
        f.write(_json.dumps({"meta": {"process": {
            "role": "router", "pid": 1, "addr": "h:1"},
            "offset_s": 0.0}}) + "\n")
        f.write(_json.dumps({"seq": 0, "name": "ingress",
                             "track": "req:t", "ts": 50.0, "dur": 2.0,
                             "attrs": {"trace_id": "aa"}}) + "\n")
    replica = tmp_path / "replica.jsonl"
    with open(replica, "w") as f:
        f.write(_json.dumps({"meta": {"process": {
            "role": "replica", "pid": 2, "addr": "h:2"},
            "offset_s": 45.0}}) + "\n")           # epoch 45s behind
        f.write(_json.dumps({"seq": 0, "name": "decode",
                             "track": "req:t", "ts": 5.5, "dur": 1.0,
                             "attrs": {"trace_id": "aa"}}) + "\n")

    # meta line is transparent to the single-file loaders
    assert [s["name"] for s in load_spans(str(router))] == ["ingress"]
    meta, spans = load_trace_file(str(replica))
    assert meta["process"]["role"] == "replica" and len(spans) == 1

    out = tmp_path / "fleet.json"
    assert main([str(router), str(replica), "--merge",
                 "-o", str(out)]) == 0
    assert "2 processes" in capsys.readouterr().out
    merged = _json.loads(out.read_text())
    evs = merged["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in evs
             if e.get("name") == "process_name"}
    assert len(procs) == 2
    ing = next(e for e in evs if e["name"] == "ingress")
    dec = next(e for e in evs if e["name"] == "decode")
    assert ing["pid"] != dec["pid"]
    # offset applied then globally rebased: decode starts 0.5s into
    # the ingress span (50.5 vs 50.0 in the aligned timebase)
    assert ing["ts"] == 0.0
    assert dec["ts"] == 0.5e6
    assert dec["args"]["trace_id"] == ing["args"]["trace_id"]

    # several files WITHOUT --merge is an explicit error, not a guess
    assert main([str(router), str(replica)]) == 2
    # single-file path unchanged (no --merge needed)
    assert main([str(router), "-o", str(tmp_path / "one.json")]) == 0


def test_trace_dump_summary_lanes_and_compile_breakdown(tmp_path, capsys):
    """ISSUE 6: --summary must make a recompile storm visible from the
    trace file alone — per-lane counts plus a compile-lane table with
    signatures × compile-time and STORMS markers."""
    import json

    from tools.trace_dump import compile_breakdown, load_spans, main

    spans = [
        {"seq": 0, "name": "queued", "track": "req:a", "ts": 0.0,
         "dur": 0.1},
        {"seq": 1, "name": "decode", "track": "req:a", "ts": 0.1,
         "dur": 0.4},
        {"seq": 2, "name": "queued", "track": "req:b", "ts": 0.0,
         "dur": 0.2},
        {"seq": 3, "name": "decode_step", "track": "engine", "ts": 0.1,
         "dur": 0.2},
        {"seq": 4, "name": "serving.prefill", "track": "compile",
         "ts": 0.0, "dur": 0.8, "attrs": {"sig": "int32[1,8]"}},
        {"seq": 5, "name": "serving.prefill", "track": "compile",
         "ts": 1.0, "dur": 0.6, "attrs": {"sig": "int32[1,16]"}},
        {"seq": 6, "name": "recompile_storm", "track": "compile",
         "ts": 1.5, "instant": True,
         "attrs": {"site": "serving.prefill", "signatures": 6}},
    ]
    src = tmp_path / "spans.jsonl"
    src.write_text("".join(json.dumps(s) + "\n" for s in spans))

    assert main([str(src), "--summary"]) == 0
    out = capsys.readouterr().out
    # per-lane counts: request lanes collapse to one req:* row
    assert "req:*" in out and "compile" in out and "engine" in out
    assert "7 spans on 3 lanes" in out
    # the compile breakdown: 2 compiles, 2 sigs, 1400ms, storm marker
    assert "compile lane (2 compiles):" in out
    lines = out.splitlines()
    start = next(i for i, l in enumerate(lines)
                 if l.startswith("compile lane"))
    line = next(l for l in lines[start:]
                if l.strip().startswith("serving.prefill"))
    assert "2" in line and "1400.00" in line and "STORMS=1" in line

    # a trace with no compile lane gets no breakdown (older traces)
    assert compile_breakdown(load_spans(str(src))[:4]) == ""
    plain = tmp_path / "plain.jsonl"
    plain.write_text("".join(json.dumps(s) + "\n" for s in spans[:4]))
    assert main([str(plain), "--summary"]) == 0
    assert "compile lane" not in capsys.readouterr().out


def test_merge_model_roundtrip(tmp_path):
    import jax

    from paddle_tpu.graph.builder import GraphExecutor
    from paddle_tpu.tools.merge_model import load_bundle, merge_model
    from paddle_tpu.trainer import checkpoint as ckpt

    cfg = _config()
    ex = GraphExecutor(cfg.model_config)
    params = {k: np.asarray(v) for k, v in
              ex.init_params(jax.random.PRNGKey(0)).items()}
    d = ckpt.save_checkpoint(str(tmp_path / "ck"), 0, params,
                             config_json=cfg.to_json())
    bundle = str(tmp_path / "model.bundle")
    merge_model(d, bundle)
    cfg2, params2 = load_bundle(bundle)
    assert cfg2.model_config.layer("hidden").size == 5
    assert set(params2) == set(params)
    for k in params:
        np.testing.assert_array_equal(params2[k], params[k])


def test_model_diagram():
    from paddle_tpu.tools.make_model_diagram import model_to_dot

    cfg = _config()
    dot = model_to_dot(cfg.model_config)
    assert dot.startswith("digraph")
    assert '"hidden"' in dot and '"out"' in dot
    assert '"hidden" -> "out"' in dot


def test_plotcurve_parsing():
    from paddle_tpu.tools.plotcurve import ascii_plot, parse_costs

    lines = [
        "I 0701 paddle_tpu.trainer] pass 0 batch 10: cost 1.5 err 0.4",
        "noise line",
        "I 0701 paddle_tpu.trainer] pass 0 batch 20: cost 0.75 err 0.2",
    ]
    ys = parse_costs(lines)
    assert ys == [1.5, 0.75]
    art = ascii_plot(ys)
    assert "final 0.7500" in art


def test_image_augmentation():
    from paddle_tpu.tools import image_util as iu

    rng = np.random.default_rng(0)
    img = rng.integers(0, 255, (32, 32, 3)).astype(np.uint8)
    chw = iu.to_chw(img)
    assert chw.shape == (3, 32, 32)
    c = iu.center_crop(chw, 28)
    assert c.shape == (3, 28, 28)
    np.testing.assert_array_equal(c, chw[:, 2:30, 2:30])
    r = iu.random_crop(chw, 28, rng)
    assert r.shape == (3, 28, 28)
    f = iu.horizontal_flip(c)
    np.testing.assert_array_equal(f[:, :, 0], c[:, :, -1])
    a = iu.augment(chw, 28, rng, train=True, mean=127.5, scale=1 / 127.5)
    assert a.shape == (3, 28, 28) and a.dtype == np.float32
    assert np.abs(a).max() <= 1.0


def test_torch2paddle_convert():
    import torch

    from paddle_tpu.tools.torch2paddle import convert_state_dict

    cfg = _config()
    # torch Linear mirror of the model: 6->5->3 with biases
    net = torch.nn.Sequential(
        torch.nn.Linear(6, 5), torch.nn.Tanh(),
        torch.nn.Linear(5, 3))
    params = convert_state_dict(net.state_dict(), cfg.model_config)
    # every model parameter matched, linear weights transposed
    w_hidden = [v for k, v in params.items() if v.shape == (6, 5)]
    assert w_hidden, {k: v.shape for k, v in params.items()}
    np.testing.assert_allclose(
        w_hidden[0], net[0].weight.detach().numpy().T, rtol=1e-6)


def test_dump_config_cli(tmp_path):
    conf_file = tmp_path / "conf.py"
    conf_file.write_text(
        "from paddle_tpu.dsl import *\n"
        "settings(batch_size=4, learning_rate=0.1)\n"
        "x = data_layer(name='x', size=4)\n"
        "out = fc_layer(input=x, size=2, act=SoftmaxActivation(), name='out')\n"
        "classification_cost(input=out, label=data_layer(name='y', size=2))\n")
    env = dict(os.environ, PYTHONPATH="/root/repo", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tools.dump_config", str(conf_file)],
        capture_output=True, text=True, env=env, timeout=240)
    assert r.returncode == 0, r.stderr
    assert '"out"' in r.stdout


def test_bundle_into_gradient_machine(tmp_path):
    import jax

    from paddle_tpu import api
    from paddle_tpu.tools.merge_model import merge_model
    from paddle_tpu.trainer import checkpoint as ckpt

    cfg = _config()
    m = api.GradientMachine.createFromConfigProto(cfg.model_config, seed=5)
    d = ckpt.save_checkpoint(str(tmp_path / "ck"), 0,
                             {k: np.asarray(v) for k, v in m.params.items()},
                             config_json=cfg.to_json())
    bundle = str(tmp_path / "model.bundle")
    merge_model(d, bundle)
    m2 = api.GradientMachine.createFromFile(bundle)
    for k in m.params:
        np.testing.assert_array_equal(np.asarray(m.params[k]),
                                      np.asarray(m2.params[k]))
    # deployable: forward works
    batch = {"x": __import__("paddle_tpu.parameter.argument",
                             fromlist=["Argument"]).Argument(
        value=np.zeros((2, 6), np.float32))}
    outs = m2.forwardTest(batch)
    assert "out" in outs


def test_embedding_zoo_roundtrip(tmp_path):
    """extract/to_text/from_text (ref: demo/model_zoo/embedding/
    extract_para.py, paraconvert.py)."""
    import numpy as np

    from paddle_tpu.tools import embedding_zoo as ez

    rng = np.random.default_rng(0)
    pre = rng.normal(size=(6, 4)).astype(np.float32)
    pre_words = ["<unk>", "the", "cat", "sat", "mat", "dog"]
    usr_words = ["cat", "unicorn", "dog"]

    out = ez.extract_rows(pre, pre_words, usr_words)
    np.testing.assert_array_equal(out[0], pre[2])     # cat
    np.testing.assert_array_equal(out[1], pre[0])     # OOV -> <unk> row
    np.testing.assert_array_equal(out[2], pre[5])     # dog

    # without an <unk> row, OOV falls back to the mean vector
    out2 = ez.extract_rows(pre[1:], pre_words[1:], ["unicorn"])
    np.testing.assert_allclose(out2[0], pre[1:].mean(0), rtol=1e-6)

    txt = tmp_path / "emb.txt"
    ez.to_text(out, usr_words, str(txt))
    back, words = ez.from_text(str(txt))
    assert words == usr_words
    np.testing.assert_allclose(back, out, rtol=1e-5, atol=1e-6)

    # CLI end to end
    pre_npy = tmp_path / "pre.npy"
    np.save(pre_npy, pre)
    (tmp_path / "pre.dict").write_text("\n".join(pre_words) + "\n")
    (tmp_path / "usr.dict").write_text("\n".join(usr_words) + "\n")
    usr_npy = tmp_path / "usr.npy"
    ez.main(["extract", "--pre_model", str(pre_npy),
             "--pre_dict", str(tmp_path / "pre.dict"),
             "--usr_model", str(usr_npy),
             "--usr_dict", str(tmp_path / "usr.dict")])
    np.testing.assert_array_equal(np.load(usr_npy), out)


def test_cli_multiplexer_dispatch(tmp_path, capsys):
    """`python -m paddle_tpu <cmd>` dispatches like the reference's `paddle`
    shell wrapper (ref: paddle/scripts/submit_local.sh.in:109-134)."""
    import paddle_tpu.__main__ as cli

    assert cli.main(["--help"]) == 0
    assert "train" in capsys.readouterr().out
    assert cli.main(["version"]) == 0
    assert "paddle_tpu" in capsys.readouterr().out
    assert cli.main(["no_such_cmd"]) == 2

    # a real dispatch: dump_config through the multiplexer
    cfg = tmp_path / "c.py"
    cfg.write_text(
        "from paddle_tpu.dsl import *\n"
        "settings(batch_size=4, learning_rate=0.1)\n"
        "x = data_layer(name='x', size=4)\n"
        "o = fc_layer(input=x, size=2, act=SoftmaxActivation())\n"
        "classification_cost(input=o, label=data_layer(name='y', size=2))\n")
    assert cli.main(["dump_config", str(cfg)]) == 0
    out = capsys.readouterr().out
    import json
    assert json.loads(out)["model_config"]["layers"]


# -- tools/serve.py: the flags the benchmark's configurations pass ------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _serve_parse(argv):
    """tools/serve.py's own main() parses `argv`, stopped where it would
    start the server (the hook benchmark/kinds/serve.py uses)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tools_serve_flags", os.path.join(_ROOT, "tools", "serve.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    got = {}

    async def capture(args):
        got["args"] = args
        return 0

    tool.amain = capture
    tool.main(argv)
    return got["args"]


@pytest.mark.parametrize("config", ["starcoder2-3b-serve.json",
                                    "gigachat3.1-702b-a36b-serve.json"])
def test_serve_parses_every_server_flag_of_the_configuration(config):
    """Every key of a serve configuration's `server_flags` is a flag of
    tools/serve.py with the value it was given (`max_context` ->
    `--max-context`): the benchmark passes them by data alone."""
    import json
    with open(os.path.join(_ROOT, "benchmark", "configs", config)) as f:
        flags = json.load(f)["server_flags"]
    assert flags, config
    args = _serve_parse([w for k, v in flags.items()
                         for w in ("--" + k.replace("_", "-"), str(v))])
    for k, v in flags.items():
        assert getattr(args, k) == v, (k, getattr(args, k), v)


@pytest.mark.parametrize("argv", [["--decode-mode", "auto"],
                                  ["--prefill-chunk", "-1"]],
                         ids=["decode-mode", "negative-prefill-chunk"])
def test_serve_refuses_the_retired_fork_selectors(argv, capsys):
    """`--decode-mode` is gone and a negative `--prefill-chunk` selected
    the whole-prompt prefill that is gone: both are parser errors, not
    silently accepted spellings of the default."""
    with pytest.raises(SystemExit) as ei:
        _serve_parse(argv)
    assert ei.value.code == 2
    assert argv[0] in capsys.readouterr().err


def test_bench_prefix_evict_walk_and_kept_free_the_same_pages(
        tmp_path, monkeypatch, capsys):
    """tools/bench_prefix_evict.py end to end at a small size: the kept
    frontier and the walking reference evict the same pages call for
    call, and the table's line lands where the chip tool collects it."""
    import json

    from tools import bench_prefix_evict

    monkeypatch.chdir(tmp_path)
    assert bench_prefix_evict.main(
        ["--nodes", "256", "--calls", "4", "--chain", "8",
         "--victims", "16"]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["same_victims"] is True and row["nodes"] == 256
    assert row["pops"]["victim"] == 4 * 16
    assert row["walk_ms"] > 0 and row["kept_ms"] > 0
    with open(tmp_path / "chiprun_out" / "bench_prefix_evict.jsonl") as f:
        assert json.loads(f.read().strip()) == row


@pytest.mark.parametrize("seconds", [40.0, 120.0])
def test_closed_loop_spread_a_longer_window_swings_less(seconds, capsys):
    """tools/closed_loop_spread.py on the Olmo-Hybrid cell's own mix: the
    simulated loop serves what the chip served (800-900 tokens/s, four steps
    in five mixed, `itl_p95_ms` a mixed step's), tokens/s spreads by more
    than the tail, and a window three times as long by less than half."""
    import json

    from tools import closed_loop_spread

    assert closed_loop_spread.main(["--seeds", "24", "--seconds",
                                    str(seconds)]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["simulated"] is True and row["seeds"] == 24
    assert 800 < row["tokens_per_s"]["median"] < 900
    assert 30 < row["itl_p95_ms"]["median"] < 40
    assert 0.75 < row["mixed"] / (row["mixed"] + row["decode"]) < 0.9
    assert row["tokens_per_s"]["spread_pct"] > row["itl_p95_ms"]["spread_pct"]
    lo, hi = (2.0, 6.0) if seconds == 40.0 else (0.4, 2.0)
    assert lo < row["tokens_per_s"]["spread_pct"] < hi
