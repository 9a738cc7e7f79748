"""The mesh train step, as far as the CPU can say: who gets the asynchronous
all-reduce options (parallel/dp.py:step_compile_options), what the schedule
reader makes of each form a compiled module prints (parallel/schedule.py),
that a mesh Trainer builds its step ONCE (every leaf of the optimizer state
is placed on the mesh before the first call), and that the step path reads
nothing: the `trainer_step_collectives` gauges lower and read the executable
when they are first collected.  The compile for described TPUs is
tests/test_mosaic_compile.py's."""

import types

import jax
import numpy as np
import pytest

from paddle_tpu.parallel.dp import (ASYNC_ALL_REDUCE_OPTIONS,
                                    step_compile_options)
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.schedule import (read_collectives, shape_bytes,
                                          summarize)


def _described(platform: str, **axes):
    """A mesh's observable face (axis names, device grid, platform) without
    devices of that platform: all `step_compile_options` reads."""
    dev = types.SimpleNamespace(platform=platform)
    grid = np.empty(tuple(axes.values()), object)
    grid.fill(dev)
    return types.SimpleNamespace(axis_names=tuple(axes), devices=grid)


@pytest.mark.parametrize("mesh,want", [
    ("none", {}),
    ("cpu data:4", {}),
    ("tpu data:4", ASYNC_ALL_REDUCE_OPTIONS),
    ("tpu data:2 model:2", ASYNC_ALL_REDUCE_OPTIONS),
    ("tpu data:1 model:4", {}),
    ("tpu model:4", {}),
    ("gpu data:4", {}),
], ids=lambda v: v if isinstance(v, str) else None)
def test_options_follow_the_data_axis_and_the_platform(mesh, want):
    if mesh == "none":
        built = None
    elif mesh == "cpu data:4":
        built = make_mesh(data=4, devices=jax.devices()[:4])
    else:
        platform, *axes = mesh.split()
        built = _described(platform, **{
            a.split(":")[0]: int(a.split(":")[1]) for a in axes})
    got = step_compile_options(built)
    assert got == want
    if got:
        got["xla_enable_async_all_reduce"] = False
        assert ASYNC_ALL_REDUCE_OPTIONS["xla_enable_async_all_reduce"], \
            "the caller was handed the module's own dict"


#: one instruction of each form, as libtpu 0.0.34 prints a scheduled module
MODULE = """HloModule jit_train_step, is_scheduled=true

%add.clone (x: bf16[], y: bf16[]) -> bf16[] {
  %x = bf16[]{:T(256)} parameter(0)
  %y = bf16[]{:T(256)} parameter(1)
  ROOT %add.2 = bf16[]{:T(256)} add(%x, %y)
}

%fused_computation.378 (param_0.1: bf16[12288,3072]) -> (bf16[12288,3072], bf16[12288,3072], u32[]) {
  %param_0.1 = bf16[12288,3072]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.43 = bf16[12288,3072]{1,0:T(8,128)(2,1)} all-reduce(%param_0.1), channel_id=8, replica_groups=[1,4]<=[4], to_apply=%add.clone
  ROOT %custom-call.11 = (bf16[12288,3072]{1,0:T(8,128)(2,1)}, bf16[12288,3072]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) custom-call(%all-reduce.43), custom_call_target="AllReduceStart"
}

%body (p: (s32[], bf16[64])) -> (s32[], bf16[64]) {
  %p = (s32[], bf16[64]{0}) parameter(0)
  %g = bf16[64]{0} get-tuple-element(%p), index=1
  %all-gather.1 = bf16[256]{0} all-gather(%g), channel_id=3, dimensions={0}
  ROOT %t = (s32[], bf16[64]{0}) tuple(%p)
}

ENTRY %main.52_spmd (a: bf16[12288,3072], b: bf16[3072], c: f32[]) -> bf16[12288,3072] {
  %a = bf16[12288,3072]{1,0:T(8,128)(2,1)} parameter(0)
  %b = bf16[3072]{0:T(1024)(128)(2,1)} parameter(1)
  %c = f32[]{:T(128)} parameter(2)
  %all-reduce.48 = (bf16[3072]{0:T(1024)(128)(2,1)}, f32[]{:T(128)}) all-reduce(%b, %c), channel_id=10, replica_groups=[1,4]<=[4], to_apply=%add.clone, frontend_attributes={async_collective_name="all-reduce-start.3"}
  %async-collective-start = (bf16[12288,3072]{1,0:T(8,128)(2,1)}, bf16[12288,3072]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) fusion(%a), kind=kCustom, calls=%fused_computation.378
  %get-tuple-element.468 = bf16[12288,3072]{1,0:T(8,128)(2,1)S(1)} get-tuple-element(%async-collective-start), index=1
  %fusion.264 = (bf16[3072,49152]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) fusion(%get-tuple-element.468), kind=kOutput, calls=%add.clone
  %divide_subtract_fusion.5 = f32[3072]{0} fusion(%b), kind=kLoop, calls=%add.clone
  %get-tuple-element.505 = u32[]{:S(2)} get-tuple-element(%fusion.264), index=1
  %async-collective-done = bf16[12288,3072]{1,0:T(8,128)(2,1)S(1)} fusion(%get-tuple-element.505), kind=kCustom, calls=%add.clone
  %all-reduce-start.1 = (bf16[3072]{0}, bf16[3072]{0}) all-reduce-start(%b), channel_id=4, replica_groups=[1,4]<=[4], to_apply=%add.clone
  %convolution_bitcast_fusion.2 = bf16[3072]{0} fusion(%b), kind=kOutput, calls=%add.clone
  %all-reduce-done.1 = bf16[3072]{0} all-reduce-done(%all-reduce-start.1)
  %while.18 = (s32[], bf16[64]{0}) while(%c), condition=%add.clone, body=%body
  ROOT %copy.1 = bf16[12288,3072]{1,0:T(8,128)(2,1)} copy(%async-collective-done)
}
"""


def test_reader_tells_the_three_forms_apart():
    found = {c["name"]: c for c in read_collectives(MODULE)}
    assert list(found) == ["all-reduce.48", "async-collective-start",
                           "all-reduce-start.1", "all-gather.1"]
    # the combiner's tuple: synchronous, its leaves' bytes, and the mark of
    # an all-reduce the scheduler lifted and the fusion pass put back
    tup = found["all-reduce.48"]
    assert (tup["form"], tup["bytes"], tup["put_back"]) == \
        ("sync", 3072 * 2 + 4, True)
    # the TPU's fused form: a custom fusion NAMED async-collective-start,
    # the all-reduce inside the computation it calls
    fused = found["async-collective-start"]
    assert (fused["kind"], fused["form"]) == ("all-reduce", "async")
    assert fused["bytes"] == 12288 * 3072 * 2
    assert fused["between"] == {
        "instructions": 4, "work": 2,
        "first": ["fusion.264", "divide_subtract_fusion.5"]}
    # XLA's own pair: the payload is the results' half of the start's shape
    pair = found["all-reduce-start.1"]
    assert (pair["form"], pair["bytes"]) == ("async", 3072 * 2)
    assert pair["between"]["first"] == ["convolution_bitcast_fusion.2"]
    # a loop body's collective is the step's too
    assert found["all-gather.1"]["kind"] == "all-gather"
    assert summarize(found.values()) == {
        "async": {"count": 2, "bytes": 12288 * 3072 * 2 + 3072 * 2},
        "sync": {"count": 2, "bytes": 3072 * 2 + 4 + 256 * 2}}


def test_shape_bytes_of_tuples_and_layouts():
    assert shape_bytes("bf16[3072,49152]{1,0:T(8,128)(2,1)}") == \
        3072 * 49152 * 2
    assert shape_bytes("(f32[]{:T(128)}, f32[4]{0}, pred[8]{0})") == 28
    assert shape_bytes("token[]") == 0


def _lm_trainer(mesh, accum: int = 1):
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.parameter.argument import Argument
    from paddle_tpu.trainer.trainer import Trainer
    pc = parse_config(
        "benchmark/configs/starcoder2.py",
        "vocab=128,dim=64,layers=1,heads=2,kv_heads=1,ffn=128,batch_size=8,"
        "seq_len=17,attn_impl=dense,compute_dtype=float32")
    pc.opt_config.num_batches_per_send_parameter = accum
    ids = np.random.default_rng(0).integers(0, 128, (8, 17)).astype(np.int32)
    lens = np.full((8,), 16, np.int32)
    batch = {"tokens": Argument(ids=ids[:, :-1], lengths=lens),
             "next_tokens": Argument(ids=ids[:, 1:], lengths=lens)}
    return Trainer(pc, seed=1, mesh=mesh), batch


def _jit_work(fn) -> dict:
    """What jit did while `fn` ran: `lowerings` (jaxpr -> module) and
    `compiles` (executables XLA built or loaded)."""
    from jax import monitoring
    n = {"lowerings": 0, "compiles": 0}

    def on(event, _seconds, **_kw):
        n["lowerings"] += event.endswith("/jaxpr_to_mlir_module_duration")
        n["compiles"] += event.endswith("/backend_compile_duration")

    monitoring.register_event_duration_secs_listener(on)
    try:
        fn()
    finally:
        monitoring.unregister_event_duration_listener(on)
    return n


def _cpu_mesh():
    return make_mesh(data=4, devices=jax.devices()[:4])


@pytest.mark.parametrize("how", ["per-batch", "scan", "accumulate",
                                 "restored"])
def test_a_mesh_trainers_step_is_built_once(how, tmp_path):
    """Three `train_one_pass` of one batch (the benchmark's warm-up) on a
    4-device CPU mesh: the first builds the step, the others build NOTHING.
    The parent built `train_step` again at pass 1: `num_samples`,
    `num_updates` and `pass_id` went in off the mesh and came back on it."""
    tr, batch = _lm_trainer(_cpu_mesh(), accum=2 if how == "accumulate" else 1)
    if how == "restored":
        tr.train_one_pass(batches=iter([batch]))
        tr.save(str(tmp_path))
        tr, _ = _lm_trainer(_cpu_mesh())
        tr.load(str(tmp_path / "pass-00000"))
    for leaf in jax.tree.leaves(tr.opt_state):
        assert leaf.committed and len(leaf.sharding.device_set) == 4, \
            tr.opt_state.keys()
    n = 4 if how == "scan" else 2 if how == "accumulate" else 1
    kw = {"steps_per_dispatch": 2} if how == "scan" else {}
    built = [_jit_work(lambda: tr.train_one_pass(
        batches=iter([batch] * n), **kw))["compiles"] for _ in range(3)]
    assert built[0] >= 1 and built[1:] == [0, 0], built


#: compile options the CPU's compiler takes and that change nothing
HARMLESS = {"xla_embed_ir_in_executable": False}


@pytest.mark.parametrize("fused", [False, True], ids=["per-batch", "scan"])
def test_the_step_path_reads_nothing(monkeypatch, fused):
    """Where the mesh gives the step compile options, a new signature's
    first call lowers and compiles what a trainer WITHOUT the gauges does;
    the first `metrics.snapshot()` afterwards lowers each noted signature,
    reads its executable and fills the gauges, and the next one reads
    nothing more."""
    from paddle_tpu.parallel import dp, schedule
    monkeypatch.setattr(dp, "step_compile_options",
                        lambda mesh: dict(HARMLESS) if mesh is not None
                        else {})
    texts, read = [], schedule.read_collectives
    monkeypatch.setattr(schedule, "read_collectives",
                        lambda text: (texts.append(text), read(text))[1])

    def first_calls(tr, batch):
        if fused:
            return lambda: tr.train_one_pass(batches=iter([batch] * 4),
                                             steps_per_dispatch=2)
        return lambda: [tr.train_one_batch(batch) for _ in range(3)]

    def trainer(gauges: bool):
        tr, batch = _lm_trainer(_cpu_mesh())
        assert tr._step_collectives is not None
        if not gauges:
            tr._step_collectives = None
        return tr, batch

    # the process's first trainer also builds the eager helpers (rng split,
    # loss drain) that every later one finds cached: it is not compared
    first_calls(*trainer(gauges=False))()
    silent, batch = trainer(gauges=False)
    reading, _ = trainer(gauges=True)
    assert _jit_work(first_calls(reading, batch)) == \
        _jit_work(first_calls(silent, batch))
    noted = [site for site, *_ in reading._step_collectives._noted]
    assert noted == ["trainer.fused_step" if fused else "trainer.train_step"]
    assert not texts

    # collected: jit hands the signature's own lowering and executable back
    # (nothing is built), the text is read once, the gauges are filled
    snap = {}
    assert _jit_work(lambda: snap.update(reading.metrics.snapshot())) == \
        {"lowerings": 0, "compiles": 0}
    assert len(texts) == 1 and "all-reduce" in texts[0]
    assert snap['trainer_step_collectives{form="async"}'] == 0
    assert snap['trainer_step_collectives{form="sync"}'] >= 1
    assert snap['trainer_step_collective_bytes{form="async"}'] == 0
    # every parameter's gradient crosses: at least the parameters' bytes
    n_bytes = sum(int(v.size) * v.dtype.itemsize
                  for v in reading.params.values())
    assert snap['trainer_step_collective_bytes{form="sync"}'] >= n_bytes
    again = reading.metrics.snapshot()
    assert len(texts) == 1
    assert {k: v for k, v in again.items() if "collective" in k} == \
        {k: v for k, v in snap.items() if "collective" in k}
    assert not [k for k in silent.metrics.snapshot() if "collective" in k]


@pytest.mark.parametrize("mesh", ["none", "cpu data:4"])
def test_no_options_no_collective_gauges(mesh):
    """The gauges exist where `step_compile_options` asks for something: a
    Trainer without a mesh, or on a CPU mesh, keeps no shapes and has
    neither."""
    tr, batch = _lm_trainer(None if mesh == "none" else _cpu_mesh())
    assert tr._step_collectives is None
    tr.train_one_batch(batch)
    assert not [k for k in tr.metrics.snapshot() if "collective" in k]
