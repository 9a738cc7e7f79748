"""The weights a serving step reads: cast once when `params` is set.

The contract (docs/serving.md "Weights a step reads"): `ServingEngine.params`
is the weight tree exactly as given; the compiled steps are handed a tree
derived from it ONCE, in the executor's compute dtype, by the predicate
`GraphExecutor.prepare` applies inside a step (`cast_params`).  So an
engine given float32 weights under bfloat16 compute serves token for token
what an engine given the same tree cast by hand serves, its step programs
hold no cast of a weight, and where the dtypes already agree the derived
leaves ARE the given leaves."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.config.parser import parse_config
from paddle_tpu.parameter.argument import Argument
from paddle_tpu.obs.hbm import hbm_collector, hbm_snapshot, tree_bytes
from paddle_tpu.obs.metrics import process_counters
from paddle_tpu.parallel.mesh import model_mesh
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.trainer.trainer import Trainer

VOCAB = 61
ARGS = f"vocab={VOCAB},dim=32,layers=2,heads=4,batch_size=4"
KW = dict(num_slots=3, page_size=8, max_context=64)
CASTS = "serving_step_weight_casts_total"
CAST_BYTES = "serving_step_weight_cast_bytes_total"


def _make(compute_dtype: str = "bfloat16", seed: int = 7):
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       f"{ARGS},compute_dtype={compute_dtype}")
    return Trainer(cfg, seed=seed)


@pytest.fixture(scope="module")
def tr():
    return _make()


def _by_hand(params, dtype=jnp.bfloat16):
    return {k: (v.astype(dtype) if jnp.issubdtype(v.dtype, jnp.floating)
                else v) for k, v in params.items()}


def _reqs(seed: int = 1):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(2, VOCAB, n).astype(np.int32), max_new=m)
            for i, (n, m) in enumerate(zip((3, 19, 5, 12, 7), (6, 5, 9, 4, 8)))]


def _counters():
    snap = process_counters().snapshot()
    return snap.get(CASTS, 0), snap.get(CAST_BYTES, 0)


def _same(a: dict, b: dict, what: str):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"{what}: {k!r}")


STEP_KINDS = {
    # pure-decode steps between admissions, whole prompts in one chunk
    "decode": (dict(prefill_chunk=32), "n_decode_steps"),
    # small chunks: most steps carry chunk rows beside decode rows
    "mixed": (dict(prefill_chunk=4, max_step_tokens=8), "n_mixed_steps"),
}


@pytest.mark.parametrize("kind", sorted(STEP_KINDS))
def test_fp32_given_serves_what_the_tree_cast_by_hand_serves(tr, kind):
    kw, ran = STEP_KINDS[kind]
    given = ServingEngine(tr.executor, tr.params, **KW, **kw)
    by_hand = ServingEngine(tr.executor, _by_hand(tr.params), **KW, **kw)
    _same(by_hand.run(_reqs()), given.run(_reqs()), kind)
    assert getattr(given, ran) > 0
    assert given.step_weight_bytes > 0 and by_hand.step_weight_bytes == 0


def _lower(eng, kind: str, params):
    """The step programs as the pump dispatches them (tools/
    hlo_shard_check.py), with `params` in the weights' place."""
    eng.add_request(Request("probe", np.arange(2, 7, dtype=np.int32),
                            max_new=4))
    eng.step()
    eng._sync_run_mask([s for s in range(len(eng.slots))
                        if eng.slots[s] is not None])
    eng._sync_device_state()
    if kind == "decode":
        return eng._decode_step.lower(params, eng._build_state(),
                                      eng._d_run).as_text()
    T, S = eng.max_step_tokens, len(eng.slots)
    z = np.zeros(T, np.int32)
    return eng._mixed_step.lower(
        params, eng._build_state(), eng._stage(z),
        eng._stage(np.full(T, S, np.int32)), eng._stage(z),
        eng._stage(np.zeros(S, np.int32)), eng._stage(np.zeros(S, np.int32)),
        eng._stage(np.zeros(S, bool))).as_text()


def _weight_casts(text: str, params) -> list[str]:
    """`convert` ops from f32 to bf16 whose shape is a parameter's."""
    shapes = {"x".join(map(str, v.shape)) for v in params.values()}
    pat = re.compile(r"convert.*tensor<([0-9x]+)xf32>\) -> tensor<\1xbf16>")
    return [m.group(1) for m in map(pat.search, text.splitlines())
            if m and m.group(1) in shapes]


@pytest.mark.parametrize("kind", ["decode", "mixed"])
def test_the_lowered_step_takes_bf16_weights_and_casts_none(tr, kind):
    eng = ServingEngine(tr.executor, tr.params, **KW)
    floating = [k for k, v in tr.params.items()
                if jnp.issubdtype(v.dtype, jnp.floating)]
    assert all(eng._step_params[k].dtype == jnp.bfloat16 for k in floating)
    text = _lower(eng, kind, eng._step_params)
    assert _weight_casts(text, tr.params) == []
    # the control: handed the given tree, the same program casts every
    # floating weight itself (what each step did before), so the pattern
    # sees a weight's cast where there is one
    given = _weight_casts(_lower(eng, kind, eng.params), tr.params)
    assert len(given) >= len({tuple(tr.params[k].shape) for k in floating})


def test_assignment_derives_again_and_reads_back_what_was_given(tr):
    other = _make(seed=11).params
    # no prefix index: pages cached under the first weights would answer
    # for the second (an assignment leaves the cache's contents alone)
    eng = ServingEngine(tr.executor, tr.params, prefix_cache=False, **KW)
    eng.run(_reqs())
    eng.params = other
    assert eng.params is other
    assert all(v.dtype == other[k].dtype for k, v in eng.params.items())
    fresh = ServingEngine(tr.executor, other, **KW)
    _same(fresh.run(_reqs(2)), eng.run(_reqs(2)), "after the assignment")
    first = ServingEngine(tr.executor, tr.params, **KW).run(_reqs(2))
    assert any(not np.array_equal(first[k], v)
               for k, v in fresh.run(_reqs(2)).items())


@pytest.mark.parametrize("compute_dtype,given", [
    ("bfloat16", jnp.bfloat16), ("", jnp.float32), ("", jnp.bfloat16)])
def test_agreeing_dtypes_share_every_leaf_and_count_nothing(
        compute_dtype, given):
    t = _make(compute_dtype)
    w = _by_hand(t.params, given)
    before = _counters()
    eng = ServingEngine(t.executor, w, **KW)
    eng.params = w
    assert all(eng._step_params[k] is v for k, v in w.items())
    assert eng.step_weight_bytes == 0
    assert _counters() == before
    assert eng.run(_reqs())


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
def test_derived_leaves_keep_their_sources_shardings():
    t = _make()
    t.executor.mesh = None
    try:
        eng = ServingEngine(t.executor, t.params, mesh=model_mesh(2), **KW)
        sharded = 0
        for k, v in eng.params.items():
            d = eng._step_params[k]
            assert d.sharding == v.sharding == eng._param_shardings_tree[k]
            sharded += not v.sharding.is_fully_replicated
        assert sharded > 0
        single = ServingEngine(_make().executor, t.params, **KW)
        _same(single.run(_reqs()), eng.run(_reqs()), "model=2")
    finally:
        t.executor.mesh = None


def test_cast_params_is_the_cast_prepare_makes(tr):
    ex = tr.executor
    feed = {"x": Argument(value=jnp.ones((2, 3), jnp.float32))}
    derived = ex.cast_params(tr.params)
    inside, cast_feed = ex.prepare(tr.params, feed)
    again, _ = ex.prepare(derived, feed)
    assert cast_feed["x"].value.dtype == jnp.bfloat16
    for k, v in tr.params.items():
        assert derived[k].dtype == inside[k].dtype == again[k].dtype
        np.testing.assert_array_equal(np.asarray(derived[k], np.float32),
                                      np.asarray(inside[k], np.float32))
        # a leaf already in the compute dtype is handed on, not copied
        assert again[k] is derived[k]
        if not jnp.issubdtype(v.dtype, jnp.floating):
            assert derived[k] is v
    plain = _make("").executor
    assert plain.cast_params(tr.params) is tr.params


def test_counters_and_gauge_read_what_the_tree_sizes_say(tr):
    floating = sum(v.size for v in tr.params.values()
                   if jnp.issubdtype(v.dtype, jnp.floating))
    assert tree_bytes(tr.params) >= 4 * floating
    casts, nbytes = _counters()
    eng = ServingEngine(tr.executor, tr.params, **KW)
    assert eng.step_weight_bytes == 2 * floating
    assert _counters() == (casts + 1, nbytes + 2 * floating)
    eng.params = _make(seed=11).params
    assert eng.step_weight_bytes == 2 * floating
    assert _counters() == (casts + 2, nbytes + 4 * floating)
    snap = hbm_snapshot(params=eng.params, kv=eng.kv,
                        step_weight_bytes=eng.step_weight_bytes)
    assert snap["step_weight_bytes"] == 2 * floating
    assert snap["param_bytes"] == tree_bytes(eng.params)
    assert "step_weight_bytes" not in hbm_snapshot(params=eng.params)
    gauges = {name: v for name, _kind, _labels, v in hbm_collector(
        params_fn=lambda: eng.params,
        step_weight_bytes_fn=lambda: eng.step_weight_bytes)()}
    assert gauges["hbm_step_weight_bytes"] == 2 * floating
    assert gauges["hbm_param_bytes"] == tree_bytes(eng.params)
