"""Tensor-parallel sharded decode exactness oracles.

The contract (docs/serving.md "Sharded decode"): a `ServingEngine` over a
mesh whose `model` axis shards attention heads and the KV page pools is
TOKEN-FOR-TOKEN identical to the single-device engine — and therefore to
the per-request `lm_generate(use_cache=True)` oracle — across every
sampling knob, prefix-cache hits, chunked mixed steps, and preempt/replay,
while holding the sacred signature set (ONE compiled decode step + ONE
mixed step per token budget).  Runs on the conftest 8-virtual-CPU-device
mesh (`--xla_force_host_platform_device_count`), the same harness as the
dp-parity tests: SPMD partitioning decisions are backend-agnostic, so the
collective structure (and the exactness) is the evidence a single real
chip cannot provide."""

import numpy as np
import pytest

import jax

from paddle_tpu.config.parser import parse_config
from paddle_tpu.parallel.mesh import model_mesh
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.trainer.trainer import Trainer

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 devices (conftest provides 8 host devices)")


def _make(args: str):
    cfg = parse_config("demo/model_zoo/transformer_lm.py", args)
    return Trainer(cfg, seed=7)


def _prompts(lens, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, n).astype(np.int32) for n in lens]


def _tp_engine(tr, n: int, **kw) -> ServingEngine:
    # each engine owns the executor's mesh for its lifetime — reset it so
    # a later single-device engine (or another shard count) starts clean
    tr.executor.mesh = None
    return ServingEngine(tr.executor, tr.params,
                         mesh=model_mesh(n) if n > 1 else None, **kw)


def _assert_same_results(base: dict, tp: dict, label: str) -> None:
    assert set(base) == set(tp)
    for k in base:
        np.testing.assert_array_equal(
            base[k], tp[k],
            err_msg=f"request {k!r} diverged between single-device and "
                    f"{label} decode")


def test_tp2_and_tp4_match_single_device_across_sampling_knobs():
    """All four sampling modes (greedy / top-k / nucleus / full), mixed
    prompt lengths, chunked prefill on (the default): model=2 and model=4
    shards produce the exact token streams of the single-device engine,
    through ONE decode + ONE mixed signature each."""
    tr = _make("vocab=61,dim=32,layers=2,heads=4,batch_size=4")
    prompts = _prompts((3, 9, 5, 12), 61, seed=1)
    knobs = [dict(),                                     # greedy
             dict(temperature=0.8, top_k=5),
             dict(temperature=0.7, top_p=0.9),
             dict(temperature=1.1)]                      # full sampling

    def reqs():
        return [Request(i, p, max_new=6, rng=jax.random.PRNGKey(100 + i),
                        **kw)
                for i, (p, kw) in enumerate(zip(prompts, knobs))]

    kw = dict(num_slots=3, page_size=8, max_context=64)
    base = _tp_engine(tr, 1, **kw).run(reqs())
    for n in (2, 4):
        eng = _tp_engine(tr, n, **kw)
        _assert_same_results(base, eng.run(reqs()), f"model={n}")
        assert eng._decode_step._cache_size() == 1
        assert eng._mixed_step._cache_size() == 1
        assert eng.tp == n
        assert eng.kv.pool_bytes_per_shard == eng.kv.pool_bytes // n


def test_tp_gqa_grouped_heads_stay_exact():
    """Grouped-query attention under tensor parallelism: h_kv=2 over
    model=2 gives each device one kv head serving its two query heads —
    the pool's kv-head shard and the in-shard GQA expansion must
    reproduce the single-device tokens exactly."""
    tr = _make("vocab=97,dim=32,layers=2,heads=4,batch_size=4,kv_heads=2")
    prompts = _prompts((3, 9, 6), 97)
    kw = dict(num_slots=2, page_size=8, max_context=64)
    base = _tp_engine(tr, 1, **kw).run(
        [Request(i, p, max_new=6) for i, p in enumerate(prompts)])
    tp = _tp_engine(tr, 2, **kw).run(
        [Request(i, p, max_new=6) for i, p in enumerate(prompts)])
    _assert_same_results(base, tp, "model=2 (gqa)")


@pytest.mark.parametrize("n,row", [(2, (1, 128)), (4, (1, 64))])
def test_tp_heads_of_64_pack_whole_lane_tiles_a_shard(n, row):
    """Heads under 128 lanes are stored several a 128-lane tile
    (ops/pallas_paged.py:kv_row_shape) under a mesh too, wherever every
    shard gets whole tiles: 4 kv heads of 64 are 2 tiles, so model=2
    shards hold one tile (their own two heads, in order) and model=4
    shards one head each in the row as it was; both give the
    single-device (packed) engine's tokens."""
    tr = _make("vocab=97,dim=256,layers=2,heads=4,batch_size=4,kv_heads=4")
    prompts = _prompts((3, 9, 6), 97)
    kw = dict(num_slots=2, page_size=8, max_context=64)
    base_eng = _tp_engine(tr, 1, **kw)
    assert set(base_eng.kv.layer_specs.values()) == {(2, 128)}
    base = base_eng.run(
        [Request(i, p, max_new=6) for i, p in enumerate(prompts)])
    eng = _tp_engine(tr, n, **kw)
    pool = next(iter(eng.kv.paged_pools().values()))["k"]
    assert pool.addressable_shards[0].data.shape[2:] == row
    tp = eng.run([Request(i, p, max_new=6) for i, p in enumerate(prompts)])
    _assert_same_results(base, tp, f"model={n} (heads of 64)")
    assert eng.kv.pool_bytes_per_shard == eng.kv.pool_bytes // n


def test_tp_prefix_cache_hits_and_cow_stay_exact():
    """Prefix-cache hits under sharding: the second wave maps pages the
    first wave committed (including a mid-page COW boundary), and the
    suffix-only prefill + sharded pools still produce single-device
    tokens.  Both engines must actually HIT (same host-side tree walk —
    sharding is invisible to the allocator)."""
    tr = _make("vocab=61,dim=32,layers=2,heads=4,batch_size=4")
    rng = np.random.default_rng(5)
    shared = rng.integers(2, 61, 19).astype(np.int32)
    suffixes = [rng.integers(2, 61, n).astype(np.int32) for n in (4, 7, 3)]

    def waves():
        first = [Request("w0", shared.copy(), max_new=5)]
        second = [Request(f"s{i}", np.concatenate([shared, suf]), max_new=5)
                  for i, suf in enumerate(suffixes)]
        return first, second

    kw = dict(num_slots=2, page_size=8, max_context=64)
    engines = {1: _tp_engine(tr, 1, **kw), 2: _tp_engine(tr, 2, **kw)}
    results = {}
    for n, eng in engines.items():
        first, second = waves()
        results[n] = {**eng.run(first), **eng.run(second)}
        assert eng.n_prefix_hits > 0, f"model={n}: prefix cache never hit"
        eng.kv.check_reclaimed()
    _assert_same_results(results[1], results[2], "model=2 (prefix)")
    assert engines[1].n_prefix_hits == engines[2].n_prefix_hits
    assert engines[1].kv.n_cow == engines[2].kv.n_cow


def test_tp_overcommitted_pool_preempt_replay_stays_exact():
    """Preempt/replay under sharding: the overcommitted pool forces
    pauses and preemptions, whose deterministic replay must stay
    invisible in the sharded output exactly as in the single-device
    engine (same preemption count — scheduling is host-side and
    shard-independent)."""
    tr = _make("vocab=11,dim=16,layers=1,heads=2,batch_size=3")
    prompts = _prompts((6, 4, 5, 3, 6), 11, seed=3)
    kw = dict(num_slots=2, page_size=4, max_context=16, num_pages=6)
    base_eng = _tp_engine(tr, 1, **kw)
    base = base_eng.run([Request(i, p, max_new=8)
                         for i, p in enumerate(prompts)])
    assert base_eng.n_preemptions > 0, "pool was never overcommitted"
    tp_eng = _tp_engine(tr, 2, **kw)
    tp = tp_eng.run([Request(i, p, max_new=8)
                     for i, p in enumerate(prompts)])
    _assert_same_results(base, tp, "model=2 (preempt/replay)")
    assert tp_eng.n_preemptions == base_eng.n_preemptions
    tp_eng.kv.check_reclaimed()


def test_tp_head_divisibility_validated():
    """heads (and kv heads) must divide the model axis — a mesh the model
    cannot shard over is an actionable construction-time error, not a
    silent wrong answer."""
    tr = _make("vocab=31,dim=16,layers=1,heads=2,batch_size=4")
    with pytest.raises(ValueError, match="num_heads"):
        _tp_engine(tr, 4, num_slots=2, page_size=8, max_context=32)
