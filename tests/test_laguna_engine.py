"""Laguna through a real ServingEngine at the tiny size of
tests/test_laguna.py (a file of its own because `--dist loadfile` gives one
file to one worker): window layers whose pools are RINGS of pages beside
full layers under the logical table — chunked prefill through mixed steps
then decode with contexts of several rings, every served token
lm_generate's and the argmax of the reference's ONE full forward; the
windowed kernel interpreted; free rows for a whole prompt; an overcommitted
pool that preempts and replays; checkpoint and restore; what refuses a ring
by name; the two kinds' pages, bytes and counters in `stats` and the metrics
text; tools/serve.py:build_engine."""

import numpy as np
import pytest

from tests.model_parity import (  # noqa: F401
    CASES, ROOT, build, case, cfg, check_against_lm_generate, counted,
    engines, margin, model, pytest_generate_tests, ref, requests,
    serve_argv, serve_tool)

CASE = CASES["laguna"]
WINDOW_LAYERS = ("blk1_attn", "blk2_attn", "blk3_attn")
# window 8, pages of 4, a share of 5 rows + 2 slots = 7 rows a step:
# ceil((8 + 7) / 4) + 1 = 5 pages a slot
RING = 5


def test_engine_serves_lm_generates_tokens_through_the_rings(
        case, model, ref, engines, engine_case, monkeypatch):
    """The shared engine test's flow with rings in it: prompts of 3 to 26
    tokens and 6 new ones (contexts to 32 = 1.6 rings of 20 tokens) through
    chunks of 5 rows, the interpreted kernels, a step with free rows for a
    whole prompt — lm_generate's tokens, each the reference's argmax; the
    prefix index is off; every page is back; the rings recycled pages."""
    import jax
    e = engine_case
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1" if e.kernel else "0")
    c, ex, w = model
    if e.build:
        ex = build(case, c, **e.build)
    reqs = requests(case.prompts)
    with jax.default_matmul_precision("highest"):
        eng = engines(ex, w, max_context=case.max_context,
                      prefill_chunk=e.chunk, max_step_tokens=e.mst)
        # a step of 34 rows would size the ring at a whole context: the
        # window layers stay under the logical table there, and nothing of
        # the prefix index is refused
        ringed = e.mst is None
        assert (eng.prefix is None) == ringed
        assert set(eng.kv.ring_specs) == set(WINDOW_LAYERS if ringed else ())
        before = counted(eng)
        recycled, rows = eng.n_window_pages_recycled, eng.n_window_rows
        results = eng.run(reqs)
        check_against_lm_generate(ex, w, reqs, results)
    eng.kv.check_reclaimed()
    m = margin(ref, c, w, reqs, results)
    assert m["worst_nats"] < case.tol and m["tokens"] == 30, m
    n = counted(eng, before)
    assert n["moe_steps"] == n["n_decode_steps"] > 0
    # every row of every step went through the three window layers; the
    # two prompts past a ring's size (and their outputs) recycled pages
    if not ringed:
        assert (eng.n_window_rows, eng.n_window_pages_recycled) \
            == (rows, recycled)
        return
    done = sum(case.prompts) + len(reqs) * (reqs[0].max_new - 1)
    assert eng.n_window_rows - rows == 3 * done
    want = 3 * sum(max(0, -(-(p + reqs[0].max_new - 1) // 4) - RING)
                   for p in case.prompts)
    assert eng.n_window_pages_recycled - recycled == want > 0


def test_pools_by_kind_and_the_rings_geometry(model, engines):
    """A window layer's pool has 1 + slots x ring_pages pages, a full
    layer's the allocator's; the ring table is static; bytes by kind add up
    to `pool_bytes`; at the cell's flags the function the configuration
    file's `departures` quotes gives 4.295 + 0.667 GB."""
    import json
    from benchmark.lib import window_moe
    _, ex, w = model
    eng = engines(ex, w)
    kv = eng.kv
    assert kv.ring_specs == {n: RING for n in WINDOW_LAYERS}
    assert kv.step_tokens == eng.max_step_tokens == 7
    for n in WINDOW_LAYERS:
        assert {a.shape[0] for a in kv.pools[n].values()} == {1 + 2 * RING}
        t = kv.ring_table(n)
        assert t.shape == (3, RING) and not t[2].any()
        assert sorted(t[:2].ravel()) == list(range(1, 1 + 2 * RING))
    for n in ("blk0_attn", "blk4_attn"):
        assert {a.shape[0] for a in kv.pools[n].values()} == {kv.num_pages}
    by = kv.pool_bytes_by_kind
    page = 4 * 2 * 16 * 4                      # 4 tokens x 2 heads x 16 x f32
    assert by == {"full": 2 * 2 * kv.num_pages * page,
                  "window": 3 * 2 * (1 + 2 * RING) * page}
    assert by["full"] + by["window"] == kv.pool_bytes
    assert kv.ring_pages_for(8) == RING < kv.pages_per_slot
    with open(CASE.json_path) as f:
        cell = json.load(f)
    pools = window_moe.pool_bytes(cell)
    assert window_moe.ring_pages(cell) == 53
    assert (round(pools["full"] / 1e9, 3), round(pools["window"] / 1e9, 3)) \
        == (4.295, 0.667)
    assert any("4.295 GB" in d and "0.667 GB" in d
               for d in cell["departures"])


def test_an_overcommitted_pool_preempts_and_the_rings_stay_exact(model):
    """Too few pages for every slot's context: the youngest slot is
    preempted and replayed — its ring is simply written again from
    position 0 — and the tokens are lm_generate's; release, preemption and
    the end of the run leave `check()` clean."""
    import jax
    from paddle_tpu.serving import ServingEngine
    _, ex, w = model
    reqs = requests((19, 17, 26), max_new=10)
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                            prefill_chunk=5, num_pages=13)
        results = eng.run(reqs)
        check_against_lm_generate(ex, w, reqs, results)
    assert eng.n_preemptions > 0
    eng.kv.check_reclaimed()
    assert eng.kv.uncommit_tail(0, 0) == 0      # nothing of a ring to undo
    eng.kv.release(0)
    eng.kv.check()


def test_checkpoint_and_restore_carry_the_rings(model, engines):
    """A run frozen mid-flight, past a lap of the ring, and resumed on a
    fresh engine finishes with the undisturbed run's tokens."""
    import jax
    from paddle_tpu.serving import ServingEngine
    _, ex, w = model
    reqs = requests((19, 26), max_new=8)
    with jax.default_matmul_precision("highest"):
        a = engines(ex, w)
        for r in reqs:
            a.add_request(r)
        for _ in range(9):
            a.step()
        snap = a.checkpoint_state()
        b = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                          prefill_chunk=5)
        b.restore_state(snap)
        for n in WINDOW_LAYERS:
            for part, arr in b.kv.pools[n].items():
                assert arr.shape[0] == 1 + 2 * RING
                assert bool((np.asarray(arr) == snap["pools"][n][part]).all())
        check_against_lm_generate(ex, w, reqs, b.run())
        a.run()                 # the engine goes back idle


REFUSED = ("prefix", "spill", "spill_later", "spec", "spec_later", "export",
           "import", "role", "chunking")


@pytest.mark.parametrize("refused", REFUSED)
def test_what_needs_the_whole_context_in_pages_is_refused_by_name(
        model, refused):
    """Each mechanism that assumes the pages ARE the context raises for a
    model with rings, where it is asked for or set later, through the
    recurrent models' one function, with RING_REFUSALS' sentence; and a
    step budget past what the rings were sized for is refused too."""
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.paged_kv import RECURRENT_REFUSALS, RING_REFUSALS
    _, ex, w = model

    def engine(**kw):
        # a share of 5 rows: rings of 5 or 6 pages in contexts of 8 (the
        # default share of 16 would size them at a whole context: no ring)
        return ServingEngine(ex, w, num_slots=2, page_size=4,
                             max_context=32, prefill_chunk=5, **kw)

    with pytest.raises(ValueError) as e:
        if refused == "prefix":
            engine().set_prefix_cache(True)
        elif refused == "spill":
            engine(spill_bytes_budget=1 << 20)
        elif refused == "spill_later":
            engine().set_spill_budget(1 << 20)
        elif refused == "spec":
            engine(spec_k=2)
        elif refused == "spec_later":
            engine().set_speculation(2)
        elif refused == "export":
            engine().export_prefix([1, 2, 3, 4])
        elif refused == "import":
            engine().import_prefix([1, 2, 3, 4], {"n_pages": 1}, b"")
        elif refused == "role":
            from paddle_tpu.serving.server import ServingServer
            ServingServer(engine(), role="prefill")
        else:
            engine().set_chunking(5, max_step_tokens=12)
    msg = str(e.value)
    if refused == "chunking":
        assert "rings of pages were sized" in msg, msg
        return
    mech = refused.removesuffix("_later")
    assert RECURRENT_REFUSALS[mech][0] in msg and RING_REFUSALS[mech] in msg
    assert "window layers held as rings of pages (3 here)" in msg, msg


def test_a_sharded_engine_shards_the_rings_on_their_kv_heads(model):
    """`--mesh model=2`: a ring's pool shards on its K/V-head axis like a
    full layer's, and the tokens are the unsharded engine's."""
    import jax
    from paddle_tpu.parallel.mesh import model_mesh
    from paddle_tpu.serving import ServingEngine
    c, _, w = model
    reqs = requests((9, 19), max_new=6)
    with jax.default_matmul_precision("highest"):
        ex = build(CASE, c)
        eng = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                            prefill_chunk=5, mesh=model_mesh(2))
        results = eng.run(reqs)
        plain = ServingEngine(build(CASE, c), w, num_slots=2, page_size=4,
                              max_context=48, prefill_chunk=5).run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(results[r.req_id], plain[r.req_id])
    pool = eng.kv.pools[WINDOW_LAYERS[0]]["k"]
    assert pool.shape[0] == 1 + 2 * RING
    assert pool.sharding.spec[2] == "model"
    eng.kv.check_reclaimed()


def test_stats_and_metrics_hold_the_two_kinds(model, engines):
    """`stats` and the metrics text: the pools' bytes by kind, the rings'
    pages a slot, pages resident by kind (a gauge), pages recycled and rows
    through window layers (counters), every family with HELP and TYPE."""
    import jax
    from paddle_tpu.serving.server import ServingServer
    _, ex, w = model
    eng = engines(ex, w)
    with jax.default_matmul_precision("highest"):
        for r in requests((26, 19), max_new=6):
            eng.add_request(r)
        for _ in range(8):
            eng.step()
        live = eng.kv_pages_resident()
        # both slots hold more tokens than a page: the full layers' pages
        # and, capped at the ring's size, the rings'
        assert 0 < live["window"] <= 2 * RING and live["full"] > 0
        assert live["full"] == eng.kv.pages_in_use
        eng.run()
    assert eng.kv_pages_resident() == {"full": 0, "window": 0}
    srv = ServingServer(eng)
    st = srv._engine_stats()
    assert st["kv_pool_bytes_by_kind"] == eng.kv.pool_bytes_by_kind
    assert st["ring_pages"] == {n: RING for n in WINDOW_LAYERS}
    assert st["window_pages_recycled"] == eng.n_window_pages_recycled > 0
    assert st["window_rows"] == eng.n_window_rows > 0
    # the full layers' call: its rows, and those on a tile's shared walk
    # (none here: a step is under a tile's 8 rows of one slot)
    assert st["kv_rows"] == eng.n_kv_rows > 0
    assert st["kv_shared_rows"] == eng.n_kv_shared_rows < eng.n_kv_rows
    assert st["attn_gated_layers"] == 5
    text = srv.metrics.render()
    for family in ("serving_window_pages_recycled_total",
                   "serving_window_rows_total", "serving_window_steps_total",
                   "serving_kv_rows_total", "serving_kv_shared_rows_total",
                   "serving_kv_pages_resident", "serving_kv_pool_bytes"):
        assert f"# HELP {family}" in text and f"# TYPE {family}" in text
    assert 'serving_kv_pages_resident{kind="window"} 0' in text
    assert 'serving_kv_pool_bytes{kind="window"} %d' % \
        eng.kv.pool_bytes_by_kind["window"] in text


def test_build_engine_serves_the_model_in_bf16(case, monkeypatch):
    """tools/serve.py:build_engine, no flag of the model's own: bf16
    parameters and pools, rings for the window layers, and a flag that
    needs the whole context in pages refused from the command line."""
    from paddle_tpu.serving import Request
    monkeypatch.chdir(ROOT)
    tool, parse = serve_tool()
    argv = serve_argv(case, cfg(case), "--prefill-chunk", "8",
                      "--param-dtype", "bfloat16")
    eng = tool.build_engine(parse(argv))
    assert {str(v.dtype) for v in eng.params.values()} == {"bfloat16"}
    for n, row in case.paged.items():
        for pool in eng.kv.pools[n].values():
            assert pool.shape[2:] == row and str(pool.dtype) == "bfloat16"
    # window 8 + (8 + 2 slots) rows a step: 5 pages and one more
    assert eng.kv.ring_specs == {n: 6 for n in WINDOW_LAYERS}
    out = eng.run([Request("a", np.asarray([3, 5, 7], np.int32), max_new=4)])
    assert len(out["a"]) == 7
    with pytest.raises(ValueError, match="rings of pages"):
        tool.build_engine(parse(argv + ["--spec-k", "2"]))
