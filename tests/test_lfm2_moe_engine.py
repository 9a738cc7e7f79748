"""LFM2-MoE through a real ServingEngine at the tiny size of
tests/test_lfm2_moe.py (a file of its own because `--dist loadfile` gives one
file to one worker): the shared engine tests of tests/model_parity.py over
its case — chunked prefill then decode, the packed pool through the
interpreted kernel, checkpoint and restore, the refusals,
tools/serve.py:build_engine — and what is this model's own: the expert block
forced onto its grouped form."""

import numpy as np

from tests.model_parity import (  # noqa: F401
    CASES, case, engines, model,
    pytest_generate_tests, ref, requests,
    test_build_engine_serves_the_model_in_bf16,
    test_checkpoint_and_restore_round_trip_the_slot_parts,
    test_engine_serves_lm_generates_tokens,
    test_what_needs_a_state_snapshot_is_refused_by_name)

CASE = CASES["lfm2_moe"]


def test_engine_serves_the_same_tokens_on_the_grouped_form(model,
                                                            monkeypatch):
    """The expert block forced onto its grouped form (the rule's constant
    lowered: every row count passes the ridge; 4 slots an expert, so the
    steps run one round to several; all 16 experts held) serves
    the greedy tokens the dense form serves, and
    `serving_moe_grouped_steps_total{kind}` counts every step landed — none
    where the rule keeps the dense form."""
    import jax
    from paddle_tpu.obs.metrics import counter_key, process_counters
    from paddle_tpu.parallel import moe
    from paddle_tpu.serving import ServingEngine
    _, ex, w = model
    keys = {k: counter_key("serving_moe_grouped_steps_total", kind=k)
            for k in ("decode", "mixed")}
    traced, grouped_form = [], moe._experts_grouped
    monkeypatch.setattr(moe, "_experts_grouped", lambda x, *a, **kw: (
        traced.append(x.shape[0]), grouped_form(x, *a, **kw))[1])

    def serve():
        before = process_counters().snapshot()
        reqs = requests(CASE.prompts)
        with jax.default_matmul_precision("highest"):
            eng = ServingEngine(ex, w, num_slots=2, page_size=4,
                                max_context=48, prefill_chunk=5)
            results = eng.run(reqs)
        after = process_counters().snapshot()
        return eng, results, {k: after.get(key, 0) - before.get(key, 0)
                              for k, key in keys.items()}

    dense, want, counted = serve()
    assert dense.moe_grouped_steps == {} and not any(counted.values())
    assert not traced
    monkeypatch.setattr(moe, "_GROUPED_OVER_RIDGE", 0.0)
    monkeypatch.setattr(moe, "_GROUP_SLOTS", 4)
    grouped, got, counted = serve()
    # the step programs themselves were traced to it: the decode step at
    # the slots' rows, the mixed step at its token budget
    assert {len(grouped.slots), grouped.max_step_tokens} <= set(traced)
    assert set(got) == set(want)
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
    assert grouped.moe_steps == dense.moe_steps > 0
    assert counted == grouped.moe_grouped_steps
    assert counted["mixed"] == grouped.n_mixed_steps > 0
    assert counted["decode"] == grouped.moe_steps - counted["mixed"] > 0



def test_overflow_counters_equal_a_count_from_the_routed_ids(model,
                                                             monkeypatch):
    """The first round forced small (tiles of 2 slots and one mean load:
    4 slots an expert in a 16-row mixed step, 2 in a decode step) so that
    steps overflow: the
    step's overflow tiles and the busiest expert of its busiest LAYER, as
    the one read-back banks them, equal a numpy count from the ids each
    layer's call routed, step by step — and the tokens are the dense
    form's.  (A decode step hands its layers no `live` rows: its idle
    slots' rows are routed and may fill a tile, and are not counted among
    the pairs, so its maximum may read under the routed ids'.)"""
    import jax
    from paddle_tpu.obs.metrics import process_counters
    from paddle_tpu.parallel import moe
    from paddle_tpu.serving import ServingEngine
    _, ex, w = model
    names = ("serving_moe_overflow_tiles_total",
             "serving_moe_layer_pairs_max_total")

    def serve(log=None):
        reqs = requests(CASE.prompts)
        with jax.default_matmul_precision("highest"):
            eng = ServingEngine(ex, w, num_slots=4, page_size=4,
                                max_context=48, prefill_chunk=12)
            if log is not None:
                count = eng._count_moe

                def counting(nxt, n_rows, kind):
                    was = eng.moe_overflow_tiles, eng.moe_layer_pairs_max_sum
                    out = count(nxt, n_rows, kind)
                    log.append((kind, eng.moe_overflow_tiles - was[0],
                                eng.moe_layer_pairs_max_sum - was[1]))
                    return out
                eng._count_moe = counting
            return eng, eng.run(reqs)

    dense, want = serve()
    assert dense.moe_overflow_tiles == 0
    assert 0 < dense.moe_layer_pairs_max_sum <= dense.moe_pairs_max_sum
    monkeypatch.setattr(moe, "_GROUPED_OVER_RIDGE", 0.0)
    monkeypatch.setattr(moe, "_GROUP_SLOTS", 2)
    monkeypatch.setattr(moe, "_FIRST_ROUND_OVER_MEAN", 1.0)
    calls, grouped_form = [], moe._experts_grouped

    def routed(idx, valid):
        calls.append((np.asarray(idx), np.asarray(valid)))

    def all_rows(idx):
        calls.append((np.asarray(idx), np.ones(len(idx), bool)))

    def recording(x, experts, idx, weight, first_expert, activation, valid,
                  n_experts):
        assert first_expert == 0 and experts[0].shape[0] == n_experts
        if valid is None:
            jax.debug.callback(all_rows, idx, ordered=True)
        else:
            jax.debug.callback(routed, idx, valid, ordered=True)
        return grouped_form(x, experts, idx, weight, first_expert,
                            activation, valid, n_experts)
    monkeypatch.setattr(moe, "_experts_grouped", recording)
    before = process_counters().snapshot()
    landed = []
    grouped, got = serve(landed)
    jax.effects_barrier()
    after = process_counters().snapshot()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])

    layers = len(grouped._moe_layers)
    assert layers > 1 and len(calls) == layers * len(landed) > 0
    for step, (kind, landed_tiles, landed_busiest) in enumerate(landed):
        tiles, busiest = 0, 0
        for idx, valid in calls[step * layers:(step + 1) * layers]:
            sizes = np.bincount(idx[valid].reshape(-1), minlength=16)
            first = moe.first_round_slots(*idx.shape, 16)
            tiles += int(np.sum(-(-np.maximum(sizes - first, 0) // 2)))
            busiest = max(busiest, int(sizes.max()))
        assert landed_tiles == tiles, (step, kind)
        assert landed_busiest == busiest if kind == "mixed" \
            else 0 < landed_busiest <= busiest, (step, kind)
    assert {"mixed", "decode"} == {kind for kind, _, _ in landed}
    assert sum(t for _, t, _ in landed) == grouped.moe_overflow_tiles > 0
    assert [after[n] - before.get(n, 0) for n in names] == \
        [grouped.moe_overflow_tiles, grouped.moe_layer_pairs_max_sum]
