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

