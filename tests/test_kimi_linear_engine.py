"""Kimi-Linear through a real ServingEngine at the tiny size of
tests/test_kimi_linear.py (a file of its own because `--dist loadfile` gives
one file to one worker): the shared engine tests of tests/model_parity.py
over its case — chunked prefill then decode, the step kernel interpreted, the
scanned step, free rows for a whole prompt (one segment of
`kda.segment_rows`), checkpoint and restore, the refusals,
tools/serve.py:build_engine — and what the recurrent state forced first
here: preempt-and-replay, and no run mask for a model without it."""

from tests.model_parity import (  # noqa: F401
    CASES, ROOT, case, check_against_lm_generate, engines, model,
    pytest_generate_tests, ref, requests,
    test_build_engine_serves_the_model_in_bf16,
    test_checkpoint_and_restore_round_trip_the_slot_parts,
    test_engine_serves_lm_generates_tokens,
    test_what_needs_a_state_snapshot_is_refused_by_name)

CASE = CASES["kimi_linear"]


def test_preempt_and_replay_gives_the_tokens_of_an_undisturbed_run(model,
                                                                   engines):
    """With no prefix index the victim prefills again from position 0 and
    its state is rebuilt: correct, and slow."""
    import jax
    _, ex, w = model
    reqs = requests((11, 14, 7), max_new=8)
    with jax.default_matmul_precision("highest"):
        eng = engines(ex, w)
        preempted = eng.n_preemptions
        for r in reqs:
            eng.add_request(r)
        for _ in range(7):
            eng.step()
        victim = max((s for s in range(2) if eng.slots[s] is not None),
                     key=lambda s: eng.slots[s].admit_seq)
        assert eng.slots[victim].gen > 0        # mid-decode
        eng._preempt(victim)
        results = eng.run()
        assert eng.n_preemptions == preempted + 1
        check_against_lm_generate(ex, w, reqs, results)
    eng.kv.check_reclaimed()


def test_models_without_recurrent_layers_hand_their_layers_no_run_mask(
        monkeypatch):
    """The existing configurations' step programs gain no operand: the
    state a K/V layer is handed holds what it held before."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.graph import GraphExecutor
    from paddle_tpu.serving import ServingEngine
    monkeypatch.chdir(ROOT)
    pc = parse_config("benchmark/configs/starcoder2.py",
                      "vocab=64,dim=32,layers=1,heads=4,kv_heads=2,"
                      "ffn=64,batch_size=1,compute_dtype=,attn_impl=dense")
    ex = GraphExecutor(pc.model_config, compute_dtype="")
    eng = ServingEngine(ex, ex.init_params(jax.random.PRNGKey(0)),
                        num_slots=2, page_size=4, max_context=16)
    eng._sync_device_state()
    st = eng._layer_state(eng._build_state(), jnp.ones((2,), bool),
                          page_table=eng._d_table[:2], pos=eng._d_pos)
    (name, got), = st.items()
    assert set(got) == {"k_pages", "v_pages", "page_table", "pos"}
    assert not eng._recurrent and eng.prefix is not None
    assert eng.kv.slot_state_bytes == 0
