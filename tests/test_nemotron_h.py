"""Nemotron-H (Mamba-2 state-space layers + grouped-query attention without
rotation + bias-free relu^2 experts with a shared expert, every block ONE
mixer) against the plain reference (benchmark/reference/nemotron_h.py): the
shared parity tests of tests/model_parity.py over its case — the whole
sequence chunkwise (and without the `D x` term, which must fail), the decode
step and the ragged mixed step through the cache manager's slot parts (and
from a state rounded to bfloat16, which must fail), the parts declared by the
layer type, paused slots, re-admission — and what is this model's own: one
mixer a block, attention without rotation, and the other recurrent kinds'
pools after the lift into graph/slot_steps.py.  Its engines are
tests/test_nemotron_h_engine.py's."""

import os

import numpy as np
import pytest

from tests.model_parity import (  # noqa: F401
    CASES, ROOT, build, case, cfg, logits, model, nemotron_pattern,
    pytest_generate_tests, ref, ref_logits,
    test_a_paused_slots_parts_are_bit_equal_after_the_step,
    test_a_reused_slot_starts_from_zeros, test_layer_kinds_by_depth,
    test_ragged_chunks_then_decode_through_the_pools_on_logits,
    test_reference_imports_nothing_of_the_program,
    test_slot_parts_are_declared_by_the_layer_type,
    test_weights_fit_the_programs_parameters,
    test_whole_sequence_logits_against_the_reference)

CASE = CASES["nemotron_h"]


def test_every_block_is_one_norm_and_one_mixer(model):
    import jax
    c, ex, w = model
    kinds = {"M": "ssm", "E": "moe", "*": "attn"}
    for i, letter in enumerate(nemotron_pattern(c)):
        mine = {k.split(".")[0] for k in w if k.startswith(f"_blk{i}_")}
        assert mine == {f"_blk{i}_ln", f"_blk{i}_{kinds[letter]}"}, mine
    # in: z 64 + xBC 64 + 2 x 2 x 16 + dt 4; 4 taps and a bias over x, B, C
    assert w["_blk0_ssm.w0"].shape == (48, 64 + 128 + 4)
    assert w["_blk0_ssm.w1"].shape == (4, 128)
    assert w["_blk0_ssm.w2"].shape == (1, 128)
    assert abs(np.asarray(w["_blk0_ssm.w1"])).max() <= 0.5
    a_log = np.asarray(w["_blk0_ssm.w3"])
    assert a_log.min() >= 0 and a_log.max() <= np.log(16.0) + 1e-6
    assert bool((np.asarray(w["_blk0_ssm.w4"]) == 1).all())      # D
    dt = np.log1p(np.exp(np.asarray(w["_blk0_ssm.w5"], np.float64)))
    assert dt.min() >= 1e-3 * (1 - 1e-4) and dt.max() <= 0.1 * (1 + 1e-4)
    # bias-free non-gated experts: two stacks, no bias; the shared expert
    # of the same form; attention 4 heads of 16 beside a hidden size of 48
    assert w["_blk1_moe.w1"].shape == (16, 48, 24)
    assert w["_blk1_moe.w2"].shape == (16, 24, 48)
    assert w["_blk1_moe.w4"].shape == (48, 40)
    assert w["_blk1_moe.w5"].shape == (40, 48)
    assert w["_blk3_attn.w0"].shape == (48, 64)
    assert w["_blk3_attn.w3"].shape == (64, 48)
    # the program's own initializer draws the taps from the same range
    own = np.asarray(ex.init_params(jax.random.PRNGKey(1))["_blk0_ssm.w1"])
    assert 0.3 < abs(own).max() <= 0.5


def test_attention_applies_no_rotation_unless_the_file_says_so(model, ref):
    c, ex, w = model
    attn = next(l for l in ex.model.layers if l.name == "blk3_attn")
    assert "use_rope" not in attn.attrs and attn.attrs["num_kv_heads"] == 2
    assert attn.size == 64                 # the heads' width, not the model's
    rot = cfg(CASE, attn_use_rope=True)
    ex2 = build(CASE, rot)
    assert next(l for l in ex2.model.layers
                if l.name == "blk3_attn").attrs["use_rope"] is True
    seq = np.random.default_rng(1).integers(0, 64, 16)
    got = np.asarray(logits(ex2, w, seq[None])[0][0])
    assert float(np.abs(got - ref_logits(ref, rot, w, seq)).max()) < CASE.tol
    assert float(np.abs(got - ref_logits(ref, c, w, seq)).max()) > \
        50 * CASE.tol


def test_the_cache_manager_names_no_recurrent_kind():
    with open(os.path.join(ROOT, "paddle_tpu", "serving",
                           "paged_kv.py")) as f:
        code = f.read().split('"""', 2)[2]
    assert "mamba" not in code and '"state"' not in code


@pytest.mark.parametrize("kind", ["kimi_linear", "lfm2_moe"])
def test_the_other_recurrent_kinds_pools_are_what_they_were(kind):
    """The hybrid and the short-convolution models' slot parts and pool
    bytes in float32 after the lift into graph/slot_steps.py and
    ops/slot_rows.py: their cases' numbers (their logits and tokens through
    the lifted code are tests/test_kimi_linear.py's and
    tests/test_lfm2_moe.py's; that the KDA step still compiles to ONE kernel
    named `kda_step` is tests/test_mosaic_compile.py's)."""
    from paddle_tpu.serving import PagedKVCache
    other = CASES[kind]
    kv = PagedKVCache(build(other, cfg(other)), num_slots=3, page_size=4,
                      pages_per_slot=4)
    assert kv.slot_specs == {n: other.slot_shapes for n in other.recurrent}
    assert kv.slot_state_bytes == 4 * other.slot_row_bytes
    assert kv.pool_bytes == kv.num_pages * 4 * 4 * sum(
        int(np.prod(row)) * len(kv.pools[n])
        for n, row in other.paged.items())
