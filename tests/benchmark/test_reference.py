"""The plain reference against the program at a tiny size on the CPU, both
in float32 with dense attention: same loss, same gradients, same served
tokens.  And the control: the reference in fp8, put in the program's place,
must come out far from the reference — kept failing here at a size a test
run can hold (PERF.md has the readings at the cells' own size on the chip)."""

import os

import pytest

CFG = dict(hidden_size=32, intermediate_size=128, num_attention_heads=4,
           num_key_value_heads=2, num_hidden_layers=2, vocab_size=64,
           norm_epsilon=1e-6, rope_theta=999999.4420358813)
ARGS = ("vocab=64,dim=32,layers=2,heads=4,kv_heads=2,ffn=128,batch_size=2,"
        "seq_len=17,compute_dtype={dt},attn_impl=dense")


@pytest.fixture(scope="module")
def setup(root):
    import jax
    import jax.numpy as jnp

    from benchmark.lib.spec import Benchmark
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.trainer.trainer import Trainer

    cwd = os.getcwd()
    os.chdir(root)
    try:
        ref = Benchmark(root).reference("starcoder2")
        tr = Trainer(parse_config("benchmark/configs/starcoder2.py",
                                  ARGS.format(dt="float32")), seed=3)
        w = ref.make_weights(CFG, 3)
        batch = next(iter(tr.train_batches()))
    finally:
        os.chdir(cwd)
    toks = jnp.asarray(batch["tokens"].ids)
    labs = jnp.asarray(batch["next_tokens"].ids)
    return dict(jax=jax, ref=ref, tr=tr, w=w, batch=batch, toks=toks,
                labs=labs, root=root)


def test_weights_fit_the_programs_parameters(setup):
    jax, tr, w = setup["jax"], setup["tr"], setup["w"]
    shapes = jax.eval_shape(tr.executor.init_params, jax.random.PRNGKey(0))
    assert {k: v.shape for k, v in shapes.items()} == \
        {k: v.shape for k, v in w.items()}
    w2 = setup["ref"].make_weights(CFG, 3)
    w3 = setup["ref"].make_weights(CFG, 4)
    assert all(bool((w[k] == w2[k]).all()) for k in w)
    assert not bool((w["_lm_head.w0"] == w3["_lm_head.w0"]).all())
    assert float(w["_blk0_ln1.w0"].mean()) == pytest.approx(1.0, abs=0.02)


def test_reference_loss_and_gradient_match_the_program_in_float32(setup):
    from benchmark.lib.check import rel_err_tree
    from paddle_tpu.graph.context import TRAIN
    jax, ref, tr, w = setup["jax"], setup["ref"], setup["tr"], setup["w"]
    key = jax.random.PRNGKey(0)
    with jax.default_matmul_precision("highest"):
        ref_loss, g_ref = ref.jitted("loss_grad", CFG)(
            w, setup["toks"], setup["labs"])
        loss, g = jax.value_and_grad(
            lambda p: tr.executor.loss(p, setup["batch"], {}, TRAIN,
                                       key)[0])(w)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert rel_err_tree(jax, g, g_ref) < 1e-4


@pytest.mark.parametrize("number", ["grad", "loss"])
def test_control_fp8_in_the_programs_place_is_far_off(setup, number):
    """The step below bfloat16 must not pass for the program: the fp8
    reference's gradient is tens of times further from the float32
    reference than the program's bfloat16 path is."""
    from benchmark.lib.check import rel_err_tree
    jax, ref, w = setup["jax"], setup["ref"], setup["w"]
    t, l = setup["toks"], setup["labs"]
    with jax.default_matmul_precision("highest"):
        loss, g = ref.jitted("loss_grad", CFG)(w, t, l)
        loss8, g8 = ref.jitted("loss_grad", CFG, "fp8")(w, t, l)
        loss16, g16 = ref.jitted("loss_grad", CFG, "bf16")(w, t, l)
    if number == "grad":
        ctl, sound = rel_err_tree(jax, g8, g), rel_err_tree(jax, g16, g)
        assert ctl > 0.1 and ctl > 10 * sound
    else:
        assert abs(float(loss8) - float(loss)) > \
            abs(float(loss16) - float(loss))


def test_served_margin_is_zero_for_the_reference_itself_and_not_for_fp8(setup):
    """Teacher-forced greedy tokens: the reference's own argmax trails
    nothing; the fp8 control picks tokens that trail the argmax."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.check import served_margin
    jax, ref, w = setup["jax"], setup["ref"], setup["w"]
    prompt = [5, 9, 33, 2, 17, 40, 21, 8]
    seq = list(prompt)
    lp = ref.jitted("log_probs", CFG)
    for _ in range(24):                    # greedy decode with the reference
        ids = np.zeros(32, np.int32)
        ids[:len(seq)] = seq
        rows = np.zeros(32, np.int32)
        rows[0] = len(seq) - 1
        with jax.default_matmul_precision("highest"):
            seq.append(int(jnp.argmax(lp(w, jnp.asarray(ids),
                                         jnp.asarray(rows))[0])))
    served = [(prompt, seq[len(prompt):])]
    own = served_margin(jax, ref, CFG, w, served, 32)
    assert own["mean_nats"] == 0.0 and own["argmax_share"] == 1.0
    assert own["tokens"] == 24
    wrong = [(prompt, [(t + 1) % 64 for t in seq[len(prompt):]])]
    assert served_margin(jax, ref, CFG, w, wrong, 32)["mean_nats"] > 0.01
    with pytest.raises(ValueError):
        served_margin(jax, ref, CFG, w, [(prompt * 5, [1])], 32)
