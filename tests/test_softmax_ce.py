"""fc(softmax) + multi-class-cross-entropy as one log-sum-exp op.

The op (ops/softmax_ce.py) against a float32 log-softmax reference, and the
executor's choice of it (graph/builder.py:_fusable_softmax_costs): an
eligible graph takes it in `loss`, every other reader of the probabilities
keeps the composition."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.config.parser import parse_config_callable
from paddle_tpu.graph.builder import GraphExecutor
from paddle_tpu.graph.context import TEST, TRAIN
from paddle_tpu.obs.metrics import process_counters
from paddle_tpu.ops.softmax_ce import (NLL_CEILING, linear_softmax_ce,
                                       time_chunks)
from paddle_tpu.parameter.argument import Argument
from paddle_tpu.trainer.evaluators import EvaluatorSet

COUNTER = "graph_fused_softmax_cost_total"


def _fused_count() -> float:
    return process_counters().snapshot().get(COUNTER, 0)


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

def _reference(x, w, b, y):
    z = x.astype(jnp.float32) @ w.astype(jnp.float32)
    if b is not None:
        z = z + b.astype(jnp.float32)
    lp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.take_along_axis(lp, y[..., None], axis=-1)[..., 0], z


def _problem(shape, classes=11, dtype=jnp.float32, bias=True, seed=0):
    k = jax.random.PRNGKey(seed)
    x = jax.random.normal(k, shape).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(k, 1), (shape[-1], classes))
         * 0.5).astype(dtype)
    b = (jax.random.normal(jax.random.fold_in(k, 2), (1, classes))
         .astype(dtype) if bias else None)
    y = jax.random.randint(jax.random.fold_in(k, 3), shape[:-1], 0, classes)
    g = jax.random.normal(jax.random.fold_in(k, 4), shape[:-1])
    return x, w, b, y, g


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("shape", [(6, 16), (2, 8, 16)], ids=["rows", "seq"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bf16"])
def test_op_matches_log_softmax_reference(dtype, tol, shape, bias):
    """value, dx, dw, db against the float32 reference on the SAME operands
    (bf16 operands are taken up to float32 by the reference): the op's
    float32 accumulation leaves only the rounding of its outputs."""
    x, w, b, y, g = _problem(shape, dtype=dtype, bias=bias)
    args = (x, w) + ((b,) if bias else ())

    def fused(*a):
        nll, _ = linear_softmax_ce(a[0], a[1], a[2] if bias else None, y)
        return jnp.sum(nll * g), nll

    def ref(*a):
        nll, _ = _reference(a[0], a[1], a[2] if bias else None, y)
        return jnp.sum(nll * g), nll

    argnums = tuple(range(len(args)))
    (_, nll), grads = jax.value_and_grad(fused, argnums, has_aux=True)(*args)
    (_, nll_ref), grads_ref = jax.value_and_grad(ref, argnums,
                                                 has_aux=True)(*args)
    assert nll.dtype == jnp.float32
    np.testing.assert_allclose(nll, nll_ref, rtol=2e-6, atol=2e-6)
    for got, want in zip(grads, grads_ref):
        assert got.dtype == want.dtype and got.shape == want.shape
        scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) + 1e-6
        np.testing.assert_allclose(got.astype(jnp.float32),
                                   want.astype(jnp.float32),
                                   rtol=0, atol=tol * scale)


def test_op_masked_ragged_sequences():
    """The executor's masked per-sequence sum over the op's per-token nll:
    tokens past a sequence's length add nothing to the cost or to dx."""
    x, w, b, y, _ = _problem((3, 6, 8))
    lengths = jnp.asarray([6, 2, 4])
    mask = (jnp.arange(6)[None, :] < lengths[:, None]).astype(jnp.float32)

    def cost(fn, x):
        return jnp.sum(jnp.sum(fn(x) * mask, axis=-1))

    fused = lambda x: linear_softmax_ce(x, w, b, y)[0]
    ref = lambda x: _reference(x, w, b, y)[0]
    v, dx = jax.value_and_grad(lambda x: cost(fused, x))(x)
    v_ref, dx_ref = jax.value_and_grad(lambda x: cost(ref, x))(x)
    np.testing.assert_allclose(v, v_ref, rtol=1e-6)
    np.testing.assert_allclose(dx, dx_ref, atol=2e-6)
    assert float(jnp.max(jnp.abs(dx[1, 2:]))) == 0.0
    assert float(jnp.max(jnp.abs(dx[2, 4:]))) == 0.0


@pytest.mark.parametrize("gap", [30.0, 100.0], ids=["gap30", "gap100"])
def test_op_ceiling_and_its_zero_gradient(gap):
    """A row whose label's probability is under 1e-10 costs -log(1e-10)
    and sends no gradient — what `log(max(p, 1e-10))` did; a row beside it
    is untouched."""
    x = jnp.asarray([[1.0, 0.0], [0.1, 0.2]])
    w = jnp.asarray([[gap, 0.0, 0.0], [0.0, 1.0, 0.5]])
    y = jnp.asarray([2, 1])           # row 0: p[label] = e^-gap < 1e-10

    nll, _ = linear_softmax_ce(x, w, None, y)
    assert float(nll[0]) == pytest.approx(NLL_CEILING, rel=1e-7)
    assert NLL_CEILING == pytest.approx(-math.log(1e-10))
    dx, dw = jax.grad(
        lambda x, w: jnp.sum(linear_softmax_ce(x, w, None, y)[0]), (0, 1))(x, w)
    assert float(jnp.max(jnp.abs(dx[0]))) == 0.0
    _, dw_row1 = jax.grad(
        lambda x, w: jnp.sum(_reference(x[1:], w, None, y[1:])[0]), (0, 1))(x, w)
    np.testing.assert_allclose(dw, dw_row1, atol=1e-6)


@pytest.mark.parametrize("chunks", [2, 4, 8, 16])
def test_op_chunked_equals_unchunked(chunks):
    """Walking the time axis in pieces changes no number: value, argmax
    and every gradient equal the one-piece op's."""
    x, w, b, y, g = _problem((3, 16, 8))

    def run(n):
        def f(x, w, b):
            nll, pred = linear_softmax_ce(x, w, b, y, n)
            return jnp.sum(nll * g), (nll, pred)
        return jax.value_and_grad(f, (0, 1, 2), has_aux=True)(x, w, b)

    (_, (nll1, pred1)), grads1 = run(1)
    (_, (nlln, predn)), gradsn = run(chunks)
    np.testing.assert_array_equal(pred1, predn)
    np.testing.assert_allclose(nll1, nlln, rtol=1e-6, atol=1e-6)
    for a, c in zip(grads1, gradsn):
        np.testing.assert_allclose(a, c, rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("batch,steps,classes,want", [
    (2, 4096, 49152, 16),       # the seq4k head: 16 pieces of 2 x 256
    (8, 4096, 49152, 64),       # eight sequences on one device
    (32, 100, 10, 1),           # a tagger's few classes fit whole
    (128, 1, 49152, 1),         # one step cannot be cut
    (2, 7, 49152 * 256, 7),     # a prime length: down to single steps
    (1, 10, 49152 * 128, 5),    # 3 and 4 do not divide 10, 5 does
], ids=["seq4k", "dp-global", "few-classes", "one-step", "prime",
        "next-divisor"])
def test_time_chunks_rule(batch, steps, classes, want):
    assert time_chunks(batch, steps, classes) == want


def test_op_argmax_takes_first_index_on_ties():
    x = jnp.ones((3, 2))
    w = jnp.asarray([[1.0, 2.0, 2.0, 0.0], [1.0, 2.0, 2.0, 0.0]])
    _, pred = linear_softmax_ce(x, w, None, jnp.zeros((3,), jnp.int32))
    np.testing.assert_array_equal(pred, [1, 1, 1])
    assert pred.dtype == jnp.int32


# ---------------------------------------------------------------------------
# the executor: which graphs take the op
# ---------------------------------------------------------------------------

CLASSES, DIM, BATCH, STEPS = 7, 12, 5, 6


def _head(extra=None, seq=False, weight=False, coeff=1.0, **fc_kw):
    """A config ending in fc(softmax) + classification_cost; `extra(out,
    label)` adds whatever else reads the head."""
    def conf():
        from paddle_tpu import dsl
        dsl.settings(batch_size=BATCH, learning_rate=0.1,
                     learning_method=dsl.MomentumOptimizer(momentum=0.0))
        if seq:
            tok = dsl.data_layer(name="x", size=20)
            h = dsl.embedding_layer(input=tok, size=DIM)
        else:
            h = dsl.data_layer(name="x", size=DIM)
        h = dsl.fc_layer(input=h, size=DIM, act=dsl.TanhActivation())
        out = dsl.fc_layer(input=h, size=CLASSES,
                           act=dsl.SoftmaxActivation(), name="head", **fc_kw)
        label = dsl.data_layer(name="y", size=CLASSES)
        wl = dsl.data_layer(name="wt", size=1) if weight else None
        dsl.classification_cost(input=out, label=label, weight=wl,
                                coeff=coeff, name="cost")
        if extra is not None:
            extra(out, label)
    return parse_config_callable(conf).model_config


def _feed(seq=False, weight=False, seed=0):
    rng = np.random.default_rng(seed)
    if seq:
        feed = {"x": Argument(ids=jnp.asarray(rng.integers(0, 20, (BATCH, STEPS)),
                                              jnp.int32),
                              lengths=jnp.asarray([6, 3, 1, 5, 2], jnp.int32)),
                "y": Argument(ids=jnp.asarray(
                    rng.integers(0, CLASSES, (BATCH, STEPS)), jnp.int32),
                    lengths=jnp.asarray([6, 3, 1, 5, 2], jnp.int32))}
    else:
        feed = {"x": Argument(value=jnp.asarray(
                    rng.standard_normal((BATCH, DIM)), jnp.float32)),
                "y": Argument(ids=jnp.asarray(
                    rng.integers(0, CLASSES, BATCH), jnp.int32))}
    if weight:
        feed["wt"] = Argument(value=jnp.asarray(
            rng.random((BATCH, 1)), jnp.float32))
    return feed


def _composed_loss(ex, params, feed, mode=TRAIN):
    """Today's composition: `forward` runs every layer as configured."""
    _, costs, _ = ex.forward(params, feed, None, mode, jax.random.PRNGKey(1))
    return sum(jnp.mean(c.astype(jnp.float32)) for c in costs.values())


def _setup(model, compute_dtype=""):
    ex = GraphExecutor(model, compute_dtype=compute_dtype)
    return ex, ex.init_params(jax.random.PRNGKey(0))


@pytest.mark.parametrize("seq", [False, True], ids=["rows", "seq"])
def test_eligible_graph_takes_the_fused_op(seq):
    """The counter grows when the pair is traced, the head's entry in
    `loss`'s outputs is the rows' argmax with no value, and the traced
    value-and-grad program divides no [rows, classes] array (the
    composition's softmax does)."""
    ex, params = _setup(_head(seq=seq))
    feed = _feed(seq=seq)
    assert list(ex._fusable_softmax_costs()) == ["head"]
    before = _fused_count()
    loss, (outputs, costs, _) = ex.loss(params, feed, None, TRAIN,
                                        jax.random.PRNGKey(1))
    assert _fused_count() == before + 1
    assert outputs["head"].value is None
    assert outputs["head"].ids.shape == feed["y"].ids.shape
    assert costs["cost"].dtype == jnp.float32

    def table_divides(fn):
        jaxpr = jax.make_jaxpr(jax.value_and_grad(fn))(params)
        found = []

        def walk(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "div" and any(
                        v.aval.shape[-1:] == (CLASSES,) for v in eqn.outvars):
                    found.append(eqn)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        return len(found)

    key = jax.random.PRNGKey(1)
    assert table_divides(lambda p: ex.loss(p, feed, None, TRAIN, key)[0]) == 0
    assert table_divides(lambda p: _composed_loss(ex, p, feed)) >= 1


def _second_reader(out, label):
    from paddle_tpu import dsl
    dsl.fc_layer(input=out, size=3, act=dsl.LinearActivation(), name="more")


def _other_evaluator(out, label):
    from paddle_tpu import dsl
    dsl.sum_evaluator(input=out)


def _host_evaluator(out, label):
    from paddle_tpu import dsl
    dsl.maxid_printer_evaluator(input=out)


def _probe(out, label):
    from paddle_tpu import dsl
    dsl.gradient_printer_evaluator(input=out)


def _declared_output(out, label):
    from paddle_tpu import dsl
    dsl.outputs(out)


def _other_label(out, label):
    from paddle_tpu import dsl
    dsl.classification_error_evaluator(
        input=out, label=dsl.data_layer(name="y2", size=CLASSES))


INELIGIBLE = {
    "second-reader": dict(extra=_second_reader),
    "other-evaluator": dict(extra=_other_evaluator),
    "host-evaluator": dict(extra=_host_evaluator),
    "gradient-probe": dict(extra=_probe),
    "declared-output": dict(extra=_declared_output),
    "evaluator-other-label": dict(extra=_other_label),
    "dropout": dict(layer_attr="dropout"),
}


@pytest.mark.parametrize("case", list(INELIGIBLE))
def test_ineligible_graph_keeps_the_composition(case):
    """Anything else that reads the head leaves the counter alone, and
    `loss` gives the composition's numbers bit for bit."""
    kw = dict(INELIGIBLE[case])
    if kw.pop("layer_attr", None):
        from paddle_tpu.dsl import ExtraLayerAttribute
        kw["layer_attr"] = ExtraLayerAttribute(drop_rate=0.5)
    ex, params = _setup(_head(**kw))
    feed = _feed()
    if case == "evaluator-other-label":
        feed["y2"] = feed["y"]
    assert ex._fusable_softmax_costs() == {}
    before = _fused_count()
    key = jax.random.PRNGKey(1)
    loss, (outputs, _, _) = ex.loss(params, feed, None, TRAIN, key)
    assert _fused_count() == before
    assert outputs["head"].value.shape == (BATCH, CLASSES)
    assert float(loss) == float(_composed_loss(ex, params, feed))


def test_tp_out_stamp_read_at_trace_time():
    """The serving engine stamps `tp_out` after the executor is built: the
    walk reads it at each trace."""
    ex, _ = _setup(_head())
    assert list(ex._fusable_softmax_costs()) == ["head"]
    ex.layer_map["head"].attrs["tp_out"] = "replicated"
    assert ex._fusable_softmax_costs() == {}


def test_head_inside_recurrent_group_keeps_the_composition():
    """A vocabulary projection inside a recurrent group (the seq2seq
    decoder's deferred head) is the group's to run."""
    def conf():
        from paddle_tpu import dsl
        dsl.settings(batch_size=BATCH, learning_rate=0.1)
        tok = dsl.data_layer(name="x", size=20)
        emb = dsl.embedding_layer(input=tok, size=DIM)

        def step(e):
            mem = dsl.memory(name="state", size=DIM)
            state = dsl.fc_layer(input=[e, mem], size=DIM,
                                 act=dsl.TanhActivation(), name="state")
            return dsl.fc_layer(input=state, size=CLASSES,
                                act=dsl.SoftmaxActivation(), name="head")
        out = dsl.recurrent_group(step=step, input=emb, name="rg")
        dsl.classification_cost(input=out,
                                label=dsl.data_layer(name="y", size=CLASSES),
                                name="cost")
    ex, params = _setup(parse_config_callable(conf).model_config)
    feed = _feed(seq=True)
    assert ex._fusable_softmax_costs() == {}
    before = _fused_count()
    loss, (outputs, _, _) = ex.loss(params, feed, None, TRAIN,
                                    jax.random.PRNGKey(1))
    assert _fused_count() == before
    assert outputs["head"].value.shape == (BATCH, STEPS, CLASSES)
    assert float(loss) == float(_composed_loss(ex, params, feed))


@pytest.mark.parametrize("with_label", [False, True],
                         ids=["no-label", "label-fed"])
def test_forward_and_generation_keep_the_probabilities(with_label):
    """`forward` — what generation, the serving engine and the Python API
    call — hands every layer's output to its caller: probabilities, fed
    label or not."""
    ex, params = _setup(_head())
    feed = _feed()
    if not with_label:
        del feed["y"]
    before = _fused_count()
    outputs, costs, _ = ex.forward(params, feed, None, TEST, None)
    assert _fused_count() == before
    p = outputs["head"].value
    np.testing.assert_allclose(jnp.sum(p, axis=-1), 1.0, rtol=1e-6)
    assert ("cost" in costs) == with_label


def test_sparse_row_input_keeps_the_composition():
    """A head straight on a sparse-row feed gathers rows of its weight:
    the dense op does not apply, and what the feed shows decides."""
    def conf():
        from paddle_tpu import dsl
        dsl.settings(batch_size=BATCH, learning_rate=0.1)
        x = dsl.data_layer(name="x", size=30)
        out = dsl.fc_layer(input=x, size=CLASSES,
                           act=dsl.SoftmaxActivation(), name="head")
        dsl.classification_cost(input=out,
                                label=dsl.data_layer(name="y", size=CLASSES),
                                name="cost")
    ex, params = _setup(parse_config_callable(conf).model_config)
    rng = np.random.default_rng(0)
    feed = {"x": Argument(ids=jnp.asarray(rng.integers(0, 30, (BATCH, 4)),
                                          jnp.int32),
                          sparse_vals=jnp.ones((BATCH, 4), jnp.float32),
                          sparse_dim=30),
            "y": _feed()["y"]}
    assert list(ex._fusable_softmax_costs()) == ["head"]   # the graph allows it
    before = _fused_count()
    loss, (outputs, _, _) = ex.loss(params, feed, None, TRAIN,
                                    jax.random.PRNGKey(1))
    assert _fused_count() == before
    assert outputs["head"].value.shape == (BATCH, CLASSES)
    assert float(loss) == float(_composed_loss(ex, params, feed))


VARIANTS = {
    "rows": dict(),
    "seq": dict(seq=True),
    "weight": dict(weight=True),
    "seq-weight-coeff": dict(seq=True, weight=True, coeff=0.3),
    "no-bias": dict(bias_attr=False),
}


@pytest.mark.parametrize("case", list(VARIANTS))
@pytest.mark.parametrize("mode", [TRAIN, TEST])
def test_paths_agree_at_float32(case, mode):
    """Loss, every parameter's gradient and `classification_error` equal
    between the fused op and the composition at float32 compute: masks,
    the per-sequence sum, the weight input and `coeff` go through
    `_record` unchanged."""
    kw = VARIANTS[case]
    model = _head(**kw)
    ex, params = _setup(model)
    feed = _feed(seq=kw.get("seq", False), weight=kw.get("weight", False))
    key = jax.random.PRNGKey(1)
    before = _fused_count()
    (loss, (outputs, costs, _)), grads = jax.value_and_grad(
        lambda p: ex.loss(p, feed, None, mode, key), has_aux=True)(params)
    assert _fused_count() == before + 1
    loss_c, grads_c = jax.value_and_grad(
        lambda p: _composed_loss(ex, p, feed, mode))(params)
    np.testing.assert_allclose(loss, loss_c, rtol=2e-6)
    for name in params:
        np.testing.assert_allclose(grads[name], grads_c[name], rtol=2e-5,
                                   atol=2e-7, err_msg=name)
    outputs_c, costs_c, _ = ex.forward(params, feed, None, mode, key)
    np.testing.assert_allclose(costs["cost"], costs_c["cost"], rtol=2e-6)
    evs = EvaluatorSet(model)
    got = evs.batch_partials(outputs, feed)
    want = evs.batch_partials(outputs_c, feed)
    assert got.keys() == want.keys() and len(got) == 1
    for name in got:
        for k in got[name]:
            assert float(got[name][k]) == float(want[name][k])


def test_bf16_compute_loss_stays_float32():
    """Under a bfloat16 compute dtype the composition's cost is a bfloat16
    number; the op's is float32 from float32-accumulated logits, and sits
    nearer the float32 model's loss."""
    model = _head(seq=True)
    ex32, params = _setup(model)
    ex16, _ = _setup(model, compute_dtype="bfloat16")
    feed = _feed(seq=True)
    key = jax.random.PRNGKey(1)
    exact = float(ex32.loss(params, feed, None, TRAIN, key)[0])
    loss, (_, costs, _) = ex16.loss(params, feed, None, TRAIN, key)
    _, costs_c, _ = ex16.forward(params, feed, None, TRAIN, key)
    assert costs["cost"].dtype == jnp.float32
    assert costs_c["cost"].dtype == jnp.bfloat16
    assert abs(float(loss) - exact) / exact < 5e-3


def test_trainer_counts_classification_error_through_the_fused_head():
    """train_one_pass and test both take the fused pair and their
    `classification_error` is the composition's on the same batches."""
    from paddle_tpu.trainer.trainer import Trainer

    def conf_of(extra):
        def conf():
            from paddle_tpu import dsl
            dsl.settings(batch_size=BATCH, learning_rate=0.0,
                         learning_method=dsl.MomentumOptimizer(momentum=0.0))
            h = dsl.data_layer(name="x", size=DIM)
            out = dsl.fc_layer(input=h, size=CLASSES,
                               act=dsl.SoftmaxActivation(), name="head")
            label = dsl.data_layer(name="y", size=CLASSES)
            dsl.classification_cost(input=out, label=label, name="cost")
            if extra:
                dsl.outputs(out)
        return parse_config_callable(conf)

    batches = [_feed(seed=s) for s in range(3)]
    before = _fused_count()
    fused = Trainer(conf_of(False), seed=3)
    stats = fused.train_one_pass(batches=iter(batches))
    tstats = fused.test(batches=iter(batches))
    assert _fused_count() >= before + 2
    plain = Trainer(conf_of(True), seed=3)
    assert plain.executor._fusable_softmax_costs() == {}
    want = plain.train_one_pass(batches=iter(batches))
    twant = plain.test(batches=iter(batches))
    key = "classification_error"
    assert stats[key] == want[key] and tstats[key] == twant[key]
    assert stats["cost"] == pytest.approx(want["cost"], rel=2e-6)
    assert tstats["cost"] == pytest.approx(twant["cost"], rel=2e-6)


def test_four_device_data_mesh_matches_one_device():
    """The fused head under a `data:4` mesh of CPU devices (batch axis
    sharded, the time axis walked in pieces) trains as on one device."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.trainer.parity import assert_dp_parity
    import paddle_tpu.ops.softmax_ce as mod

    def conf():
        from paddle_tpu import dsl
        dsl.settings(batch_size=8, learning_rate=0.05,
                     learning_method=dsl.AdamOptimizer())
        tok = dsl.data_layer(name="x", size=20)
        h = dsl.embedding_layer(input=tok, size=DIM)
        out = dsl.fc_layer(input=h, size=CLASSES,
                           act=dsl.SoftmaxActivation(), name="head")
        dsl.classification_cost(input=out,
                                label=dsl.data_layer(name="y", size=CLASSES))

    rng = np.random.default_rng(2)
    lens = jnp.asarray([8, 3, 5, 8, 1, 6, 2, 7], jnp.int32)
    batches = [{"x": Argument(ids=jnp.asarray(rng.integers(0, 20, (8, 8)),
                                              jnp.int32), lengths=lens),
                "y": Argument(ids=jnp.asarray(rng.integers(0, CLASSES, (8, 8)),
                                              jnp.int32), lengths=lens)}
               for _ in range(4)]
    old = mod._BLOCK_BYTES
    mod._BLOCK_BYTES = 2 * 2 * CLASSES * 4   # 2 rows a device: 4 pieces
    try:
        before = _fused_count()
        assert_dp_parity(parse_config_callable(conf), batches,
                         make_mesh(data=4, devices=jax.devices()[:4]),
                         config2=parse_config_callable(conf))
        assert _fused_count() >= before + 2
    finally:
        mod._BLOCK_BYTES = old
