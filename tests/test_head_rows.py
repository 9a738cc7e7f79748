"""The head on the rows a step samples (`GraphExecutor.forward(rows=)`,
serving/engine.py `_mixed_impl` / `_spec_impl`).

A mixed step packs `max_step_tokens` rows and samples `slots` of them; a
verify step samples its chains' rows.  The layers from the head's input
onward run on those rows and on no others: same operands, same product,
same softmax — so the tokens are the parent's (the engine's own files hold
them to `lm_generate`'s), and the step programs hold no `[T, vocab]`
array."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.config.parser import parse_config, parse_config_callable
from paddle_tpu.graph.builder import GraphExecutor
from paddle_tpu.graph.context import TEST
from paddle_tpu.obs.metrics import process_counters
from paddle_tpu.parallel.mesh import model_mesh
from paddle_tpu.parameter.argument import Argument
from paddle_tpu.serving import NgramDrafter, Request, ServingEngine
from paddle_tpu.trainer.trainer import Trainer

VOCAB, DIM, STEPS = 19, 8, 12


# ---------------------------------------------------------------------------
# the executor's keyword
# ---------------------------------------------------------------------------

def _lm(softmax: bool, extra=None):
    """tokens -> embedding -> norm -> head (-> cost), and whatever `extra`
    hangs on the head."""
    def conf():
        from paddle_tpu import dsl
        dsl.settings(batch_size=2, learning_rate=0.1)
        tok = dsl.data_layer(name="tokens", size=VOCAB)
        emb = dsl.embedding_layer(input=tok, size=DIM)
        final = dsl.layer_norm_layer(input=emb, name="final_ln")
        act = dsl.SoftmaxActivation() if softmax else dsl.LinearActivation()
        head = dsl.fc_layer(input=final, size=VOCAB, act=act, name="head",
                            bias_attr=True)
        if extra is not None:
            extra(dsl, head)
        dsl.classification_cost(
            input=head, label=dsl.data_layer(name="next", size=VOCAB),
            name="cost")
    return parse_config_callable(conf).model_config


def _setup(model, compute_dtype=""):
    ex = GraphExecutor(model, compute_dtype=compute_dtype)
    return ex, ex.init_params(jax.random.PRNGKey(0))


def _feed(batch=2, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (batch, STEPS), 0,
                             VOCAB)
    return {"tokens": Argument(ids=ids,
                               lengths=jnp.full((batch,), STEPS, jnp.int32))}


# repeated, out of order, first and last: what a step's sample rows look like
IDX = np.array([7, 0, 7, 11, 3, 3, 1], np.int32)


@pytest.mark.parametrize("softmax", [True, False], ids=["softmax", "raw"])
def test_rows_equal_the_full_forward_gathered_float32(softmax):
    ex, params = _setup(_lm(softmax))
    feed = _feed()
    full, _, _ = ex.forward(params, feed, None, TEST, None)
    cut, costs, _ = ex.forward(params, feed, None, TEST, None,
                               rows={"head": jnp.asarray(IDX)})
    np.testing.assert_array_equal(np.asarray(full["head"].value)[:, IDX],
                                  np.asarray(cut["head"].value))
    assert cut["head"].value.shape == (2, IDX.size, VOCAB)
    np.testing.assert_array_equal(cut["head"].lengths, [IDX.size] * 2)
    # the layers in front of the cut keep every row, and the cost (its
    # label not fed) does not run
    np.testing.assert_array_equal(full["final_ln"].value,
                                  cut["final_ln"].value)
    assert not costs


@pytest.mark.parametrize("softmax", [True, False], ids=["softmax", "raw"])
def test_rows_equal_the_full_forward_gathered_bfloat16(softmax):
    """bf16 operands: the same product on fewer rows, to one rounding."""
    ex, params = _setup(_lm(softmax), compute_dtype="bfloat16")
    feed = _feed()
    full, _, _ = ex.forward(params, feed, None, TEST, None)
    cut, _, _ = ex.forward(params, feed, None, TEST, None,
                           rows={"head": jnp.asarray(IDX)})
    want = np.asarray(full["head"].value, np.float32)[:, IDX]
    got = np.asarray(cut["head"].value, np.float32)
    assert cut["head"].value.dtype == full["head"].value.dtype
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -9)


def test_rows_none_is_the_same_program():
    """No `rows`, no `scopes`: the jaxpr is today's, equation for
    equation — the trainer's, `lm_generate`'s and the drafter's calls."""
    ex, params = _setup(_lm(True))
    feed = _feed()

    def run(**kw):
        return str(jax.make_jaxpr(
            lambda p, f: ex.forward(p, f, None, TEST, None, **kw)[0]["head"]
            .value)(params, feed))
    assert run() == run(rows=None, scopes=None) == run(rows={}, scopes={})


def test_scopes_name_the_layers_ops():
    ex, params = _setup(_lm(True))
    feed = _feed()
    jaxpr = jax.make_jaxpr(
        lambda p, f: ex.forward(p, f, None, TEST, None,
                                scopes={"head": "lm.head"})[0]["head"].value)(
        params, feed)
    stacks = {str(e.source_info.name_stack) for e in jaxpr.jaxpr.eqns}
    assert any("lm.head" in s for s in stacks)
    assert any("lm.head" not in s for s in stacks)


def _reader_of_head(dsl, head):
    dsl.fc_layer(input=head, size=4, act=dsl.LinearActivation(),
                 name="reader")


def _group_on_head(dsl, head):
    def step(h):
        mem = dsl.memory(name="state", size=4)
        return dsl.fc_layer(input=[h, mem], size=4,
                            act=dsl.TanhActivation(), name="state")
    dsl.recurrent_group(step=step, input=head, name="rg")


def _head_in_group():
    def conf():
        from paddle_tpu import dsl
        dsl.settings(batch_size=2, learning_rate=0.1)
        tok = dsl.data_layer(name="tokens", size=VOCAB)
        emb = dsl.embedding_layer(input=tok, size=DIM)

        def step(e):
            mem = dsl.memory(name="state", size=DIM)
            state = dsl.fc_layer(input=[e, mem], size=DIM,
                                 act=dsl.TanhActivation(), name="state")
            return dsl.fc_layer(input=state, size=VOCAB,
                                act=dsl.SoftmaxActivation(), name="head")
        dsl.recurrent_group(step=step, input=emb, name="rg")
    return parse_config_callable(conf).model_config


@pytest.mark.parametrize("case,says", [
    ("in-group", "not a top-level layer"), ("unknown", "not a top-level"),
    ("reader", "layer 'reader' reads 'head'"),
    ("group", "recurrent group 'rg' reads 'head'"),
    ("label-fed", "layer 'cost' reads 'head'"),
    ("not-rows", "not a dense"), ("ids-input", "not a dense"),
    ("index-shape", "1-D integer")])
def test_rows_refusals(case, says):
    idx = jnp.asarray(IDX)
    feed = _feed()
    model = {"in-group": _head_in_group,
             "reader": lambda: _lm(True, _reader_of_head),
             "group": lambda: _lm(True, _group_on_head)}.get(
                 case, lambda: _lm(True))()
    rows = {"head": idx}
    if case == "unknown":
        rows = {"no_such_layer": idx}
    elif case == "label-fed":
        # with its label fed the cost RUNS, and reads a cut-down head
        feed["next"] = Argument(ids=feed["tokens"].ids,
                                lengths=feed["tokens"].lengths)
    elif case == "not-rows":
        # a [B, d] input has no token axis to gather along
        feed = {"tokens": Argument(ids=feed["tokens"].ids[:, 0])}
    elif case == "ids-input":
        # the embedding reads token ids, not rows of values
        (name,) = [l.name for l in model.layers
                   if l.inputs and l.inputs[0].input_layer_name == "tokens"]
        rows = {name: idx}
    elif case == "index-shape":
        rows = {"head": idx.reshape(1, -1)}
    ex, params = _setup(model)
    with pytest.raises(ValueError, match=says):
        jax.make_jaxpr(lambda p, f: ex.forward(
            p, f, None, TEST, None, rows=rows))(params, feed)


# ---------------------------------------------------------------------------
# the engine's step programs
# ---------------------------------------------------------------------------

LM_VOCAB, SLOTS, BUDGET, SPEC_K = 23, 2, 9, 2


@pytest.fixture(scope="module")
def tr():
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       f"vocab={LM_VOCAB},dim=16,layers=1,heads=2,"
                       f"batch_size=4")
    return Trainer(cfg, seed=7)


def _engine(tr, **kw):
    return ServingEngine(tr.executor, tr.params, num_slots=SLOTS,
                         page_size=4, max_context=32, prefill_chunk=4,
                         max_step_tokens=BUDGET, **kw)


def _shapes(jaxpr) -> set:
    """Every array shape a (closed) jaxpr names, its nested jaxprs' too."""
    seen = set()

    def walk(j):
        for v in (*j.invars, *j.constvars, *j.outvars):
            seen.add(tuple(getattr(v.aval, "shape", ())))
        for eqn in j.eqns:
            for v in (*eqn.invars, *eqn.outvars):
                seen.add(tuple(getattr(v.aval, "shape", ())))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return seen


def _row_operands(T, S):
    z = lambda n, dt=np.int32: jnp.zeros((n,), dt)
    return z(T), jnp.full((T,), S, jnp.int32), z(T)


@pytest.mark.parametrize("kind", ["mixed", "spec"])
def test_step_programs_hold_no_budget_by_vocab_array(tr, kind):
    """THE regression guard: the head's product and its softmax are taken
    on the sampled rows, so no array of the step's program is `[T, V]`."""
    eng = _engine(tr, **(dict(spec_k=SPEC_K) if kind == "spec" else {}))
    eng._sync_device_state()
    T, S = eng.max_step_tokens, SLOTS
    st = eng._build_state()
    rows = _row_operands(T, S)
    zs = jnp.zeros((S,), jnp.int32)
    if kind == "mixed":
        jaxpr = jax.make_jaxpr(eng._mixed_impl)(
            eng._step_params, st, *rows, zs, zs, zs.astype(bool))
        sampled = S
    else:
        jaxpr = jax.make_jaxpr(eng._spec_impl)(
            eng._step_params, st, *rows, zs, zs,
            jnp.zeros((S, SPEC_K), jnp.int32), zs.astype(bool),
            zs.astype(bool), zs)
        sampled = S * (SPEC_K + 1)
    shapes = _shapes(jaxpr)
    assert T not in (S, sampled)
    wide = sorted(s for s in shapes
                  if len(s) >= 2 and s[-2:] == (T, LM_VOCAB))
    assert not wide, f"the {kind} step builds {wide}"
    assert any(len(s) >= 2 and s[-2:] == (sampled, LM_VOCAB)
               for s in shapes)
    # ...while the stack in front of the head still runs every row
    assert any(len(s) >= 2 and s[-2:] == (T, 16) for s in shapes)


def _head_rows() -> float:
    return process_counters().snapshot().get("serving_head_rows_total", 0)


def test_head_rows_counter_by_step_kind(tr):
    """S a decode step and S a mixed step, whatever the step packs."""
    rng = np.random.default_rng(3)
    eng = _engine(tr)
    eng.add_request(Request("a", rng.integers(2, LM_VOCAB, 3).astype(
        np.int32), max_new=6))
    seen = {"mixed": 0, "decode": 0}
    while eng.slots.count(None) < SLOTS or eng.queue:
        before = (_head_rows(), eng.n_head_rows, eng.n_mixed_steps,
                  eng.n_decode_steps, eng.n_kv_rows)
        eng.step()
        if eng.n_decode_steps == before[3]:
            continue
        kind = "mixed" if eng.n_mixed_steps > before[2] else "decode"
        seen[kind] += 1
        assert _head_rows() - before[0] == SLOTS
        assert eng.n_head_rows - before[1] == SLOTS
        assert eng.n_kv_rows - before[4] == \
            (BUDGET if kind == "mixed" else SLOTS)
    assert seen["mixed"] and seen["decode"]


def test_head_rows_counter_spec(tr):
    """S x (K + 1) a verify step."""
    rng = np.random.default_rng(4)
    prompt = np.tile(rng.integers(2, LM_VOCAB, 4).astype(np.int32), 3)
    eng = _engine(tr, spec_k=SPEC_K, drafter=NgramDrafter())
    before = _head_rows()
    eng.run([Request("s", prompt, max_new=6)])
    assert eng.n_spec_steps > 0
    assert _head_rows() - before == eng.n_head_rows == \
        eng.n_spec_steps * SLOTS * (SPEC_K + 1) + \
        (eng.n_decode_steps - eng.n_spec_steps) * SLOTS


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs 2 devices (conftest provides 8)")
def test_tensor_parallel_head_gives_the_same_tokens():
    """The two-device mesh: the head's weight is sharded on its
    contraction axis and its input pinned the same way; a gather along the
    token axis leaves both alone, and the tokens are the single device's
    across the sampling knobs, through mixed and verify steps."""
    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=61,dim=32,layers=2,heads=4,batch_size=4")
    tr = Trainer(cfg, seed=7)
    knobs = [dict(), dict(temperature=0.8, top_k=5),
             dict(temperature=0.7, top_p=0.9), dict(temperature=1.1)]

    def reqs():
        r = np.random.default_rng(5)
        return [Request(i, r.integers(2, 61, n).astype(np.int32), max_new=6,
                        rng=jax.random.PRNGKey(100 + i), **kw)
                for i, (n, kw) in enumerate(zip((3, 19, 5, 12), knobs))]

    def engine(n, **kw):
        tr.executor.mesh = None
        return ServingEngine(tr.executor, tr.params, num_slots=3,
                             page_size=8, max_context=64, prefill_chunk=8,
                             max_step_tokens=16,
                             mesh=model_mesh(n) if n > 1 else None, **kw)

    base = engine(1).run(reqs())
    for kw in (dict(), dict(spec_k=2)):
        eng = engine(2, **kw)
        got = eng.run(reqs())
        assert eng._tp_lm_head is not None and eng.n_mixed_steps > 0
        for k in base:
            np.testing.assert_array_equal(base[k], got[k])
    tr.executor.mesh = None
