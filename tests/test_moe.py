"""Mixture-of-experts tests: routing invariants (plain softmax top-k and
the sigmoid group-limited family), the dropless expert block against a
literal loop, the held-share decomposition, single-expert oracle,
mesh-sharded equivalence (expert parallelism), DSL layer training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel import moe
from paddle_tpu.parallel.moe import combine_weights, moe_ffn, moe_route


def _loop_route(scores, bias, k, n_group, topk_group, scale):
    """DeepSeek-V3's selection, spelled out one token at a time."""
    T, E = scores.shape
    per = E // n_group
    ids, ws = [], []
    for t in range(T):
        choice = scores[t] + bias
        gscore = [np.sort(choice[g * per:(g + 1) * per])[-2:].sum()
                  for g in range(n_group)]
        kept = np.argsort(gscore)[-topk_group:]
        masked = np.full(E, -np.inf)
        for g in kept:
            masked[g * per:(g + 1) * per] = choice[g * per:(g + 1) * per]
        pick = np.argsort(masked)[-k:]
        w = scores[t, pick]                # weights: from the scores alone
        ids.append(sorted(pick))
        ws.append({int(e): float(x / w.sum() * scale)
                   for e, x in zip(pick, w)})
    return ids, ws


class TestRouting:
    def test_dropless_under_a_skew_a_capacity_would_drop(self):
        """Every token prefers expert 0 (32 tokens, 4 experts, top-2: a
        capacity of 1.25 * 2 * 32 / 4 = 20 slots would drop 12 of expert
        0's pairs).  Here every token keeps both its pairs at full weight
        and the block equals the literal sum over its picks."""
        rng = np.random.default_rng(0)
        B, E, D, H = 32, 4, 8, 16
        logits = jnp.asarray(rng.normal(size=(B, E)) * 0.1, jnp.float32)
        logits = logits.at[:, 0].add(5.0)
        idx, w, _ = moe_route(logits, top_k=2)
        assert bool((idx[:, 0] == 0).all())
        comb = combine_weights(idx, w, 0, E)
        assert int((comb[:, 0] > 0).sum()) == B          # nothing dropped
        np.testing.assert_allclose(comb.sum(-1), np.ones(B), rtol=1e-5)
        # the block itself, against a loop over each token's picks
        x = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)
        w_r = jnp.zeros((D, E), jnp.float32).at[0, 0].set(1.0)
        x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 3.0)   # x W_r favors expert 0
        w1 = jnp.asarray(rng.normal(size=(E, D, H)) * 0.3, jnp.float32)
        w2 = jnp.asarray(rng.normal(size=(E, H, D)) * 0.3, jnp.float32)
        b1, b2 = jnp.zeros((E, H)), jnp.zeros((E, D))
        y, _, pairs = moe_ffn(x, w_r, (w1, b1, w2, b2), top_k=2)
        idx, w, _ = moe_route(x @ w_r, top_k=2)
        want = np.zeros((B, D), np.float32)
        for b in range(B):
            for e, g in zip(np.asarray(idx[b]), np.asarray(w[b])):
                want[b] += g * np.asarray(
                    jax.nn.relu(x[b] @ w1[e]) @ w2[e])
        np.testing.assert_allclose(y, want, rtol=2e-5, atol=1e-5)
        assert int(pairs.sum()) == 2 * B and bool(pairs[:, 0].all())

    def test_combine_weights_normalized(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
        idx, w, _ = moe_route(logits, top_k=2)
        sums = jnp.sum(combine_weights(idx, w, 0, 4), axis=1)
        np.testing.assert_allclose(sums, np.ones(8), rtol=1e-5)

    def test_aux_loss_uniform_is_one(self):
        # uniform routing -> aux loss == 1 (its minimum for balanced load)
        logits = jnp.zeros((16, 4), jnp.float32)
        _, _, aux = moe_route(logits, top_k=1)
        np.testing.assert_allclose(float(aux), 1.0, rtol=1e-5)

    def test_padding_tokens_are_never_routed(self):
        logits = jnp.asarray(np.random.default_rng(2).normal(size=(6, 4)),
                             jnp.float32)
        valid = jnp.asarray([1, 1, 0, 1, 0, 1], bool)
        idx, w, _ = moe_route(logits, top_k=2, valid=valid)
        assert float(jnp.abs(w[~valid]).max()) == 0.0
        assert float(w[valid].sum()) == pytest.approx(4.0, rel=1e-5)

    def test_group_limited_sigmoid_routing_matches_a_literal_loop(self):
        """Groups, the bias used for SELECTION only, renormalization over
        the picks, the scaling factor — against the loop above."""
        rng = np.random.default_rng(3)
        T, E, k, G, kg, scale = 24, 32, 4, 8, 3, 2.5
        logits = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
        bias = jnp.asarray(rng.normal(size=(E,)) * 0.3, jnp.float32)
        idx, w, _ = moe_route(logits, k, scoring="sigmoid", n_group=G,
                              topk_group=kg, select_bias=bias, scale=scale)
        scores = np.asarray(jax.nn.sigmoid(logits), np.float64)
        ids, ws = _loop_route(scores, np.asarray(bias, np.float64), k, G, kg,
                              scale)
        changed = 0
        for t in range(T):
            assert sorted(np.asarray(idx[t]).tolist()) == ids[t], t
            for e, g in zip(np.asarray(idx[t]), np.asarray(w[t])):
                assert g == pytest.approx(ws[t][int(e)], rel=1e-5)
            assert float(w[t].sum()) == pytest.approx(scale, rel=1e-5)
            unbiased = np.argsort(scores[t])[-k:]
            changed += sorted(unbiased.tolist()) != ids[t]
        # the bias and the groups really steer the selection in this draw
        assert changed > 0

    def test_held_shares_add_up_to_the_whole_layer(self):
        """Expert parallelism without the exchange: the parts the ranks'
        blocks give add up to the block that holds every expert."""
        rng = np.random.default_rng(4)
        B, E, D, H, k = 12, 8, 8, 16, 3
        x = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)
        w_r = jnp.asarray(rng.normal(size=(D, E)), jnp.float32)
        wg = jnp.asarray(rng.normal(size=(E, D, H)) * 0.3, jnp.float32)
        wu = jnp.asarray(rng.normal(size=(E, D, H)) * 0.3, jnp.float32)
        wd = jnp.asarray(rng.normal(size=(E, H, D)) * 0.3, jnp.float32)
        kw = dict(top_k=k, scoring="sigmoid", n_group=4, topk_group=2,
                  scale=2.5)
        whole, _, all_pairs = moe_ffn(x, w_r, (wg, wu, wd), **kw)
        parts, n_pairs = 0.0, 0
        for r in range(4):
            sl = slice(2 * r, 2 * r + 2)
            y, _, pairs = moe_ffn(x, w_r, (wg[sl], wu[sl], wd[sl]),
                                  first_expert=2 * r, **kw)
            parts = parts + y
            n_pairs += int(pairs.sum())
        np.testing.assert_allclose(parts, whole, rtol=2e-5, atol=1e-5)
        assert n_pairs == int(all_pairs.sum()) == B * k


class TestMoeFfn:
    def _params(self, rng, E, D, H, Dout):
        return dict(
            w_router=jnp.asarray(rng.normal(size=(D, E)) * 0.1, jnp.float32),
            experts=(
                jnp.asarray(rng.normal(size=(E, D, H)) * 0.3, jnp.float32),
                jnp.zeros((E, H), jnp.float32),
                jnp.asarray(rng.normal(size=(E, H, Dout)) * 0.3, jnp.float32),
                jnp.zeros((E, Dout), jnp.float32)),
        )

    def test_single_expert_equals_plain_ffn(self):
        rng = np.random.default_rng(2)
        p = self._params(rng, E=1, D=8, H=16, Dout=8)
        x = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)
        y, aux, _ = moe_ffn(x, **p, top_k=1)
        w1, b1, w2, b2 = p["experts"]
        ref = jax.nn.relu(x @ w1[0] + b1[0]) @ w2[0] + b2[0]
        np.testing.assert_allclose(y, ref, rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(float(aux), 1.0, rtol=1e-5)

    def test_sharded_matches_single_device(self):
        """Expert params sharded over `model` + tokens over `data` must give
        the same result as unsharded execution."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.parallel.mesh import make_mesh
        rng = np.random.default_rng(3)
        E, D, H = 4, 8, 16
        p = self._params(rng, E=E, D=D, H=H, Dout=D)
        x = jnp.asarray(rng.normal(size=(16, D)), jnp.float32)
        ref = moe_ffn(x, **p, top_k=2)[0]

        mesh = make_mesh(data=2, model=4)
        px = jax.device_put(x, NamedSharding(mesh, P("data")))
        experts = tuple(
            jax.device_put(w, NamedSharding(
                mesh, P("model", *([None] * (w.ndim - 1)))))
            for w in p["experts"])

        @jax.jit
        def run(x, w_router, experts):
            return moe_ffn(x, w_router, experts, top_k=2)[0]

        out = run(px, p["w_router"], experts)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=1e-6)

    def test_grads_flow_to_all_params(self):
        rng = np.random.default_rng(4)
        p = self._params(rng, E=4, D=8, H=16, Dout=8)
        x = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)

        def loss(p):
            y, aux, _ = moe_ffn(x, **p, top_k=2)
            return jnp.sum(jnp.square(y)) + 0.01 * aux

        g = jax.grad(loss)(p)
        for v in [g["w_router"], *g["experts"]]:
            assert float(jnp.abs(v).max()) > 0.0


#: the grouped form against the dense form: what each case varies
GROUPED_CASES = {
    "gated-all-held": dict(gated=True),
    "plain-all-held": dict(gated=False),
    # experts [2, 6) of 8 held: pairs of the others belong elsewhere
    "gated-held-block": dict(gated=True, first=2, held=4),
    "plain-held-block": dict(gated=False, first=2, held=4),
    # expert 0 draws every row (24 pairs: a first round of 12 slots, then
    # two tiles of 8), expert 1 none
    "hot-and-idle-expert": dict(gated=True, skew=True),
    "plain-hot-and-idle-expert": dict(gated=False, skew=True, first=1,
                                      held=5),
    "valid-mask": dict(gated=True, masked=True),
    "plain-valid-mask-held-block": dict(gated=False, masked=True, first=3,
                                        held=5),
    # 37 rows x top-3: neither the rows nor the pairs a multiple of 8
    "rows-off-the-tile": dict(gated=True, B=37, k=3),
    "one-row": dict(gated=False, B=1, k=2),
    "no-row-valid": dict(gated=True, masked="all"),
    "one-round-at-128-slots": dict(gated=True, slots=128, B=48, k=4),
    "bf16": dict(gated=True, dtype="bfloat16", D=128, H=256, B=48, k=4),
    "bf16-plain-held-block": dict(gated=False, dtype="bfloat16", D=128,
                                  H=128, first=2, held=4),
    "bf16-hot-and-idle": dict(gated=True, dtype="bfloat16", D=128, H=128,
                              skew=True),
    # the overflow's own (first round 8 slots at 32 rows top-2 of 16
    # experts scored, tiles of 8): expert 0 draws all 32 rows — three tiles past the first round
    # — and expert 1 none, the rest a pair or two each
    "overflow-three-tiles": dict(gated=True, B=32, E=16, held=16, hot=[0]),
    # experts 0 and 1 draw 32 and 19 rows: three tiles and two, the last
    # of expert 1 holding three pairs
    "overflow-two-experts": dict(gated=False, B=32, E=16, held=16,
                                 hot=[0, 1], hot_rows=[32, 19]),
    # loads of exactly the first round and of exactly one tile more: no
    # tile for the one, one full tile and no empty second for the other
    "overflow-exact-fill": dict(gated=True, B=32, E=16, held=16,
                                hot=[0, 1], hot_rows=[8, 16]),
    # the overflowing expert 0 is held elsewhere and draws nothing here;
    # expert 5, held, overflows by a tile and a half
    "overflow-held-elsewhere": dict(gated=True, B=32, E=16, first=4,
                                    held=8, hot=[0, 5], hot_rows=[32, 20]),
    "overflow-valid-mask": dict(gated=False, B=32, E=16, held=16, hot=[2],
                                masked=True),
    "overflow-bf16": dict(gated=True, dtype="bfloat16", D=128, H=128, B=32,
                          E=16, held=16, hot=[0, 3], hot_rows=[32, 21]),
}


@pytest.mark.parametrize("case", list(GROUPED_CASES))
def test_grouped_form_equals_the_dense_form(case, monkeypatch):
    """The routed pairs in their experts' slots against every row times
    every held expert: the same `y` within the operands' rounding, `pairs`
    and `aux` identical.  Tiles of 8 slots (the first round 8 or 12 an
    expert), so the cases run the first round alone to three tiles past
    it."""
    c = dict(dict(B=24, k=2, E=8, first=0, held=8, D=8, H=16,
                  dtype="float32", skew=False, masked=False, slots=8,
                  hot=(), hot_rows=None),
             **GROUPED_CASES[case])
    monkeypatch.setattr(moe, "_GROUP_SLOTS", c["slots"])
    rng = np.random.default_rng(sum(map(ord, case)))
    dtype = jnp.dtype(c["dtype"])
    B, E, h, D, H = c["B"], c["E"], c["held"], c["D"], c["H"]
    x = rng.normal(size=(B, D))
    w_r = rng.normal(size=(D, E))
    if c["skew"]:
        x[:, 0] = np.abs(x[:, 0]) + 3.0
        w_r[0, 0], w_r[0, 1] = 10.0, -10.0
    # a hot expert j draws the first hot_rows[j] rows (all of them without
    # `hot_rows`): feature j of those rows decides for it
    for j, e in enumerate(c["hot"]):
        n = B if c["hot_rows"] is None else c["hot_rows"][j]
        x[:, j] = np.where(np.arange(B) < n, 4.0 + 0.01 * j, -4.0)
        w_r[j] = 0.0
        w_r[j, e] = 10.0 * (D / 8) ** 0.5     # past the other features' sum
    x = jnp.asarray(x, dtype)
    shapes = [(h, D, H), (h, D, H), (h, H, D)] if c["gated"] \
        else [(h, D, H), (h, H), (h, H, D), (h, D)]
    experts = tuple(jnp.asarray(rng.normal(size=sh) * 0.3, dtype)
                    for sh in shapes)
    valid = None
    if c["masked"]:
        valid = jnp.asarray(rng.random(B) > (1.0 if c["masked"] == "all"
                                             else 0.3))
    kw = dict(top_k=c["k"], first_expert=c["first"], valid=valid)
    y_d, aux_d, pairs_d = moe_ffn(x, jnp.asarray(w_r, jnp.float32), experts,
                                  form="dense", **kw)
    y_g, aux_g, pairs_g = moe_ffn(x, jnp.asarray(w_r, jnp.float32), experts,
                                  form="grouped", **kw)
    assert y_g.dtype == y_d.dtype and y_g.shape == y_d.shape
    np.testing.assert_array_equal(pairs_g, pairs_d)
    assert float(aux_g) == float(aux_d)
    if c["skew"]:
        lo = c["first"]
        assert lo > 0 or bool(pairs_d[:, 0].all())      # draws every row
        assert not bool(pairs_d[:, 1 - lo].any())       # draws none
    if c["hot"]:
        # the case runs the overflow it names: the tiles the loop ran,
        # counted from the pairs as the engine's counter counts them
        lo, live = c["first"], np.ones(B, bool) if valid is None \
            else np.asarray(valid)
        rows = [live.sum() if c["hot_rows"] is None else n
                for n in c["hot_rows"] or [None] * len(c["hot"])]
        sizes = np.asarray(pairs_d).sum(axis=0)
        for e, n in zip(c["hot"], rows):
            if lo <= e < lo + h:
                assert sizes[e - lo] == n, (sizes, e, n)
        first = moe.first_round_slots(B, c["k"], E)
        assert first == c["slots"]
        tiles = np.asarray(moe.overflow_tiles(jnp.asarray(sizes, jnp.int32),
                                              first))
        want = [-(-max(n - first, 0) // c["slots"]) if lo <= e < lo + h
                else 0 for e, n in zip(c["hot"], rows)]
        assert [int(tiles[e - lo]) if lo <= e < lo + h else 0
                for e in c["hot"]] == want and tiles.sum() >= sum(want) > 0
    y_d, y_g = (np.asarray(y, np.float32) for y in (y_d, y_g))
    assert np.isfinite(y_g).all()
    if c["masked"] == "all":
        assert not y_g.any()
    # one rounding of the result apart (the dense form also rounds the
    # combine weights where the grouped form keeps them float32)
    eps = float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(y_g, y_d, rtol=4 * eps,
                               atol=4 * eps * float(np.abs(y_d).max()))


#: (rows, top_k, scored) x held: the MoE serve cells' step programs, bf16
RULE_AT_THE_CELLS = {
    "gigachat-decode-64x8": ((64, 8, 256), "dense"),
    "gigachat-mixed-128x8": ((128, 8, 256), "dense"),
    "kimi-decode-128x16": ((128, 8, 256), "dense"),
    "kimi-mixed-320x16": ((320, 8, 256), None),
    "lfm2-decode-256x64": ((256, 4, 64), None),
    "lfm2-mixed-512x64": ((512, 4, 64), "grouped"),
    # 68 pairs an expert on the mean: one first round of 192 slots, the
    # busiest experts' overflow in tiles (two or three whole rounds of 128
    # before PR 65)
    "xing-mixed-1088x64": ((1088, 4, 64), "grouped"),
    "xing-decode-48x64": ((48, 4, 64), "dense"),
    "laguna-mixed-320x256": ((320, 8, 256), None),
    "solar-mixed-320x40": ((320, 8, 320), None),
    "nemotron-mixed-512x32": ((512, 6, 128), "grouped"),
    "nemotron-decode-256x32": ((256, 6, 128), None),
}


@pytest.mark.parametrize("cell", list(RULE_AT_THE_CELLS))
def test_the_rule_at_the_cells_shapes(cell):
    """Dense under the ridge (the GigaChat cell's steps and Kimi's decode
    step, whatever the constant), grouped for LFM2's 512-row mixed step;
    the shapes in between go where `_GROUPED_OVER_RIDGE` says.  A
    `model`-axis mesh and training keep the dense form at every shape."""
    (rows, k, scored), want = RULE_AT_THE_CELLS[cell]
    if want is None:
        want = "grouped" if rows >= moe._GROUPED_OVER_RIDGE * \
            moe.ridge_rows(2) else "dense"
    assert moe.expert_form(rows, k, scored, 2) == want
    assert moe.expert_form(rows, k, scored, 2, partitioned=True) == "dense"
    assert moe.expert_form(rows, k, scored, 2, training=True) == "dense"


@pytest.mark.parametrize("cell", list(RULE_AT_THE_CELLS))
def test_the_first_rounds_slots_at_the_cells_shapes(cell):
    """One tile of 128 slots an expert at every cell's two step shapes but
    the Xing cell's mixed step, which takes a tile and a half (twice its 68
    pairs an expert on the mean; the measurement beside
    `_FIRST_ROUND_OVER_MEAN`); the rule counts a first round wider than the
    ridge as the reads it is worth."""
    (rows, k, scored), _ = RULE_AT_THE_CELLS[cell]
    assert moe._GROUP_SLOTS == 128
    want = 192 if cell == "xing-mixed-1088x64" else 128
    assert moe.first_round_slots(rows, k, scored) == want
    assert (2 * rows * k / scored <= 128) == (want == 128)
    # 100 pairs an expert on the mean want 256 slots, a second read's worth
    # of rows (the ridge is 240): 1,600 rows are past 1.25 x 2 x 240
    assert moe.first_round_slots(1600, 4, 64) == 256
    assert moe.expert_form(1600, 4, 64, 2) == "grouped"
    # 256 pairs an expert on the mean: 512 slots are three reads' worth,
    # and 512 rows are under 1.25 x 3 x 240
    assert moe.first_round_slots(512, 4, 8) == 512
    assert moe.expert_form(512, 4, 8, 2) == "dense"
    assert moe.expert_form(4096, 4, 64, 2) == "grouped"


def test_the_rule_as_the_layer_asks_it():
    """graph/layers_moe.py:expert_form_of reads the shapes off the layer's
    parameters; a mesh with a `model` axis keeps the dense form, a data
    mesh does not; float32 weights double the ridge; where so few experts
    are scored that the first round's slots pass the ridge and cost a
    second read's worth, or where a tile's slots would themselves pass the
    ridge (1-byte weights), dense stays."""
    from types import SimpleNamespace as NS
    from paddle_tpu.graph.layers_moe import expert_form_of
    from paddle_tpu.parallel.mesh import make_mesh
    cfg = NS(inputs=[NS(input_parameter_name="r"),
                     NS(input_parameter_name="g")], attrs={"top_k": 4})

    def params(dtype, scored=64):
        return {"r": jax.ShapeDtypeStruct((2048, scored), jnp.float32),
                "g": jax.ShapeDtypeStruct((scored, 2048, 1536), dtype)}

    assert expert_form_of(cfg, params(jnp.bfloat16), 512) == "grouped"
    assert expert_form_of(cfg, params(jnp.bfloat16), 512,
                          training=True) == "dense"
    assert expert_form_of(cfg, params(jnp.bfloat16), 512,
                          make_mesh(data=2, model=4)) == "dense"
    assert expert_form_of(cfg, params(jnp.bfloat16), 512,
                          make_mesh(data=8, model=1)) == "grouped"
    assert expert_form_of(cfg, params(jnp.float32), 512) == "dense"
    assert expert_form_of(cfg, params(jnp.float32), 1024) == "grouped"
    assert expert_form_of(cfg, params(jnp.bfloat16, scored=8),
                          512) == "dense"
    assert expert_form_of(cfg, params(jnp.float8_e4m3fn), 512) == "dense"


class TestMoeLayer:
    def test_dsl_layer_trains(self):
        from paddle_tpu.config.parser import parse_config_callable
        from paddle_tpu.dsl import (
            MomentumOptimizer, SoftmaxActivation, classification_cost,
            data_layer, fc_layer, moe_layer, settings,
        )
        from paddle_tpu.parameter.argument import Argument
        from paddle_tpu.trainer.trainer import Trainer

        def conf():
            settings(batch_size=16, learning_rate=0.05,
                     learning_method=MomentumOptimizer(momentum=0.9))
            x = data_layer(name="x", size=12)
            h = moe_layer(x, num_experts=4, expert_hidden=32)
            out = fc_layer(input=h, size=4, act=SoftmaxActivation())
            classification_cost(input=out, label=data_layer(name="y", size=4))

        tr = Trainer(parse_config_callable(conf), seed=0)
        rng = np.random.default_rng(0)

        def batch():
            x = rng.normal(size=(16, 12)).astype(np.float32)
            y = (x.sum(-1) > 0).astype(np.int32) * 3
            return {"x": Argument(value=jnp.asarray(x)),
                    "y": Argument(ids=jnp.asarray(y))}

        losses = [tr.train_one_batch(batch()) for _ in range(15)]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0], losses

    def test_dsl_layer_on_mesh(self):
        """Same config trains on a (data, model) mesh with expert params
        sharded by their partition specs."""
        from paddle_tpu.config.parser import parse_config_callable
        from paddle_tpu.dsl import (
            MomentumOptimizer, SoftmaxActivation, classification_cost,
            data_layer, fc_layer, moe_layer, settings,
        )
        from paddle_tpu.parallel.mesh import make_mesh
        from paddle_tpu.parameter.argument import Argument
        from paddle_tpu.trainer.trainer import Trainer

        def conf():
            settings(batch_size=16, learning_rate=0.05,
                     learning_method=MomentumOptimizer(momentum=0.9))
            x = data_layer(name="x", size=12)
            h = moe_layer(x, num_experts=4, expert_hidden=32)
            out = fc_layer(input=h, size=4, act=SoftmaxActivation())
            classification_cost(input=out, label=data_layer(name="y", size=4))

        mesh = make_mesh(data=2, model=4)
        tr = Trainer(parse_config_callable(conf), seed=0, mesh=mesh)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(16, 12)).astype(np.float32)
        y = rng.integers(0, 4, 16).astype(np.int32)
        loss = tr.train_one_batch({"x": Argument(value=jnp.asarray(x)),
                                   "y": Argument(ids=jnp.asarray(y))})
        assert np.isfinite(loss)
