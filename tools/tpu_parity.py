"""On-device Pallas parity checks — run on a REAL TPU (one process, one chip).

The interpret-mode oracles (tests/test_pallas_attention.py,
tests/test_additive_attention.py, tests/test_serving.py) validate the math
and tests/test_mosaic_compile.py asks the chip's compiler; this RUNS the
kernels on hardware and compares against the jnp / lax.scan references at
the widths the demos use plus the Mosaic-risk shapes (bf16 sublane
minimums, short/unaligned sequences).  The reference side runs on the host
CPU backend at true-fp32 matmul precision.

Prints one JSON line per case (with its max abs error) and a final
`{"all_ok": ...}` line; exit 0 iff every case passed.  Off the chip it
exits 1 at once — `--interpret` is the CPU rehearsal (Pallas interpret
mode; use `--only=` to pick small cases) and says so in its output.

  python tools/tpu_parity.py [--only=flash,paged] [--interpret]
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

_ORACLE_DEV = None   # the host-CPU device every reference runs on (main)


def _oracle(fn, *args):
    """Run the reference side on the host CPU backend at true-fp32 matmul
    precision (under --interpret everything already is the CPU)."""
    with jax.default_device(_ORACLE_DEV), \
            jax.default_matmul_precision("highest"):
        out = fn(*args)
        return jax.tree.map(np.asarray, out)


def _oracle_scan(fn, *args):
    """_oracle + forced lax.scan path: lstm_scan/gru_scan self-route to the
    pallas kernels (ops/rnn.py:_use_fused), which would compare the kernel
    against itself — PADDLE_TPU_PALLAS=0 pins the reference to the scan."""
    prev = os.environ.get("PADDLE_TPU_PALLAS")
    os.environ["PADDLE_TPU_PALLAS"] = "0"
    try:
        return _oracle(fn, *args)
    finally:
        if prev is None:
            os.environ.pop("PADDLE_TPU_PALLAS", None)
        else:
            os.environ["PADDLE_TPU_PALLAS"] = prev


def _case(name, fn) -> bool:
    """Run one case; print {"case", "ok", "max_err" | "error"}."""
    rec = {"case": name}
    try:
        rec["max_err"] = fn()
        rec["ok"] = True
    except Exception as e:   # noqa: BLE001 — reported and counted as FAILED
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    print(json.dumps(rec), flush=True)
    return rec["ok"]


def _close(got, want, tol) -> float:
    """assert_allclose(rtol=atol=tol) in fp32; returns the max abs error."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    return float(np.max(np.abs(got - want))) if got.size else 0.0


def _close_scaled(got, want, tol) -> float:
    """max|got - want| <= tol * max|want|; returns the normalized error.
    For arrays whose error scales with their magnitude (gradients summed
    over B*T steps): an elementwise atol would judge the near-zero elements
    of a scale-60 array against a scale-1 bar."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))
    assert np.isfinite(got).all() and err <= tol, \
        f"normalized max error {err:.3e} > {tol} (scale " \
        f"{float(np.max(np.abs(want))):.3g})"
    return err


def _seed(name: str) -> int:
    """Stable per-case data seed derived from the case NAME (shape+dtype),
    not its list position — inserting/reordering cases must not silently
    change what data an already-validated case reruns on."""
    import zlib
    return zlib.crc32(name.encode()) % 100000


def flash_cases():
    from paddle_tpu.ops import pallas_attention
    from paddle_tpu.ops.attention import dot_product_attention

    cases = []
    # the Mosaic-risk shapes (short / unaligned) first, then the LM trainer's
    #       B, T,    H, H_kv, D,  dtype,     causal, tol
    shapes = [
        (1, 7, 2, 2, 64, jnp.bfloat16, False, 3e-2),     # T < 16 (bf16 min)
        (2, 300, 4, 4, 80, jnp.float32, True, 2e-3),     # T,D unaligned
        (2, 256, 2, 2, 256, jnp.bfloat16, True, 3e-2),   # head dim > one lane
        #                                                  tile
        (2, 512, 4, 4, 64, jnp.float32, True, 2e-3),
        (2, 1024, 8, 8, 64, jnp.bfloat16, True, 3e-2),
        (64, 512, 8, 8, 64, jnp.bfloat16, True, 3e-2),   # the LM train step
        (8, 512, 8, 2, 64, jnp.bfloat16, True, 3e-2),    # grouped-query
    ]
    for B, T, H, H_kv, D, dt, causal, tol in shapes:
        name = (f"flash_B{B}_T{T}_H{H}_D{D}_{jnp.dtype(dt).name}"
                f"{'_causal' if causal else ''}"
                f"{f'_kv{H_kv}' if H_kv != H else ''}")

        def run(name=name, B=B, T=T, H=H, H_kv=H_kv, D=D, dt=dt,
                causal=causal, tol=tol):
            # per-case seed from the NAME: an --only-filtered rerun or a
            # reordered matrix must see the same data as the full suite
            rng = np.random.default_rng(_seed(name))
            q = jnp.asarray(rng.normal(size=(B, T, H, D)), dt)
            k = jnp.asarray(rng.normal(size=(B, T, H_kv, D)), dt)
            v = jnp.asarray(rng.normal(size=(B, T, H_kv, D)), dt)
            got = jax.jit(lambda q, k, v: pallas_attention.flash_attention(
                q, k, v, causal=causal))(q, k, v)
            # fp32 reference at true-fp32 matmul precision ON THE HOST CPU:
            # the kernel runs its fp32 dots at HIGHEST, so the dense bar
            # must not carry the MXU's default single-bf16-pass rounding
            want = _oracle(lambda q, k, v: dot_product_attention(
                q, k, v, causal=causal), q, k, v)
            err = _close(got, want, tol)
            # backward compiles + matches
            g1 = jax.jit(jax.grad(
                lambda q: jnp.sum(pallas_attention.flash_attention(
                    q, k, v, causal=causal).astype(jnp.float32))))(q)
            g2 = _oracle(lambda q: jax.grad(
                lambda q: jnp.sum(dot_product_attention(
                    q, k, v, causal=causal).astype(jnp.float32)))(q), q)
            return {"fwd": err, "dq": _close(g1, g2, tol * 5)}
        cases.append((name, run))
    return cases


def paged_cases():
    """The serving engine's decode hot path: the Pallas ragged-paged kernel
    against the jnp page-gather read of the SAME step function —
    one-token-per-slot decode and the row-indirected mixed form — at the
    smoke's engine shape (16 slots, page 16, context 768, 8 kv heads x 64
    stored as they are: the kernel pads their lanes) and at the serve
    cells' own pool rows (`kv_row_shape`): StarCoder2's and Nemotron's
    [P, 16, 2, 128] (a page's copy crosses from the pool's (2,128) tiles to
    the kernel's dense operand rows: only the chip can say it is exact),
    LFM2's packed [P, 16, 4, 128], a table shorter than a block,
    Jamba's lone head stored two tokens a row, [P, 8, 2, 128],
    Solar-Open2's 64 query heads over 8 KV heads of 128, [P, 16, 8, 128],
    Laguna's 48 over 8 (groups of 6) with its window layers' call, and
    Olmo-Hybrid's group size ONE, 30 heads on 30 stored as [P, 16, 32,
    128].  Wherever a row holds several heads a RUN of rows (the mixed
    cases' chunk) reads them one at a time through strided uint32 loads of
    the block in VMEM (`split_heads`): only the chip can say those are
    exact."""
    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)
    from paddle_tpu.ops.pallas_paged import kv_page_shape, kv_row_shape

    ps = 16
    dt, tol = jnp.bfloat16, 3e-2

    def build(S, ctx, H, h_kv, D, row, tag, page=None, **kw):
        maxp = ctx // ps
        page = page or (ps,) + row

        def pool(rng):
            P = S * maxp + 1                       # + the trash page 0
            kp = jnp.asarray(rng.normal(size=(P,) + page), dt)
            vp = jnp.asarray(rng.normal(size=(P,) + page), dt)
            # every slot owns maxp distinct physical pages, shuffled
            table = (rng.permutation(S * maxp) + 1).reshape(S, maxp)
            return kp, vp, jnp.asarray(table, jnp.int32)

        def edges(pos):
            # a row of one token, rows that end on a page's and a block's
            # last token and on the first of the next, a full table
            for i, p in enumerate((0, ps - 1, 127, 128, 255, 256, ctx - 1)):
                pos[i % len(pos)] = min(p, ctx - 1)
            return pos

        def run_decode():
            rng = np.random.default_rng(_seed(f"paged_decode{tag}"))
            kp, vp, table = pool(rng)
            pos = jnp.asarray(edges(rng.integers(0, ctx, S)), jnp.int32)
            q, k, v = (jnp.asarray(rng.normal(size=(S, 1, h, D)), dt)
                       for h in (H, h_kv, h_kv))

            def step(use_kernel):
                return jax.jit(lambda *a: paged_attention_step(
                    *a, use_kernel=use_kernel, **kw)[0])(
                        q, k, v, kp, vp, table, pos)

            return {"out": _close(step(True), _oracle(lambda: step(False)),
                                  tol)}

        def run_mixed():
            rng = np.random.default_rng(_seed(f"paged_mixed{tag}"))
            kp, vp, table = pool(rng)
            T = 4 * ps + S                          # default max_step_tokens
            # one 64-token prompt chunk on slot 0 (it crosses a block's
            # edge where the context allows), then one decode row a slot
            start = min(224, ctx - 4 * ps - 1)
            row_slot = np.concatenate([np.zeros(4 * ps, np.int32),
                                       np.arange(S, dtype=np.int32)])
            dec = rng.integers(min(200, ctx - 1), ctx, S)
            # slot 0's decode row is the token after its chunk
            dec[0], dec[1:] = start + 4 * ps, edges(dec[1:])
            row_pos = np.concatenate([start + np.arange(4 * ps),
                                      dec]).astype(np.int32)
            q, k, v = (jnp.asarray(rng.normal(size=(T, h, D)), dt)
                       for h in (H, h_kv, h_kv))

            def step(use_kernel):
                return jax.jit(lambda *a: ragged_paged_attention_step(
                    *a, use_kernel=use_kernel, **kw)[0])(
                        q, k, v, kp, vp, table, jnp.asarray(row_slot),
                        jnp.asarray(row_pos))

            return {"out": _close(step(True), _oracle(lambda: step(False)),
                                  tol)}

        return [(f"paged_decode{tag}_S{S}_ctx{ctx}_bf16", run_decode),
                (f"paged_mixed{tag}_T{4 * ps + S}_ctx{ctx}_bf16", run_mixed)]

    return (build(16, 768, 8, 8, 64, (8, 64), "")
            + build(32, 2048, 24, 2, 128, kv_row_shape(2, 128), "_kv2x128")
            + build(32, 2048, 32, 8, 64, kv_row_shape(8, 64), "_packed4x128")
            + build(32, 2048, 32, 2, 128, kv_row_shape(2, 128),
                    "_groups_of_16")
            + build(8, 128, 24, 2, 128, kv_row_shape(2, 128), "_short_table")
            # Jamba's lone KV head of 128 under 20 query heads, stored two
            # tokens a sublane row ([P, 8, 2, 128]: kv_page_shape)
            + build(32, 2048, 20, 1, 128, kv_row_shape(1, 128),
                    "_lone_head_paired", page=kv_page_shape(ps, 1, 128, 2))
            # Solar-Open2's 8 groups of 8: 64 query rows a slot
            + build(32, 2048, 64, 8, 128, kv_row_shape(8, 128),
                    "_64q_over_8kv")
            # Laguna's full layers (groups of 6) and its window layers' call
            + build(32, 2048, 48, 8, 128, kv_row_shape(8, 128),
                    "_48q_over_8kv")
            + build(32, 2048, 64, 8, 128, kv_row_shape(8, 128),
                    "_64q_over_8kv_window512", window=512)
            # Olmo-Hybrid's group size one: 30 heads stored as 32
            + build(24, 2048, 30, 30, 128, kv_row_shape(30, 128),
                    "_30q_on_30kv"))


def kda_cases():
    """`kda_seg` against the literal recurrence (below), and
    `kda_step` (ops/pallas_kda.py) against the jnp step of ops/kda.py at
    the two cells' head counts, 128 rows of 128 x 128 float32 state: Kimi's
    32 heads with beta in (0, 1) and Solar-Open2's 64 (`head_block` 16:
    four grid steps a row) with beta near 2 — unit-norm q and k, a paused
    row and a slot indirection among the rows."""
    from paddle_tpu.ops import kda

    def build(H, neg_eigval, tag):
        def run():
            R, d = 128, 128
            rng = np.random.default_rng(_seed(f"kda_step{tag}"))
            f = lambda *shape: jnp.asarray(rng.normal(size=shape),
                                           jnp.float32)
            state = f(R + 1, H, d, d)
            q, k = kda.l2norm(f(R, H, d)), kda.l2norm(f(R, H, d))
            v, g = f(R, H, d), -jnp.exp(f(R, H, d) - 3.0)
            beta = (2.0 * jax.nn.sigmoid(6.0 + 0.5 * f(R, H)) if neg_eigval
                    else jax.nn.sigmoid(f(R, H)))
            live = jnp.asarray(rng.random(R) > 0.1)
            slot = jnp.asarray(rng.permutation(R), jnp.int32)

            def step(use_kernel):
                return jax.jit(lambda *a: kda.step_rows(
                    *a, use_kernel=use_kernel))(state, slot, live, q, k, v,
                                                g, beta)

            got, want = step(True), _oracle(lambda: step(False))
            rows = np.asarray(live)
            return {"out": _close(np.asarray(got[0])[rows], want[0][rows],
                                  2e-4),
                    "state": _close(got[1][:R], want[1][:R], 2e-4)}
        return [(f"kda_step{tag}_R128_H{H}_f32", run)]

    def build_seg(H, neg_eigval, tag):
        """`kda_seg` (ops/pallas_kda_seg.py) against the literal recurrence
        (`kda.recurrent`, the host's float32) over each run: 192 chunk rows
        in three runs — 70 rows from position 0, one row, 100 rows
        continuing a state — and 21 rows of padding."""
        def run():
            P, S, d = 192, 128, 128
            rng = np.random.default_rng(_seed(f"kda_seg{tag}"))
            f = lambda *shape: jnp.asarray(rng.normal(size=shape),
                                           jnp.float32)
            state = f(S + 1, H, d, d)
            q, k = kda.l2norm(f(P, H, d)), kda.l2norm(f(P, H, d))
            v, g = f(P, H, d), -jnp.exp(f(P, H, d) - 3.0)
            beta = (2.0 * jax.nn.sigmoid(6.0 + 0.5 * f(P, H)) if neg_eigval
                    else jax.nn.sigmoid(f(P, H)))
            runs = [(5, 0, 70, 0), (77, 70, 1, 9), (2, 71, 100, 300)]
            slot, pos = np.full(P, S, np.int32), np.zeros(P, np.int32)
            for s, at, n, p0 in runs:
                slot[at:at + n], pos[at:at + n] = s, np.arange(p0, p0 + n)
            o, new, n_seg = jax.jit(lambda *a: kda.segment_rows(
                *a, use_kernel=True))(state, jnp.asarray(slot),
                                      jnp.asarray(pos), q, k, v, g, beta)
            assert int(n_seg) == len(runs)
            o, new = np.asarray(o), np.asarray(new)
            out = {"out": 0.0, "state": 0.0}
            for s, at, n, p0 in runs:
                xs = [np.asarray(a)[None, at:at + n]
                      for a in (q, k, v, g, beta)]
                S0 = None if p0 == 0 else np.asarray(state)[s][None]
                want_o, want_S = _oracle(
                    lambda: kda.recurrent(*map(jnp.asarray, xs),
                                          None if S0 is None
                                          else jnp.asarray(S0)))
                out["out"] = max(out["out"],
                                 _close(o[at:at + n], want_o[0], 2e-4))
                out["state"] = max(out["state"],
                                   _close(new[s], want_S[0], 2e-4))
            idle = np.setdiff1d(np.arange(S + 1), [r[0] for r in runs])
            assert (new[idle] == np.asarray(state)[idle]).all()
            assert not o[slot == S].any()
            return out
        return [(f"kda_seg{tag}_P192_H{H}_f32", run)]

    return build(32, False, "_beta_under_1") + build(64, True, "_beta_near_2") \
        + build_seg(32, False, "_beta_under_1") \
        + build_seg(64, True, "_beta_near_2")


def gdn_cases():
    """`gdn_step` and `gdn_seg` — the delta rule with ONE decay a head on a
    rectangular state — at the Olmo-Hybrid cell's shapes: 24 slots, 30 heads
    of 96 x 192 float32, beta near 2.  The step against the jnp step (a
    paused row and a slot indirection among its 24 rows); the segments
    against the literal recurrence (`kda.recurrent`, the host's float32)
    over each run of a mixed step's 1,024 chunk rows: 512 rows continuing a
    state, 300 rows from position 0, one row, and 211 rows of padding."""
    from paddle_tpu.ops import kda
    H, dk, dv, S = 30, 96, 192, 24

    def operands(name, rows):
        rng = np.random.default_rng(_seed(name))
        f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
        state = f(S + 1, H, dk, dv)
        q, k = kda.l2norm(f(rows, H, dk)), kda.l2norm(f(rows, H, dk))
        v, g = f(rows, H, dv), -jnp.exp(f(rows, H) - 3.0)
        beta = 2.0 * jax.nn.sigmoid(6.0 + 0.5 * f(rows, H))
        return rng, state, (q, k, v, g, beta)

    def step_case():
        rng, state, xs = operands("gdn_step", S)
        live = jnp.asarray(rng.random(S) > 0.1)
        slot = jnp.asarray(rng.permutation(S), jnp.int32)

        def step(use_kernel):
            return jax.jit(lambda *a: kda.step_rows(
                *a, use_kernel=use_kernel))(state, slot, live, *xs)

        got, want = step(True), _oracle(lambda: step(False))
        rows = np.asarray(live)
        return {"out": _close(np.asarray(got[0])[rows], want[0][rows], 2e-4),
                "state": _close(got[1][:S], want[1][:S], 2e-4)}

    def seg_case():
        P = 1024
        _, state, xs = operands("gdn_seg", P)
        runs = [(5, 0, 512, 4096), (17, 512, 300, 0), (2, 812, 1, 77)]
        slot, pos = np.full(P, S, np.int32), np.zeros(P, np.int32)
        for s, at, n, p0 in runs:
            slot[at:at + n], pos[at:at + n] = s, np.arange(p0, p0 + n)
        o, new, n_seg = jax.jit(lambda *a: kda.segment_rows(
            *a, use_kernel=True))(state, jnp.asarray(slot), jnp.asarray(pos),
                                  *xs)
        assert int(n_seg) == len(runs)
        o, new = np.asarray(o), np.asarray(new)
        out = {"out": 0.0, "state": 0.0}
        for s, at, n, p0 in runs:
            part = [np.asarray(a)[None, at:at + n] for a in xs]
            S0 = None if p0 == 0 else jnp.asarray(np.asarray(state)[s][None])
            want_o, want_S = _oracle(
                lambda: kda.recurrent(*map(jnp.asarray, part), S0))
            out["out"] = max(out["out"], _close(o[at:at + n], want_o[0], 2e-4))
            out["state"] = max(out["state"], _close(new[s], want_S[0], 2e-4))
        idle = np.setdiff1d(np.arange(S + 1), [r[0] for r in runs])
        assert (new[idle] == np.asarray(state)[idle]).all()
        assert not o[slot == S].any()
        return out

    return [("gdn_step_R24_H30_96x192_f32", step_case),
            ("gdn_seg_P1024_H30_96x192_f32", seg_case)]


def mhc_cases():
    """`mhc_mix` (ops/pallas_hyper_conn.py) against the jnp stream pass of
    ops/hyper_conn.py at the Xing4.0 cell's shapes: a mixed step's 1,088
    rows (tiles of 32) and a decode step's 48 (tiles of 16) of 4 streams of
    3,584, bfloat16 streams under float32 maps from the seeded spread, and
    float32 streams."""
    from paddle_tpu.ops import hyper_conn, pallas_hyper_conn

    def build(rows, dtype):
        name = f"mhc_mix_R{rows}_n4_C3584_{dtype}"

        def run():
            n, c = 4, 3584
            rng = np.random.default_rng(_seed(name))
            f = lambda *shape: jnp.asarray(rng.normal(size=shape),
                                           jnp.float32)
            x, y = f(rows, n * c).astype(dtype), f(rows, c).astype(dtype)
            m = hyper_conn.maps(
                x, 0.02 * f(n * c, 24), f(1, 24),
                jnp.asarray([[1.0, 0.9, 1.1]], jnp.float32), n=n, iters=20,
                eps=1e-6, clamp=(-30.0, 30.0))
            got = pallas_hyper_conn.mhc_mix(x, y, m, n)
            want = _oracle(hyper_conn.mix, x, y, m, n)
            assert got.dtype == x.dtype
            # float32: the same five terms in another order; bfloat16: one
            # rounding of a sum of magnitude up to 4
            return _close(got, want, 1e-5 if dtype == "float32" else 2e-2)
        return name, run

    return [build(r, d) for r in (1088, 48)
            for d in ("bfloat16", "float32")]


def additive_cases():
    from paddle_tpu.ops import pallas_additive
    from paddle_tpu.ops.attention import additive_attention_step as ref

    cases = []
    shapes = [
        (64, 30, 512, 512, 512, jnp.bfloat16, 8e-2),  # the seq2seq shape
        # ... and its fp32 form.  2e-3, not the 2e-4 of the small cases:
        # at D=512 the scores are ~sqrt(D) large, so the softmax turns the
        # in-kernel dots' rounding (Mosaic runs an fp32 HIGHEST dot as
        # multi-pass bf16) into ~1e-3 of output; measured on v5e max abs
        # 1.1e-3, 1.4% of elements over 2e-4 (PERF.md, PR 23)
        (64, 30, 512, 512, 512, jnp.float32, 2e-3),
        (5, 7, 11, 19, 13, jnp.float32, 2e-4),        # everything unaligned
        (3, 5, 8, 16, 16, jnp.bfloat16, 8e-2),        # T < 16 bf16
    ]
    for B, T, Ds, D, Dv, dt, tol in shapes:
        name = f"additive_B{B}_T{T}_D{Ds}x{D}x{Dv}_{jnp.dtype(dt).name}"

        def run(name=name, B=B, T=T, Ds=Ds, D=D, Dv=Dv, dt=dt, tol=tol):
            rng = np.random.default_rng(_seed(name))
            dec = jnp.asarray(rng.normal(size=(B, Ds)), dt)
            w = jnp.asarray(rng.normal(size=(Ds, D)) * 0.2, dt)
            v = jnp.asarray(rng.normal(size=(D,)), dt)
            proj = jnp.asarray(rng.normal(size=(B, T, D)), dt)
            seq = jnp.asarray(rng.normal(size=(B, T, Dv)), dt)
            lens = rng.integers(1, T + 1, B).astype(np.int32)
            mask = jnp.arange(T)[None, :] < jnp.asarray(lens)[:, None]
            got = jax.jit(pallas_additive.additive_attention_step)(
                dec, w, v, proj, seq, mask)
            # oracle in fp32 on the host CPU: the kernel keeps everything
            # fp32 internally, so bf16 cases compare against the fp32 math
            # with a bf16-rounding tolerance (the bf16-throughout jnp path
            # is the NOISIER of the two)
            want = _oracle(lambda *a: ref(*a, mask),
                           *(x.astype(jnp.float32)
                             for x in (dec, w, v, proj, seq)))
            return {"out": _close(got, want, tol)}
        cases.append((name, run))
    return cases


def rnn_cases():
    """Pallas LSTM/GRU vs the lax.scan reference, fwd + grads, on device.
    Both paths compute fp32 internally; tolerance covers MXU pass-order
    differences between the kernel's per-step matmul and the scan's.
    Gradients are held to 5e-3 of their array's max magnitude: their error
    grows with B*T and with the gradient's scale (measured on v5e at
    B128 T100 D512: 0.068 abs on a scale-60 array, 4 of 1M elements over
    an elementwise 0.05 — the tail of rounding noise, not a fault).

    Recurrent weights are 1/sqrt(D)-scaled (standard init): a fixed 0.2
    std at D=512 puts the backward recurrence in an exploding-gradient
    regime (per-step gain > 1) where fp32 op-ordering differences amplify
    exponentially and NO two fp32 implementations agree."""
    from paddle_tpu.ops import pallas_rnn, rnn

    cases = []
    #        B,  T,  D,   kinds
    shapes = [
        (4, 6, 8, ("lstm", "gru")),        # tiny/unaligned
        (5, 7, 24, ("lstm", "gru")),       # everything unaligned
        (128, 100, 512, ("lstm",)),        # the sentiment demo's LSTM
        (64, 30, 512, ("lstm", "gru")),    # the seq2seq GRU shape
    ]

    def grads_close(gf, gr):
        return max(_close_scaled(a, b, 5e-3) for a, b in zip(gf, gr))

    for B, T, D, kinds in shapes:
        lstm_name = f"lstm_B{B}_T{T}_D{D}"
        gru_name = f"gru_B{B}_T{T}_D{D}"

        def run_lstm(name=lstm_name, B=B, T=T, D=D):
            rng = np.random.default_rng(_seed(name))
            x4 = jnp.asarray(rng.standard_normal((B, T, 4 * D)) * 0.5,
                             jnp.float32)
            w = jnp.asarray(rng.standard_normal((D, 4 * D)) * D ** -0.5,
                            jnp.float32)
            lens = jnp.asarray(rng.integers(1, T + 1, B), jnp.int32)
            z = jnp.zeros((B, D), jnp.float32)
            peeps = jnp.zeros((3, D), jnp.float32)

            def fused(x4, w):
                hs, hl, cl = pallas_rnn.lstm_fused(
                    x4, lens, w, peeps, z, z, active_type="tanh",
                    gate_active_type="sigmoid", state_active_type="tanh",
                    reverse=False)
                return jnp.sum(hs * hs) + jnp.sum(hl) + jnp.sum(cl * cl)

            def ref(x4, w):
                hs, hl, cl = rnn.lstm_scan(x4, lens, w, None, reverse=False)
                return jnp.sum(hs * hs) + jnp.sum(hl) + jnp.sum(cl * cl)

            lf, gf = jax.jit(jax.value_and_grad(fused, argnums=(0, 1)))(
                x4, w)
            lr, gr = _oracle_scan(jax.value_and_grad(ref, argnums=(0, 1)),
                                  x4, w)
            np.testing.assert_allclose(float(lf), float(lr), rtol=2e-2)
            return {"loss_rel": abs(float(lf) - float(lr)) / abs(float(lr)),
                    "grads_rel_to_scale": grads_close(gf, gr)}

        def run_gru(name=gru_name, B=B, T=T, D=D):
            rng = np.random.default_rng(_seed(name))
            x3 = jnp.asarray(rng.standard_normal((B, T, 3 * D)) * 0.5,
                             jnp.float32)
            wg = jnp.asarray(rng.standard_normal((D, 2 * D)) * D ** -0.5,
                             jnp.float32)
            wc = jnp.asarray(rng.standard_normal((D, D)) * D ** -0.5,
                             jnp.float32)
            lens = jnp.asarray(rng.integers(1, T + 1, B), jnp.int32)
            z = jnp.zeros((B, D), jnp.float32)

            def fused(x3, wg, wc):
                hs, hl = pallas_rnn.gru_fused(
                    x3, lens, wg, wc, z, active_type="tanh",
                    gate_active_type="sigmoid", reverse=False)
                return jnp.sum(hs * hs) + jnp.sum(hl)

            def ref(x3, wg, wc):
                hs, hl = rnn.gru_scan(x3, lens, wg, wc, None, reverse=False)
                return jnp.sum(hs * hs) + jnp.sum(hl)

            lf, gf = jax.jit(jax.value_and_grad(
                fused, argnums=(0, 1, 2)))(x3, wg, wc)
            lr, gr = _oracle_scan(
                jax.value_and_grad(ref, argnums=(0, 1, 2)), x3, wg, wc)
            np.testing.assert_allclose(float(lf), float(lr), rtol=2e-2)
            return {"loss_rel": abs(float(lf) - float(lr)) / abs(float(lr)),
                    "grads_rel_to_scale": grads_close(gf, gr)}

        if "lstm" in kinds:
            cases.append((lstm_name, run_lstm))
        if "gru" in kinds:
            cases.append((gru_name, run_gru))
    return cases


def _build_selected(only):
    selected = [(name, fn)
                for build in (flash_cases, paged_cases, kda_cases, gdn_cases,
                              mhc_cases,
                              additive_cases, rnn_cases)
                for name, fn in build()
                if not only or any(name.startswith(o) for o in only)]
    names = [n for n, _ in selected]
    assert len(names) == len(set(names)), (
        f"duplicate parity case names "
        f"{sorted(set(n for n in names if names.count(n) > 1))} — the name "
        f"is the data seed, so every case must encode its full "
        f"distinguishing shape in its name")
    return selected


def main(argv=None) -> int:
    global _ORACLE_DEV
    only: list[str] = []
    interpret = False
    for a in (sys.argv[1:] if argv is None else argv):
        if a.startswith("--only="):
            only = [p for p in a.split("=", 1)[1].split(",") if p]
        elif a == "--interpret":
            interpret = True
        else:
            print(f"unknown argument {a!r}", file=sys.stderr)
            return 2

    selected = _build_selected(only)
    if not selected:   # a typo'd --only must not produce a vacuous green
        print(json.dumps({"all_ok": False,
                          "error": f"--only={only} matched no cases"}))
        return 1

    # the host CPU backend must coexist with the TPU so the reference side
    # of every case runs there; the first-listed platform stays the default
    cur = jax.config.jax_platforms
    if cur and "cpu" not in cur.split(","):
        jax.config.update("jax_platforms", cur + ",cpu")
    dev = jax.devices()[0]
    if interpret:
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    elif dev.platform != "tpu":
        print(f"tpu_parity: no TPU (platform={dev.platform!r}); "
              f"--interpret is the CPU rehearsal", file=sys.stderr)
        return 1
    _ORACLE_DEV = jax.devices("cpu")[0]
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "mode": "interpret-rehearsal" if interpret
                      else "on-chip", "n_cases": len(selected)}),
          flush=True)

    ok = True
    for name, fn in selected:
        ok &= _case(name, fn)
    print(json.dumps({"all_ok": bool(ok), "n_cases": len(selected)}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
