"""The comparisons that decide `correct`: the program against the plain
float32 reference on the run's own seeded weights, outside the timed window.

train  loss_rel   |program loss - reference loss| / reference loss on the
                  first batch, the program's number coming out of the real
                  train step (train_one_pass)
       grad_rel   ||g_program - g_reference|| / ||g_reference|| over every
                  parameter, on a seeded sample of sequences, the program's
                  gradient through its own executor.loss (flash forward and
                  backward, bf16 compute)
serve  margin     mean over the served greedy tokens of how far (nats) the
                  served token's reference log-probability trails the
                  reference's argmax, teacher-forced on prompt + served
                  tokens: prefill, paged cache and decode against ONE full
                  forward.  0 when every token is the reference's argmax.

The same functions take the control (the reference in fp8, put in the
program's place): benchmark/calibrate.py reads both on the chip and
tests/benchmark/test_reference.py keeps the control failing at a tiny size."""

from __future__ import annotations


def rel_err_tree(jax, a: dict, b: dict) -> float:
    """||a - b||_2 / ||b||_2 over two dicts of arrays (b the reference)."""
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        num = sum(jnp.sum((a[k].astype(jnp.float32) - b[k]) ** 2) for k in b)
        den = sum(jnp.sum(b[k] ** 2) for k in b)
        return jnp.sqrt(num / den)

    return float(f(a, b))


def served_margin(jax, ref, cfg: dict, w, served: list, pad_to: int,
                  quant: str = "") -> dict:
    """`served` is [(prompt ids, served new ids)].  Returns the mean and the
    largest margin, the share of tokens equal to the reference's argmax and
    the number of tokens held.  With `quant` the served tokens are NOT used:
    the reference in that precision decides the tokens (teacher-forced on
    the same contexts) — the control."""
    import jax.numpy as jnp
    import numpy as np

    lp_ref = ref.jitted("log_probs", cfg)
    lp_ctl = ref.jitted("log_probs", cfg, quant) if quant else None

    @jax.jit
    def margins(w, ids, rows, toks):
        lp = lp_ref(w, ids, rows)
        if lp_ctl is not None:
            toks = jnp.argmax(lp_ctl(w, ids, rows), axis=-1)
        got = jnp.take_along_axis(lp, toks[:, None], axis=1)[:, 0]
        return jnp.max(lp, axis=-1) - got

    total = n = exact = 0.0
    worst = 0.0
    for prompt, new in served:
        seq = list(prompt) + list(new)
        if len(seq) > pad_to:
            raise ValueError(f"a checked sequence has {len(seq)} tokens, the "
                             f"reference is compiled for {pad_to}")
        k = len(new)
        ids = np.zeros(pad_to, np.int32)
        ids[:len(seq)] = seq
        # the row that predicts new token j is position len(prompt) + j - 1
        rows = np.zeros(pad_to, np.int32)
        rows[:k] = np.arange(len(prompt) - 1, len(prompt) - 1 + k)
        toks = np.zeros(pad_to, np.int32)
        toks[:k] = new
        with jax.default_matmul_precision("highest"):
            m = np.asarray(margins(w, jnp.asarray(ids), jnp.asarray(rows),
                                   jnp.asarray(toks)))[:k]
        total += float(m.sum())
        worst = max(worst, float(m.max()))
        exact += float((m == 0).sum())
        n += k
    return {"mean_nats": total / n, "worst_nats": worst,
            "argmax_share": exact / n, "tokens": int(n)}
