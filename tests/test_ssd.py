"""The Mamba-2 recurrence alone (ops/ssd.py), on the CPU in float32: the
chunkwise form against the literal per-token scan at lengths that are and
are not multiples of the chunk, from the zero state and from a carried one;
and the Pallas step `ssd_step` (ops/pallas_kda.py: the body `kda_step` runs,
without the delta rule's correction) in interpret mode against the jnp step.
The layer, the slot parts and the engine are tests/test_nemotron_h.py's and
tests/test_nemotron_h_engine.py's."""

import numpy as np
import pytest


def _ssd_inputs(T, seed=0, B=2, H=4, P=16, G=2, N=16):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    Bm = jax.random.normal(ks[1], (B, T, G, N))
    Cm = jax.random.normal(ks[2], (B, T, G, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[4], (H,)) * 2.7)
    S0 = jax.random.normal(ks[5], (B, H, P, N))
    return x, Bm, Cm, dt, A, S0


@pytest.mark.parametrize("T", [8, 24, 5, 29, 1])
@pytest.mark.parametrize("start", ["zero", "state"])
def test_chunkwise_against_the_per_token_scan(T, start):
    """Lengths that are and are not multiples of the chunk (8), from the
    zero state and from a carried one: outputs and the state left."""
    import jax
    from paddle_tpu.ops import ssd
    x, Bm, Cm, dt, A, S0 = _ssd_inputs(T)
    S0 = None if start == "zero" else S0
    with jax.default_matmul_precision("highest"):
        y1, s1 = ssd.recurrent(x, Bm, Cm, dt, A, S0)
        y2, s2 = ssd.chunkwise(x, Bm, Cm, dt, A, S0, chunk=8)
    assert float(abs(y1 - y2).max()) < 2e-5
    assert float(abs(s1 - s2).max()) < 2e-5


@pytest.mark.parametrize("H,P,G", [(8, 16, 2), (32, 8, 8)],
                         ids=["one-group-a-block", "eight-groups-a-block"])
@pytest.mark.parametrize("rows", ["decode", "mixed-rows"])
def test_ssd_step_kernel_against_the_jnp_step(rows, H, P, G, monkeypatch):
    """Interpreted: the Pallas step (the body `kda_step` runs, without the
    delta-rule correction) against ops/ssd.py's jnp step — live rows agree,
    dead rows give zeros and leave every state, the trash row's too."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ssd
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    S, N = 5, 128
    x, Bm, Cm, dt, A, _ = _ssd_inputs(1, seed=3, B=S, H=H, P=P, G=G, N=N)
    x, Bm, Cm, dt = x[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0]
    state = jax.random.normal(jax.random.PRNGKey(9), (S + 1, H, P, N))
    live = jnp.asarray([True, False, True, True, False])
    slot = None if rows == "decode" else jnp.asarray([3, 0, 1, 4, 2],
                                                     jnp.int32)
    y1, s1 = ssd.step_rows(state, slot, live, x, Bm, Cm, dt, A)
    y2, s2 = ssd.step_rows(state, slot, live, x, Bm, Cm, dt, A,
                           use_kernel=True)
    assert float(abs(y1 - y2)[np.asarray(live)].max()) < 1e-5
    assert bool((y2[~np.asarray(live)] == 0).all())
    touched = np.asarray(jnp.arange(S) if slot is None else slot)[
        np.asarray(live)]
    assert float(abs(s1 - s2)[touched].max()) < 1e-5
    rest = [i for i in range(S + 1) if i not in touched]
    assert bool((s2[np.asarray(rest)] == state[np.asarray(rest)]).all())
