"""Plain reference for Nemotron-H (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-
BF16, model_type nemotron_h; arXiv:2504.03624, Mamba-2 arXiv:2405.21060):
the forward pass in straightforward jax.numpy, float32 arithmetic under
jax.default_matmul_precision("highest") — no kernels, no cache, no slot
state, no chunks.

  block   x <- x + Mixer_i(RMSNorm(x)), eps 1e-5, EVERY layer one mixer, by
          the letter of `pattern` (the published `hybrid_override_pattern`
          from `first_layer`, counting from 1, on): M, E or *; a final
          RMSNorm; an untied head (tie_word_embeddings false).
  M       Mamba-2, H = 64 heads of P = 64, state N = 128, G = 8 groups of
          8 heads, d_in = H P = 4096:
          [z_t, xBC_t, dt_t] = u_t W_in   (4096 + 6144 + 64 columns)
          xBC'_t = silu(b + sum_{j=0..3} w_j * xBC_{t-3+j}) a channel — a
            literal sum over four shifted copies, zeros before position 0
          x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC'_t)
          dt_t = softplus(dt_t + dt_bias) a head (no clamp: time_step_limit
            is (0, inf));  a_t = exp(dt_t A),  A = -exp(A_log) a head
          S_t[h] = a_t[h] S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)] — THE
            RECURRENCE A LITERAL PER-TOKEN lax.scan OVER S [H, P, N]
          y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
          v_t = y_t * silu(z_t), RMS-normed over each of the 8 groups of
            512 channels (eps 1e-5), times a 4096-wide scale;  v_t W_out
  *       q = x W_q (32 heads of 128), k = x W_k, v = x W_v (2 heads of
          128), no bias, NO ROTATION (`attn_use_rope` false: assumed, see
          the configuration file); each group of 16 query heads reads one
          KV head; scores * 128^-1/2; a full softmax over a causal mask,
          in blocks of query rows; concat_h(P v) W_o
  E       s = sigmoid(x W_r) in float32 over all n_routed_experts; the top
          num_experts_per_tok of s + b (n_group 1: no group limit);
          weights s_i / (sum(s_selected) + 1e-20) * routed_scaling_factor
          (from s, not s + b); y = sum over selected AND HELD experts of
          w_i relu(x W_up_i)^2 W_down_i, a loop over the held experts with
          a mask, no biases; plus the shared expert relu(x W_su)^2 W_sd,
          whole

Departures (the configuration file lists them too):
  * `experts_held` / `ep_rank` cut the experts as one expert-parallel
    rank's share: only the `experts_held` experts from `ep_rank *
    experts_held` on have weights, what the others would add is left out
    (tests/test_nemotron_h.py adds four 1/4 shares and the shared expert
    counted once up to the uncut layer);
  * none other known.

The weights are the benchmark's: made here from the seed, on the device, in
one jitted call, in the dtype the configuration stores them in (bfloat16),
under the names benchmark/configs/nemotron_h.py gives its parameters.
Every matmul takes them up to float32.  `quant=` puts a lower precision in
every MATMUL's place — the control that `correct` has to refuse (fp8 e4m3
with a per-tensor scale, the step below the configuration's bfloat16)."""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

ATTN_BLOCK = 256        # query rows a block: [heads, block, T] scores


def _sizes(cfg: dict) -> dict:
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return dict(d=cfg["hidden_size"], v=cfg["vocab_size"],
                H=H, P=P, G=G, N=N, d_in=H * P, conv=H * P + 2 * G * N,
                taps=cfg["conv_kernel"],
                Ha=cfg["num_attention_heads"],
                Hkv=cfg["num_key_value_heads"], dh=cfg["head_dim"],
                fm=cfg["moe_intermediate_size"],
                fs=cfg["moe_shared_expert_intermediate_size"],
                E=cfg["n_routed_experts"], held=cfg["experts_held"])


def layer_kinds(cfg: dict) -> str:
    """The letters of the layers held, in order: the published pattern
    from `first_layer` (counting from 1) on."""
    first = int(cfg.get("first_layer", 1)) - 1
    kinds = cfg["hybrid_override_pattern"][
        first:first + cfg["num_hidden_layers"]]
    assert len(kinds) == cfg["num_hidden_layers"], \
        f"the pattern holds no {cfg['num_hidden_layers']} layers from " \
        f"layer {first + 1} on"
    return kinds


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) in the DSL file's naming; kind says how the
    seed fills it (make_weights)."""
    z = _sizes(cfg)
    d = z["d"]
    out = {"_tok_embedding": ((z["v"], d), "matrix")}
    for i, kind in enumerate(layer_kinds(cfg)):
        b = f"_blk{i}_"
        out[b + "ln.w0"] = ((1, d), "scale")
        if kind == "M":
            out.update({
                b + "ssm.w0": ((d, 2 * z["d_in"] + 2 * z["G"] * z["N"]
                                + z["H"]), "matrix"),
                b + "ssm.w1": ((z["taps"], z["conv"]), "conv"),
                b + "ssm.w2": ((1, z["conv"]), "conv"),
                b + "ssm.w3": ((1, z["H"]), "a_log"),
                b + "ssm.w4": ((1, z["H"]), "one"),
                b + "ssm.w5": ((1, z["H"]), "dt_bias"),
                b + "ssm.w6": ((1, z["d_in"]), "scale"),
                b + "ssm.w7": ((z["d_in"], d), "matrix")})
        elif kind == "*":
            wq = z["Ha"] * z["dh"]
            out.update({b + "attn.w0": ((d, wq), "matrix"),
                        b + "attn.w1": ((d, z["Hkv"] * z["dh"]), "matrix"),
                        b + "attn.w2": ((d, z["Hkv"] * z["dh"]), "matrix"),
                        b + "attn.w3": ((wq, d), "matrix")})
        else:
            assert kind == "E", f"pattern letter {kind!r} (M, E or *)"
            e, fm, fs = z["held"], z["fm"], z["fs"]
            out.update({b + "moe.w0": ((d, z["E"]), "matrix"),
                        b + "moe.w1": ((e, d, fm), "matrix"),
                        b + "moe.w2": ((e, fm, d), "matrix"),
                        b + "moe.w3": ((1, z["E"]), "select_bias"),
                        b + "moe.w4": ((d, fs), "matrix"),
                        b + "moe.w5": ((fs, d), "matrix")})
    out.update({"_final_ln.w0": ((1, d), "scale"),
                "_lm_head.w0": ((d, z["v"]), "matrix")})
    return out


def make_weights(cfg: dict, seed: int, shardings=None):
    """Every weight from the seed, on the device, in ONE jitted call, in the
    configuration's stored dtype: matrices N(0, init_std), norm scales
    1 + init_std n, the router's selection bias N(0, select_bias_std), the
    convolution taps and bias U(-taps^-1/2, taps^-1/2) (a depthwise
    Conv1d's default), A_log = log(U(1, 16)), D = 1, dt_bias the inverse
    softplus of exp(U(log time_step_min, log time_step_max)) floored at
    time_step_floor (the family's initializers)."""
    shapes = param_shapes(cfg)
    std = float(cfg.get("init_std", 0.02))
    bias_std = float(cfg.get("select_bias_std", 0.05))
    dtype = jnp.dtype(cfg.get("param_dtype", "float32"))
    bound = float(cfg["conv_kernel"]) ** -0.5
    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
    floor = float(cfg["time_step_floor"])

    def fill(kind, k, shape):
        if kind == "one":
            return jnp.ones(shape, jnp.float32)
        u = jax.random.uniform(k, shape, jnp.float32)
        if kind == "conv":
            return bound * (2.0 * u - 1.0)
        if kind == "a_log":
            return jnp.log(1.0 + 15.0 * u)
        if kind == "dt_bias":
            dt = jnp.maximum(jnp.exp(lo + (hi - lo) * u), floor)
            return dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)
        x = jax.random.normal(k, shape, jnp.float32)
        return {"matrix": std * x, "scale": 1.0 + std * x,
                "select_bias": bias_std * x}[kind]

    def build(key):
        out = {}
        for name, (shape, kind) in shapes.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            out[name] = fill(kind, k, shape).astype(dtype)
        return out

    fn = jax.jit(build, out_shardings=shardings)
    return fn(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


def fp8_quant(x):
    """The control's precision: e4m3 with a per-tensor scale."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def bf16_quant(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(a, b, quant):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(jnp.float32).reshape(-1)


def _mamba2(cfg, wl, u, quant):
    """The Mamba-2 mixer, one sequence u [T, d] -> [T, d]."""
    z = _sizes(cfg)
    H, P, G, N, d_in = z["H"], z["P"], z["G"], z["N"], z["d_in"]
    t = u.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    zxd = _mm(u, wl["ssm.w0"], quant)
    gate, xbc, dt = (zxd[:, :d_in], zxd[:, d_in:d_in + z["conv"]],
                     zxd[:, d_in + z["conv"]:])
    w = f32(wl["ssm.w1"])                              # [taps, conv]
    taps = w.shape[0]
    c = f32(wl["ssm.w2"]).reshape(-1) + xbc * w[taps - 1]
    for j in range(1, taps):                           # xBC shifted j back
        c = c + jnp.concatenate([jnp.zeros((j, z["conv"])), xbc])[:t] \
            * w[taps - 1 - j]
    c = jax.nn.silu(c)
    x = c[:, :d_in].reshape(t, H, P)
    Bm = jnp.repeat(c[:, d_in:d_in + G * N].reshape(t, G, N), H // G, axis=1)
    Cm = jnp.repeat(c[:, d_in + G * N:].reshape(t, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + f32(wl["ssm.w5"]).reshape(-1))     # [t, H]
    a = jnp.exp(dt * -jnp.exp(f32(wl["ssm.w3"]).reshape(-1)))

    def token(S, xs):
        x_t, b_t, c_t, dt_t, a_t = xs       # [H, P], [H, N], [H, N], [H]
        S = a_t[:, None, None] * S + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return S, jnp.sum(S * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (x, Bm, Cm, dt, a))
    y = y + f32(wl["ssm.w4"]).reshape(H, 1) * x
    v = (y.reshape(t, d_in) * jax.nn.silu(gate)).reshape(t, G, d_in // G)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                          + cfg["norm_eps"])
    v = v.reshape(t, d_in) * f32(wl["ssm.w6"]).reshape(-1)
    return _mm(v, wl["ssm.w7"], quant)


def _rotate(x, theta):
    """x [T, H, D] rotated at positions 0..T-1, rotate-half layout."""
    t, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(cfg, wl, a, quant):
    """Grouped-query attention, one sequence a [T, d] -> [T, d]."""
    z = _sizes(cfg)
    H, Hkv, dh = z["Ha"], z["Hkv"], z["dh"]
    t = a.shape[0]
    q = _mm(a, wl["attn.w0"], quant).reshape(t, H, dh)
    k = _mm(a, wl["attn.w1"], quant).reshape(t, Hkv, dh)
    v = _mm(a, wl["attn.w2"], quant).reshape(t, Hkv, dh)
    if cfg.get("attn_use_rope", False):
        q, k = _rotate(q, float(cfg["rope_theta"])), \
            _rotate(k, float(cfg["rope_theta"]))
    rep = H // Hkv                     # query head h reads KV head h // rep
    kh = jnp.swapaxes(jnp.repeat(k, rep, axis=1), 0, 1)          # [H, T, dh]
    vh = jnp.swapaxes(jnp.repeat(v, rep, axis=1), 0, 1)

    blk = min(ATTN_BLOCK, t)
    assert t % blk == 0, f"{t} tokens do not split in blocks of {blk}"
    qb = jnp.swapaxes(q, 0, 1).reshape(H, t // blk, blk, dh)

    def one(args):
        qi, i = args                                  # [H, blk, dh], block no
        s = _mm(qi, jnp.swapaxes(kh, 1, 2), quant) * dh ** -0.5  # [H,blk,T]
        rows = i * blk + jnp.arange(blk)
        s = jnp.where(jnp.arange(t)[None, None] <= rows[None, :, None],
                      s, -1e30)
        return _mm(jax.nn.softmax(s, axis=-1), vh, quant)        # [H,blk,dh]

    o = jax.lax.map(one, (jnp.swapaxes(qb, 0, 1), jnp.arange(t // blk)))
    o = jnp.transpose(o, (0, 2, 1, 3)).reshape(t, H * dh)
    return _mm(o, wl["attn.w3"], quant)


def _relu2_mlp(x, w_up, w_down, quant):
    return _mm(jnp.square(jax.nn.relu(_mm(x, w_up, quant))), w_down, quant)


def route(cfg, scores, bias):
    """scores [T, E] (sigmoid, float32), bias [E] -> (ids [T, k], weights
    [T, k]): the top k of scores + bias, weights from the scores alone,
    renormalized with the family's + 1e-20, times routed_scaling_factor."""
    ids = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(scores, ids, axis=1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids, w * cfg["routed_scaling_factor"]


def _moe(cfg, wl, x, quant):
    held = cfg["experts_held"]
    first = cfg.get("ep_rank", 0) * held
    scores = jax.nn.sigmoid(jnp.matmul(
        x, wl["moe.w0"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    ids, w = route(cfg, scores, wl["moe.w3"].astype(jnp.float32).reshape(-1))

    def expert(j, y):
        wj = jnp.sum(jnp.where(ids == first + j, w, 0.0), axis=-1)   # [T]
        return y + wj[:, None] * _relu2_mlp(x, wl["moe.w1"][j],
                                            wl["moe.w2"][j], quant)

    # a loop over the held experts, one expert's two matrices taken up to
    # float32 at a time
    y = jax.lax.fori_loop(0, held, expert, jnp.zeros_like(x))
    return y + _relu2_mlp(x, wl["moe.w4"], wl["moe.w5"], quant)


_MIXERS = {"M": _mamba2, "*": _attention, "E": _moe}


def hidden_states(w, cfg: dict, tokens, quant=None):
    """Final-RMSNorm hidden states [T, d] of ONE sequence of token ids."""
    eps = cfg["norm_eps"]
    x = w["_tok_embedding"][tokens].astype(jnp.float32)
    for i, kind in enumerate(layer_kinds(cfg)):
        b = f"_blk{i}_"
        wl = {k[len(b):]: v for k, v in w.items() if k.startswith(b)}
        x = x + _MIXERS[kind](cfg, wl, _rms_norm(x, wl["ln.w0"], eps), quant)
    return _rms_norm(x, w["_final_ln.w0"], eps)


def log_probs(w, cfg: dict, tokens, rows=None, quant=None):
    """log softmax of the head over the vocabulary at `rows` (all rows if
    None) of one sequence: [n_rows, vocab]."""
    h = hidden_states(w, cfg, tokens, quant)
    if rows is not None:
        h = h[rows]
    return jax.nn.log_softmax(_mm(h, w["_lm_head.w0"], quant), axis=-1)


@functools.lru_cache(maxsize=None)
def _jitted(what: str, cfg_key: tuple, quant_name: str):
    cfg = dict(cfg_key)
    quant = {"": None, "fp8": fp8_quant, "bf16": bf16_quant}[quant_name]
    if what == "log_probs":
        return jax.jit(lambda w, t, r: log_probs(w, cfg, t, r, quant))
    raise KeyError(what)


KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "num_hidden_layers", "first_layer",
        "hybrid_override_pattern", "vocab_size", "norm_eps",
        "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
        "conv_kernel", "attn_use_rope", "rope_theta",
        "moe_intermediate_size", "moe_shared_expert_intermediate_size",
        "n_routed_experts", "experts_held", "ep_rank",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")


def jitted(what: str, cfg: dict, quant: str = ""):
    """A jitted reference function ('log_probs') for this configuration's
    sizes; quant '' = the float32 reference, 'fp8' = the control, 'bf16' =
    the precision the configuration states."""
    return _jitted(what, tuple((k, cfg[k]) for k in KEYS), quant)
