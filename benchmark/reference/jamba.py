"""Plain reference for Jamba (ai21labs/AI21-Jamba2-3B, model_type jamba;
arXiv:2403.19887, Mamba arXiv:2312.00752): the forward pass in
straightforward jax.numpy, float32 arithmetic under
jax.default_matmul_precision("highest") — no kernels, no cache, no slot
state, no batching.

  block   x <- x + Mixer_i(RMSNorm(x)); x <- x + W_down(silu(W_gate n) *
          (W_up n)), n = RMSNorm'(x); eps 1e-6; a final RMSNorm; the head
          (untied here: see Departures).  Layer i (from 0) is attention
          where i % attn_layer_period == attn_layer_offset, Mamba elsewhere.
          num_experts 1: every MLP is the dense SwiGLU.
  Mamba   d_in = mamba_expand x d = 5120, N = 16, R = 160, 4 taps:
          [x_t, z_t] = u_t W_in
          x'_t = silu(b + sum_{j=0..3} w_j * x_{t-3+j}) a channel — a
            literal sum over four shifted copies, zeros before position 0
          [r_t, B_t, C_t] = x'_t W_x;  each RMS-normed with a learned scale
            (Jamba's three inner norms: `inner_norms`, on as published)
          dt_t = softplus(r_t W_dt + b_dt);  A = -exp(A_log)   [d_in, N]
          h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n]
                      + dt_t[c] x'_t[c] B_t[n] — THE RECURRENCE A LITERAL
            PER-TOKEN lax.scan OVER h [d_in, N]
          y_t[c] = sum_n h_t[c, n] C_t[n] + D[c] x'_t[c]
          out_t = (y_t * silu(z_t)) W_out
  attn    q = x W_q (20 heads of 128), k = x W_k, v = x W_v (ONE head of
          128), no bias, NO ROTATION and no position embedding of any kind
          (`attn_use_rope` false: model_type jamba applies none); every
          query head reads the one KV head; scores * 128^-1/2; a full
          softmax over a causal mask, in blocks of query rows;
          concat_h(P v) W_o

Departures (the configuration file lists them too):
  * the head is its own matrix where the published model ties it to the
    embedding (`tie_word_embeddings`; the DSL cannot say a tied head);
  * A_log is stored [N, d_in] — the program's state orientation — and
    transposed here; the arithmetic is the published one.

The weights are the benchmark's: made here from the seed, on the device, in
one jitted call, in the dtype the configuration stores them in (bfloat16),
under the names benchmark/configs/jamba.py gives its parameters.  Every
matmul takes them up to float32.  `quant=` puts a lower precision in every
MATMUL's place — the control that `correct` has to refuse (fp8 e4m3 with a
per-tensor scale, the step below the configuration's bfloat16)."""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

ATTN_BLOCK = 256        # query rows a block: [heads, block, T] scores


def _sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return dict(d=d, v=cfg["vocab_size"], f=cfg["intermediate_size"],
                d_in=cfg["mamba_expand"] * d, N=cfg["mamba_d_state"],
                R=cfg["mamba_dt_rank"], taps=cfg["mamba_d_conv"],
                H=cfg["num_attention_heads"],
                Hkv=cfg["num_key_value_heads"], dh=cfg["head_dim"])


def is_attention(cfg: dict, i: int) -> bool:
    """Layer i (from 0) is attention, else Mamba (the family's
    `layers_block_type`)."""
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) in the DSL file's naming; kind says how the
    seed fills it (make_weights)."""
    z = _sizes(cfg)
    d, d_in, N, R = z["d"], z["d_in"], z["N"], z["R"]
    out = {"_tok_embedding": ((z["v"], d), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        b = f"_blk{i}_"
        out[b + "ln1.w0"] = ((1, d), "scale")
        if is_attention(cfg, i):
            wq = z["H"] * z["dh"]
            out.update({b + "attn.w0": ((d, wq), "matrix"),
                        b + "attn.w1": ((d, z["Hkv"] * z["dh"]), "matrix"),
                        b + "attn.w2": ((d, z["Hkv"] * z["dh"]), "matrix"),
                        b + "attn.w3": ((wq, d), "matrix")})
        else:
            out.update({
                b + "mamba.w0": ((d, 2 * d_in), "matrix"),
                b + "mamba.w1": ((z["taps"], d_in), "conv"),
                b + "mamba.w2": ((1, d_in), "conv"),
                b + "mamba.w3": ((d_in, R + 2 * N), "matrix"),
                b + "mamba.w4": ((1, R), "scale"),
                b + "mamba.w5": ((1, N), "scale"),
                b + "mamba.w6": ((1, N), "scale"),
                b + "mamba.w7": ((R, d_in), "w_dt"),
                b + "mamba.w8": ((1, d_in), "dt_bias"),
                b + "mamba.w9": ((N, d_in), "a_log"),
                b + "mamba.w10": ((1, d_in), "one"),
                b + "mamba.w11": ((d_in, d), "matrix")})
        out.update({b + "ln2.w0": ((1, d), "scale"),
                    b + "ffn.w0": ((d, z["f"]), "matrix"),
                    b + "ffn.w1": ((d, z["f"]), "matrix"),
                    b + "ffn.w2": ((z["f"], d), "matrix")})
    out.update({"_final_ln.w0": ((1, d), "scale"),
                "_lm_head.w0": ((d, z["v"]), "matrix")})
    return out


def make_weights(cfg: dict, seed: int, shardings=None):
    """Every weight from the seed, on the device, in ONE jitted call, in the
    configuration's stored dtype: matrices N(0, init_std), norm scales
    1 + init_std n, the convolution taps and bias U(-taps^-1/2, taps^-1/2)
    (a depthwise Conv1d's default), A_log[n, c] = log(n + 1) (the family's
    log(1..N) a channel), D = 1, W_dt U(-R^-1/2, R^-1/2), b_dt the inverse
    softplus of exp(U(log 1e-3, log 1e-1)) (the family's initializers)."""
    shapes = param_shapes(cfg)
    std = float(cfg.get("init_std", 0.02))
    dtype = jnp.dtype(cfg.get("param_dtype", "float32"))
    bound = float(cfg["mamba_d_conv"]) ** -0.5
    dt_bound = float(cfg["mamba_dt_rank"]) ** -0.5
    lo, hi = math.log(1e-3), math.log(1e-1)

    def fill(kind, k, shape):
        if kind == "one":
            return jnp.ones(shape, jnp.float32)
        if kind == "a_log":
            n = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
            return jnp.broadcast_to(jnp.log(n)[:, None], shape)
        u = jax.random.uniform(k, shape, jnp.float32)
        if kind == "conv":
            return bound * (2.0 * u - 1.0)
        if kind == "w_dt":
            return dt_bound * (2.0 * u - 1.0)
        if kind == "dt_bias":
            dt = jnp.exp(lo + (hi - lo) * u)
            return dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)
        x = jax.random.normal(k, shape, jnp.float32)
        return {"matrix": std * x, "scale": 1.0 + std * x}[kind]

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


def _mamba(cfg, wl, u, quant):
    """The Mamba-1 mixer, one sequence u [T, d] -> [T, d]."""
    z = _sizes(cfg)
    d_in, N, R, eps = z["d_in"], z["N"], z["R"], cfg["rms_norm_eps"]
    t = u.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    xz = _mm(u, wl["mamba.w0"], quant)
    x, gate = xz[:, :d_in], xz[:, d_in:]
    w = f32(wl["mamba.w1"])                            # [taps, d_in]
    taps = w.shape[0]
    c = f32(wl["mamba.w2"]).reshape(-1) + x * w[taps - 1]
    for j in range(1, taps):                           # x shifted j back
        c = c + jnp.concatenate([jnp.zeros((j, d_in)), x])[:t] \
            * w[taps - 1 - j]
    x = jax.nn.silu(c)
    rbc = _mm(x, wl["mamba.w3"], quant)
    r, Bm, Cm = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    if cfg.get("inner_norms", True):
        r = _rms_norm(r, wl["mamba.w4"], eps)
        Bm = _rms_norm(Bm, wl["mamba.w5"], eps)
        Cm = _rms_norm(Cm, wl["mamba.w6"], eps)
    dt = jax.nn.softplus(_mm(r, wl["mamba.w7"], quant)
                         + f32(wl["mamba.w8"]).reshape(-1))      # [t, d_in]
    A = -jnp.exp(f32(wl["mamba.w9"])).T                          # [d_in, N]

    def token(h, xs):
        x_t, b_t, c_t, dt_t = xs            # [d_in], [N], [N], [d_in]
        h = jnp.exp(dt_t[:, None] * A) * h + \
            (dt_t * x_t)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((d_in, N), jnp.float32),
                        (x, Bm, Cm, dt))
    y = y + f32(wl["mamba.w10"]).reshape(-1) * x
    return _mm(y * jax.nn.silu(gate), wl["mamba.w11"], quant)


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
    """Multi-query attention, one sequence a [T, d] -> [T, d]."""
    z = _sizes(cfg)
    H, Hkv, dh = z["H"], z["Hkv"], z["dh"]
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


def _swiglu(x, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def hidden_states(w, cfg: dict, tokens, quant=None):
    """Final-RMSNorm hidden states [T, d] of ONE sequence of token ids."""
    eps = cfg["rms_norm_eps"]
    x = w["_tok_embedding"][tokens].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        b = f"_blk{i}_"
        wl = {k[len(b):]: v for k, v in w.items() if k.startswith(b)}
        mixer = _attention if is_attention(cfg, i) else _mamba
        x = x + mixer(cfg, wl, _rms_norm(x, wl["ln1.w0"], eps), quant)
        x = x + _swiglu(_rms_norm(x, wl["ln2.w0"], eps), wl["ffn.w0"],
                        wl["ffn.w1"], wl["ffn.w2"], quant)
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


KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_hidden_layers", "vocab_size",
        "rms_norm_eps", "attn_layer_period", "attn_layer_offset",
        "mamba_expand", "mamba_d_state", "mamba_dt_rank", "mamba_d_conv",
        "attn_use_rope", "rope_theta")


def jitted(what: str, cfg: dict, quant: str = ""):
    """A jitted reference function ('log_probs') for this configuration's
    sizes; quant '' = the float32 reference, 'fp8' = the control, 'bf16' =
    the precision the configuration states."""
    key = tuple((k, cfg[k]) for k in KEYS)
    return _jitted(what, key + (("inner_norms",
                                 bool(cfg.get("inner_norms", True))),), quant)
