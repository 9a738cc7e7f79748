"""Plain reference for Solar-Open2 (upstage/Solar-Open2-250B, model_type
solar_open2): the forward pass in straightforward jax.numpy, float32
arithmetic under jax.default_matmul_precision("highest") — no kernels, no
cache, no chunks, no batching.

  block   h = x + Mixer_i(RMSNorm(x));  y = h + MoE(RMSNorm(h)), eps 1e-5;
          final RMSNorm; untied head.  Layers count from 0: layer i is gated
          GQA if i is in gqa_layers (i % 4 == 0), else KDA; every layer's
          MLP is the expert layer (first_k_dense_replace 0).
  GQA     q = x W_q (H heads of head_dim), k = x W_k, v = x W_v (H_kv
          heads); no bias, no rotation (use_rope false), no per-head norm;
          causal softmax of q k^T head_dim^-1/2, H / H_kv query heads a KV
          head, in blocks of query rows; a = concat_h(P v);
          y = (a * sigmoid(x W_g)) W_o  (use_gqa_gate: an elementwise gate
          from the layer's input, W_g [d, H head_dim])
  KDA     q = l2norm(silu(conv4(x W_q))), k = l2norm(silu(conv4(x W_k))),
          v = silu(conv4(x W_v)); conv4 = a causal depthwise convolution
          over the last 4 positions, written as a sum of four shifted
          products; l2norm a head, x / sqrt(sum x^2 + 1e-6).
          g = -exp(A_log_h) softplus(x W_fa W_fb + dt_bias) a channel
          (kda_use_full_proj false: rank head_dim),
          beta = 2 sigmoid(x W_b) a head (kda_allow_neg_eigval; sigmoid
          alone where it is false).  A head's state S [128, 128], zero at
          position 0, one token at a time in a lax.scan:
            S <- Diag(exp g_t) S;  u = beta_t (v_t - S^T k_t);
            S <- S + k_t u^T;      o_t = S^T q_t 128^-1/2
          y = (RMSNorm_head(o_t) * sigmoid(x W_ga W_gb)) W_o
  MoE     s = sigmoid(x W_r) in float32 over all n_routed_experts; the top
          num_experts_per_tok of s + b; weights s_i / sum(s_selected)
          (norm_topk_prob) * routed_scaling_factor (from s, not s + b);
          y = sum over selected AND HELD experts of w_i Expert_i(x)
            + Shared(x), SwiGLU experts (silu(x W_g) * x W_u) W_d, a loop
          over the held experts

Departures (the configuration file lists them too):
  * one expert-parallel rank's share: of the n_routed_experts the router
    scores, only the `experts_held` from `ep_rank * experts_held` on have
    weights; what the others would add is left out, here and in the program
    alike.  With experts_held = n_routed_experts this is the uncut layer
    (tests/test_solar_open2.py adds the eight shares up);
  * the KDA head count follows the attention head count where that is
    smaller (min(linear_attn_config.num_heads, num_attention_heads)): a
    rehearsal shrinks both with one key;
  * the vocabulary is the configuration's slice.

The weights are the benchmark's: made here from the seed, on the device, in
one jitted call, in the dtype the configuration stores them in (bfloat16),
under the names benchmark/configs/solar_open2.py gives its parameters.
Every matmul takes them up to float32.  The recurrence itself is float32
whatever `quant` says: `quant=` puts a lower precision in every MATMUL's
place — the control that `correct` has to refuse (fp8 e4m3 with a
per-tensor scale, the step below the configuration's bfloat16)."""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

ATTN_BLOCK = 256        # query rows a block: [heads, block, T] scores


def _sizes(cfg: dict) -> dict:
    la = cfg["linear_attn_config"]
    return dict(
        d=cfg["hidden_size"], H=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], dh=cfg["head_dim"],
        Hk=min(la["num_heads"], cfg["num_attention_heads"]),
        dk=la["head_dim"], taps=la["short_conv_kernel_size"],
        fm=cfg["moe_intermediate_size"], E=cfg["n_routed_experts"],
        held=cfg["experts_held"],
        fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        v=cfg["vocab_size"])


def gqa_layers(cfg: dict) -> set:
    """The 0-based indices of the gated GQA layers at this depth."""
    return {i for i in cfg["gqa_layers"] if i < cfg["num_hidden_layers"]}


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) in the DSL file's naming; kind says how the
    seed fills it (make_weights)."""
    z = _sizes(cfg)
    d, H, Hk, dk = z["d"], z["H"], z["Hk"], z["dk"]
    assert cfg["first_k_dense_replace"] == 0, "no leading dense layer"
    gqa = gqa_layers(cfg)
    out = {"_tok_embedding": ((z["v"], d), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        b = f"_blk{i}_"
        out[b + "ln1.w0"] = ((1, d), "scale")
        if i in gqa:
            out.update({
                b + "attn.w0": ((d, H * z["dh"]), "matrix"),
                b + "attn.w1": ((d, z["Hkv"] * z["dh"]), "matrix"),
                b + "attn.w2": ((d, z["Hkv"] * z["dh"]), "matrix"),
                b + "attn.w3": ((H * z["dh"], d), "matrix")})
            if cfg["use_gqa_gate"]:
                out[b + "attn.w4"] = ((d, H * z["dh"]), "matrix")
        else:
            out.update({
                b + "kda.w0": ((d, Hk * dk), "matrix"),
                b + "kda.w1": ((d, Hk * dk), "matrix"),
                b + "kda.w2": ((d, Hk * dk), "matrix"),
                b + "kda.w3": ((z["taps"], Hk * dk), "conv"),
                b + "kda.w4": ((z["taps"], Hk * dk), "conv"),
                b + "kda.w5": ((z["taps"], Hk * dk), "conv"),
                b + "kda.w6": ((d, dk), "matrix"),
                b + "kda.w7": ((dk, Hk * dk), "matrix"),
                b + "kda.w8": ((1, Hk), "a_log"),
                b + "kda.w9": ((1, Hk * dk), "dt_bias"),
                b + "kda.w10": ((d, Hk), "matrix"),
                b + "kda.w11": ((d, dk), "matrix"),
                b + "kda.w12": ((dk, Hk * dk), "matrix"),
                b + "kda.w13": ((1, dk), "scale"),
                b + "kda.w14": ((Hk * dk, d), "matrix")})
        out[b + "ln2.w0"] = ((1, d), "scale")
        e, fm, fs = z["held"], z["fm"], z["fs"]
        out.update({b + "moe.w0": ((d, z["E"]), "matrix"),
                    b + "moe.w1": ((e, d, fm), "matrix"),
                    b + "moe.w2": ((e, d, fm), "matrix"),
                    b + "moe.w3": ((e, fm, d), "matrix"),
                    b + "moe.w4": ((1, z["E"]), "select_bias"),
                    b + "moe.w5": ((d, fs), "matrix"),
                    b + "moe.w6": ((d, fs), "matrix"),
                    b + "moe.w7": ((fs, d), "matrix")})
    out.update({"_final_ln.w0": ((1, d), "scale"),
                "_lm_head.w0": ((d, z["v"]), "matrix")})
    return out


def make_weights(cfg: dict, seed: int, shardings=None):
    """Every weight from the seed, on the device, in ONE jitted call, in the
    configuration's stored dtype: matrices N(0, init_std), norm scales
    1 + init_std n, the router's selection bias N(0, select_bias_std), the
    convolution taps U(-1/2, 1/2) (a 4-tap depthwise Conv1d's default), and
    fla's KDA initializers: A_log = log U(1, 16), dt_bias = softplus^-1 of a
    step drawn log-uniformly from [1e-3, 1e-1]."""
    shapes = param_shapes(cfg)
    std = float(cfg.get("init_std", 0.02))
    bias_std = float(cfg.get("select_bias_std", 0.05))
    dtype = jnp.dtype(cfg.get("param_dtype", "float32"))

    def fill(kind, k, shape):
        if kind in ("matrix", "scale", "select_bias"):
            x = jax.random.normal(k, shape, jnp.float32)
            return {"matrix": std * x, "scale": 1.0 + std * x,
                    "select_bias": bias_std * x}[kind]
        u = jax.random.uniform(k, shape, jnp.float32)
        if kind == "conv":
            return u - 0.5
        if kind == "a_log":
            return jnp.log(1.0 + 15.0 * u)
        dt = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
        return dt + jnp.log(-jnp.expm1(-dt))                # dt_bias

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


def _conv(x, w):
    """x [T, C], w [taps, C], w[-1] on the current position: the sum of
    `taps` shifted products, zeros before position 0."""
    taps, t = w.shape[0], x.shape[0]
    w = w.astype(jnp.float32)
    y = x * w[taps - 1]
    for j in range(1, taps):
        y = y + jnp.concatenate([jnp.zeros((j, x.shape[1])), x])[:t] \
            * w[taps - 1 - j]
    return y


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(cfg, wl, a, quant):
    """The KDA mixer, one sequence a [T, d] -> [T, d]."""
    z = _sizes(cfg)
    H, dk = z["Hk"], z["dk"]
    t = a.shape[0]
    heads = lambda x: x.reshape(t, H, dk)
    q = _l2norm(heads(jax.nn.silu(
        _conv(_mm(a, wl["kda.w0"], quant), wl["kda.w3"]))))
    k = _l2norm(heads(jax.nn.silu(
        _conv(_mm(a, wl["kda.w1"], quant), wl["kda.w4"]))))
    v = heads(jax.nn.silu(_conv(_mm(a, wl["kda.w2"], quant), wl["kda.w5"])))
    f = heads(_mm(_mm(a, wl["kda.w6"], quant), wl["kda.w7"], quant))
    g = -jnp.exp(wl["kda.w8"].astype(jnp.float32).reshape(H, 1)) * \
        jax.nn.softplus(f + wl["kda.w9"].astype(jnp.float32).reshape(H, dk))
    beta = jax.nn.sigmoid(_mm(a, wl["kda.w10"], quant))          # [T, H]
    if cfg["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    gate = heads(_mm(_mm(a, wl["kda.w11"], quant), wl["kda.w12"], quant))

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs         # [H, dk] x4, [H]
        S = jnp.exp(g_t)[:, :, None] * S
        u = b_t[:, None] * (v_t - jnp.sum(S * k_t[:, :, None], axis=1))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.sum(S * (q_t * dk ** -0.5)[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dk), jnp.float32),
                        (q, k, v, g, beta))                       # [T, H, dk]
    o = _rms_norm(o, wl["kda.w13"], cfg["rms_norm_eps"]) * \
        jax.nn.sigmoid(gate)
    return _mm(o.reshape(t, H * dk), wl["kda.w14"], quant)


def _attention(cfg, wl, a, quant):
    """Gated NoPE GQA, one sequence a [T, d] -> [T, d]."""
    z = _sizes(cfg)
    H, Hkv, dh = z["H"], z["Hkv"], z["dh"]
    assert not cfg["use_rope"], "this family rotates nothing"
    t = a.shape[0]
    rep = H // Hkv
    q = _mm(a, wl["attn.w0"], quant).reshape(t, H, dh)
    k = _mm(a, wl["attn.w1"], quant).reshape(t, Hkv, dh)
    v = _mm(a, wl["attn.w2"], quant).reshape(t, Hkv, dh)
    # query head h reads KV head h // rep
    kh = jnp.repeat(jnp.swapaxes(k, 0, 1), rep, axis=0)          # [H, T, dh]
    vh = jnp.repeat(jnp.swapaxes(v, 0, 1), rep, axis=0)

    blk = min(ATTN_BLOCK, t)
    assert t % blk == 0, f"{t} tokens do not split in blocks of {blk}"
    qb = jnp.swapaxes(q, 0, 1).reshape(H, t // blk, blk, dh)

    def one(args):
        qi, i = args                                  # [H, blk, dh], block no
        s = _mm(qi, jnp.swapaxes(kh, 1, 2), quant) * dh ** -0.5  # [H,blk,T]
        rows = i * blk + jnp.arange(blk)
        s = jnp.where(jnp.arange(t)[None, None] <= rows[None, :, None],
                      s, -1e30)
        return _mm(jax.nn.softmax(s, axis=-1), vh, quant)       # [H,blk,dh]

    o = jax.lax.map(one, (jnp.swapaxes(qb, 0, 1), jnp.arange(t // blk)))
    o = jnp.transpose(o, (0, 2, 1, 3)).reshape(t, H * dh)
    if cfg["use_gqa_gate"]:
        o = o * jax.nn.sigmoid(_mm(a, wl["attn.w4"], quant))
    return _mm(o, wl["attn.w3"], quant)


def _swiglu(x, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def route(cfg, scores, bias):
    """scores [T, E] (sigmoid, float32), bias [E] -> (ids [T, k], weights
    [T, k]): the top k of scores + bias, weights from the scores alone,
    renormalized, times routed_scaling_factor."""
    ids = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(scores, ids, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w * cfg["routed_scaling_factor"]


def _moe(cfg, wl, x, quant):
    z = _sizes(cfg)
    first = cfg.get("ep_rank", 0) * z["held"]
    scores = jax.nn.sigmoid(jnp.matmul(
        x, wl["moe.w0"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    ids, w = route(cfg, scores, wl["moe.w4"].astype(jnp.float32).reshape(-1))
    y = _swiglu(x, wl["moe.w5"], wl["moe.w6"], wl["moe.w7"], quant)
    for j in range(z["held"]):
        wj = jnp.sum(jnp.where(ids == first + j, w, 0.0), axis=-1)   # [T]
        y = y + wj[:, None] * _swiglu(x, wl["moe.w1"][j], wl["moe.w2"][j],
                                      wl["moe.w3"][j], quant)
    return y


def hidden_states(w, cfg: dict, tokens, quant=None):
    """Final-RMSNorm hidden states [T, d] of ONE sequence of token ids."""
    eps = cfg["rms_norm_eps"]
    gqa = gqa_layers(cfg)
    x = w["_tok_embedding"][tokens].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        b = f"_blk{i}_"
        wl = {k[len(b):]: v for k, v in w.items() if k.startswith(b)}
        mixer = _attention if i in gqa else _kda
        x = x + mixer(cfg, wl, _rms_norm(x, wl["ln1.w0"], eps), quant)
        x = x + _moe(cfg, wl, _rms_norm(x, wl["ln2.w0"], eps), quant)
    return _rms_norm(x, w["_final_ln.w0"], eps)


def log_probs(w, cfg: dict, tokens, rows=None, quant=None):
    """log softmax of the head over the vocabulary at `rows` (all rows if
    None) of one sequence: [n_rows, vocab]."""
    h = hidden_states(w, cfg, tokens, quant)
    if rows is not None:
        h = h[rows]
    return jax.nn.log_softmax(_mm(h, w["_lm_head.w0"], quant), axis=-1)


def _freeze(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return tuple(v) if isinstance(v, list) else v


@functools.lru_cache(maxsize=None)
def _jitted(what: str, cfg_key: tuple, quant_name: str):
    cfg = {k: (dict(v) if k == "linear_attn_config" else v)
           for k, v in cfg_key}
    quant = {"": None, "fp8": fp8_quant, "bf16": bf16_quant}[quant_name]
    if what == "log_probs":
        return jax.jit(lambda w, t, r: log_probs(w, cfg, t, r, quant))
    raise KeyError(what)


KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "num_hidden_layers", "vocab_size", "rms_norm_eps",
        "linear_attn_config", "gqa_layers", "use_rope", "use_gqa_gate",
        "kda_allow_neg_eigval", "moe_intermediate_size", "n_routed_experts",
        "experts_held", "ep_rank", "num_experts_per_tok", "n_shared_experts",
        "norm_topk_prob", "routed_scaling_factor", "first_k_dense_replace")


def jitted(what: str, cfg: dict, quant: str = ""):
    """A jitted reference function ('log_probs') for this configuration's
    sizes; quant '' = the float32 reference, 'fp8' = the control, 'bf16' =
    the precision the configuration states."""
    return _jitted(what, tuple((k, _freeze(cfg[k])) for k in KEYS), quant)
