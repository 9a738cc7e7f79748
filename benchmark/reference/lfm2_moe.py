"""Plain reference for LFM2-MoE (LiquidAI/LFM2-24B-A2B, model_type
lfm2_moe): the forward pass in straightforward jax.numpy, float32 arithmetic
under jax.default_matmul_precision("highest") — no kernels, no cache, no
slot tails, no chunks.

  block   h = x + Mixer(RMSNorm(x));  y = h + FFN(RMSNorm(h)), eps 1e-5;
          final RMSNorm; untied head.  The layers are `num_hidden_layers`
          entries of the published `layer_types` from `first_layer`
          (counting from 1) on: "conv" or "full_attention".
  conv    [B, C, x~] = x W_in (three d-wide parts, no bias); u = B * x~;
          c_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t a channel — a literal
          sum over three shifted copies, zeros before position 0, no bias,
          no activation; y = (C * c) W_out
  attn    q = x W_q (32 heads of 64), k = x W_k, v = x W_v (8 heads of 64);
          q and k RMS-normed a head with a learned 64-wide scale each, eps
          1e-5; then rotated, rotate-half layout (feature i pairs with
          i + 32), theta 1e6; each group of 4 query heads reads one KV
          head; scores * 64^-1/2; a full softmax over a causal mask, in
          blocks of query rows; concat_h(P v) W_o
  FFN     SwiGLU (silu(x W_g) * x W_u) W_d: width intermediate_size in the
          first num_dense_layers layers, then MoE: s = sigmoid(x W_r) in
          float32 over all num_experts; the top num_experts_per_tok of
          s + b; weights s_i / (sum(s_selected) + 1e-6) *
          routed_scaling_factor (from s, not s + b: norm_topk_prob);
          y = sum over selected AND HELD experts of w_i Expert_i(x), a loop
          over the held experts with a mask; no shared expert

Departures (the configuration file lists them too):
  * the head is untied (the family ties it; the program cannot);
  * `experts_held` / `ep_rank` cut the experts as one expert-parallel
    rank's share: only the `experts_held` experts from `ep_rank *
    experts_held` on have weights, what the others would add is left out.
    The cell holds all 64, so this is the uncut layer there
    (tests/test_lfm2_moe.py adds eight 8-expert shares up to it).

The weights are the benchmark's: made here from the seed, on the device, in
one jitted call, in the dtype the configuration stores them in (bfloat16),
under the names benchmark/configs/lfm2_moe.py gives its parameters.  Every
matmul takes them up to float32.  `quant=` puts a lower precision in every
MATMUL's place — the control that `correct` has to refuse (fp8 e4m3 with a
per-tensor scale, the step below the configuration's bfloat16)."""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

ATTN_BLOCK = 256        # query rows a block: [heads, block, T] scores


def _sizes(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    return dict(d=cfg["hidden_size"], H=h, Hkv=cfg["num_key_value_heads"],
                dh=cfg["hidden_size"] // h, taps=cfg["conv_L_cache"],
                f=cfg["intermediate_size"], fm=cfg["moe_intermediate_size"],
                E=cfg["num_experts"], held=cfg["experts_held"],
                v=cfg["vocab_size"])


def layer_kinds(cfg: dict) -> list:
    """The kinds of the layers held, in order: the published list from
    `first_layer` (counting from 1) on."""
    first = int(cfg.get("first_layer", 1)) - 1
    kinds = list(cfg["layer_types"][first:first + cfg["num_hidden_layers"]])
    assert len(kinds) == cfg["num_hidden_layers"], \
        f"layer_types holds no {cfg['num_hidden_layers']} layers from " \
        f"layer {first + 1} on"
    return kinds


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) in the DSL file's naming; kind says how the
    seed fills it (make_weights)."""
    z = _sizes(cfg)
    d, H, Hkv, dh = z["d"], z["H"], z["Hkv"], z["dh"]
    out = {"_tok_embedding": ((z["v"], d), "matrix")}
    for i, kind in enumerate(layer_kinds(cfg)):
        b = f"_blk{i}_"
        out[b + "ln1.w0"] = ((1, d), "scale")
        if kind == "full_attention":
            out.update({b + "attn.w0": ((d, H * dh), "matrix"),
                        b + "attn.w1": ((d, Hkv * dh), "matrix"),
                        b + "attn.w2": ((d, Hkv * dh), "matrix"),
                        b + "attn.w3": ((H * dh, d), "matrix"),
                        b + "attn.w4": ((1, dh), "scale"),
                        b + "attn.w5": ((1, dh), "scale")})
        else:
            out.update({b + "conv.w0": ((d, 3 * d), "matrix"),
                        b + "conv.w1": ((z["taps"], d), "conv"),
                        b + "conv.w2": ((d, d), "matrix")})
        out[b + "ln2.w0"] = ((1, d), "scale")
        if i < cfg["num_dense_layers"]:
            out.update({b + "ffn.w0": ((d, z["f"]), "matrix"),
                        b + "ffn.w1": ((d, z["f"]), "matrix"),
                        b + "ffn.w2": ((z["f"], d), "matrix")})
        else:
            e, fm = z["held"], z["fm"]
            out.update({b + "moe.w0": ((d, z["E"]), "matrix"),
                        b + "moe.w1": ((e, d, fm), "matrix"),
                        b + "moe.w2": ((e, d, fm), "matrix"),
                        b + "moe.w3": ((e, fm, d), "matrix"),
                        b + "moe.w4": ((1, z["E"]), "select_bias")})
    out.update({"_final_ln.w0": ((1, d), "scale"),
                "_lm_head.w0": ((d, z["v"]), "matrix")})
    return out


def make_weights(cfg: dict, seed: int, shardings=None):
    """Every weight from the seed, on the device, in ONE jitted call, in the
    configuration's stored dtype: matrices N(0, init_std), norm scales
    1 + init_std n, the router's selection bias N(0, select_bias_std), the
    convolution taps U(-taps^-1/2, taps^-1/2) (a depthwise Conv1d's
    default)."""
    shapes = param_shapes(cfg)
    std = float(cfg.get("init_std", 0.02))
    bias_std = float(cfg.get("select_bias_std", 0.05))
    dtype = jnp.dtype(cfg.get("param_dtype", "float32"))
    bound = float(cfg["conv_L_cache"]) ** -0.5

    def fill(kind, k, shape):
        if kind == "conv":
            return bound * (2.0 * jax.random.uniform(k, shape, jnp.float32)
                            - 1.0)
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


def _short_conv(cfg, wl, a, quant):
    """The gated short-convolution mixer, one sequence a [T, d] -> [T, d]."""
    d = cfg["hidden_size"]
    t = a.shape[0]
    bcx = _mm(a, wl["conv.w0"], quant)
    gate_b, gate_c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    u = gate_b * x
    w = wl["conv.w1"].astype(jnp.float32)             # [taps, d]
    taps = w.shape[0]
    c = u * w[taps - 1]
    for j in range(1, taps):                          # u shifted j back
        c = c + jnp.concatenate([jnp.zeros((j, d)), u])[:t] * w[taps - 1 - j]
    return _mm(gate_c * c, wl["conv.w2"], quant)


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
    """QK-normed grouped-query attention, one sequence a [T, d] -> [T, d]."""
    z = _sizes(cfg)
    H, Hkv, dh = z["H"], z["Hkv"], z["dh"]
    t = a.shape[0]
    eps, theta = cfg["norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    q = _mm(a, wl["attn.w0"], quant).reshape(t, H, dh)
    k = _mm(a, wl["attn.w1"], quant).reshape(t, Hkv, dh)
    v = _mm(a, wl["attn.w2"], quant).reshape(t, Hkv, dh)
    q = _rotate(_rms_norm(q, wl["attn.w4"], eps), theta)
    k = _rotate(_rms_norm(k, wl["attn.w5"], eps), theta)
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


def route(cfg, scores, bias):
    """scores [T, E] (sigmoid, float32), bias [E] -> (ids [T, k], weights
    [T, k]): the top k of scores + bias, weights from the scores alone,
    renormalized with the family's + 1e-6, times routed_scaling_factor."""
    if not cfg.get("use_expert_bias", True):
        bias = jnp.zeros_like(bias)
    ids = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(scores, ids, axis=1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return ids, w * cfg["routed_scaling_factor"]


def _moe(cfg, wl, x, quant):
    held = cfg["experts_held"]
    first = cfg.get("ep_rank", 0) * held
    scores = jax.nn.sigmoid(jnp.matmul(
        x, wl["moe.w0"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    ids, w = route(cfg, scores, wl["moe.w4"].astype(jnp.float32).reshape(-1))

    def expert(j, y):
        wj = jnp.sum(jnp.where(ids == first + j, w, 0.0), axis=-1)   # [T]
        return y + wj[:, None] * _swiglu(x, wl["moe.w1"][j], wl["moe.w2"][j],
                                         wl["moe.w3"][j], quant)

    # a loop over the held experts, one expert's three matrices taken up
    # to float32 at a time
    return jax.lax.fori_loop(0, held, expert, jnp.zeros_like(x))


def hidden_states(w, cfg: dict, tokens, quant=None):
    """Final-RMSNorm hidden states [T, d] of ONE sequence of token ids."""
    eps = cfg["norm_eps"]
    x = w["_tok_embedding"][tokens].astype(jnp.float32)
    for i, kind in enumerate(layer_kinds(cfg)):
        b = f"_blk{i}_"
        wl = {k[len(b):]: v for k, v in w.items() if k.startswith(b)}
        mixer = _attention if kind == "full_attention" else _short_conv
        x = x + mixer(cfg, wl, _rms_norm(x, wl["ln1.w0"], eps), quant)
        a = _rms_norm(x, wl["ln2.w0"], eps)
        if i < cfg["num_dense_layers"]:
            x = x + _swiglu(a, wl["ffn.w0"], wl["ffn.w1"], wl["ffn.w2"], quant)
        else:
            x = x + _moe(cfg, wl, a, quant)
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
    cfg = {k: (dict(v) if k == "rope_parameters" else v) for k, v in cfg_key}
    quant = {"": None, "fp8": fp8_quant, "bf16": bf16_quant}[quant_name]
    if what == "log_probs":
        return jax.jit(lambda w, t, r: log_probs(w, cfg, t, r, quant))
    raise KeyError(what)


KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "num_hidden_layers", "num_dense_layers",
        "first_layer", "layer_types", "vocab_size", "norm_eps",
        "conv_L_cache", "rope_parameters", "moe_intermediate_size",
        "num_experts", "experts_held", "ep_rank", "num_experts_per_tok",
        "norm_topk_prob", "use_expert_bias", "routed_scaling_factor")


def jitted(what: str, cfg: dict, quant: str = ""):
    """A jitted reference function ('log_probs') for this configuration's
    sizes; quant '' = the float32 reference, 'fp8' = the control, 'bf16' =
    the precision the configuration states."""
    return _jitted(what, tuple((k, _freeze(cfg[k])) for k in KEYS), quant)
