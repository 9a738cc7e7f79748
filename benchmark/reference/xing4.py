"""Plain reference for Xing4.0-29B-A4B (XingChen-AGI/Xing4.0-29B-A4B,
model_type xing4_0): the forward pass in straightforward jax.numpy, float32
arithmetic under jax.default_matmul_precision("highest") — no kernels, no
cache, no absorption of the latent projections, no batching tricks.

  streams X [T, n, C], n = hc_mult: the embedding copied into each; after
          the last block the streams are summed, final RMSNorm, untied head
  sublayer (attention, then the MLP, each with its own maps; mHC,
          arXiv:2512.24880 on arXiv:2409.19606):
          x~ = RMSNorm(vec(X)) over all n C values, eps hc_eps, no scale
          H~_pre  = a_pre  (x~ phi_pre)  + b_pre        [n]
          H~_post = a_post (x~ phi_post) + b_post       [n]
          H~_res  = a_res mat(x~ phi_res) + b_res       [n, n]
          H_pre = sigmoid(H~_pre); H_post = 2 sigmoid(H~_post);
          H_res = Sinkhorn-Knopp(exp(clip(H~_res, clamp_min, clamp_max)),
          hc_sinkhorn_iters iterations: rows to sum 1, then columns, each
          iteration; hc_eps in both denominators)
          u = sum_i H_pre[i] X[i];  y = F(RMSNorm(u));
          X'[j] = sum_i H_res[j, i] X[i] + H_post[j] y
  MLA     c_q = RMSNorm(x W_qa); per head [q_nope, q_pe] = c_q W_qb;
          [c_kv, k_pe] = x W_kva, c_kv <- RMSNorm(c_kv); q_pe, k_pe rotated
          (YaRN frequencies; k_pe is one per token, shared by the heads);
          per head [k_nope, v] = c_kv W_kvb; scores
          (q_nope.k_nope + q_pe.k_pe) * (nope+rope)^-0.5 * mscale^2,
          mscale = 0.1 * mscale_all_dim * ln(factor) + 1; causal softmax;
          concat_h(P v) W_o — the EXPANDED form only, queries in blocks
  FFN     SwiGLU (silu(x W_g) * x W_u) W_d: dense in the first
          first_k_dense_replace layers, then MoE: s = sigmoid(x W_r) in
          float32 over the n_routed_experts; the top num_experts_per_tok by
          s + b (n_group = topk_group = 1: no group limit); weights
          s_i / sum(s_selected) * routed_scaling_factor (from s, not s + b);
          y = Shared(x) + sum over the selected experts of w_i Expert_i(x),
          a scan over ALL the experts (every one is held: ep_size 1)

The parameters of a sublayer's maps are ONE matrix phi [n C, 2 n + n^2]
whose columns are [pre | post | res, row j then column i], a bias row of
that width and the three gates [a_pre, a_post, a_res].

Departures (the configuration file lists them too):
  * half-split rotation (feature i pairs with i + D/2) where the checkpoint
    interleaves (2i, 2i+1): a fixed permutation of the rope columns of W_qb
    and W_kva, the same q_pe . k_pe;
  * no multi-token-prediction module (num_nextn_predict_layers 0);
  * the depth is the stage's.

The weights are the benchmark's: made here from the seed, on the device, in
one jitted call, in the dtype the configuration stores them in (bfloat16),
under the names benchmark/configs/xing4.py gives its parameters.  Every
matmul takes them up to float32.  The seeded maps are LARGE on purpose
(`assumed` in the configuration file): phi ~ N(0, init_std), biases
N(0, hc_bias_std), gates hc_alpha_init (1 + 0.1 n) — the published initial
gate 0.01 would hide every part of the maps behind the limit.

`quant=` puts a lower precision in every matmul's place: the control that
`correct` has to refuse (fp8 e4m3 with a per-tensor scale, the step below
the configuration's bfloat16).  Four more controls are configuration
overrides (tools/serve_controls.py, tests/model_parity.py `ref_controls`):
`hc_sinkhorn_iters` 1; `hc_post_scale` 1 (H_post = sigmoid); `hc_dynamic`
false (the gates taken as 0); `hc_plain_residual` true (one stream,
x + F(RMSNorm(x)), the maps unused).

The head runs in blocks of the vocabulary written into one [rows, vocab]
table: beside 12.3 GB of weights and cache a second table, or the head's
matrix whole in float32, does not fit the chip."""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

ATTN_BLOCK = 256        # query rows a block: [heads, block, T] scores
HEAD_BLOCK = 8192       # vocabulary columns a block of the head


def _sizes(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], H=cfg["num_attention_heads"],
        qr=cfg["q_lora_rank"], kr=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        vd=cfg["v_head_dim"], f=cfg["intermediate_size"],
        fm=cfg["moe_intermediate_size"], E=cfg["n_routed_experts"],
        fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        v=cfg["vocab_size"], n=cfg["hc_mult"])


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) in the DSL file's naming; kind is 'matrix',
    'scale', 'select_bias', 'hc_bias' or 'hc_alpha'."""
    z = _sizes(cfg)
    d, H, n = z["d"], z["H"], z["n"]
    m = 2 * n + n * n

    def maps(name):
        return {name + "_maps.w0": ((n * d, m), "matrix"),
                name + "_maps.w1": ((1, m), "hc_bias"),
                name + "_maps.w2": ((1, 3), "hc_alpha")}
    out = {"_tok_embedding": ((z["v"], d), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        b = f"_blk{i}_"
        out.update(maps(b + "hc1"))
        out.update({
            b + "ln1.w0": ((1, d), "scale"),
            b + "attn.w0": ((d, z["qr"]), "matrix"),
            b + "attn.w1": ((1, z["qr"]), "scale"),
            b + "attn.w2": ((z["qr"], H * (z["nope"] + z["rope"])), "matrix"),
            b + "attn.w3": ((d, z["kr"] + z["rope"]), "matrix"),
            b + "attn.w4": ((1, z["kr"]), "scale"),
            b + "attn.w5": ((z["kr"], H * (z["nope"] + z["vd"])), "matrix"),
            b + "attn.w6": ((H * z["vd"], d), "matrix"),
        })
        out.update(maps(b + "hc2"))
        out[b + "ln2.w0"] = ((1, d), "scale")
        if i < cfg["first_k_dense_replace"]:
            out.update({b + "ffn.w0": ((d, z["f"]), "matrix"),
                        b + "ffn.w1": ((d, z["f"]), "matrix"),
                        b + "ffn.w2": ((z["f"], d), "matrix")})
        else:
            e, fm, fs = z["E"], z["fm"], z["fs"]
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
    maps' biases N(0, hc_bias_std), their gates hc_alpha_init (1 + 0.1 n)."""
    shapes = param_shapes(cfg)
    std = float(cfg.get("init_std", 0.02))
    bias_std = float(cfg.get("select_bias_std", 0.05))
    hc_bias_std = float(cfg.get("hc_bias_std", 1.0))
    hc_alpha = float(cfg.get("hc_alpha_init", 1.0))
    dtype = jnp.dtype(cfg.get("param_dtype", "float32"))

    def build(key):
        out = {}
        for name, (shape, kind) in shapes.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            x = jax.random.normal(k, shape, jnp.float32)
            x = {"matrix": std * x, "scale": 1.0 + std * x,
                 "select_bias": bias_std * x, "hc_bias": hc_bias_std * x,
                 "hc_alpha": hc_alpha * (1.0 + 0.1 * x)}[kind]
            out[name] = x.astype(dtype)
        return out

    fn = jax.jit(build, out_shardings=shardings)
    return fn(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


def fp8_quant(x, amax=None):
    """The control's precision: e4m3 with a per-tensor scale (`amax`: the
    whole tensor's, where `x` is a block of it)."""
    if amax is None:
        amax = jnp.max(jnp.abs(x))
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def bf16_quant(x, amax=None):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(a, b, quant):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(jnp.float32).reshape(-1)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, rs: dict) -> np.ndarray:
    """YaRN (arXiv:2309.00071) as DeepSeek-V3 applies it: pair i keeps
    theta^(-2i/dim) if it turns more than beta_fast times over the original
    context, takes that over `factor` if fewer than beta_slow times, and a
    linear ramp in i blends the two between the correction dims."""
    i = np.arange(0, dim, 2, dtype=np.float64)
    base = theta ** (-i / dim)
    factor, orig = float(rs["factor"]), \
        float(rs["original_max_position_embeddings"])

    def corr(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = max(math.floor(corr(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rs["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (base / factor * ramp + base * (1 - ramp)).astype(np.float32)


def _rotate(x, inv_freq, amp):
    """x [T, ..., D] at positions 0..T-1, half-split pairs."""
    t, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(cfg, wl, a, quant):
    """MLA, expanded form, one sequence a [T, d] -> [T, d]."""
    z = _sizes(cfg)
    H, nope, rope, vd, kr = z["H"], z["nope"], z["rope"], z["vd"], z["kr"]
    rs = cfg["rope_scaling"]
    eps = cfg["rms_norm_eps"]
    t = a.shape[0]
    inv_freq = yarn_inv_freq(rope, float(cfg["rope_theta"]), rs)
    amp = _mscale(rs["factor"], rs["mscale"]) / \
        _mscale(rs["factor"], rs["mscale_all_dim"])
    scale = (nope + rope) ** -0.5 * _mscale(rs["factor"],
                                            rs["mscale_all_dim"]) ** 2

    c_q = _rms_norm(_mm(a, wl["attn.w0"], quant), wl["attn.w1"], eps)
    q = _mm(c_q, wl["attn.w2"], quant).reshape(t, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], inv_freq, amp)],
                        -1)
    ckv = _mm(a, wl["attn.w3"], quant)
    c_kv = _rms_norm(ckv[:, :kr], wl["attn.w4"], eps)
    k_pe = _rotate(ckv[:, kr:], inv_freq, amp)                   # [T, rope]
    kv = _mm(c_kv, wl["attn.w5"], quant).reshape(t, H, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe[:, None], (t, H, rope))], -1)
    v = kv[..., nope:]
    kh, vh = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)       # [H, T, .]

    blk = min(ATTN_BLOCK, t)
    assert t % blk == 0, f"{t} tokens do not split in blocks of {blk}"
    qb = jnp.swapaxes(q, 0, 1).reshape(H, t // blk, blk, nope + rope)

    def one(args):
        qi, i = args                                  # [H, blk, D], block no
        s = _mm(qi, jnp.swapaxes(kh, 1, 2), quant) * scale       # [H,blk,T]
        rows = i * blk + jnp.arange(blk)
        s = jnp.where(jnp.arange(t)[None, None] <= rows[None, :, None],
                      s, -1e30)
        return _mm(jax.nn.softmax(s, axis=-1), vh, quant)       # [H,blk,vd]

    o = jax.lax.map(one, (jnp.swapaxes(qb, 0, 1), jnp.arange(t // blk)))
    o = jnp.transpose(o, (0, 2, 1, 3)).reshape(t, H * vd)
    return _mm(o, wl["attn.w6"], quant)


def _swiglu(x, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def route(cfg, scores, bias):
    """scores [T, E] (sigmoid, float32), bias [E] -> (ids [T, k], weights
    [T, k]): the top k by scores + bias among all the experts, weights from
    the scores alone, renormalized, times routed_scaling_factor."""
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    ids = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(scores, ids, axis=1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w * cfg["routed_scaling_factor"]


def _moe(cfg, wl, x, quant):
    scores = jax.nn.sigmoid(jnp.matmul(
        x, wl["moe.w0"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    ids, w = route(cfg, scores, wl["moe.w4"].astype(jnp.float32).reshape(-1))

    def expert(y, e):
        j, wg, wu, wd = e
        wj = jnp.sum(jnp.where(ids == j, w, 0.0), axis=-1)           # [T]
        return y + wj[:, None] * _swiglu(x, wg, wu, wd, quant), None

    y, _ = jax.lax.scan(
        expert, _swiglu(x, wl["moe.w5"], wl["moe.w6"], wl["moe.w7"], quant),
        (jnp.arange(cfg["n_routed_experts"]), wl["moe.w1"], wl["moe.w2"],
         wl["moe.w3"]))
    return y


def sinkhorn(m, iters: int, eps: float):
    """m [T, n, n] positive: each iteration the rows to sum 1 (row j, the
    sum over i), then the columns."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def hyper_maps(cfg, wl, name, X, quant):
    """X [T, n, C] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]) of the
    sublayer whose maps are `name`."""
    t, n, _ = X.shape
    eps = float(cfg["hc_eps"])
    x = X.reshape(t, -1)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    a = wl[name + ".w2"].astype(jnp.float32).reshape(3)
    if not cfg.get("hc_dynamic", True):
        a = a * 0.0
    b = wl[name + ".w1"].astype(jnp.float32).reshape(-1)
    z = _mm(x, wl[name + ".w0"], quant)                      # [T, 2n + n^2]
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = float(cfg.get("hc_post_scale", 2.0)) * jax.nn.sigmoid(
        a[1] * z[:, n:2 * n] + b[n:2 * n])
    res = (a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(t, n, n)
    res = jnp.exp(jnp.clip(res, float(cfg["mhc_h_res_clamp_min"]),
                           float(cfg["mhc_h_res_clamp_max"])))
    return pre, post, sinkhorn(res, int(cfg["hc_sinkhorn_iters"]), eps)


def hidden_states(w, cfg: dict, tokens, quant=None):
    """Final-RMSNorm hidden states [T, d] of ONE sequence of token ids."""
    eps = cfg["rms_norm_eps"]
    n = cfg["hc_mult"]
    plain = bool(cfg.get("hc_plain_residual", False))
    x = w["_tok_embedding"][tokens].astype(jnp.float32)
    X = x[:, None, :] if plain else jnp.tile(x[:, None, :], (1, n, 1))
    for i in range(cfg["num_hidden_layers"]):
        b = f"_blk{i}_"
        wl = {k[len(b):]: v for k, v in w.items() if k.startswith(b)}

        def mlp(a):
            if i < cfg["first_k_dense_replace"]:
                return _swiglu(a, wl["ffn.w0"], wl["ffn.w1"], wl["ffn.w2"],
                               quant)
            return _moe(cfg, wl, a, quant)

        for maps, norm, f in (
                ("hc1_maps", "ln1.w0",
                 lambda a: _attention(cfg, wl, a, quant)),
                ("hc2_maps", "ln2.w0", mlp)):
            if plain:
                X = X + f(_rms_norm(X[:, 0], wl[norm], eps))[:, None]
                continue
            pre, post, res = hyper_maps(cfg, wl, maps, X, quant)
            u = jnp.einsum("ti,tic->tc", pre, X, precision="highest")
            y = f(_rms_norm(u, wl[norm], eps))
            X = jnp.einsum("tji,tic->tjc", res, X, precision="highest") \
                + post[:, :, None] * y[:, None, :]
    return _rms_norm(jnp.sum(X, axis=1), w["_final_ln.w0"], eps)


def log_probs(w, cfg: dict, tokens, rows=None, quant=None):
    """log softmax of the head over the vocabulary at `rows` (all rows if
    None) of one sequence: [n_rows, vocab].  The head in blocks of
    HEAD_BLOCK columns, each written into the one table; a per-tensor scale
    of `quant` is the whole matrix's."""
    h = hidden_states(w, cfg, tokens, quant)
    if rows is not None:
        h = h[rows]
    head = w["_lm_head.w0"]
    v = head.shape[1]
    blk = HEAD_BLOCK if v % HEAD_BLOCK == 0 else v
    if quant is not None:
        amax = jnp.max(jnp.abs(head)).astype(jnp.float32)
        h = quant(h)

    def block(i, table):
        wb = jax.lax.dynamic_slice_in_dim(head, i * blk, blk, axis=1)
        wb = wb.astype(jnp.float32)
        if quant is not None:
            wb = quant(wb, amax)
        lb = jnp.matmul(h, wb, precision=jax.lax.Precision.HIGHEST)
        return jax.lax.dynamic_update_slice_in_dim(table, lb, i * blk, axis=1)

    logits = jax.lax.fori_loop(
        0, v // blk, block, jnp.zeros((h.shape[0], v), jnp.float32))
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)

    def shift(i, table):
        # in place, a block at a time: `logits - lse` whole is a second table
        lb = jax.lax.dynamic_slice_in_dim(table, i * blk, blk, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(table, lb - lse, i * blk,
                                                   axis=1)

    return jax.lax.fori_loop(0, v // blk, shift, logits)


def _freeze(v):
    return tuple(sorted((k, _freeze(x)) for k, x in v.items())) \
        if isinstance(v, dict) else v


def _thaw(v):
    return {k: _thaw(x) for k, x in v} if isinstance(v, tuple) else v


@functools.lru_cache(maxsize=None)
def _jitted(what: str, cfg_key: tuple, quant_name: str):
    cfg = {k: (_thaw(v) if k == "rope_scaling" else v) for k, v in cfg_key}
    quant = {"": None, "fp8": fp8_quant, "bf16": bf16_quant}[quant_name]
    if what == "log_probs":
        return jax.jit(lambda w, t, r: log_probs(w, cfg, t, r, quant))
    raise KeyError(what)


KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "num_hidden_layers", "vocab_size", "rms_norm_eps", "rope_theta",
        "rope_scaling", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size",
        "n_routed_experts", "num_experts_per_tok", "n_group", "topk_group",
        "n_shared_experts", "routed_scaling_factor", "first_k_dense_replace",
        "norm_topk_prob", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
        "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
# the controls' settings: absent from a configuration file
CONTROL_KEYS = {"hc_post_scale": 2.0, "hc_dynamic": True,
                "hc_plain_residual": False}


def jitted(what: str, cfg: dict, quant: str = ""):
    """A jitted reference function ('log_probs') for this configuration's
    sizes; quant '' = the float32 reference, 'fp8' = the control, 'bf16' =
    the precision the configuration states."""
    key = tuple((k, _freeze(cfg[k])) for k in KEYS) + tuple(
        (k, cfg.get(k, v)) for k, v in CONTROL_KEYS.items())
    return _jitted(what, key, quant)
