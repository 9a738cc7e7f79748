"""Plain reference for Laguna (poolside/Laguna-XS.2, model_type laguna):
the forward pass in straightforward jax.numpy, float32 arithmetic under
jax.default_matmul_precision("highest") — no kernels, no cache, no pages,
no chunks, no batching.  Written from the equations below, not from the
program.

  block   h' = h + Attn_kind(RMSNorm(h));  h'' = h' + MLP(RMSNorm(h')),
          eps 1e-6; final RMSNorm; untied head; no bias anywhere.  Layers
          count from 0; layer i's attention kind is layer_types[i], its
          query heads num_attention_heads_per_layer[i], its MLP
          mlp_layer_types[i] — the published lists, read up to the depth.
  Attn    q = x W_q (H_i heads of head_dim), k = x W_k, v = x W_v (H_kv
          heads); the first r = head_dim * partial_rotary_factor columns of
          every q and k head rotate by position in rotate-half pairing
          (column c with c + r/2), the others carry no position:
            full_attention     r = 64, theta 500,000 under YaRN (factor 64,
                               original 4,096, beta_fast 64, beta_slow 1):
                               pair p's frequency theta^(-2p/r) is kept
                               where it turns more than beta_fast times in
                               the original context, divided by the factor
                               where fewer than beta_slow, a linear ramp
                               over p between; cos and sin of the rotated
                               columns times attention_factor (1.41589)
            sliding_attention  r = 128, theta 10,000, no scaling
          causal softmax of q k^T head_dim^-1/2, H_i / H_kv query heads a
          K/V head, in blocks of query rows; a sliding layer's query i sees
          key j iff 0 <= i - j < sliding_window.
          g = sigmoid(x W_g), W_g [d, H_i] (gating: ONE value a head, from
          the layer's normed input);  y = concat_h(a_h * g_h) W_o
  MLP     dense:  (silu(x W_gate) * (x W_up)) W_down, width 8,192
          sparse: s = sigmoid(x W_r) in float32 over the 256 experts; the
          top 8 of s; weights s_e / sum(s chosen) * 2.5;
          y = Shared(x) + sum_e w_e Expert_e(x), every expert and the
          shared one the SwiGLU above at width 512 — a scan over the
          experts, each applied to every token and weighed (0 where a token
          did not choose it)

Settings the controls turn (benchmark/configs/laguna-xs2-33b-serve.json
`limits`; each must fail the cell's limit): `sliding_window` 0 = the window
left out of the sliding layers; `rope_parameters.full_attention.
partial_rotary_factor` 1 = the whole head rotated in a full layer; `gating`
false = no gate.

The cut (the configuration file lists it too): the depth alone — the five
layers are published layers 0-4 as the lists give them, every expert held,
the vocabulary whole.  A rehearsal shrinks the head counts with
num_attention_heads (`heads_of`: the list's value in the proportion
num_attention_heads bears to the list's first entry, a whole number of
query heads a K/V head); at the published 48 it is the list as it is.

The weights are the benchmark's: made here from the seed, on the device, in
one jitted call, in the dtype the configuration stores them in (bfloat16),
under the names benchmark/configs/laguna.py gives its parameters.  Every
matmul takes them up to float32.  `quant=` puts a lower precision in every
matmul's place — the control that `correct` has to refuse (fp8 e4m3 with a
per-tensor scale, the step below the configuration's bfloat16)."""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

ATTN_BLOCK = 256        # query rows a block: [heads, block, T] scores


def heads_of(cfg: dict, i: int) -> int:
    """Layer i's query heads: the published list's, scaled with
    num_attention_heads where a rehearsal shrinks it."""
    per, hkv = cfg["num_attention_heads_per_layer"], \
        cfg["num_key_value_heads"]
    return max(hkv, per[i] * cfg["num_attention_heads"] // per[0]
               // hkv * hkv)


def is_window(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "sliding_attention"


def is_sparse(cfg: dict, i: int) -> bool:
    return cfg["mlp_layer_types"][i] == "sparse"


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) in the DSL file's naming; kind says how the
    seed fills it (make_weights)."""
    d, dh, hkv = cfg["hidden_size"], cfg["head_dim"], \
        cfg["num_key_value_heads"]
    E, fm = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs, f = cfg["shared_expert_intermediate_size"], cfg["intermediate_size"]
    out = {"_tok_embedding": ((cfg["vocab_size"], d), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        b, H = f"_blk{i}_", heads_of(cfg, i)
        out.update({b + "ln1.w0": ((1, d), "scale"),
                    b + "attn.w0": ((d, H * dh), "matrix"),
                    b + "attn.w1": ((d, hkv * dh), "matrix"),
                    b + "attn.w2": ((d, hkv * dh), "matrix"),
                    b + "attn.w3": ((H * dh, d), "matrix")})
        if cfg["gating"]:
            out[b + "attn.w4"] = ((d, H), "matrix")
        out[b + "ln2.w0"] = ((1, d), "scale")
        if is_sparse(cfg, i):
            out.update({b + "moe.w0": ((d, E), "matrix"),
                        b + "moe.w1": ((E, d, fm), "matrix"),
                        b + "moe.w2": ((E, d, fm), "matrix"),
                        b + "moe.w3": ((E, fm, d), "matrix"),
                        b + "moe.w4": ((d, fs), "matrix"),
                        b + "moe.w5": ((d, fs), "matrix"),
                        b + "moe.w6": ((fs, d), "matrix")})
        else:
            out.update({b + "ffn.w0": ((d, f), "matrix"),
                        b + "ffn.w1": ((d, f), "matrix"),
                        b + "ffn.w2": ((f, d), "matrix")})
    out.update({"_final_ln.w0": ((1, d), "scale"),
                "_lm_head.w0": ((d, cfg["vocab_size"]), "matrix")})
    return out


def make_weights(cfg: dict, seed: int, shardings=None):
    """Every weight from the seed, on the device, in ONE jitted call, in the
    configuration's stored dtype: matrices N(0, init_std), norm scales
    1 + init_std n."""
    shapes = param_shapes(cfg)
    std = float(cfg.get("init_std", 0.02))
    dtype = jnp.dtype(cfg.get("param_dtype", "float32"))

    def build(key):
        out = {}
        for name, (shape, kind) in shapes.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            x = std * jax.random.normal(k, shape, jnp.float32)
            out[name] = (x if kind == "matrix" else 1.0 + x).astype(dtype)
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


def rotary_frequencies(r: int, rp: dict):
    """The r/2 frequencies of a rotation of r columns under one entry of
    `rope_parameters`: theta^(-2p/r), blended under rope_type yarn as
    transformers' _compute_yarn_parameters does."""
    p = jnp.arange(r // 2, dtype=jnp.float32)
    theta = float(rp["rope_theta"])
    plain = theta ** (-2.0 * p / r)
    if rp.get("rope_type", "default") != "yarn":
        return plain
    factor, orig = float(rp["factor"]), \
        float(rp["original_max_position_embeddings"])

    def pair_that_turns(n):        # the pair index that turns n times in orig
        return r * math.log(orig / (n * 2.0 * math.pi)) / \
            (2.0 * math.log(theta))

    low = max(math.floor(pair_that_turns(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(pair_that_turns(float(rp["beta_slow"]))), r - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((p - low) / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _rotate(x, rp: dict):
    """x [T, heads, head_dim] rotated at positions 0..T-1 as `rp` says."""
    t, _, dh = x.shape
    r = int(dh * float(rp.get("partial_rotary_factor", 1.0)))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * \
        rotary_frequencies(r, rp)[None, :]                       # [T, r/2]
    amp = float(rp.get("attention_factor", 1.0)) \
        if rp.get("rope_type", "default") == "yarn" else 1.0
    cos, sin = (amp * jnp.cos(ang))[:, None, :], (amp * jnp.sin(ang))[:, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, x[..., r:]],
                           axis=-1)


def _attention(cfg, wl, a, i, quant):
    """Layer i's attention, one sequence a [T, d] -> [T, d]."""
    H, hkv, dh = heads_of(cfg, i), cfg["num_key_value_heads"], cfg["head_dim"]
    window = int(cfg["sliding_window"] or 0) if is_window(cfg, i) else 0
    rp = cfg["rope_parameters"]["sliding_attention" if is_window(cfg, i)
                                else "full_attention"]
    t = a.shape[0]
    rep = H // hkv
    q = _rotate(_mm(a, wl["attn.w0"], quant).reshape(t, H, dh), rp)
    k = _rotate(_mm(a, wl["attn.w1"], quant).reshape(t, hkv, dh), rp)
    v = _mm(a, wl["attn.w2"], quant).reshape(t, hkv, dh)
    # query head h reads K/V head h // rep
    kh = jnp.repeat(jnp.swapaxes(k, 0, 1), rep, axis=0)          # [H, T, dh]
    vh = jnp.repeat(jnp.swapaxes(v, 0, 1), rep, axis=0)

    blk = min(ATTN_BLOCK, t)
    assert t % blk == 0, f"{t} tokens do not split in blocks of {blk}"
    qb = jnp.swapaxes(q, 0, 1).reshape(H, t // blk, blk, dh)

    def one(args):
        qi, n = args                                  # [H, blk, dh], block no
        s = _mm(qi, jnp.swapaxes(kh, 1, 2), quant) * dh ** -0.5  # [H,blk,T]
        gap = (n * blk + jnp.arange(blk))[:, None] - jnp.arange(t)[None, :]
        seen = gap >= 0
        if window:
            seen = jnp.logical_and(seen, gap < window)
        s = jnp.where(seen[None], s, -1e30)
        return _mm(jax.nn.softmax(s, axis=-1), vh, quant)       # [H,blk,dh]

    o = jax.lax.map(one, (jnp.swapaxes(qb, 0, 1), jnp.arange(t // blk)))
    o = jnp.transpose(o, (0, 2, 1, 3)).reshape(t, H, dh)
    if cfg["gating"]:
        o = o * jax.nn.sigmoid(_mm(a, wl["attn.w4"], quant))[:, :, None]
    return _mm(o.reshape(t, H * dh), wl["attn.w3"], quant)


def _swiglu(x, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def route(cfg, scores):
    """scores [T, E] (sigmoid, float32) -> (ids [T, k], weights [T, k]): the
    top k by score, renormalised over the chosen, times the scale."""
    w, ids = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w * cfg["moe_routed_scaling_factor"]


def _moe(cfg, wl, x, quant):
    scores = jax.nn.sigmoid(jnp.matmul(
        x, wl["moe.w0"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    ids, w = route(cfg, scores)

    def expert(y, e):
        j, wg, wu, wd = e
        wj = jnp.sum(jnp.where(ids == j, w, 0.0), axis=-1)           # [T]
        return y + wj[:, None] * _swiglu(x, wg, wu, wd, quant), None

    y, _ = jax.lax.scan(
        expert, _swiglu(x, wl["moe.w4"], wl["moe.w5"], wl["moe.w6"], quant),
        (jnp.arange(cfg["num_experts"]), wl["moe.w1"], wl["moe.w2"],
         wl["moe.w3"]))
    return y


def hidden_states(w, cfg: dict, tokens, quant=None):
    """Final-RMSNorm hidden states [T, d] of ONE sequence of token ids."""
    eps = cfg["rms_norm_eps"]
    x = w["_tok_embedding"][tokens].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        b = f"_blk{i}_"
        wl = {k[len(b):]: v for k, v in w.items() if k.startswith(b)}
        x = x + _attention(cfg, wl, _rms_norm(x, wl["ln1.w0"], eps), i, quant)
        a = _rms_norm(x, wl["ln2.w0"], eps)
        x = x + (_moe(cfg, wl, a, quant) if is_sparse(cfg, i) else
                 _swiglu(a, wl["ffn.w0"], wl["ffn.w1"], wl["ffn.w2"], quant))
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


def _thaw(v):
    if isinstance(v, tuple) and v and all(
            isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], str)
            for x in v):
        return {k: _thaw(x) for k, x in v}
    return v


@functools.lru_cache(maxsize=None)
def _jitted(what: str, cfg_key: tuple, quant_name: str):
    cfg = {k: _thaw(v) for k, v in cfg_key}
    quant = {"": None, "fp8": fp8_quant, "bf16": bf16_quant}[quant_name]
    if what == "log_probs":
        return jax.jit(lambda w, t, r: log_probs(w, cfg, t, r, quant))
    raise KeyError(what)


KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_hidden_layers", "vocab_size",
        "rms_norm_eps", "sliding_window", "gating", "rope_parameters",
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
        "num_experts", "num_experts_per_tok", "moe_intermediate_size",
        "shared_expert_intermediate_size", "moe_routed_scaling_factor")


def jitted(what: str, cfg: dict, quant: str = ""):
    """A jitted reference function ('log_probs') for this configuration's
    sizes; quant '' = the float32 reference, 'fp8' = the control, 'bf16' =
    the precision the configuration states."""
    return _jitted(what, tuple((k, _freeze(cfg[k])) for k in KEYS), quant)
