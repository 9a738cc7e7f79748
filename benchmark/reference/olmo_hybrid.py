"""Plain reference for Olmo-Hybrid (allenai/Olmo-Hybrid-7B, model_type
olmo_hybrid): the forward pass in straightforward jax.numpy, float32
arithmetic under jax.default_matmul_precision("highest") — no kernels, no
cache, no chunks, no batching.

  block   h = x + RMSNorm_a(Mixer_i(x));  y = h + RMSNorm_f(MLP(h)), eps
          1e-6: NO norm in front of a sublayer, one on its output (the
          Olmo 2 block, arXiv:2501.00656: `norm_after_sublayer`, assumed —
          false reads the pre-norm block, the control); a final RMSNorm in
          front of the untied head.  Layer i is `layer_types[i]`:
          full_attention where i % 4 == 3, else linear_attention.
          MLP(h) = (silu(h W_g) * (h W_u)) W_d, no biases anywhere.
  linear_attention — Gated DeltaNet (arXiv:2412.06464), H heads of dk x dv:
          q~ = x W_q, k~ = x W_k [H dk], v~ = x W_v [H dv]; conv4 = a causal
          depthwise convolution over the last 4 positions, no bias, written
          as a sum of four shifted products, then silu; q = l2norm(q~), k =
          l2norm(k~) a head, x / sqrt(sum x^2 + 1e-6); q scaled by dk^-1/2.
          g = -exp(A_log_h) softplus(x W_a + dt_bias_h): ONE log decay a
          head; beta = 2 sigmoid(x W_b) a head (linear_allow_neg_eigval;
          sigmoid alone where it is false).  A head's state S [dk, dv], zero
          at position 0, one token at a time in a lax.scan:
            S <- exp(g_t) S;        u = beta_t (v_t - S^T k_t);
            S <- S + k_t u^T;       o_t = S^T q_t
          y = (RMSNorm_dv(o_t) * silu(x W_z)) W_o, the norm's scale [dv].
  full_attention: q = x W_q, k = x W_k, v = x W_v, H heads of d / H on as
          many KV heads; an RMSNorm over the WHOLE projected q and the whole
          k before the heads are split (`use_qk_norm`, `qk_norm_whole`:
          Olmo 2's QK-norm, assumed; `qk_norm_whole` false norms a head);
          no rotation (`use_rope` false: the published
          rope_parameters.rope_theta is null; assumed); causal softmax of
          q k^T (d / H)^-1/2 in blocks of query rows; y = concat_h(P v) W_o.

Departures (the configuration file lists them too):
  * the linear layers' head count follows the attention head count where
    that is smaller (min(linear_num_key_heads, num_attention_heads)): a
    rehearsal shrinks both with one key;
  * `linear_decay` (absent from a configuration file: true) is a control's
    switch — false puts g = 0 in the rule's place, the delta rule without
    its gate.

The weights are the benchmark's: made here from the seed, on the device, in
one jitted call, in the dtype the configuration stores them in (bfloat16),
under the names benchmark/configs/olmo_hybrid.py gives its parameters.
Every matmul takes them up to float32.  The recurrence itself is float32
whatever `quant` says: `quant=` puts a lower precision in every MATMUL's
place — the control that `correct` has to refuse (fp8 e4m3 with a
per-tensor scale, the step below the configuration's bfloat16).  The head
runs in blocks of HEAD_BLOCK vocabulary columns into ONE table: [6144,
100352] float32 is 2.5 GB, and the check runs beside a 12 GB server."""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

ATTN_BLOCK = 256        # query rows a block: [heads, block, T] scores
HEAD_BLOCK = 7168       # vocabulary columns a block of the head (14 blocks)


def _sizes(cfg: dict) -> dict:
    H = cfg["num_attention_heads"]
    assert cfg["linear_num_key_heads"] == cfg["linear_num_value_heads"]
    return dict(
        d=cfg["hidden_size"], H=H, Hkv=cfg["num_key_value_heads"],
        dh=cfg["hidden_size"] // H, f=cfg["intermediate_size"],
        Hl=min(cfg["linear_num_key_heads"], H),
        dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        taps=cfg["linear_conv_kernel_dim"], v=cfg["vocab_size"])


def full_layers(cfg: dict) -> set:
    """The 0-based indices of the full-attention layers at this depth."""
    return {i for i, t in enumerate(
        cfg["layer_types"][:cfg["num_hidden_layers"]])
        if t == "full_attention"}


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) in the DSL file's naming; kind says how the
    seed fills it (make_weights)."""
    z = _sizes(cfg)
    d, H, Hl, dk, dv = z["d"], z["H"], z["Hl"], z["dk"], z["dv"]
    full = full_layers(cfg)
    out = {"_tok_embedding": ((z["v"], d), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        b = f"_blk{i}_"
        if i in full:
            out.update({
                b + "attn.w0": ((d, d), "matrix"),
                b + "attn.w1": ((d, z["Hkv"] * z["dh"]), "matrix"),
                b + "attn.w2": ((d, z["Hkv"] * z["dh"]), "matrix"),
                b + "attn.w3": ((d, d), "matrix")})
            if cfg["use_qk_norm"]:
                whole = cfg["qk_norm_whole"]
                out[b + "attn.w4"] = ((1, d if whole else z["dh"]), "scale")
                out[b + "attn.w5"] = (
                    (1, z["Hkv"] * z["dh"] if whole else z["dh"]), "scale")
        else:
            out.update({
                b + "gdn.w0": ((d, Hl * dk), "matrix"),
                b + "gdn.w1": ((d, Hl * dk), "matrix"),
                b + "gdn.w2": ((d, Hl * dv), "matrix"),
                b + "gdn.w3": ((z["taps"], Hl * dk), "conv"),
                b + "gdn.w4": ((z["taps"], Hl * dk), "conv"),
                b + "gdn.w5": ((z["taps"], Hl * dv), "conv"),
                b + "gdn.w6": ((d, Hl), "matrix"),
                b + "gdn.w7": ((1, Hl), "a_log"),
                b + "gdn.w8": ((1, Hl), "dt_bias"),
                b + "gdn.w9": ((d, Hl), "matrix"),
                b + "gdn.w10": ((d, Hl * dv), "matrix"),
                b + "gdn.w11": ((1, dv), "scale"),
                b + "gdn.w12": ((Hl * dv, d), "matrix")})
        out.update({b + "ln1.w0": ((1, d), "scale"),
                    b + "ffn.w0": ((d, z["f"]), "matrix"),
                    b + "ffn.w1": ((d, z["f"]), "matrix"),
                    b + "ffn.w2": ((z["f"], d), "matrix"),
                    b + "ln2.w0": ((1, d), "scale")})
    out.update({"_final_ln.w0": ((1, d), "scale"),
                "_lm_head.w0": ((d, z["v"]), "matrix")})
    return out


def make_weights(cfg: dict, seed: int, shardings=None):
    """Every weight from the seed, on the device, in ONE jitted call, in the
    configuration's stored dtype: matrices N(0, init_std), norm scales
    1 + init_std n, the convolution taps U(-1/2, 1/2) (a 4-tap depthwise
    Conv1d's default), and fla's GatedDeltaNet initializers: A_log = log
    U(0, 16) a head (held off zero at 1e-4), dt_bias = softplus^-1 of a
    step drawn log-uniformly from [1e-3, 1e-1] a head."""
    shapes = param_shapes(cfg)
    std = float(cfg.get("init_std", 0.02))
    dtype = jnp.dtype(cfg.get("param_dtype", "float32"))

    def fill(kind, k, shape):
        if kind in ("matrix", "scale"):
            x = jax.random.normal(k, shape, jnp.float32)
            return std * x if kind == "matrix" else 1.0 + std * x
        u = jax.random.uniform(k, shape, jnp.float32)
        if kind == "conv":
            return u - 0.5
        if kind == "a_log":
            return jnp.log(jnp.maximum(16.0 * u, 1e-4))
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


def _gdn(cfg, wl, a, quant):
    """The Gated DeltaNet mixer, one sequence a [T, d] -> [T, d]."""
    z = _sizes(cfg)
    H, dk, dv = z["Hl"], z["dk"], z["dv"]
    t = a.shape[0]
    f32 = lambda name: wl[name].astype(jnp.float32)
    q = _l2norm(jax.nn.silu(_conv(_mm(a, wl["gdn.w0"], quant),
                                  wl["gdn.w3"])).reshape(t, H, dk))
    k = _l2norm(jax.nn.silu(_conv(_mm(a, wl["gdn.w1"], quant),
                                  wl["gdn.w4"])).reshape(t, H, dk))
    v = jax.nn.silu(_conv(_mm(a, wl["gdn.w2"], quant),
                          wl["gdn.w5"])).reshape(t, H, dv)
    g = -jnp.exp(f32("gdn.w7").reshape(H)) * jax.nn.softplus(
        _mm(a, wl["gdn.w6"], quant) + f32("gdn.w8").reshape(H))  # [T, H]
    if not cfg.get("linear_decay", True):
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(_mm(a, wl["gdn.w9"], quant))           # [T, H]
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    gate = _mm(a, wl["gdn.w10"], quant).reshape(t, H, dv)

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs         # [H, dk] x2, [H, dv], [H] x2
        S = jnp.exp(g_t)[:, None, None] * S
        u = b_t[:, None] * (v_t - jnp.sum(S * k_t[:, :, None], axis=1))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.sum(S * (q_t * dk ** -0.5)[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, g, beta))                       # [T, H, dv]
    o = _rms_norm(o, wl["gdn.w11"], cfg["rms_norm_eps"]) * jax.nn.silu(gate)
    return _mm(o.reshape(t, H * dv), wl["gdn.w12"], quant)


def _attention(cfg, wl, a, quant):
    """Full attention, one sequence a [T, d] -> [T, d]."""
    z = _sizes(cfg)
    H, Hkv, dh = z["H"], z["Hkv"], z["dh"]
    assert not cfg["use_rope"], "this reading rotates nothing"
    eps = cfg["rms_norm_eps"]
    t = a.shape[0]
    rep = H // Hkv
    q, k = _mm(a, wl["attn.w0"], quant), _mm(a, wl["attn.w1"], quant)
    if cfg["use_qk_norm"] and cfg["qk_norm_whole"]:
        q, k = _rms_norm(q, wl["attn.w4"], eps), _rms_norm(k, wl["attn.w5"],
                                                          eps)
    q, k = q.reshape(t, H, dh), k.reshape(t, Hkv, dh)
    if cfg["use_qk_norm"] and not cfg["qk_norm_whole"]:
        q, k = _rms_norm(q, wl["attn.w4"], eps), _rms_norm(k, wl["attn.w5"],
                                                          eps)
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
    return _mm(o, wl["attn.w3"], quant)


def _mlp(wl, x, quant):
    return _mm(jax.nn.silu(_mm(x, wl["ffn.w0"], quant)) *
               _mm(x, wl["ffn.w1"], quant), wl["ffn.w2"], quant)


def hidden_states(w, cfg: dict, tokens, quant=None):
    """Final-RMSNorm hidden states [T, d] of ONE sequence of token ids."""
    eps = cfg["rms_norm_eps"]
    full = full_layers(cfg)
    post = cfg["norm_after_sublayer"]
    x = w["_tok_embedding"][tokens].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        b = f"_blk{i}_"
        wl = {k[len(b):]: v for k, v in w.items() if k.startswith(b)}
        mixer = _attention if i in full else _gdn
        for norm, fn in ((wl["ln1.w0"], lambda y: mixer(cfg, wl, y, quant)),
                         (wl["ln2.w0"], lambda y: _mlp(wl, y, quant))):
            x = x + (_rms_norm(fn(x), norm, eps) if post
                     else fn(_rms_norm(x, norm, eps)))
    return _rms_norm(x, w["_final_ln.w0"], eps)


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


@functools.lru_cache(maxsize=None)
def _jitted(what: str, cfg_key: tuple, quant_name: str):
    cfg = {k: (list(v) if isinstance(v, tuple) else v) for k, v in cfg_key}
    quant = {"": None, "fp8": fp8_quant, "bf16": bf16_quant}[quant_name]
    if what == "log_probs":
        return jax.jit(lambda w, t, r: log_probs(w, cfg, t, r, quant))
    raise KeyError(what)


KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "num_hidden_layers", "vocab_size",
        "rms_norm_eps", "layer_types", "linear_num_key_heads",
        "linear_num_value_heads", "linear_key_head_dim",
        "linear_value_head_dim", "linear_conv_kernel_dim",
        "linear_allow_neg_eigval", "norm_after_sublayer", "use_qk_norm",
        "qk_norm_whole", "use_rope")
# the controls' settings: absent from a configuration file
CONTROL_KEYS = {"linear_decay": True}


def jitted(what: str, cfg: dict, quant: str = ""):
    """A jitted reference function ('log_probs') for this configuration's
    sizes; quant '' = the float32 reference, 'fp8' = the control, 'bf16' =
    the precision the configuration states."""
    key = tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                for k in KEYS) + tuple(
        (k, cfg.get(k, v)) for k, v in CONTROL_KEYS.items())
    return _jitted(what, key, quant)
