"""Plain reference for the StarCoder2 family: the forward pass, the loss and
(through jax.grad) its gradients in straightforward jax.numpy and float32
under jax.default_matmul_precision("highest") — no kernels, no cache, no
batching tricks.  It follows the published architecture (arXiv:2402.19173:
pre-norm LayerNorm, GQA with rotate-half RoPE, biased tanh-GELU MLP) with the
departures the configuration file lists (untied head, one attention bias,
eps as run, no window below 4,096 positions).

The weights are the benchmark's: made here from the seed, on the device, in
one jitted call, in the float32 the program serves them in, under the names
benchmark/configs/starcoder2.py gives its parameters.  The program receives
them; the reference takes nothing the program made.

`quant=` puts a lower precision in every matmul's place: the control that
`correct` has to refuse (fp8 e4m3 with a per-tensor scale, the step below the
configuration's bfloat16)."""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) in the DSL file's naming; kind is 'matrix',
    'bias' or 'scale'."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    out = {"_tok_embedding": ((v, d), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        b = f"_blk{i}_"
        out.update({
            b + "ln1.w0": ((1, d), "scale"), b + "ln1.wbias": ((1, d), "bias"),
            b + "attn.w0": ((d, d), "matrix"), b + "attn.w1": ((d, kv), "matrix"),
            b + "attn.w2": ((d, kv), "matrix"), b + "attn.w3": ((d, d), "matrix"),
            b + "attn.wbias": ((1, d), "bias"),
            b + "ln2.w0": ((1, d), "scale"), b + "ln2.wbias": ((1, d), "bias"),
            b + "ffn1.w0": ((d, f), "matrix"), b + "ffn1.wbias": ((1, f), "bias"),
            b + "ffn2.w0": ((f, d), "matrix"), b + "ffn2.wbias": ((1, d), "bias"),
        })
    out.update({"_final_ln.w0": ((1, d), "scale"),
                "_final_ln.wbias": ((1, d), "bias"),
                "_lm_head.w0": ((d, v), "matrix")})
    return out


def make_weights(cfg: dict, seed: int, shardings=None, std: float = 0.02):
    """Every weight from the seed, on the device, in ONE jitted call."""
    shapes = param_shapes(cfg)

    def build(key):
        out = {}
        for name, (shape, kind) in shapes.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            x = std * jax.random.normal(k, shape, jnp.float32)
            out[name] = x + 1.0 if kind == "scale" else x
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
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g.reshape(-1) + b.reshape(-1)


def _rope(x, theta):
    """x [T, H, D], rotate-half convention, positions 0..T-1."""
    t, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden_states(w, cfg: dict, tokens, quant=None):
    """Final-LayerNorm hidden states [T, d] of ONE sequence of token ids."""
    d = cfg["hidden_size"]
    h_q = cfg["num_attention_heads"]
    h_kv = cfg["num_key_value_heads"]
    hd = d // h_q
    eps = cfg["norm_epsilon"]
    t = tokens.shape[0]
    x = w["_tok_embedding"][tokens]
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint          # gradients keep one layer's activations at a time
    def block(x, wl):
        a = _layer_norm(x, wl["ln1.w0"], wl["ln1.wbias"], eps)
        q = _mm(a, wl["attn.w0"], quant).reshape(t, h_q, hd)
        k = _mm(a, wl["attn.w1"], quant).reshape(t, h_kv, hd)
        v = _mm(a, wl["attn.w2"], quant).reshape(t, h_kv, hd)
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        k = jnp.repeat(k, h_q // h_kv, axis=1)
        v = jnp.repeat(v, h_q // h_kv, axis=1)
        qh, kh, vh = (jnp.swapaxes(z, 0, 1) for z in (q, k, v))   # [H, T, D]
        s = _mm(qh, jnp.swapaxes(kh, 1, 2), quant) * hd ** -0.5
        s = jnp.where(causal[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.swapaxes(_mm(p, vh, quant), 0, 1).reshape(t, d)
        x = x + _mm(o, wl["attn.w3"], quant) + wl["attn.wbias"].reshape(-1)
        a = _layer_norm(x, wl["ln2.w0"], wl["ln2.wbias"], eps)
        u = _mm(a, wl["ffn1.w0"], quant) + wl["ffn1.wbias"].reshape(-1)
        u = jax.nn.gelu(u, approximate=True)
        return x + _mm(u, wl["ffn2.w0"], quant) + wl["ffn2.wbias"].reshape(-1)

    for i in range(cfg["num_hidden_layers"]):
        b = f"_blk{i}_"
        x = block(x, {k[len(b):]: v for k, v in w.items() if k.startswith(b)})
    return _layer_norm(x, w["_final_ln.w0"], w["_final_ln.wbias"], eps)


def log_probs(w, cfg: dict, tokens, rows=None, quant=None):
    """log softmax of the head over the vocabulary at `rows` (all rows if
    None) of one sequence: [n_rows, vocab]."""
    h = hidden_states(w, cfg, tokens, quant)
    if rows is not None:
        h = h[rows]
    return jax.nn.log_softmax(_mm(h, w["_lm_head.w0"], quant), axis=-1)


def sequence_loss(w, cfg: dict, tokens, labels, quant=None):
    """Next-token cross entropy of one sequence, summed over its positions
    (the program's classification_cost over a sequence)."""
    lp = log_probs(w, cfg, tokens, quant=quant)
    return -jnp.sum(jnp.take_along_axis(lp, labels[:, None], axis=1))


def batch_loss(w, cfg: dict, tokens, labels, quant=None):
    """Mean over the sequences of a [B, T] batch of their summed losses
    (one sequence at a time: lax.map keeps the program and its memory at one
    sequence's size)."""
    per_seq = jax.lax.map(
        lambda tl: sequence_loss(w, cfg, tl[0], tl[1], quant),
        (tokens, labels))
    return jnp.mean(per_seq)


@functools.lru_cache(maxsize=None)
def _jitted(what: str, cfg_key: tuple, quant_name: str):
    cfg = dict(cfg_key)
    quant = {"": None, "fp8": fp8_quant, "bf16": bf16_quant}[quant_name]
    if what == "loss":
        return jax.jit(lambda w, t, l: batch_loss(w, cfg, t, l, quant))
    if what == "loss_grad":
        return jax.jit(jax.value_and_grad(
            lambda w, t, l: batch_loss(w, cfg, t, l, quant)))
    if what == "log_probs":
        return jax.jit(lambda w, t, r: log_probs(w, cfg, t, r, quant))
    raise KeyError(what)


def jitted(what: str, cfg: dict, quant: str = ""):
    """A jitted reference function ('loss', 'loss_grad', 'log_probs') for
    this configuration's sizes; quant '' = the float32 reference, 'fp8' =
    the control, 'bf16' = the precision the configuration states."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "norm_epsilon", "rope_theta")
    return _jitted(what, tuple((k, cfg[k]) for k in keys), quant)
