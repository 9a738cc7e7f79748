"""Olmo-Hybrid (allenai/Olmo-Hybrid-7B, model_type olmo_hybrid) in the
config DSL: the Olmo block — NO norm in front of a sublayer, an RMSNorm on
its OUTPUT, h = x + RMSNorm(Mixer(x)), y = h + RMSNorm(MLP(h)) — whose token
mixer is, by `layer_types`, either a Gated DeltaNet layer (`linear_attention`:
the delta rule with ONE decay a head and a rectangular state [96, 192] a
head, a full-rank silu output gate, write strength 2 sigmoid;
graph/layers_kda.py with `decay="head"`) or softmax attention of as many KV
heads as query heads, without rotation, with an RMSNorm over the WHOLE
projected q and k (`full_attention`: graph/layers_attn.py,
`qk_norm="whole"`); every layer's MLP is a dense SwiGLU.

The ten sizes benchmark/kinds/serve.py sends (vocab, dim, layers, heads,
kv_heads, ffn, rope_theta, batch_size, compute_dtype, attn_impl) come in as
config arguments; every other size is a config argument too, whose DEFAULT
is the value of benchmark/configs/olmo-hybrid-7b-serve.json
(tests/test_olmo_hybrid.py holds each default to that file), so a rehearsal
shrinks the hidden size, heads, depth and vocabulary and keeps the linear
layers' head sizes as published.  The attention's width is the hidden size
(30 heads of 128 = 3,840).  Booleans are sent as 0 / 1.

`layer_types` is the published list by first letter (l / f), read up to the
depth.  The linear layers have their own head count
(`linear_num_key_heads`, shrunk with `heads` only when heads is smaller).
`norm_after_sublayer`, `use_qk_norm`, `qk_norm_whole` and `use_rope` are the
configuration file's `assumed` readings, one key each: the published config
does not say where the norms are, and gives `rope_theta: null`."""

from paddle_tpu.dsl import *

vocab = get_config_arg("vocab", int, 256)
dim = get_config_arg("dim", int, 64)
n_layers = get_config_arg("layers", int, 2)
n_heads = get_config_arg("heads", int, 4)
n_kv_heads = get_config_arg("kv_heads", int, 4)
ffn = get_config_arg("ffn", int, 128)
rope_theta = get_config_arg("rope_theta", float, 500000.0)
init_std = get_config_arg("init_std", float, 0.02)
batch_size = get_config_arg("batch_size", int, 2)
compute_dtype = get_config_arg("compute_dtype", str, "bfloat16")
attn_impl = get_config_arg("attn_impl", str, "flash")
seq_len = get_config_arg("seq_len", int, 33)

layer_types = get_config_arg(
    "layer_types", str,
    "l,l,l,f,l,l,l,f,l,l,l,f,l,l,l,f,l,l,l,f,l,l,l,f,l,l,l,f,l,l,l,f"
).replace(";", ",").split(",")
linear_num_key_heads = min(get_config_arg("linear_num_key_heads", int, 30),
                           n_heads)
linear_num_value_heads = min(get_config_arg("linear_num_value_heads", int, 30),
                             n_heads)
assert linear_num_key_heads == linear_num_value_heads, \
    "a value head a key head: the rule's state is [dk, dv] a head"
linear_key_head_dim = get_config_arg("linear_key_head_dim", int, 96)
linear_value_head_dim = get_config_arg("linear_value_head_dim", int, 192)
linear_conv_kernel_dim = get_config_arg("linear_conv_kernel_dim", int, 4)
linear_allow_neg_eigval = get_config_arg("linear_allow_neg_eigval", int, 1)
rms_norm_eps = get_config_arg("rms_norm_eps", float, 1e-06)
norm_after_sublayer = get_config_arg("norm_after_sublayer", int, 1)
use_qk_norm = get_config_arg("use_qk_norm", int, 1)
qk_norm_whole = get_config_arg("qk_norm_whole", int, 1)
use_rope = get_config_arg("use_rope", int, 0)

define_py_data_sources2(
    train_list="demo/model_zoo/lm_train.list", test_list=None,
    module="demo.model_zoo.lm_provider", obj="process",
    args={"vocab": vocab, "seq_len": seq_len})

settings(
    batch_size=batch_size,
    learning_rate=3e-4,
    learning_method=AdamOptimizer(),
    gradient_clipping_threshold=1.0,
    compute_dtype=compute_dtype)

w = lambda: ParamAttr(initial_std=init_std)
impl = attn_impl if attn_impl != "auto" else None
qk_norm = ("whole" if qk_norm_whole else True) if use_qk_norm else False


def sublayer(x, i, which, fn):
    """x + RMSNorm(fn(x)) — or, the other reading, x + fn(RMSNorm(x))."""
    norm = lambda y: rms_norm_layer(input=y, eps=rms_norm_eps,
                                    name=f"blk{i}_ln{which}")
    y = norm(fn(x)) if norm_after_sublayer else fn(norm(x))
    return addto_layer(input=[x, y], act=LinearActivation(),
                       name=f"blk{i}_res{which}", bias_attr=False)


def mixer(i):
    if layer_types[i][0] == "f":
        return lambda x: multi_head_attention_layer(
            x, size=dim, num_heads=n_heads, num_kv_heads=n_kv_heads,
            causal=True, use_rope=bool(use_rope), rope_theta=rope_theta,
            qk_norm=qk_norm, rms_eps=rms_norm_eps, attn_impl=impl,
            param_attr=[w() for _ in "qkvo"], name=f"blk{i}_attn")
    return lambda x: kda_attention_layer(
        x, num_heads=linear_num_key_heads, head_dim=linear_key_head_dim,
        value_dim=linear_value_head_dim, conv_size=linear_conv_kernel_dim,
        rms_eps=rms_norm_eps, allow_neg_eigval=bool(linear_allow_neg_eigval),
        decay="head", full_proj=True, gate_act="silu", attn_impl=impl,
        param_attr=w(), name=f"blk{i}_gdn")


tokens = data_layer(name="tokens", size=vocab)
h = embedding_layer(input=tokens, size=dim,
                    param_attr=ParamAttr(name="_tok_embedding",
                                         initial_std=init_std))
for i in range(n_layers):
    h = sublayer(h, i, 1, mixer(i))
    h = sublayer(h, i, 2, lambda x: gated_ffn_layer(
        x, hidden=ffn, param_attr=w(), name=f"blk{i}_ffn"))

final = rms_norm_layer(input=h, eps=rms_norm_eps, name="final_ln")
logits = fc_layer(input=final, size=vocab, act=SoftmaxActivation(),
                  name="lm_head", param_attr=w(), bias_attr=False)
labels = data_layer(name="next_tokens", size=vocab)
classification_cost(input=logits, label=labels)
