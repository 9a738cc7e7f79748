"""Laguna (poolside/Laguna-XS.2, model_type laguna) in the config DSL:
pre-norm RMSNorm blocks whose token mixer is grouped-query attention of one
of two KINDS by layer (`layer_types`, period 4 from layer 0): a
`full_attention` layer sees every earlier key, rotates the first half of
each head (`partial_rotary_factor` 0.5) with theta 500,000 under YaRN and
has 48 query heads; a `sliding_attention` layer sees the last
`sliding_window` 512 keys, rotates the whole head with theta 10,000 and has
64 (`num_attention_heads_per_layer`); both have 8 K/V heads of 128 and a
sigmoid gate a head from the layer's input in front of the output
projection (`gating`; dsl `out_gate="head"`).  Layer 0's MLP is a dense
SwiGLU, the others' the expert layer (`mlp_layer_types`): sigmoid top-8 of
256 SwiGLU experts renormalised over the chosen, times 2.5, beside one
shared expert.

The ten sizes benchmark/kinds/serve.py sends (vocab, dim, layers, heads,
kv_heads, ffn, rope_theta, batch_size, compute_dtype, attn_impl) come in as
config arguments; every other size is a config argument too, whose DEFAULT
is the value of benchmark/configs/laguna-xs2-33b-serve.json
(tests/test_laguna.py holds each default to that file), so a rehearsal
shrinks the hidden size, heads, depth and vocabulary and keeps the head
size, the window and the expert widths as published.  `heads` is the FULL
layers' head count (the file's `num_attention_heads`, 48) and `rope_theta`
their theta; a layer's own count is the published list's in the proportion
`heads` bears to 48 (`heads_of`: a rehearsal at 6 heads gets 6 and 8), and
the window layers' theta is `window_rope_theta`.  The per-layer
lists arrive as strings, a letter or a number a layer joined by `;` (`f` full
/ `s` sliding, `d` dense / `s` sparse, the head counts), published whole and
read up to the depth; Booleans are sent as 0 / 1."""

from paddle_tpu.dsl import *

vocab = get_config_arg("vocab", int, 256)
dim = get_config_arg("dim", int, 64)
n_layers = get_config_arg("layers", int, 2)
n_heads = get_config_arg("heads", int, 4)
n_kv_heads = get_config_arg("kv_heads", int, 2)
ffn = get_config_arg("ffn", int, 128)
rope_theta = get_config_arg("rope_theta", float, 500000.0)
init_std = get_config_arg("init_std", float, 0.02)
batch_size = get_config_arg("batch_size", int, 2)
compute_dtype = get_config_arg("compute_dtype", str, "bfloat16")
attn_impl = get_config_arg("attn_impl", str, "flash")
seq_len = get_config_arg("seq_len", int, 33)

head_dim = get_config_arg("head_dim", int, 128)
layer_types = get_config_arg(
    "layer_types", str,
    "f;s;s;s;f;s;s;s;f;s;s;s;f;s;s;s;f;s;s;s;f;s;s;s;f;s;s;s;f;s;s;s;f;s;s;s;f;s;s;s").replace(";", "")
mlp_layer_types = get_config_arg(
    "mlp_layer_types", str,
    "d;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s;s").replace(";", "")
heads_per_layer = [int(v) for v in get_config_arg(
    "num_attention_heads_per_layer", str,
    "48;64;64;64;48;64;64;64;48;64;64;64;48;64;64;64;48;64;64;64;48;64;64;64;48;64;64;64;48;64;64;64;48;64;64;64;48;64;64;64").split(";")]
sliding_window = get_config_arg("sliding_window", int, 512)
gating = get_config_arg("gating", int, 1)
# rope_parameters.full_attention / .sliding_attention
partial_rotary_factor = get_config_arg("partial_rotary_factor", float, 0.5)
yarn_factor = get_config_arg("yarn_factor", float, 64.0)
yarn_original = get_config_arg("original_max_position_embeddings", int, 4096)
yarn_beta_fast = get_config_arg("beta_fast", float, 64.0)
yarn_beta_slow = get_config_arg("beta_slow", float, 1.0)
attention_factor = get_config_arg("attention_factor", float,
                                  1.4158883083359672)
window_rope_theta = get_config_arg("window_rope_theta", float, 10000.0)
window_partial_rotary_factor = get_config_arg(
    "window_partial_rotary_factor", float, 1.0)
rms_norm_eps = get_config_arg("rms_norm_eps", float, 1e-06)
moe_intermediate_size = get_config_arg("moe_intermediate_size", int, 512)
shared_expert_intermediate_size = get_config_arg(
    "shared_expert_intermediate_size", int, 512)
num_experts = get_config_arg("num_experts", int, 256)
num_experts_per_tok = get_config_arg("num_experts_per_tok", int, 8)
moe_routed_scaling_factor = get_config_arg("moe_routed_scaling_factor",
                                           float, 2.5)

# a layer's head count: the published list's, in the proportion `heads`
# bears to the published full layers' 48 (the cell sends 48: the list as it
# is; a rehearsal at heads=6 gets 6 and 8)
published_full = heads_per_layer[0]


def heads_of(i):
    return max(n_kv_heads, heads_per_layer[i] * n_heads // published_full
               // n_kv_heads * n_kv_heads)


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
yarn = {"factor": yarn_factor,
        "original_max_position_embeddings": yarn_original,
        "beta_fast": yarn_beta_fast, "beta_slow": yarn_beta_slow}

tokens = data_layer(name="tokens", size=vocab)
h = embedding_layer(input=tokens, size=dim,
                    param_attr=ParamAttr(name="_tok_embedding",
                                         initial_std=init_std))
for i in range(n_layers):
    mix_in = rms_norm_layer(input=h, eps=rms_norm_eps, name=f"blk{i}_ln1")
    H = heads_of(i)
    if layer_types[i] == "f":
        kind = dict(rope_theta=rope_theta,
                    rotary_dim=int(head_dim * partial_rotary_factor),
                    rope_scaling=yarn, attention_factor=attention_factor)
    else:
        kind = dict(rope_theta=window_rope_theta, window=sliding_window,
                    rotary_dim=int(head_dim * window_partial_rotary_factor))
    mix = multi_head_attention_layer(
        mix_in, size=H * head_dim, out_size=dim, num_heads=H,
        num_kv_heads=n_kv_heads, causal=True, use_rope=True,
        out_gate="head" if gating else False, attn_impl=impl,
        param_attr=[w() for _ in ("qkvog" if gating else "qkvo")],
        name=f"blk{i}_attn", **kind)
    h = addto_layer(input=[h, mix], act=LinearActivation(),
                    name=f"blk{i}_res1", bias_attr=False)
    ffn_in = rms_norm_layer(input=h, eps=rms_norm_eps, name=f"blk{i}_ln2")
    if mlp_layer_types[i] == "d":
        ffn_o = gated_ffn_layer(ffn_in, hidden=ffn, param_attr=w(),
                                name=f"blk{i}_ffn")
    else:
        ffn_o = moe_layer(
            ffn_in, num_experts=num_experts,
            expert_hidden=moe_intermediate_size, top_k=num_experts_per_tok,
            gated=True, scoring="sigmoid", norm_topk=True,
            routed_scale=moe_routed_scaling_factor,
            shared_hidden=shared_expert_intermediate_size,
            aux_weight=0.0, param_attr=w(), name=f"blk{i}_moe")
    h = addto_layer(input=[h, ffn_o], act=LinearActivation(),
                    name=f"blk{i}_res2", bias_attr=False)

final = rms_norm_layer(input=h, eps=rms_norm_eps, name="final_ln")
logits = fc_layer(input=final, size=vocab, act=SoftmaxActivation(),
                  name="lm_head", param_attr=w(), bias_attr=False)
labels = data_layer(name="next_tokens", size=vocab)
classification_cost(input=logits, label=labels)
