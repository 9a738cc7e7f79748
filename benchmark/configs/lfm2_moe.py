"""LFM2-MoE (LiquidAI/LFM2-24B-A2B, model_type lfm2_moe) in the config DSL:
pre-norm RMSNorm blocks whose token mixer is, by `layer_types`, either a
gated short convolution (graph/layers_sconv.py: three of every four) or
grouped-query attention with an RMSNorm a head on q and k before the
rotation (graph/layers_attn.py, `qk_norm`); a SwiGLU MLP in the leading
dense layers and sigmoid top-4 routing over 64 gated experts with a
selection-only bias after them, no shared expert.

The ten sizes benchmark/kinds/serve.py sends (vocab, dim, layers, heads,
kv_heads, ffn, rope_theta, batch_size, compute_dtype, attn_impl) come in as
config arguments; every other size is a config argument too, whose DEFAULT
is the value of benchmark/configs/lfm2-24b-a2b-serve.json
(tests/test_lfm2_moe.py holds each default to that file), so a rehearsal
shrinks the hidden size, heads, depth and vocabulary and keeps the expert
width, the expert count and the taps as published.

`layer_types` is the cut's own list — published layers 2-6, one pipeline
stage of eight: conv, full_attention, conv, conv, conv — of which the first
`layers` are built; a rehearsal at 2 layers is a conv layer with the dense
MLP and an attention layer with experts.  The first `num_dense_layers`
layers carry the dense MLP.

Every expert is held here (`experts_held` = `num_experts`, `ep_rank` 0):
the router scores all `num_experts`, this chip holds the `experts_held`
from `ep_rank * experts_held` on (parallel/moe.py)."""

from paddle_tpu.dsl import *

vocab = get_config_arg("vocab", int, 256)
dim = get_config_arg("dim", int, 64)
n_layers = get_config_arg("layers", int, 2)
n_heads = get_config_arg("heads", int, 4)
n_kv_heads = get_config_arg("kv_heads", int, 2)
ffn = get_config_arg("ffn", int, 128)
rope_theta = get_config_arg("rope_theta", float, 1000000.0)
init_std = get_config_arg("init_std", float, 0.02)
batch_size = get_config_arg("batch_size", int, 2)
compute_dtype = get_config_arg("compute_dtype", str, "bfloat16")
attn_impl = get_config_arg("attn_impl", str, "flash")
seq_len = get_config_arg("seq_len", int, 33)

layer_types = get_config_arg(
    "layer_types", str, "conv;full_attention;conv;conv;conv"
).replace(",", ";").split(";")[:n_layers]
assert len(layer_types) == n_layers, \
    f"{n_layers} layers, {len(layer_types)} layer types"
conv_L_cache = get_config_arg("conv_L_cache", int, 3)
norm_eps = get_config_arg("norm_eps", float, 1e-5)
moe_intermediate_size = get_config_arg("moe_intermediate_size", int, 1536)
num_experts = get_config_arg("num_experts", int, 64)
experts_held = get_config_arg("experts_held", int, 64)
ep_rank = get_config_arg("ep_rank", int, 0)
num_experts_per_tok = get_config_arg("num_experts_per_tok", int, 4)
num_dense_layers = get_config_arg("num_dense_layers", int, 1)
routed_scaling_factor = get_config_arg("routed_scaling_factor", float, 1.0)

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

tokens = data_layer(name="tokens", size=vocab)
h = embedding_layer(input=tokens, size=dim,
                    param_attr=ParamAttr(name="_tok_embedding",
                                         initial_std=init_std))
for i, kind in enumerate(layer_types):
    mix_in = rms_norm_layer(input=h, eps=norm_eps, name=f"blk{i}_ln1")
    if kind == "full_attention":
        mix = multi_head_attention_layer(
            mix_in, size=dim, num_heads=n_heads, num_kv_heads=n_kv_heads,
            causal=True, use_rope=True, rope_theta=rope_theta, qk_norm=True,
            rms_eps=norm_eps, attn_impl=impl, param_attr=[w() for _ in "qkvo"],
            name=f"blk{i}_attn")
    else:
        assert kind == "conv", f"layer type {kind!r} (conv or full_attention)"
        mix = short_conv_layer(mix_in, conv_size=conv_L_cache, param_attr=w(),
                               name=f"blk{i}_conv")
    h = addto_layer(input=[h, mix], act=LinearActivation(),
                    name=f"blk{i}_res1", bias_attr=False)
    ffn_in = rms_norm_layer(input=h, eps=norm_eps, name=f"blk{i}_ln2")
    if i < num_dense_layers:
        ffn_o = gated_ffn_layer(ffn_in, hidden=ffn, param_attr=w(),
                                name=f"blk{i}_ffn")
    else:
        ffn_o = moe_layer(
            ffn_in, num_experts=num_experts,
            expert_hidden=moe_intermediate_size, top_k=num_experts_per_tok,
            gated=True, scoring="sigmoid", select_bias=True, norm_topk=True,
            routed_scale=routed_scaling_factor, shared_hidden=0,
            experts_held=experts_held, first_expert=ep_rank * experts_held,
            aux_weight=0.0, param_attr=w(), name=f"blk{i}_moe")
    h = addto_layer(input=[h, ffn_o], act=LinearActivation(),
                    name=f"blk{i}_res2", bias_attr=False)

final = rms_norm_layer(input=h, eps=norm_eps, name="final_ln")
logits = fc_layer(input=final, size=vocab, act=SoftmaxActivation(),
                  name="lm_head", param_attr=w(), bias_attr=False)
labels = data_layer(name="next_tokens", size=vocab)
classification_cost(input=logits, label=labels)
