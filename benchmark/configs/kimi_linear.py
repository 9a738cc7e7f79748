"""Kimi-Linear (moonshotai/Kimi-Linear-48B-A3B-Instruct, model_type
kimi_linear, arXiv:2510.26692) in the config DSL: pre-norm RMSNorm blocks
whose token mixer is, by layer index, either a KDA gated delta-rule layer
(graph/layers_kda.py: three of every four) or NoPE multi-head latent
attention (graph/layers_attn.py mla_attention without a query rank and
without rotation); a SwiGLU MLP in the leading dense layer and sigmoid
top-8 routing over 256 gated experts plus one shared expert after it.

The ten sizes benchmark/kinds/serve.py sends (vocab, dim, layers, heads,
kv_heads, ffn, rope_theta, batch_size, compute_dtype, attn_impl) come in as
config arguments; every other size is a config argument too, whose DEFAULT
is the value of benchmark/configs/kimi-linear-48b-a3b-serve.json
(tests/test_kimi_linear.py holds each default to that file), so a rehearsal
shrinks the hidden size, heads, depth and vocabulary and keeps the KDA,
latent and expert widths as published.

Layers count from 1, as the published lists do.  `full_attn_layers`
defaults to the published list cut to the depth; where the depth holds
none of them (a rehearsal at 2 layers) the last layer is the
full-attention one, as the published model's own layer 27 is, off the
period.  `heads` is the MLA head count; the KDA layers have their own
(`kda_num_heads`, shrunk with `heads` only when heads is smaller).

One expert-parallel rank's share: the router scores all `num_experts`, this
rank holds the `experts_held` experts from `ep_rank * experts_held` on
(parallel/moe.py); the shared expert, both token mixers and the dense layer
are replicated on every rank.  `kv_heads` and `rope_theta` are taken and
ignored: latent attention has one latent row a token, and nothing is
rotated."""

from paddle_tpu.dsl import *

vocab = get_config_arg("vocab", int, 256)
dim = get_config_arg("dim", int, 64)
n_layers = get_config_arg("layers", int, 2)
n_heads = get_config_arg("heads", int, 4)
get_config_arg("kv_heads", int, 0)
ffn = get_config_arg("ffn", int, 128)
get_config_arg("rope_theta", float, 10000.0)
init_std = get_config_arg("init_std", float, 0.02)
batch_size = get_config_arg("batch_size", int, 2)
compute_dtype = get_config_arg("compute_dtype", str, "bfloat16")
attn_impl = get_config_arg("attn_impl", str, "flash")
seq_len = get_config_arg("seq_len", int, 33)

kda_num_heads = min(get_config_arg("kda_num_heads", int, 32), n_heads)
kda_head_dim = get_config_arg("kda_head_dim", int, 128)
short_conv_kernel_size = get_config_arg("short_conv_kernel_size", int, 4)
full_attn_layers = get_config_arg(
    "full_attn_layers", str, "4,8,12,16,20,24,27").replace(";", ",")
full_attn = {int(i) for i in full_attn_layers.split(",") if i
             and int(i) <= n_layers} or {n_layers}
kv_lora_rank = get_config_arg("kv_lora_rank", int, 512)
qk_nope_head_dim = get_config_arg("qk_nope_head_dim", int, 128)
qk_rope_head_dim = get_config_arg("qk_rope_head_dim", int, 64)
v_head_dim = get_config_arg("v_head_dim", int, 128)
rms_norm_eps = get_config_arg("rms_norm_eps", float, 1e-5)
moe_intermediate_size = get_config_arg("moe_intermediate_size", int, 1024)
num_experts = get_config_arg("num_experts", int, 256)
experts_held = get_config_arg("experts_held", int, 16)
ep_rank = get_config_arg("ep_rank", int, 0)
num_experts_per_token = get_config_arg("num_experts_per_token", int, 8)
num_expert_group = get_config_arg("num_expert_group", int, 1)
topk_group = get_config_arg("topk_group", int, 1)
num_shared_experts = get_config_arg("num_shared_experts", int, 1)
routed_scaling_factor = get_config_arg("routed_scaling_factor", float, 2.446)
first_k_dense_replace = get_config_arg("first_k_dense_replace", int, 1)

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
for i in range(n_layers):
    mix_in = rms_norm_layer(input=h, eps=rms_norm_eps, name=f"blk{i}_ln1")
    if i + 1 in full_attn:
        mix = mla_attention_layer(
            mix_in, num_heads=n_heads, q_lora_rank=None,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            use_rope=False, rms_eps=rms_norm_eps, attn_impl=impl,
            param_attr=w(), name=f"blk{i}_attn")
    else:
        mix = kda_attention_layer(
            mix_in, num_heads=kda_num_heads, head_dim=kda_head_dim,
            conv_size=short_conv_kernel_size, rms_eps=rms_norm_eps,
            attn_impl=impl, param_attr=w(), name=f"blk{i}_kda")
    h = addto_layer(input=[h, mix], act=LinearActivation(),
                    name=f"blk{i}_res1", bias_attr=False)
    ffn_in = rms_norm_layer(input=h, eps=rms_norm_eps, name=f"blk{i}_ln2")
    if i < first_k_dense_replace:
        ffn_o = gated_ffn_layer(ffn_in, hidden=ffn, param_attr=w(),
                                name=f"blk{i}_ffn")
    else:
        ffn_o = moe_layer(
            ffn_in, num_experts=num_experts,
            expert_hidden=moe_intermediate_size, top_k=num_experts_per_token,
            gated=True, scoring="sigmoid", n_group=num_expert_group,
            topk_group=topk_group, select_bias=True, norm_topk=True,
            routed_scale=routed_scaling_factor,
            shared_hidden=num_shared_experts * moe_intermediate_size,
            experts_held=experts_held, first_expert=ep_rank * experts_held,
            aux_weight=0.0, param_attr=w(), name=f"blk{i}_moe")
    h = addto_layer(input=[h, ffn_o], act=LinearActivation(),
                    name=f"blk{i}_res2", bias_attr=False)

final = rms_norm_layer(input=h, eps=rms_norm_eps, name="final_ln")
logits = fc_layer(input=final, size=vocab, act=SoftmaxActivation(),
                  name="lm_head", param_attr=w(), bias_attr=False)
labels = data_layer(name="next_tokens", size=vocab)
classification_cost(input=logits, label=labels)
