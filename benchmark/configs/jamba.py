"""Jamba (ai21labs/AI21-Jamba2-3B, model_type jamba; arXiv:2403.19887) in
the config DSL: pre-norm RMSNorm blocks, x + Mixer(RMSNorm(x)) then x +
MLP(RMSNorm(x)), whose token mixer is multi-query attention WITHOUT rotation
or any position embedding (graph/layers_attn.py) in the layers i with
i % attn_layer_period == attn_layer_offset, and the Mamba-1 selective-scan
mixer with Jamba's three inner RMSNorms (graph/layers_mamba.py) everywhere
else; every MLP the dense SwiGLU (`num_experts` 1: no expert layer, whatever
`expert_layer_period` says); a final RMSNorm; an UNTIED head (the published
model ties it to the embedding, which the DSL cannot say: ROADMAP R0).

The ten sizes benchmark/kinds/serve.py sends (vocab, dim, layers, heads,
kv_heads, ffn, rope_theta, batch_size, compute_dtype, attn_impl) come in as
config arguments; every other size is a config argument too, whose DEFAULT
is the value of benchmark/configs/jamba2-3b-serve.json
(tests/benchmark/test_cell_jamba.py holds each default to that file), so a
rehearsal shrinks the hidden size, heads, depth and vocabulary and keeps the
Mamba sizes (state 16, time-step rank 160, 4 taps, expansion 2) and the
attention head size as published.  `rope_theta` is sent and used only where
`attn_use_rope` is set; the family's attention applies no rotation.

A rehearsal at 2 layers is two Mamba layers, a stack with no attention layer
and no page-indexed part."""

from paddle_tpu.dsl import *

vocab = get_config_arg("vocab", int, 256)
dim = get_config_arg("dim", int, 64)
n_layers = get_config_arg("layers", int, 2)
n_heads = get_config_arg("heads", int, 4)
n_kv_heads = get_config_arg("kv_heads", int, 1)
ffn = get_config_arg("ffn", int, 128)
rope_theta = get_config_arg("rope_theta", float, 10000.0)
init_std = get_config_arg("init_std", float, 0.02)
batch_size = get_config_arg("batch_size", int, 2)
compute_dtype = get_config_arg("compute_dtype", str, "bfloat16")
attn_impl = get_config_arg("attn_impl", str, "flash")
seq_len = get_config_arg("seq_len", int, 33)

head_dim = get_config_arg("head_dim", int, 128)
attn_use_rope = get_config_arg("attn_use_rope", bool, False)
attn_layer_period = get_config_arg("attn_layer_period", int, 14)
attn_layer_offset = get_config_arg("attn_layer_offset", int, 7)
mamba_expand = get_config_arg("mamba_expand", int, 2)
mamba_d_state = get_config_arg("mamba_d_state", int, 16)
mamba_dt_rank = get_config_arg("mamba_dt_rank", int, 160)
mamba_d_conv = get_config_arg("mamba_d_conv", int, 4)
rms_norm_eps = get_config_arg("rms_norm_eps", float, 1e-6)

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
    if i % attn_layer_period == attn_layer_offset:
        mix = multi_head_attention_layer(
            mix_in, size=n_heads * head_dim, out_size=dim, num_heads=n_heads,
            num_kv_heads=n_kv_heads, causal=True, use_rope=attn_use_rope,
            rope_theta=rope_theta, qk_norm=False, attn_impl=impl,
            param_attr=[w() for _ in "qkvo"], name=f"blk{i}_attn")
    else:
        mix = mamba_layer(
            mix_in, d_inner=mamba_expand * dim, state_size=mamba_d_state,
            dt_rank=mamba_dt_rank, conv_size=mamba_d_conv,
            rms_eps=rms_norm_eps, attn_impl=impl, param_attr=w(),
            name=f"blk{i}_mamba")
    h = addto_layer(input=[h, mix], act=LinearActivation(),
                    name=f"blk{i}_res1", bias_attr=False)
    ffn_in = rms_norm_layer(input=h, eps=rms_norm_eps, name=f"blk{i}_ln2")
    ffn_o = gated_ffn_layer(ffn_in, hidden=ffn, param_attr=w(),
                            name=f"blk{i}_ffn")
    h = addto_layer(input=[h, ffn_o], act=LinearActivation(),
                    name=f"blk{i}_res2", bias_attr=False)

final = rms_norm_layer(input=h, eps=rms_norm_eps, name="final_ln")
logits = fc_layer(input=final, size=vocab, act=SoftmaxActivation(),
                  name="lm_head", param_attr=w(), bias_attr=False)
labels = data_layer(name="next_tokens", size=vocab)
classification_cost(input=logits, label=labels)
