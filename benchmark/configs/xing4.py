"""Xing4.0-29B-A4B (XingChen-AGI/Xing4.0-29B-A4B, model_type xing4_0) in the
config DSL: the DeepSeek-V3 block (arXiv:2412.19437) — pre-norm RMSNorm,
multi-head latent attention with YaRN positions, a SwiGLU MLP in the leading
dense layers and sigmoid top-4 routing over 64 gated experts plus one shared
expert after them — on a residual path of `hc_mult` STREAMS: manifold-
constrained hyper-connections (mHC, arXiv:2512.24880).  Where the GigaChat
file joins a sublayer's output with `addto_layer(input=[h, f])`, each of a
block's two sublayers here READS u = H_pre X from the streams
(`hyper_read_layer`, with the sublayer's own maps), runs on RMSNorm(u) as
before, and WRITES X' = H_res X + H_post^T y (`hyper_write_layer`, the
Pallas kernel `mhc_mix` on the TPU); the embedding is copied into the
streams and the streams are summed in front of the final norm.

The ten sizes benchmark/kinds/serve.py sends (vocab, dim, layers, heads,
kv_heads, ffn, rope_theta, batch_size, compute_dtype, attn_impl) come in as
config arguments; every other size is a config argument too, whose DEFAULT
is the value of benchmark/configs/xing4.0-29b-a4b-serve.json
(tests/test_xing4.py holds each default to that file), so a rehearsal
shrinks the hidden size, heads, depth and vocabulary and keeps the latent,
expert and stream widths as published.

Every routed expert is held (`ep_size` 1: no expert parallelism); `n_group`
and `topk_group` are 1, so the selection bias picks among all 64 with no
group limit.  `kv_heads` is taken and ignored: latent attention has one
latent row a token, not KV heads."""


from paddle_tpu.dsl import *

vocab = get_config_arg("vocab", int, 256)
dim = get_config_arg("dim", int, 64)
n_layers = get_config_arg("layers", int, 2)
n_heads = get_config_arg("heads", int, 4)
get_config_arg("kv_heads", int, 0)
ffn = get_config_arg("ffn", int, 128)
rope_theta = get_config_arg("rope_theta", float, 10000.0)
init_std = get_config_arg("init_std", float, 0.02)
batch_size = get_config_arg("batch_size", int, 2)
compute_dtype = get_config_arg("compute_dtype", str, "bfloat16")
attn_impl = get_config_arg("attn_impl", str, "flash")
seq_len = get_config_arg("seq_len", int, 33)

q_lora_rank = get_config_arg("q_lora_rank", int, 768)
kv_lora_rank = get_config_arg("kv_lora_rank", int, 512)
qk_nope_head_dim = get_config_arg("qk_nope_head_dim", int, 128)
qk_rope_head_dim = get_config_arg("qk_rope_head_dim", int, 64)
v_head_dim = get_config_arg("v_head_dim", int, 128)
rms_norm_eps = get_config_arg("rms_norm_eps", float, 1e-6)
moe_intermediate_size = get_config_arg("moe_intermediate_size", int, 1024)
n_routed_experts = get_config_arg("n_routed_experts", int, 64)
num_experts_per_tok = get_config_arg("num_experts_per_tok", int, 4)
n_group = get_config_arg("n_group", int, 1)
topk_group = get_config_arg("topk_group", int, 1)
n_shared_experts = get_config_arg("n_shared_experts", int, 1)
routed_scaling_factor = get_config_arg("routed_scaling_factor", float, 2.0)
first_k_dense_replace = get_config_arg("first_k_dense_replace", int, 1)
hc_mult = get_config_arg("hc_mult", int, 4)
hc_sinkhorn_iters = get_config_arg("hc_sinkhorn_iters", int, 20)
hc_eps = get_config_arg("hc_eps", float, 1e-6)
mhc_h_res_clamp_min = get_config_arg("mhc_h_res_clamp_min", float, -30.0)
mhc_h_res_clamp_max = get_config_arg("mhc_h_res_clamp_max", float, 30.0)
rope_scaling = {
    "type": "yarn",
    "factor": get_config_arg("rope_factor", float, 64.0),
    "original_max_position_embeddings":
        get_config_arg("rope_original_max_position_embeddings", int, 4096),
    "beta_fast": get_config_arg("rope_beta_fast", float, 32.0),
    "beta_slow": get_config_arg("rope_beta_slow", float, 1.0),
    "mscale": get_config_arg("rope_mscale", float, 1.0),
    "mscale_all_dim": get_config_arg("rope_mscale_all_dim", float, 1.0),
}

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



def read(x, name):
    return hyper_read_layer(
        x, streams=hc_mult, sinkhorn_iters=hc_sinkhorn_iters, eps=hc_eps,
        res_clamp=(mhc_h_res_clamp_min, mhc_h_res_clamp_max), param_attr=w(),
        name=name)


tokens = data_layer(name="tokens", size=vocab)
h = embedding_layer(input=tokens, size=dim,
                    param_attr=ParamAttr(name="_tok_embedding",
                                         initial_std=init_std))
x = hyper_expand_layer(h, streams=hc_mult, name="hc_expand")
for i in range(n_layers):
    u = read(x, f"blk{i}_hc1")
    attn_in = rms_norm_layer(input=u, eps=rms_norm_eps, name=f"blk{i}_ln1")
    attn = mla_attention_layer(
        attn_in, num_heads=n_heads, q_lora_rank=q_lora_rank,
        kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
        rope_theta=rope_theta, rope_scaling=rope_scaling,
        rms_eps=rms_norm_eps,
        attn_impl=attn_impl if attn_impl != "auto" else None,
        param_attr=w(), name=f"blk{i}_attn")
    x = hyper_write_layer(x, attn, read=u, name=f"blk{i}_res1")
    u = read(x, f"blk{i}_hc2")
    ffn_in = rms_norm_layer(input=u, eps=rms_norm_eps, name=f"blk{i}_ln2")
    if i < first_k_dense_replace:
        ffn_o = gated_ffn_layer(ffn_in, hidden=ffn, param_attr=w(),
                                name=f"blk{i}_ffn")
    else:
        ffn_o = moe_layer(
            ffn_in, num_experts=n_routed_experts,
            expert_hidden=moe_intermediate_size, top_k=num_experts_per_tok,
            gated=True, scoring="sigmoid", n_group=n_group,
            topk_group=topk_group, select_bias=True, norm_topk=True,
            routed_scale=routed_scaling_factor,
            shared_hidden=n_shared_experts * moe_intermediate_size,
            aux_weight=0.0, param_attr=w(), name=f"blk{i}_moe")
    x = hyper_write_layer(x, ffn_o, read=u, name=f"blk{i}_res2")

h = hyper_collapse_layer(x, streams=hc_mult, name="hc_collapse")
final = rms_norm_layer(input=h, eps=rms_norm_eps, name="final_ln")
logits = fc_layer(input=final, size=vocab, act=SoftmaxActivation(),
                  name="lm_head", param_attr=w(), bias_attr=False)
labels = data_layer(name="next_tokens", size=vocab)
classification_cost(input=logits, label=labels)
