"""Solar-Open2 (upstage/Solar-Open2-250B, model_type solar_open2) in the
config DSL: pre-norm RMSNorm blocks whose token mixer is, by layer index
from 0, either softmax grouped-query attention without rotation whose
result passes an elementwise sigmoid gate in front of the output projection
(graph/layers_attn.py, `out_gate`: the layers of `gqa_layers`, one in four)
or a KDA gated delta-rule layer whose write strength is 2 sigmoid, so a
transition may have a negative eigenvalue (graph/layers_kda.py,
`allow_neg_eigval`); every layer's MLP is the expert layer — sigmoid top-8
routing over 320 SwiGLU experts with a selection-only bias, plus one shared
expert — from layer 0 on (`first_k_dense_replace` 0: no leading dense layer).

The ten sizes benchmark/kinds/serve.py sends (vocab, dim, layers, heads,
kv_heads, ffn, rope_theta, batch_size, compute_dtype, attn_impl) come in as
config arguments; every other size is a config argument too, whose DEFAULT
is the value of benchmark/configs/solar-open2-250b-serve.json
(tests/test_solar_open2.py holds each default to that file), so a rehearsal
shrinks the hidden size, heads, depth and vocabulary and keeps the head
size, the KDA and the expert widths as published.  The attention's own
width is heads x head_dim (64 x 128 = 8,192 beside a hidden size of 4,096).
Booleans are sent as 0 / 1.

`gqa_layers` is the published list, of which the depth keeps those under
it: a rehearsal at 2 layers is one GQA and one KDA layer.  The KDA layers
have their own head count (`kda_num_heads`, shrunk with `heads` only when
heads is smaller).  `ffn` (the file's `intermediate_size`) is the width of a
leading dense MLP, which the published model has none of; `rope_theta` is
used only where `use_rope` is set.

One expert-parallel rank's share: the router scores all `n_routed_experts`,
this rank holds the `experts_held` experts from `ep_rank * experts_held` on
(parallel/moe.py); the shared expert and both token mixers are replicated
on every rank."""

from paddle_tpu.dsl import *

vocab = get_config_arg("vocab", int, 256)
dim = get_config_arg("dim", int, 64)
n_layers = get_config_arg("layers", int, 2)
n_heads = get_config_arg("heads", int, 4)
n_kv_heads = get_config_arg("kv_heads", int, 2)
ffn = get_config_arg("ffn", int, 128)
rope_theta = get_config_arg("rope_theta", float, 10000.0)
init_std = get_config_arg("init_std", float, 0.02)
batch_size = get_config_arg("batch_size", int, 2)
compute_dtype = get_config_arg("compute_dtype", str, "bfloat16")
attn_impl = get_config_arg("attn_impl", str, "flash")
seq_len = get_config_arg("seq_len", int, 33)

head_dim = get_config_arg("head_dim", int, 128)
use_rope = get_config_arg("use_rope", int, 0)
use_gqa_gate = get_config_arg("use_gqa_gate", int, 1)
gqa_layers = get_config_arg(
    "gqa_layers", str, "0,4,8,12,16,20,24,28,32,36,40,44").replace(";", ",")
gqa = {int(i) for i in gqa_layers.split(",") if i}
kda_num_heads = min(get_config_arg("kda_num_heads", int, 64), n_heads)
kda_head_dim = get_config_arg("kda_head_dim", int, 128)
short_conv_kernel_size = get_config_arg("short_conv_kernel_size", int, 4)
kda_allow_neg_eigval = get_config_arg("kda_allow_neg_eigval", int, 1)
kda_use_full_proj = get_config_arg("kda_use_full_proj", int, 0)
assert not kda_use_full_proj, \
    "the KDA layer's decay and gate projections are low-rank (rank head_dim)"
rms_norm_eps = get_config_arg("rms_norm_eps", float, 1e-05)
moe_intermediate_size = get_config_arg("moe_intermediate_size", int, 1280)
n_routed_experts = get_config_arg("n_routed_experts", int, 320)
experts_held = get_config_arg("experts_held", int, 40)
ep_rank = get_config_arg("ep_rank", int, 0)
num_experts_per_tok = get_config_arg("num_experts_per_tok", int, 8)
n_shared_experts = get_config_arg("n_shared_experts", int, 1)
norm_topk_prob = get_config_arg("norm_topk_prob", int, 1)
routed_scaling_factor = get_config_arg("routed_scaling_factor", float, 1)
first_k_dense_replace = get_config_arg("first_k_dense_replace", int, 0)

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
    if i in gqa:
        mix = multi_head_attention_layer(
            mix_in, size=n_heads * head_dim, out_size=dim, num_heads=n_heads,
            num_kv_heads=n_kv_heads, causal=True, use_rope=bool(use_rope),
            rope_theta=rope_theta, out_gate=bool(use_gqa_gate),
            attn_impl=impl,
            param_attr=[w() for _ in ("qkvog" if use_gqa_gate else "qkvo")],
            name=f"blk{i}_attn")
    else:
        mix = kda_attention_layer(
            mix_in, num_heads=kda_num_heads, head_dim=kda_head_dim,
            conv_size=short_conv_kernel_size, rms_eps=rms_norm_eps,
            allow_neg_eigval=bool(kda_allow_neg_eigval), attn_impl=impl,
            param_attr=w(), name=f"blk{i}_kda")
    h = addto_layer(input=[h, mix], act=LinearActivation(),
                    name=f"blk{i}_res1", bias_attr=False)
    ffn_in = rms_norm_layer(input=h, eps=rms_norm_eps, name=f"blk{i}_ln2")
    if i < first_k_dense_replace:
        ffn_o = gated_ffn_layer(ffn_in, hidden=ffn, param_attr=w(),
                                name=f"blk{i}_ffn")
    else:
        ffn_o = moe_layer(
            ffn_in, num_experts=n_routed_experts,
            expert_hidden=moe_intermediate_size, top_k=num_experts_per_tok,
            gated=True, scoring="sigmoid", select_bias=True,
            norm_topk=bool(norm_topk_prob), routed_scale=routed_scaling_factor,
            shared_hidden=n_shared_experts * moe_intermediate_size,
            experts_held=experts_held, first_expert=ep_rank * experts_held,
            aux_weight=0.0, param_attr=w(), name=f"blk{i}_moe")
    h = addto_layer(input=[h, ffn_o], act=LinearActivation(),
                    name=f"blk{i}_res2", bias_attr=False)

final = rms_norm_layer(input=h, eps=rms_norm_eps, name="final_ln")
logits = fc_layer(input=final, size=vocab, act=SoftmaxActivation(),
                  name="lm_head", param_attr=w(), bias_attr=False)
labels = data_layer(name="next_tokens", size=vocab)
classification_cost(input=logits, label=labels)
