"""Nemotron-H (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, model_type
nemotron_h) in the config DSL: every block is ONE mixer behind one RMSNorm,
x + Mixer(RMSNorm(x)), the mixer by the letter of `pattern` — `M` the
Mamba-2 state-space mixer (graph/layers_ssm.py), `*` grouped-query
attention without rotation (graph/layers_attn.py), `E` the expert layer:
sigmoid top-6 routing over 128 bias-free relu^2 experts with a
selection-only bias and a shared expert of the same form
(graph/layers_moe.py).  No block pairs a mixer with an FFN: a layer's
parameters are `_blk{i}_ln` and ONE of `_blk{i}_ssm`, `_blk{i}_attn`,
`_blk{i}_moe`.

The ten sizes benchmark/kinds/serve.py sends (vocab, dim, layers, heads,
kv_heads, ffn, rope_theta, batch_size, compute_dtype, attn_impl) come in as
config arguments; every other size is a config argument too, whose DEFAULT
is the value of benchmark/configs/nemotron3-nano-30b-a3b-serve.json
(tests/benchmark/test_cell_nemotron_h.py holds each default to that file),
so a rehearsal shrinks the hidden size, heads, depth and vocabulary and
keeps the Mamba-2 sizes, the attention head size, the expert widths and the
expert count as published.  `ffn` (the file's `intermediate_size`, 1856) is
sent and UNUSED: this model has no dense MLP.  `rope_theta` is sent and used
only where `attn_use_rope` is set; the family's attention applies no
rotation.

`pattern` is the cut's own list — published layers 1-9, `MEMEM*EME` — of
which the first `layers` are built; a rehearsal at 2 layers is `ME`, a
stack with no attention layer and no page-indexed part.

`experts_held` of the `n_routed_experts` the router scores are held here,
those from `ep_rank * experts_held` on (parallel/moe.py); the shared expert
is whole."""

from paddle_tpu.dsl import *

vocab = get_config_arg("vocab", int, 256)
dim = get_config_arg("dim", int, 64)
n_layers = get_config_arg("layers", int, 2)
n_heads = get_config_arg("heads", int, 4)
n_kv_heads = get_config_arg("kv_heads", int, 2)
ffn = get_config_arg("ffn", int, 128)          # unused: no dense MLP
rope_theta = get_config_arg("rope_theta", float, 10000.0)
init_std = get_config_arg("init_std", float, 0.02)
batch_size = get_config_arg("batch_size", int, 2)
compute_dtype = get_config_arg("compute_dtype", str, "bfloat16")
attn_impl = get_config_arg("attn_impl", str, "flash")
seq_len = get_config_arg("seq_len", int, 33)

pattern = get_config_arg("pattern", str, "MEMEM*EME")[:n_layers]
assert len(pattern) == n_layers and set(pattern) <= set("ME*"), \
    f"{n_layers} layers of pattern {pattern!r} (letters M, E, *)"
head_dim = get_config_arg("head_dim", int, 128)
attn_use_rope = get_config_arg("attn_use_rope", bool, False)
mamba_num_heads = get_config_arg("mamba_num_heads", int, 64)
mamba_head_dim = get_config_arg("mamba_head_dim", int, 64)
ssm_state_size = get_config_arg("ssm_state_size", int, 128)
n_groups = get_config_arg("n_groups", int, 8)
conv_kernel = get_config_arg("conv_kernel", int, 4)
chunk_size = get_config_arg("chunk_size", int, 128)
norm_eps = get_config_arg("norm_eps", float, 1e-5)
moe_intermediate_size = get_config_arg("moe_intermediate_size", int, 1856)
moe_shared_expert_intermediate_size = get_config_arg(
    "moe_shared_expert_intermediate_size", int, 3712)
n_routed_experts = get_config_arg("n_routed_experts", int, 128)
experts_held = get_config_arg("experts_held", int, 32)
ep_rank = get_config_arg("ep_rank", int, 0)
num_experts_per_tok = get_config_arg("num_experts_per_tok", int, 6)
routed_scaling_factor = get_config_arg("routed_scaling_factor", float, 2.5)

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
for i, kind in enumerate(pattern):
    mix_in = rms_norm_layer(input=h, eps=norm_eps, name=f"blk{i}_ln")
    if kind == "M":
        mix = mamba2_layer(
            mix_in, num_heads=mamba_num_heads, head_dim=mamba_head_dim,
            state_size=ssm_state_size, n_groups=n_groups,
            conv_size=conv_kernel, chunk_size=chunk_size, rms_eps=norm_eps,
            attn_impl=impl, param_attr=w(), name=f"blk{i}_ssm")
    elif kind == "*":
        mix = multi_head_attention_layer(
            mix_in, size=n_heads * head_dim, out_size=dim, num_heads=n_heads,
            num_kv_heads=n_kv_heads, causal=True, use_rope=attn_use_rope,
            rope_theta=rope_theta, qk_norm=False, attn_impl=impl,
            param_attr=[w() for _ in "qkvo"], name=f"blk{i}_attn")
    else:
        mix = moe_layer(
            mix_in, num_experts=n_routed_experts,
            expert_hidden=moe_intermediate_size, top_k=num_experts_per_tok,
            expert_act="relu2", expert_bias=False, scoring="sigmoid",
            select_bias=True, norm_topk=True,
            routed_scale=routed_scaling_factor,
            shared_hidden=moe_shared_expert_intermediate_size,
            experts_held=experts_held, first_expert=ep_rank * experts_held,
            aux_weight=0.0, param_attr=w(), name=f"blk{i}_moe")
    h = addto_layer(input=[h, mix], act=LinearActivation(),
                    name=f"blk{i}_res", bias_attr=False)

final = rms_norm_layer(input=h, eps=norm_eps, name="final_ln")
logits = fc_layer(input=final, size=vocab, act=SoftmaxActivation(),
                  name="lm_head", param_attr=w(), bias_attr=False)
labels = data_layer(name="next_tokens", size=vocab)
classification_cost(input=logits, label=labels)
