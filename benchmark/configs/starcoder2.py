"""StarCoder2 (bigcode/starcoder2-*, arXiv:2402.19173) in the config DSL:
pre-norm LayerNorm blocks, grouped-query attention with rotary positions,
a biased tanh-GELU MLP.  The benchmark's own file: every size comes in as a
config argument from benchmark/configs/<config>.json, so one file serves
every configuration of the family.

Departures the program forces today (benchmark/configs/*.json lists them):
an untied output head, ONE attention bias (on the output projection) where
the model has four, LayerNorm eps fixed at 1e-6 by graph/layers_misc.py, and
no sliding window (ops/pallas_paged.py keeps windowed decode on the jnp
fallback; no cell exceeds 4,096 positions, where the window mask is the
identity)."""

from paddle_tpu.dsl import *

vocab = get_config_arg("vocab", int, 256)
dim = get_config_arg("dim", int, 64)
n_layers = get_config_arg("layers", int, 2)
n_heads = get_config_arg("heads", int, 4)
n_kv_heads = get_config_arg("kv_heads", int, 2)
ffn = get_config_arg("ffn", int, 256)
rope_theta = get_config_arg("rope_theta", float, 999999.4420358813)
init_std = get_config_arg("init_std", float, 0.02)
batch_size = get_config_arg("batch_size", int, 2)
compute_dtype = get_config_arg("compute_dtype", str, "bfloat16")
attn_impl = get_config_arg("attn_impl", str, "flash")
seq_len = get_config_arg("seq_len", int, 33)

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

tokens = data_layer(name="tokens", size=vocab)
h = embedding_layer(input=tokens, size=dim,
                    param_attr=ParamAttr(name="_tok_embedding",
                                         initial_std=init_std))
for i in range(n_layers):
    attn_in = layer_norm_layer(input=h, name=f"blk{i}_ln1")
    attn = multi_head_attention_layer(
        attn_in, size=dim, num_heads=n_heads, causal=True, use_rope=True,
        rope_theta=rope_theta, num_kv_heads=n_kv_heads,
        attn_impl=attn_impl if attn_impl != "auto" else None,
        param_attr=[w(), w(), w(), w()], bias_attr=True,
        name=f"blk{i}_attn")
    h = addto_layer(input=[h, attn], act=LinearActivation(),
                    name=f"blk{i}_res1", bias_attr=False)
    ffn_in = layer_norm_layer(input=h, name=f"blk{i}_ln2")
    ffn_h = fc_layer(input=ffn_in, size=ffn, act=GeluActivation(),
                     name=f"blk{i}_ffn1", param_attr=w(), bias_attr=True)
    ffn_o = fc_layer(input=ffn_h, size=dim, act=LinearActivation(),
                     name=f"blk{i}_ffn2", param_attr=w(), bias_attr=True)
    h = addto_layer(input=[h, ffn_o], act=LinearActivation(),
                    name=f"blk{i}_res2", bias_attr=False)

final = layer_norm_layer(input=h, name="final_ln")
logits = fc_layer(input=final, size=vocab, act=SoftmaxActivation(),
                  name="lm_head", param_attr=w(), bias_attr=False)
labels = data_layer(name="next_tokens", size=vocab)
classification_cost(input=logits, label=labels)
