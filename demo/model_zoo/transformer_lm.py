"""Decoder-only transformer language model — the beyond-reference model
family built from this framework's long-context stack: multi-head
attention (rotary positions, grouped-query heads, sliding window, and the
dense/flash/blockwise/ring auto-selection), pre-norm residual blocks with
layer_norm + GELU, all through the classic config DSL.

Train (causal next-token loss on token sequences):
    python -m paddle_tpu train --config=demo/model_zoo/transformer_lm.py \
        --config_args=vocab=32000,dim=512,layers=8,heads=8

Real data: put text-file paths in demo/model_zoo/lm_train.list and the
provider trains BYTE-LEVEL on their contents (vocab >= 258); the stock
placeholder list keeps the hermetic synthetic motif stream.

Long sequences scale over a mesh `seq` axis (ring attention) and the
batch over `data`:  tr = Trainer(cfg, mesh=make_mesh(data=2, seq=4)).
"""

from paddle_tpu.dsl import *

vocab = get_config_arg("vocab", int, 256)
dim = get_config_arg("dim", int, 64)
n_layers = get_config_arg("layers", int, 2)
n_heads = get_config_arg("heads", int, 4)
n_kv_heads = get_config_arg("kv_heads", int, 0)       # 0 = full MHA
window = get_config_arg("window", int, 0)             # 0 = full attention
ffn_mult = get_config_arg("ffn_mult", int, 4)
batch_size = get_config_arg("batch_size", int, 16)
compute_dtype = get_config_arg("compute_dtype", str, "")
attn_impl = get_config_arg("attn_impl", str, "auto")  # auto/dense/flash/blockwise/ring/ulysses
block_k_min = get_config_arg("block_k_min", int, 0)   # 0 = default crossover
seq_len = get_config_arg("seq_len", int, 33)          # provider sequence length

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

tokens = data_layer(name="tokens", size=vocab)
h = embedding_layer(input=tokens, size=dim,
                    param_attr=ParamAttr(name="_tok_embedding",
                                         initial_std=0.02))

for i in range(n_layers):
    # pre-norm attention block: h = h + MHA(LN(h)) — rotary positions
    # instead of learned absolute embeddings
    attn_in = layer_norm_layer(input=h, name=f"blk{i}_ln1")
    attn = multi_head_attention_layer(
        attn_in, size=dim, num_heads=n_heads, causal=True, use_rope=True,
        num_kv_heads=n_kv_heads or None, window=window or None,
        attn_impl=attn_impl if attn_impl != "auto" else None,
        block_k_min=block_k_min or None,
        name=f"blk{i}_attn")
    h = addto_layer(input=[h, attn], act=LinearActivation(),
                    name=f"blk{i}_res1", bias_attr=False)
    # pre-norm GELU MLP block: h = h + W2 gelu(W1 LN(h))
    ffn_in = layer_norm_layer(input=h, name=f"blk{i}_ln2")
    ffn_h = fc_layer(input=ffn_in, size=dim * ffn_mult, act=GeluActivation(),
                     name=f"blk{i}_ffn1",
                     param_attr=ParamAttr(initial_std=0.02), bias_attr=True)
    ffn_o = fc_layer(input=ffn_h, size=dim, act=LinearActivation(),
                     name=f"blk{i}_ffn2",
                     param_attr=ParamAttr(initial_std=0.02), bias_attr=True)
    h = addto_layer(input=[h, ffn_o], act=LinearActivation(),
                    name=f"blk{i}_res2", bias_attr=False)

final = layer_norm_layer(input=h, name="final_ln")
logits = fc_layer(input=final, size=vocab, act=SoftmaxActivation(),
                  name="lm_head", param_attr=ParamAttr(initial_std=0.02),
                  bias_attr=False)
labels = data_layer(name="next_tokens", size=vocab)
classification_cost(input=logits, label=labels)
