"""One model-parity harness for the served models: a model is a `ModelCase`
(what differs, as data), the helpers that turn it into the program and the
reference at a tiny size, and the tests every served model owes, each
written once and taking `case`, `ref`, `model`.

A model's `tests/test_<model>.py` (and `tests/test_<model>_engine.py`:
`--dist loadfile` gives a file to a worker, so the split is how a model's
time is shared out) sets `CASE = CASES[<name>]`, imports the fixtures, the
`pytest_generate_tests` hook and the shared tests that apply — pytest
collects an imported test in the importing module — and adds the tests that
are the model's own.  A shared test reads the case's data and never asks
which model it serves.

Everything runs on the CPU in float32 with seeded weights.  The
tolerances: float32 under `jax.default_matmul_precision("highest")` leaves
1e-5 to 2e-5 between two orders of the same sums at these sizes (init_std
0.3, so the logits spread over several nats); a case's `tol` is at most ten
times that, and far under what its controls (`zeroed`, `ref_controls`,
`state_control`) move the logits by — each is tried and must fail."""

import dataclasses
import functools
import importlib.util
import json
import os
import re
from typing import Mapping, Optional

import numpy as np
import pytest

from tests.conftest import (  # noqa: F401
    counted, engines, interpreted, lm_oracle)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# --config-args names of the sizes every DSL file takes, and the keys of
# the configuration file they are read from
SIZES = dict(vocab="vocab_size", dim="hidden_size", layers="num_hidden_layers",
             heads="num_attention_heads", ffn="intermediate_size")
# what benchmark/kinds/serve.py sends: no DSL default stands for these
SENT = {"vocab", "dim", "layers", "heads", "kv_heads", "ffn", "rope_theta",
        "batch_size", "compute_dtype", "attn_impl", "seq_len"}
KV = dict(kv_heads="num_key_value_heads", rope_theta="rope_theta")
REFUSALS = ("prefix", "spill", "spill_later", "spec", "spec_later", "mesh",
            "export", "import", "role", "dense_cache")


@dataclasses.dataclass(frozen=True)
class EngineCase:
    """One ServingEngine a model's engine test runs: `prefill_chunk`, whether
    PADDLE_TPU_PALLAS_INTERPRET is set, `max_step_tokens`, and the
    --config-args the executor is built again with (none: the `model`
    fixture's)."""
    id: str
    chunk: int
    kernel: bool = False
    mst: Optional[int] = None
    build: Mapping = dataclasses.field(default_factory=dict)


AUTO = {"attn_impl": "auto"}
# a share of 5 rows a prompt; 34 rows a step leave 32 to the chunks: every
# prompt goes in one run
RECURRENT_ENGINES = (
    EngineCase("chunked-jnp", 5), EngineCase("chunked-kernel", 5, True,
                                             build=AUTO),
    EngineCase("one-chunk", 32), EngineCase("free-rows", 5, mst=34))


@dataclasses.dataclass(frozen=True)
class ModelCase:
    name: str                   # benchmark/reference/<name>.py
    json: str                   # the configuration file, benchmark/configs/
    dsl: str                    # the DSL file beside it
    tiny: Mapping               # the configuration file's keys at test size
    dsl_keys: tuple             # sent to the DSL under the file's own names
    renamed: Mapping = dataclasses.field(default_factory=dict)
    derived: Mapping = dataclasses.field(default_factory=dict)
    tol: float = 2e-4           # whole-sequence log-probabilities
    whole_len: int = 40
    ragged_tol: float = 2e-4
    ragged_chunks: tuple = (1, 2, 4, 7, 9)      # rows a mixed step; sum 23
    ragged_kernels: tuple = (False,)    # ... by the jnp forms / the kernels
    zeroed: tuple = ()          # a weight whose absence `tol` must see
    ref_controls: tuple = ()    # reference settings `tol` must tell apart
    state_control: bool = False     # ... and a state rounded to bfloat16
    # the recurrent layers: their names at the tiny size, their registered
    # type, and {part: (row shape, dtype; "" = the compute dtype)}
    recurrent: tuple = ()
    recurrent_type: str = ""
    slot_parts: Mapping = dataclasses.field(default_factory=dict)
    paged: Mapping = dataclasses.field(default_factory=dict)
    moe: bool = True
    margin: bool = False        # the benchmark's comparison on served tokens
    engines: tuple = RECURRENT_ENGINES
    prompts: tuple = (3, 19, 9, 17, 26)
    max_context: int = 48
    # (configuration overrides, the mixers by depth, the FFNs by depth)
    letters: Mapping = dataclasses.field(default_factory=dict)
    depths: tuple = ()
    # the configuration file against the catalog and the DSL's defaults
    catalog: str = ""
    reduced: frozenset = frozenset()
    scored_whole: str = ""      # `reduced` names it, yet the router keeps it
    dsl_nested: Mapping = dataclasses.field(default_factory=dict)
    dsl_defaults: int = 0

    @property
    def json_path(self):
        return os.path.join(ROOT, "benchmark", "configs", self.json)

    @property
    def dsl_path(self):
        return os.path.join(ROOT, "benchmark", "configs", self.dsl)

    @property
    def slot_shapes(self):
        """{part: row shape}: a recurrent layer's entry in `slot_specs`."""
        return {part: shape for part, (shape, _) in self.slot_parts.items()}

    @property
    def slot_row_bytes(self):
        """One slot's recurrent state in float32, all layers."""
        return len(self.recurrent) * 4 * sum(
            int(np.prod(shape)) for shape in self.slot_shapes.values())


def nemotron_pattern(c):
    first = c["first_layer"] - 1
    return c["hybrid_override_pattern"][
        first:first + c["num_hidden_layers"]]


# the Laguna reference with the whole head rotated in a full layer: the
# published `rope_parameters`, the full layers' partial_rotary_factor 1
LAGUNA_WHOLE_HEAD_ROTATED = {"rope_parameters": {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 1.0},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}}


CASES = {c.name: c for c in (
    ModelCase(
        name="gigachat3", json="gigachat3.1-702b-a36b-serve.json",
        dsl="gigachat3.py",
        tiny=dict(hidden_size=32, intermediate_size=64, num_attention_heads=4,
                  num_hidden_layers=2, vocab_size=64, q_lora_rank=24,
                  kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
                  v_head_dim=12, moe_intermediate_size=16,
                  n_routed_experts=16, experts_held=4, ep_rank=1, n_group=4,
                  topk_group=2, num_experts_per_tok=4, param_dtype="float32",
                  init_std=0.3, select_bias_std=0.3),
        dsl_keys=("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size",
                  "n_routed_experts", "experts_held", "ep_rank", "n_group",
                  "topk_group", "num_experts_per_tok",
                  "first_k_dense_replace"),
        tol=2e-5, whole_len=24, ragged_tol=5e-5,
        # 20 -> 128 lanes: one latent row a token
        paged={"blk0_attn": (128,), "blk1_attn": (128,)},
        # a whole prompt in ONE mixed step where the chunk allows it
        engines=(EngineCase("chunked-jnp", 4, mst=7),
                 EngineCase("chunked-kernel", 4, True, mst=7),
                 EngineCase("one-chunk", 32)),
        prompts=(3, 19, 9, 17), max_context=32,
        catalog="GigaChat3.1-702B-A36B", scored_whole="n_routed_experts",
        reduced=frozenset({"num_hidden_layers", "first_k_dense_replace",
                           "n_routed_experts", "vocab_size",
                           "num_nextn_predict_layers"}),
        dsl_nested={"rope_" + k: "rope_scaling." + k for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim")},
        dsl_defaults=23),
    ModelCase(
        name="kimi_linear", json="kimi-linear-48b-a3b-serve.json",
        dsl="kimi_linear.py",
        tiny=dict(hidden_size=32, intermediate_size=64, num_attention_heads=4,
                  num_hidden_layers=4, vocab_size=64, kv_lora_rank=16,
                  qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                  moe_intermediate_size=16, num_experts=16, experts_held=4,
                  ep_rank=1, num_experts_per_token=4, param_dtype="float32",
                  init_std=0.3, select_bias_std=0.3,
                  linear_attn_config=dict(head_dim=8, num_heads=4)),
        dsl_keys=("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim", "moe_intermediate_size", "num_experts",
                  "experts_held", "ep_rank", "num_experts_per_token",
                  "first_k_dense_replace"),
        renamed=dict(kda_head_dim="linear_attn_config.head_dim",
                     kda_num_heads="linear_attn_config.num_heads"),
        tol=1e-4, whole_len=150, ragged_chunks=(7, 9, 4, 3),
        ragged_kernels=(False, True),       # `kda_step` and `kda_seg`
        recurrent=("blk0_kda", "blk1_kda", "blk2_kda"),
        recurrent_type="kda_attention",
        slot_parts={"state": ((4, 8, 8), "float32"), "conv": ((3, 96), "")},
        paged={"blk3_attn": (128,)},
        engines=RECURRENT_ENGINES + (
            EngineCase("free-rows-kernel", 5, True, mst=34, build=AUTO),),
        letters={"kda_attention": "K", "mla_attention": "A"},
        depths=(({"num_hidden_layers": 13}, "KKKAKKKAKKKAK", "d" + "e" * 12),
                ({"num_hidden_layers": 2}, "KA", "de"),
                ({"num_hidden_layers": 27}, "KKKA" * 6 + "KKA",
                 "d" + "e" * 26)),
        catalog="Kimi-Linear-48B-A3B-Instruct", scored_whole="num_experts",
        reduced=frozenset({"num_hidden_layers", "num_experts", "vocab_size"}),
        dsl_nested=dict(
            kda_num_heads="linear_attn_config.num_heads",
            kda_head_dim="linear_attn_config.head_dim",
            short_conv_kernel_size="linear_attn_config.short_conv_kernel_size",
            full_attn_layers="linear_attn_config.full_attn_layers"),
        dsl_defaults=20),
    ModelCase(
        name="lfm2_moe", json="lfm2-24b-a2b-serve.json", dsl="lfm2_moe.py",
        # heads of 64 as published, two KV heads: one packed 128-lane row
        tiny=dict(hidden_size=256, intermediate_size=64,
                  num_attention_heads=4, num_key_value_heads=2,
                  num_hidden_layers=5, vocab_size=64,
                  moe_intermediate_size=16, num_experts=16, experts_held=16,
                  ep_rank=0, num_experts_per_tok=4, param_dtype="float32",
                  init_std=0.3, select_bias_std=0.3),
        dsl_keys=("moe_intermediate_size", "num_experts", "experts_held",
                  "ep_rank", "num_experts_per_tok", "num_dense_layers"),
        renamed=KV,
        recurrent=("blk0_conv", "blk2_conv", "blk3_conv", "blk4_conv"),
        recurrent_type="short_conv",
        slot_parts={"conv": ((2, 256), "")}, paged={"blk1_attn": (1, 128)},
        engines=RECURRENT_ENGINES,
        letters={"short_conv": "C", "multi_head_attention": "A"},
        depths=(({"num_hidden_layers": 5, "num_dense_layers": 1}, "CACCC",
                 "deeee"),
                ({"num_hidden_layers": 2, "num_dense_layers": 1}, "CA", "de"),
                ({"num_hidden_layers": 3, "num_dense_layers": 2}, "CAC",
                 "dde")),
        catalog="LFM2-24B-A2B",
        reduced=frozenset({"num_hidden_layers", "num_dense_layers"}),
        dsl_nested={"layer_types": lambda c, ref: ref.layer_kinds(c)},
        dsl_defaults=11),
    ModelCase(
        name="nemotron_h", json="nemotron3-nano-30b-a3b-serve.json",
        dsl="nemotron_h.py",
        # the published ratios: 4 Mamba-2 heads of 16 in 2 groups, state 16;
        # 4 query heads over 2 KV heads of 16, a width (64) the hidden size
        # (48) is not; 16 experts, top-3; published layers 3-7, `MEM*E`
        tiny=dict(hidden_size=48, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, num_hidden_layers=5,
                  first_layer=3, vocab_size=64, mamba_num_heads=4,
                  mamba_head_dim=16, ssm_state_size=16, n_groups=2,
                  chunk_size=8, moe_intermediate_size=24,
                  moe_shared_expert_intermediate_size=40,
                  n_routed_experts=16, experts_held=16, ep_rank=0,
                  num_experts_per_tok=3, param_dtype="float32", init_std=0.3,
                  select_bias_std=0.3),
        dsl_keys=("head_dim", "mamba_num_heads", "mamba_head_dim",
                  "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
                  "moe_intermediate_size",
                  "moe_shared_expert_intermediate_size", "n_routed_experts",
                  "experts_held", "ep_rank", "num_experts_per_tok",
                  "routed_scaling_factor", "norm_eps"),
        renamed=dict(KV, attn_use_rope="attn_use_rope"),
        derived=dict(pattern=nemotron_pattern),
        zeroed=("_blk0_ssm.w4",), state_control=True,       # the `D x` term
        recurrent=("blk0_ssm", "blk2_ssm"), recurrent_type="mamba2",
        slot_parts={"state": ((4, 16, 16), "float32"),
                    "conv": ((3, 128), "")},
        paged={"blk3_attn": (2, 16)}, margin=True,
        engines=RECURRENT_ENGINES,
        letters={"mamba2": "M", "multi_head_attention": "*"},
        depths=(({}, "MM*", "ee"),
                ({"num_hidden_layers": 2, "first_layer": 1}, "M", "e"),
                ({"num_hidden_layers": 9, "first_layer": 1}, "MMM*M", "eeee"))),
    ModelCase(
        name="jamba", json="jamba2-3b-serve.json", dsl="jamba.py",
        # the published ratios: d_in = 2 x 64 = 128 channels of 16 state
        # elements, a time-step rank of 8; 4 query heads over ONE KV head of
        # 16; 5 layers of which layer 1 is attention (period 4, offset 1)
        tiny=dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
                  num_key_value_heads=1, head_dim=16, num_hidden_layers=5,
                  attn_layer_period=4, attn_layer_offset=1, vocab_size=64,
                  mamba_dt_rank=8, param_dtype="float32", init_std=0.3),
        dsl_keys=("head_dim", "attn_layer_period", "attn_layer_offset",
                  "mamba_expand", "mamba_d_state", "mamba_dt_rank",
                  "mamba_d_conv", "rms_norm_eps"),
        renamed=dict(KV, attn_use_rope="attn_use_rope"),
        zeroed=("_blk0_mamba.w10",),                        # the `D x` term
        ref_controls=({"inner_norms": False},), state_control=True,
        ragged_kernels=(False, True),
        recurrent=("blk0_mamba", "blk2_mamba", "blk3_mamba", "blk4_mamba"),
        recurrent_type="mamba",
        slot_parts={"state": ((16, 128), "float32"), "conv": ((3, 128), "")},
        paged={"blk1_attn": (1, 16)}, moe=False, margin=True,
        engines=RECURRENT_ENGINES + (
            EngineCase("free-rows-kernel", 5, True, mst=34, build=AUTO),),
        letters={"mamba": "M", "multi_head_attention": "A"},
        depths=(({}, "MAMMM", "ddddd"),
                ({"num_hidden_layers": 2}, "MA", "dd"),
                ({"num_hidden_layers": 9}, "MAMMMAMMM", "d" * 9))),
    ModelCase(
        name="solar_open2", json="solar-open2-250b-serve.json",
        dsl="solar_open2.py",
        # the published ratios: 4 query heads over 2 KV heads of 16, a width
        # (64) twice the hidden size (32); 4 KDA heads of 8; 16 experts of
        # which rank 1 of 4 holds 4, top-4; one period: GQA, KDA, KDA, KDA
        tiny=dict(hidden_size=32, intermediate_size=64, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, num_hidden_layers=4,
                  vocab_size=64, moe_intermediate_size=16,
                  n_routed_experts=16, experts_held=4, ep_rank=1,
                  num_experts_per_tok=4, param_dtype="float32", init_std=0.3,
                  select_bias_std=0.3,
                  linear_attn_config=dict(head_dim=8, num_heads=4)),
        dsl_keys=("head_dim", "use_rope", "use_gqa_gate",
                  "kda_allow_neg_eigval", "moe_intermediate_size",
                  "n_routed_experts", "experts_held", "ep_rank",
                  "num_experts_per_tok", "n_shared_experts", "norm_topk_prob",
                  "routed_scaling_factor", "first_k_dense_replace",
                  "rms_norm_eps"),
        renamed=dict(KV, kda_head_dim="linear_attn_config.head_dim",
                     kda_num_heads="linear_attn_config.num_heads"),
        derived=dict(gqa_layers=lambda c: ";".join(map(str, c["gqa_layers"]))),
        tol=1e-4, whole_len=150, ragged_chunks=(7, 9, 4, 3),
        # the gate's matrix gone (sigmoid(0): every gate one half); the
        # reference with no gate; the reference with beta in (0, 1)
        zeroed=("_blk0_attn.w4",),
        ref_controls=({"use_gqa_gate": False},
                      {"kda_allow_neg_eigval": False}), state_control=True,
        ragged_kernels=(False, True),       # `kda_step` and `kda_seg`
        recurrent=("blk1_kda", "blk2_kda", "blk3_kda"),
        recurrent_type="kda_attention",
        slot_parts={"state": ((4, 8, 8), "float32"), "conv": ((3, 96), "")},
        paged={"blk0_attn": (2, 16)}, margin=True,
        engines=tuple(e for e in RECURRENT_ENGINES if e.id != "one-chunk")
        + (EngineCase("free-rows-kernel", 5, True, mst=34, build=AUTO),),
        letters={"kda_attention": "K", "multi_head_attention": "A"},
        depths=(({}, "AKKK", "eeee"),
                ({"num_hidden_layers": 2}, "AK", "ee"),
                ({"num_hidden_layers": 9}, "AKKKAKKKA", "e" * 9)),
        catalog="Solar-Open2-250B", scored_whole="n_routed_experts",
        reduced=frozenset({"num_hidden_layers", "n_routed_experts",
                           "vocab_size"}),
        dsl_nested=dict(
            kda_num_heads="linear_attn_config.num_heads",
            kda_head_dim="linear_attn_config.head_dim",
            short_conv_kernel_size="linear_attn_config.short_conv_kernel_size"),
        dsl_defaults=20),
    ModelCase(
        name="laguna", json="laguna-xs2-33b-serve.json", dsl="laguna.py",
        # the published ratios: full layers of 6 query heads and window
        # layers of 8 over 2 KV heads of 16 (48 : 64 over 8), a window of 8
        # tokens = 2 pages of 4; 16 experts, top-4, every one held; layers
        # 0-4: full + dense, three window + sparse, full + sparse
        tiny=dict(hidden_size=32, intermediate_size=64, num_attention_heads=6,
                  num_key_value_heads=2, head_dim=16, num_hidden_layers=5,
                  vocab_size=64, sliding_window=8, moe_intermediate_size=16,
                  shared_expert_intermediate_size=16, num_experts=16,
                  num_experts_per_tok=4, param_dtype="float32", init_std=0.3),
        dsl_keys=("head_dim", "sliding_window", "gating",
                  "moe_intermediate_size", "shared_expert_intermediate_size",
                  "num_experts", "num_experts_per_tok",
                  "moe_routed_scaling_factor", "rms_norm_eps"),
        renamed=dict(
            kv_heads="num_key_value_heads", rope_theta="rope_theta",
            partial_rotary_factor=
            "rope_parameters.full_attention.partial_rotary_factor"),
        derived=dict(
            layer_types=lambda c: ";".join(t[0] for t in c["layer_types"]),
            mlp_layer_types=lambda c: ";".join(
                t[0] for t in c["mlp_layer_types"]),
            num_attention_heads_per_layer=lambda c: ";".join(
                map(str, c["num_attention_heads_per_layer"]))),
        tol=1e-4, whole_len=40,
        # the gate's matrix gone (sigmoid(0): every gate one half); the
        # reference without the window; with the whole head rotated in a
        # full layer; with no gate
        zeroed=("_blk1_attn.w4",),
        ref_controls=({"sliding_window": 0}, LAGUNA_WHOLE_HEAD_ROTATED,
                      {"gating": False}),
        ragged_kernels=(False, True),
        paged={f"blk{i}_attn": (2, 16) for i in range(5)}, margin=True,
        engines=(EngineCase("chunked-jnp", 5),
                 EngineCase("chunked-kernel", 5, True, build=AUTO),
                 EngineCase("free-rows", 5, mst=34)),
        letters={"multi_head_attention": "A"},
        depths=(({}, "AAAAA", "deeee"),
                ({"num_hidden_layers": 2}, "AA", "de"),
                ({"num_hidden_layers": 9}, "A" * 9, "d" + "e" * 8)),
        catalog="Laguna-XS.2", reduced=frozenset({"num_hidden_layers"}),
        dsl_nested=dict(
            layer_types=lambda c, ref: [t[0] for t in c["layer_types"]],
            mlp_layer_types=lambda c, ref: [
                t[0] for t in c["mlp_layer_types"]],
            partial_rotary_factor=
            "rope_parameters.full_attention.partial_rotary_factor",
            yarn_factor="rope_parameters.full_attention.factor",
            original_max_position_embeddings=
            "rope_parameters.full_attention."
            "original_max_position_embeddings",
            beta_fast="rope_parameters.full_attention.beta_fast",
            beta_slow="rope_parameters.full_attention.beta_slow",
            attention_factor=
            "rope_parameters.full_attention.attention_factor",
            window_rope_theta="rope_parameters.sliding_attention.rope_theta",
            window_partial_rotary_factor=
            "rope_parameters.sliding_attention.partial_rotary_factor"),
        dsl_defaults=21),
    ModelCase(
        name="xing4", json="xing4.0-29b-a4b-serve.json", dsl="xing4.py",
        # the published ratios: 4 residual streams; 4 heads of 8 + 4 against
        # a latent of 16; 16 experts, top-4 of ALL of them (one group), every
        # one held; one dense layer and two sparse ones
        tiny=dict(hidden_size=32, intermediate_size=64, num_attention_heads=4,
                  num_hidden_layers=3, vocab_size=64, q_lora_rank=24,
                  kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
                  v_head_dim=12, moe_intermediate_size=16,
                  n_routed_experts=16, experts_held=16, num_experts_per_tok=4,
                  param_dtype="float32", init_std=0.3, select_bias_std=0.3),
        dsl_keys=("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size",
                  "n_routed_experts", "num_experts_per_tok",
                  "first_k_dense_replace", "hc_mult", "hc_sinkhorn_iters"),
        # float32 sums in another order: the 20 Sinkhorn iterations and the
        # 4 x 4 stream sums on top of the GigaChat case's 2e-5
        tol=1e-4, whole_len=24, ragged_tol=2e-4,
        # a map's bias gone (H_post of the first sublayer = 1 everywhere is
        # still a model: its matrix phi gone instead, every map static); the
        # four controls of the configuration file's `limits`
        zeroed=("_blk0_hc1_maps.w0",),
        ref_controls=({"hc_sinkhorn_iters": 1}, {"hc_post_scale": 1.0},
                      {"hc_dynamic": False}, {"hc_plain_residual": True}),
        ragged_kernels=(False, True),       # `mhc_mix`, `mla_paged_attn`
        paged={f"blk{i}_attn": (128,) for i in range(3)}, margin=True,
        engines=(EngineCase("chunked-jnp", 4, mst=7),
                 EngineCase("chunked-kernel", 4, True, mst=7, build=AUTO),
                 EngineCase("one-chunk", 32)),
        prompts=(3, 19, 9, 17, 26), max_context=32,
        letters={"mla_attention": "A"},
        depths=(({}, "AAA", "dee"),
                ({"num_hidden_layers": 1}, "A", "d"),
                ({"num_hidden_layers": 6}, "A" * 6, "d" + "e" * 5)),
        catalog="Xing4.0-29B-A4B",
        reduced=frozenset({"num_hidden_layers", "first_k_dense_replace",
                           "num_nextn_predict_layers"}),
        dsl_nested={"rope_" + k: "rope_scaling." + k for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim")},
        dsl_defaults=26),
    ModelCase(
        name="olmo_hybrid", json="olmo-hybrid-7b-serve.json",
        dsl="olmo_hybrid.py",
        # the published ratios: 6 heads (no power of two) on as many KV
        # heads, of 8; 6 linear heads whose state is 8 x 16 (dk != dv, 1 : 2
        # as published); one period: L L L F
        tiny=dict(hidden_size=48, intermediate_size=64, num_attention_heads=6,
                  num_key_value_heads=6, num_hidden_layers=4, vocab_size=64,
                  linear_num_key_heads=6, linear_num_value_heads=6,
                  linear_key_head_dim=8, linear_value_head_dim=16,
                  param_dtype="float32", init_std=0.3),
        dsl_keys=("linear_num_key_heads", "linear_num_value_heads",
                  "linear_key_head_dim", "linear_value_head_dim",
                  "linear_conv_kernel_dim", "linear_allow_neg_eigval",
                  "rms_norm_eps", "norm_after_sublayer", "use_qk_norm",
                  "qk_norm_whole", "use_rope"),
        renamed=dict(KV),
        derived=dict(layer_types=lambda c: ";".join(
            t[0] for t in c["layer_types"])),
        # an output norm brings a sublayer's result to unit scale whatever
        # its size, its float32 rounding with it, and the log-probabilities
        # spread over 16 nats: the median position reads 1.5e-5 between the
        # chunkwise form and the reference's recurrence, the worst of 600
        # over four seeds 4.5e-4 (measured here).  Twice that; every control
        # moves a log-probability by 4.7 nats or more
        tol=1e-3, whole_len=70, ragged_chunks=(7, 9, 4, 3),
        # the output gate's matrix gone (silu(0) = 0: the mixer silent); the
        # reference with beta in (0, 1), with no decay, with the pre-norm
        # block, with no QK-norm: the configuration file's controls
        zeroed=("_blk0_gdn.w10",),
        ref_controls=({"linear_allow_neg_eigval": False},
                      {"linear_decay": False},
                      {"norm_after_sublayer": False},
                      {"use_qk_norm": False}), state_control=True,
        ragged_kernels=(False, True),       # `gdn_step` and `gdn_seg`
        recurrent=("blk0_gdn", "blk1_gdn", "blk2_gdn"),
        recurrent_type="kda_attention",
        slot_parts={"state": ((6, 8, 16), "float32"),
                    "conv": ((3, 192), "")},
        paged={"blk3_attn": (6, 8)}, moe=False, margin=True,
        engines=(EngineCase("chunked-jnp", 5),
                 EngineCase("free-rows-kernel", 5, True, mst=34, build=AUTO)),
        letters={"kda_attention": "L", "multi_head_attention": "F"},
        depths=(({}, "LLLF", "dddd"),
                ({"num_hidden_layers": 2}, "LL", "dd"),
                ({"num_hidden_layers": 8}, "LLLFLLLF", "d" * 8)),
        catalog="Olmo-Hybrid-7B", reduced=frozenset({"num_hidden_layers"}),
        dsl_nested=dict(
            layer_types=lambda c, ref: [t[0] for t in c["layer_types"]]),
        dsl_defaults=13),
)}


def pytest_generate_tests(metafunc):
    """A shared test's cases are its module's `CASE`'s: the engines, the
    depths, the forms the ragged steps run by."""
    case = getattr(metafunc.module, "CASE", None)
    for arg, values, ids in (
            ("engine_case", "engines", lambda e: e.id),
            ("ragged_kernel", "ragged_kernels",
             lambda k: "kernel" if k else "jnp"),
            ("depth", "depths", lambda d: d[1])):
        if arg in metafunc.fixturenames:
            metafunc.parametrize(arg, getattr(case, values), ids=ids)


# -- the helpers --------------------------------------------------------------------

def at(c: dict, path: str):
    for key in path.split("."):
        c = c[key]
    return c


def cfg(case, **over) -> dict:
    """The configuration file at the test's size; a dict-valued override
    goes INTO the file's dict of that name."""
    with open(case.json_path) as f:
        out = json.load(f)
    for src in (case.tiny, over):
        for k, v in src.items():
            out[k] = dict(out[k], **v) if isinstance(v, dict) else v
    return out


def args(case, c: dict, compute_dtype="", attn_impl="dense", **extra) -> str:
    """The --config-args that build configuration `c` from the DSL file."""
    sent = {a: c[k] for a, k in SIZES.items()}
    sent.update({a: at(c, path) for a, path in case.renamed.items()})
    sent.update({a: f(c) for a, f in case.derived.items()})
    sent.update({k: c[k] for k in case.dsl_keys})
    sent.update(compute_dtype=compute_dtype, attn_impl=attn_impl,
                init_std=c["init_std"], **extra)
    return ",".join(f"{k}={int(v) if isinstance(v, bool) else v}"
                    for k, v in sent.items())


def parse(case, config_args: str):
    from paddle_tpu.config.parser import parse_config
    cwd = os.getcwd()
    os.chdir(ROOT)
    try:
        return parse_config(case.dsl_path, config_args)
    finally:
        os.chdir(cwd)


def build(case, c: dict, compute_dtype="", **extra):
    from paddle_tpu.graph import GraphExecutor
    model = parse(case, args(case, c, compute_dtype, **extra)).model_config
    return GraphExecutor(model, compute_dtype=compute_dtype)


@functools.lru_cache(maxsize=None)
def _forward(ex, interpret):
    """The executor's forward compiled once a shape: a test's mixed steps
    share one program and its decode steps another, where an eager forward
    dispatches every op of every layer."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parameter.argument import Argument

    def forward(w, ids, state):
        n = jnp.full((ids.shape[0],), ids.shape[1], jnp.int32)
        out, _, st = ex.forward(w, {"tokens": Argument(ids=ids, lengths=n)},
                                state, "test", None)
        return jnp.log(out["lm_head"].value), st
    return jax.jit(forward)


def logits(ex, w, ids, state=None):
    """Log-probabilities [B, T, V] of the head, and the new state."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return _forward(ex, interpreted())(w, jnp.asarray(ids, jnp.int32),
                                           state)


def ref_logits(ref, c, w, seq):
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.jitted("log_probs", c)(
            w, jnp.asarray(seq), jnp.arange(len(seq))))


def slot_cache(ex, S, pages=8):
    """A cache manager of S full slots, and its page table with the trash
    slot's row."""
    import jax.numpy as jnp
    from paddle_tpu.serving import PagedKVCache
    kv = PagedKVCache(ex, num_slots=S, page_size=4, pages_per_slot=pages)
    for s in range(S):
        assert kv.try_grow(s, 4 * pages)
    table = jnp.asarray(np.vstack([kv.table,
                                   np.zeros((1, pages), np.int32)]))
    return kv, table


def state_of(kv, pools, **kw):
    """The pools as the layers' state: a slot-indexed part under its own
    name, a page-indexed one as `<part>_pages`; the run mask goes to the
    recurrent layers alone."""
    out = {}
    for n, p in pools.items():
        if n in kv.slot_specs:
            out[n] = dict(p, **kw)
        else:
            shared = {k: v for k, v in kw.items() if k != "run"}
            out[n] = dict({part + "_pages": a for part, a in p.items()},
                          **shared)
    return out


def pools_of(kv, pools, out):
    return {n: {part: out[n][part if n in kv.slot_specs else part + "_pages"]
                for part in p} for n, p in pools.items()}


def noised(kv, key, shift=0.0):
    """The cache manager's pools with every slot-indexed part random."""
    import jax
    return {n: ({part: shift + jax.random.normal(key, a.shape, a.dtype)
                 for part, a in p.items()} if n in kv.slot_specs else p)
            for n, p in kv.pools.items()}


def requests(n_tokens, max_new=6, seed=3):
    import jax
    from paddle_tpu.serving import Request
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}", rng.integers(2, 64, n).astype(np.int32),
                    max_new=max_new, rng=jax.random.PRNGKey(40 + i))
            for i, n in enumerate(n_tokens)]


def check_against_lm_generate(ex, w, reqs, results):
    """The served tokens are lm_generate's whole-sequence tokens (made once
    a process for one prompt through one executor: every engine of a model
    serves the same requests)."""
    for r in reqs:
        np.testing.assert_array_equal(lm_oracle(ex, w, r, use_cache=False),
                                      results[r.req_id])


def margin(ref, c, w, reqs, results):
    """The benchmark's own comparison: how far (nats) the reference's
    log-probability of each served token trails its own argmax,
    teacher-forced on prompt + served tokens through ONE full forward a
    request."""
    import jax
    from benchmark.lib.check import served_margin
    served = [(list(r.prompt_ids), list(results[r.req_id][len(r.prompt_ids):]))
              for r in reqs]
    return served_margin(jax, ref, c, w, served, 48)


def serve_tool():
    """tools/serve.py as a module, and `parse(argv)`: its `main` up to the
    parsed arguments, nothing served."""
    spec = importlib.util.spec_from_file_location(
        "tools_serve_under_test", os.path.join(ROOT, "tools", "serve.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    got = {}

    async def capture(a):
        got["args"] = a
        return 0

    tool.amain = capture

    def parse(argv):
        tool.main(argv)
        return got["args"]

    return tool, parse


def serve_argv(case, c, *more, compute_dtype="bfloat16"):
    return ["--config", case.dsl_path, "--config-args",
            args(case, c, compute_dtype), "--slots", "2", "--page-size",
            "4", "--max-context", "32", *more]


# -- the fixtures -------------------------------------------------------------------

@pytest.fixture(scope="module")
def case(request):
    return request.module.CASE


@pytest.fixture(scope="module")
def ref(case):
    from benchmark.lib.spec import Benchmark
    return Benchmark(ROOT).reference(case.name)


@pytest.fixture(scope="module")
def model(case, ref):
    c = cfg(case)
    return c, build(case, c), ref.make_weights(c, 7)


# -- the reference and the whole sequence -------------------------------------------

def test_reference_imports_nothing_of_the_program(case):
    with open(os.path.join(ROOT, "benchmark", "reference",
                           case.name + ".py")) as f:
        src = f.read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]


def test_weights_fit_the_programs_parameters(model):
    import jax
    _, ex, w = model
    shapes = jax.eval_shape(ex.init_params, jax.random.PRNGKey(0))
    assert {k: (v.shape, str(v.dtype)) for k, v in shapes.items()} == \
        {k: (v.shape, str(v.dtype)) for k, v in w.items()}


def test_whole_sequence_logits_against_the_reference(case, model, ref):
    """The whole sequence from the zero state against the reference's one
    forward — and the tolerance separates what it must: the program without
    each `zeroed` weight, the reference under each `ref_controls` setting."""
    c, ex, w = model
    seq = np.random.default_rng(0).integers(0, c["vocab_size"],
                                            case.whole_len)
    got = np.asarray(logits(ex, w, seq[None])[0][0])
    want = ref_logits(ref, c, w, seq)
    assert float(np.abs(got - want).max()) < case.tol
    for name in case.zeroed:
        off, _ = logits(ex, dict(w, **{name: w[name] * 0}), seq[None])
        assert float(np.abs(np.asarray(off[0]) - want).max()) > 50 * case.tol
    for setting in case.ref_controls:
        bare = ref_logits(ref, dict(c, **setting), w, seq)
        assert float(np.abs(got - bare).max()) > 50 * case.tol


# -- the three paths and the slot parts ---------------------------------------------

def test_ragged_chunks_then_decode_through_the_pools_on_logits(
        case, model, ref, ragged_kernel, monkeypatch):
    """Slot 1's 23-token prompt in mixed steps whose chunk rows split it at
    uneven places (`ragged_chunks`: inside a convolution's window, inside
    and across a chunk of the chunkwise forms and a pass of the kernels;
    segments that start at 0 and that continue from the slot's state) while
    slot 0 decodes beside it in the steps' decode rows, then 6 decode steps
    of both, through the cache manager's pools: every position's logits of
    both sequences against ONE full reference forward each — by the jnp
    forms and, where the file asks, by the interpreted kernels."""
    import jax.numpy as jnp
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET",
                       "1" if ragged_kernel else "0")
    c, ex, w = model
    if ragged_kernel:
        ex = build(case, c, **AUTO)
    rng = np.random.default_rng(1)
    S, P = 2, sum(case.ragged_chunks)
    seq0 = rng.integers(0, c["vocab_size"], 16)
    seq1 = rng.integers(0, c["vocab_size"], P + 6)
    kv, table = slot_cache(ex, S)
    pools = kv.pools
    got0 = np.zeros((len(seq0), c["vocab_size"]), np.float32)
    got1 = np.zeros((len(seq1), c["vocab_size"]), np.float32)
    T = S + max(case.ragged_chunks)

    def mixed(dec_rows, chunk_slot, chunk_pos):
        """dec_rows: {slot: (token, pos)}; the chunk rows from row S on"""
        ids = np.zeros(T, int)
        slot = np.full(T, S, int)
        pos = np.zeros(T, int)
        for r, (s, (tok, p)) in enumerate(dec_rows.items()):
            ids[r], slot[r], pos[r] = tok, s, p
        n = len(chunk_pos)
        src = seq1 if chunk_slot == 1 else seq0
        ids[S:S + n] = src[chunk_pos]
        slot[S:S + n], pos[S:S + n] = chunk_slot, chunk_pos
        st = state_of(kv, pools, page_table=table,
                      row_slot=jnp.asarray(slot, jnp.int32),
                      row_pos=jnp.asarray(pos, jnp.int32))
        lp, out = logits(ex, w, ids[None], st)
        return np.asarray(lp[0]), pools_of(kv, pools, out), out

    lp, pools, _ = mixed({}, 0, np.arange(1))    # slot 0's first token
    got0[0] = lp[S]
    n0, c0 = 1, 0
    for n in case.ragged_chunks:
        lp, pools, out = mixed({0: (seq0[n0], n0)}, 1, np.arange(c0, c0 + n))
        got0[n0] = lp[0]
        got1[c0:c0 + n] = lp[S:S + n]
        n0, c0 = n0 + 1, c0 + n
        # one decode row and one segment: two states moved a recurrent
        # layer, 1 + n rows through it
        for name in case.recurrent:
            assert int(out[name]["updates"]) == 2
            assert int(out[name]["rows"]) == 1 + n
    pos = jnp.asarray([n0, P], jnp.int32)
    run = jnp.ones((S,), bool)

    def decode(pools, tokens):
        st = state_of(kv, pools, page_table=table[:S], pos=pos, run=run)
        lp, out = logits(ex, w, np.asarray(tokens)[:, None], st)
        return np.asarray(lp[:, 0]), out

    for t in range(6):
        (got0[n0 + t], got1[P + t]), out = decode(
            pools, [seq0[n0 + t], seq1[P + t]])
        for name in case.recurrent:
            assert int(out[name]["rows"]) == 2
        pools = pools_of(kv, pools, out)
        pos = pos + 1
    assert float(np.abs(got0[:n0 + 6] - ref_logits(
        ref, c, w, seq0[:n0 + 6])).max()) < case.ragged_tol
    assert float(np.abs(got1 - ref_logits(ref, c, w, seq1)).max()) < \
        case.ragged_tol
    if case.state_control:
        # what the tolerance must separate: the same decode step from a
        # state rounded to bfloat16 once
        rounded = {n: (dict(p, state=p["state"].astype(jnp.bfloat16).astype(
            jnp.float32)) if n in case.recurrent else p)
            for n, p in pools.items()}
        nxt = rng.integers(0, c["vocab_size"], 2)
        assert float(np.abs(decode(rounded, nxt)[0] -
                            decode(pools, nxt)[0]).max()) > 5 * case.ragged_tol


def test_a_paused_slots_parts_are_bit_equal_after_the_step(case, model):
    """The run mask reaches the recurrent layers: a row whose mask is false
    leaves every slot-indexed part exactly as it was (a K/V write at a
    frozen position is idempotent; a recurrence is not), and a running
    row's tail moves on by one position."""
    import jax
    import jax.numpy as jnp
    _, ex, w = model
    S = 3
    kv, table = slot_cache(ex, S)
    pools = noised(kv, jax.random.PRNGKey(0))
    st = state_of(kv, pools, page_table=table[:S],
                  pos=jnp.asarray([5, 9, 2], jnp.int32),
                  run=jnp.asarray([True, False, True]))
    _, out = logits(ex, w, np.asarray([[3], [4], [5]]), st)
    assert sorted(kv.slot_specs) == sorted(case.recurrent)
    for n in case.recurrent:
        for part in case.slot_parts:
            assert bool((out[n][part][1] == pools[n][part][1]).all()), n
            assert not bool((out[n][part][0] == pools[n][part][0]).all())
        assert bool((out[n]["conv"][0, 0] == pools[n]["conv"][0, 1]).all())
        assert int(out[n]["rows"]) == 2 and int(out[n]["updates"]) == 2


def test_a_reused_slot_starts_from_zeros(model):
    """Re-admission: a slot that holds another request's state and tail
    gives, for a prompt that begins at position 0, the logits of a fresh
    slot — inside the compiled step, nothing is cleared at admission."""
    import jax
    import jax.numpy as jnp
    c, ex, w = model
    S = 2
    kv, table = slot_cache(ex, S)
    ids = np.random.default_rng(4).integers(0, c["vocab_size"], 6)
    row_ids = np.concatenate([np.zeros(S, int), ids])[None]
    kw = dict(page_table=table,
              row_slot=jnp.asarray([S] * S + [1] * 6, jnp.int32),
              row_pos=jnp.asarray([0] * S + list(range(6)), jnp.int32))
    fresh, _ = logits(ex, w, row_ids, state_of(kv, kv.pools, **kw))
    dirty = noised(kv, jax.random.PRNGKey(1), shift=3.0)
    again, _ = logits(ex, w, row_ids, state_of(kv, dirty, **kw))
    assert bool((fresh[0, S:] == again[0, S:]).all())


def test_slot_parts_are_declared_by_the_layer_type(case):
    """One registry gives a recurrent layer's parts, row shapes and dtypes,
    and holds exactly the served kinds; the cache manager builds every kind
    from it: a state in the configuration file's `state_dtype` whatever the
    compute dtype, a tail in the compute dtype, one row a slot plus the
    trash row, beside the page-indexed pools at their rows' shapes."""
    import jax.numpy as jnp
    from paddle_tpu.graph.registry import slot_state_types
    from paddle_tpu.serving import PagedKVCache
    from paddle_tpu.serving.paged_kv import slot_state_specs
    assert sorted(slot_state_types) == sorted(
        {c.recurrent_type for c in CASES.values() if c.recurrent})
    c = cfg(case)
    ex = build(case, c, compute_dtype="bfloat16")
    dtypes = {part: jnp.dtype(dt or "bfloat16")
              for part, (_, dt) in case.slot_parts.items()}
    if "state" in dtypes:
        assert c["state_dtype"] == str(dtypes["state"])
    assert slot_state_specs(ex.model, jnp.bfloat16) == {
        n: {part: (shape, dtypes[part])
            for part, (shape, _) in case.slot_parts.items()}
        for n in case.recurrent}
    kv = PagedKVCache(ex, num_slots=3, page_size=4, pages_per_slot=4)
    assert kv.slot_specs == {n: case.slot_shapes for n in case.recurrent}
    for n in case.recurrent:
        assert set(kv.pools[n]) == set(case.slot_parts)
        for part, (shape, _) in case.slot_parts.items():
            assert kv.pools[n][part].shape == (4, *shape)
            assert kv.pools[n][part].dtype == dtypes[part]
    assert kv.slot_state_bytes == 4 * len(case.recurrent) * sum(
        int(np.prod(shape)) * dtypes[part].itemsize
        for part, (shape, _) in case.slot_parts.items())
    assert kv.layer_specs == dict(case.paged)
    rows = sum(int(np.prod(row)) * len(kv.pools[n])
               for n, row in case.paged.items())
    assert kv.page_nbytes == 4 * rows * 2        # the paged parts alone
    assert kv.pool_bytes == kv.num_pages * kv.page_nbytes


# -- the engine ---------------------------------------------------------------------

def test_engine_serves_lm_generates_tokens(case, model, ref, engines,
                                           engine_case, monkeypatch):
    """A real ServingEngine — chunked prefill through mixed steps, slots
    re-admitted after other requests, the pools through the interpreted
    kernels, a step with free rows for a whole prompt (32 chunk rows: a run
    of 26 tokens where the share is 5) — serves lm_generate's whole-sequence greedy tokens; where the case
    says `margin`, every served token is the argmax of the reference's ONE
    full forward over prompt + served tokens to within the logits'
    tolerance; and the counters came back with the tokens."""
    import jax
    e = engine_case
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1" if e.kernel else "0")
    c, ex, w = model
    if e.build:
        ex = build(case, c, **e.build)
    reqs = requests(case.prompts)
    with jax.default_matmul_precision("highest"):
        eng = engines(ex, w, max_context=case.max_context,
                      prefill_chunk=e.chunk, max_step_tokens=e.mst)
        assert (eng.prefix is None) == bool(case.recurrent)
        before = counted(eng)
        results = eng.run(reqs)
        check_against_lm_generate(ex, w, reqs, results)
    eng.kv.check_reclaimed()
    if case.margin:
        m = margin(ref, c, w, reqs, results)
        assert m["worst_nats"] < case.tol and m["tokens"] == 30, m
    n = counted(eng, before)
    layers, prompt_rows = len(case.recurrent), sum(case.prompts)
    if e.mst and e.mst - len(eng.slots) >= max(case.prompts):
        # every prompt went in one run: the rows past a share of `chunk`
        assert n["n_prefill_chunks"] == len(reqs)
        assert n["n_chunk_rows"] == prompt_rows
        assert n["n_chunk_extra_rows"] == sum(
            max(0, p - e.chunk) for p in case.prompts)
    assert n["n_decode_steps"] > 0
    steps = n["recurrent_steps"] if layers else n["n_decode_steps"]
    if case.moe:
        # the held experts' load reached the counters with the tokens
        assert n["moe_steps"] == steps
        assert 0 < n["moe_pairs_max_sum"] <= n["moe_pairs_total"]
        # the busiest expert of a step's busiest layer; no tile under the
        # dense form these widths keep
        assert 0 < n["moe_layer_pairs_max_sum"] <= n["moe_pairs_max_sum"]
        assert n["moe_overflow_tiles"] == 0
    if layers:
        # every counted step; at most one state a slot a layer a step
        assert steps >= n["n_decode_steps"]
        assert 0 < n["recurrent_slot_updates"] <= \
            layers * len(eng.slots) * steps
        assert n["recurrent_rows"] >= n["recurrent_slot_updates"] // layers
        # the recurrent layers' tokens by the call that ran them, one
        # layer's worth: every prompt token in a chunk's run, every served
        # token but a request's first as a decode row
        assert n["tokens_segment"] == prompt_rows
        assert n["tokens_step"] == len(reqs) * (reqs[0].max_new - 1)
        assert n["tokens_step"] + n["tokens_segment"] == n["recurrent_rows"]
        # the chunks `kda_seg` folded: counted only where a KDA layer's
        # chunk rows go through the kernel, a run's cdiv(rows, chunk) —
        # every run here is shorter than a chunk, so one a run
        through_seg = e.kernel and case.recurrent_type == "kda_attention"
        assert n["segment_chunks"] == \
            (n["n_prefill_chunks"] if through_seg else 0)
    assert eng.kv.slot_state_bytes == 3 * case.slot_row_bytes


def test_checkpoint_and_restore_round_trip_the_slot_parts(case, model,
                                                          engines):
    """checkpoint_state / restore_state carry the slot-indexed parts: a run
    frozen mid-flight and resumed on a fresh engine finishes with the
    undisturbed run's tokens."""
    import jax
    from paddle_tpu.serving import ServingEngine
    _, ex, w = model
    reqs = requests((9, 13), max_new=8)
    first = case.recurrent[0]
    with jax.default_matmul_precision("highest"):
        a = engines(ex, w)
        for r in reqs:
            a.add_request(r)
        for _ in range(6):
            a.step()
        snap = a.checkpoint_state()
        assert snap["config"]["slot_specs"][first] == case.slot_shapes
        assert set(snap["pools"][first]) == set(case.slot_parts)
        b = ServingEngine(ex, w, num_slots=2, page_size=4, max_context=48,
                          prefill_chunk=5)
        b.restore_state(snap)
        for n in b.kv.slot_specs:
            for part, arr in b.kv.pools[n].items():
                assert bool((np.asarray(arr) ==
                             snap["pools"][n][part]).all())
        check_against_lm_generate(ex, w, reqs, b.run())
        a.run()                 # the engine goes back idle


@pytest.mark.parametrize("refused", REFUSALS)
def test_what_needs_a_state_snapshot_is_refused_by_name(case, model, refused):
    """Each mechanism that assumes the pages ARE the context raises for a
    model with recurrent layers, where it is asked for or set later, with
    RECURRENT_REFUSALS' sentence naming what is missing."""
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.paged_kv import RECURRENT_REFUSALS
    _, ex, w = model

    def engine(**kw):
        return ServingEngine(ex, w, num_slots=2, page_size=4,
                             max_context=32, **kw)

    with pytest.raises(ValueError) as e:
        if refused == "prefix":
            engine().set_prefix_cache(True)
        elif refused == "spill":
            engine(spill_bytes_budget=1 << 20)
        elif refused == "spill_later":
            engine().set_spill_budget(1 << 20)
        elif refused == "spec":
            engine(spec_k=2)
        elif refused == "spec_later":
            engine().set_speculation(2)
        elif refused == "mesh":
            from paddle_tpu.parallel.mesh import model_mesh
            engine(mesh=model_mesh(2))
        elif refused == "export":
            engine().export_prefix([1, 2, 3, 4])
        elif refused == "import":
            engine().import_prefix([1, 2, 3, 4], {"n_pages": 1}, b"")
        elif refused == "role":
            from paddle_tpu.serving.server import ServingServer
            ServingServer(engine(), role="prefill")
        else:
            from paddle_tpu.graph.lm_decode import init_kv_caches
            init_kv_caches(ex, 1, 8)
    msg = str(e.value)
    assert "recurrent" in msg
    if refused == "dense_cache":
        assert "no dense cache" in msg, msg
    else:
        what, why = RECURRENT_REFUSALS[refused.removesuffix("_later")]
        assert what in msg and why in msg, msg
        assert f"({len(case.recurrent)} here" in msg, msg


def test_build_engine_serves_the_model_in_bf16(case, monkeypatch):
    """tools/serve.py:build_engine, no flag of the model's own: it serves
    with bf16 parameters, its state in `state_dtype` and its tails in bf16
    beside the page-indexed pools, and a flag that needs a state snapshot is
    refused from the command line."""
    from paddle_tpu.serving import Request
    monkeypatch.chdir(ROOT)
    tool, parse = serve_tool()
    argv = serve_argv(case, cfg(case), "--prefill-chunk", "8",
                      "--param-dtype", "bfloat16")
    eng = tool.build_engine(parse(argv))
    assert {str(v.dtype) for v in eng.params.values()} == {"bfloat16"}
    for n in case.recurrent:
        for part, (shape, dt) in case.slot_parts.items():
            assert eng.kv.pools[n][part].shape == (3, *shape)
            assert str(eng.kv.pools[n][part].dtype) == (dt or "bfloat16")
    for n, row in case.paged.items():
        for pool in eng.kv.pools[n].values():
            assert pool.shape[2:] == row and str(pool.dtype) == "bfloat16"
    out = eng.run([Request("a", np.asarray([3, 5, 7], np.int32), max_new=4)])
    assert len(out["a"]) == 7
    with pytest.raises(ValueError, match="recurrent"):
        tool.build_engine(parse(argv + ["--spec-k", "2"]))


# -- the configuration --------------------------------------------------------------

def test_configuration_file_is_the_catalog_row_cut_as_it_says(case):
    """Every key of the catalog's row is the file's, or is listed in
    `reduced` with the published value kept beside it (`scored_whole`: the
    router still scores all of them, `experts_held` is what was cut)."""
    with open(case.json_path) as f:
        c = json.load(f)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(ln) for ln in f
                       if f'"{case.catalog}"' in ln)
        assert c["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k in c["reduced"] and k != case.scored_whole:
                assert c[k] != v and c["published"][k] == v, k
            else:                   # a nested group whole, and no key more
                assert c[k] == v, k
    assert set(c["reduced"]) == case.reduced
    assert c["server_flags"]["param_dtype"] == c["param_dtype"] == "bfloat16"


def test_dsl_defaults_equal_the_configuration_file(case, ref):
    """benchmark/kinds/serve.py sends ten sizes; every other one reaches
    the model as the DSL file's default — held to the JSON here, a list as
    the text that separates its items by `,` or `;`."""
    with open(case.json_path) as f:
        c = json.load(f)
    with open(case.dsl_path) as f:
        src = f.read()
    defaults = {m.group(1): m.group(2).strip() for m in re.finditer(
        r'get_config_arg\(\s*"(\w+)",\s*\w+,\s*([^)]+)\)', src)}
    checked = 0
    for name, text in defaults.items():
        if name in SENT:
            continue
        where = case.dsl_nested.get(name, name)
        want = where(c, ref) if callable(where) else at(c, where)
        if isinstance(want, list):
            assert re.split("[,;]", text.strip('"')) == [str(x) for x in want]
        else:
            assert float(text) == float(want), name
        checked += 1
    assert checked == case.dsl_defaults
    assert float(defaults["rope_theta"]) == float(c["rope_theta"])


def test_layer_kinds_by_depth(case, depth):
    """The mixers and the FFNs of the stack the DSL builds at a depth, a
    letter a layer (`letters`; `d` a dense FFN, `e` an expert layer)."""
    over, mixers, ffns = depth
    layers = parse(case, args(case, cfg(case, **over))).model_config.layers
    assert "".join(case.letters[l.type] for l in layers
                   if l.type in case.letters) == mixers
    assert "".join({"gated_ffn": "d", "moe": "e"}[l.type] for l in layers
                   if l.type in ("gated_ffn", "moe")) == ffns
