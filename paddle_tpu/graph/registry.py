"""Layer registry: LayerConfig.type string -> implementation function.

TPU-native analog of the reference's REGISTER_LAYER/ClassRegistrar pattern
(ref: paddle/gserver/layers/Layer.h:32-37, paddle/utils/ClassRegistrar.h),
with layer *functions* instead of stateful Layer objects: a layer impl is a
pure function (ctx, cfg, inputs) -> Argument traced under jit, and autodiff
replaces every hand-written backward() in the reference's layer zoo.
"""

from __future__ import annotations

from typing import Callable, TYPE_CHECKING

if TYPE_CHECKING:
    from paddle_tpu.graph.context import ForwardContext
    from paddle_tpu.config.schema import LayerConfig
    from paddle_tpu.parameter.argument import Argument

LayerFn = Callable[..., "Argument"]

layer_registry: dict[str, LayerFn] = {}

# Layer types whose output is a training cost (they write ctx.costs) —
# the analog of the reference's CostLayer subtree (ref:
# paddle/gserver/layers/CostLayer.cpp). Consumers (e.g. lm_decode's
# logits-layer default) use this instead of string-matching type names.
cost_layer_types: set[str] = set()

# Validation layer types (ref: ValidationLayer.h) — in-graph evaluator
# hosts; pass-throughs, never a model's real output.
validation_layer_types: set[str] = set()


# Recurrent layer types and the SLOT-INDEXED state each keeps a sequence
# (serving/paged_kv.py "TWO FAMILIES OF PARTS"): type -> fn(cfg, compute
# dtype) -> {part: (row shape, dtype)}.  Declared beside the layer's
# registration; the serving cache manager builds, counts, dumps and
# checkpoints the parts from this and names no layer type or part itself.
slot_state_types: dict[str, Callable] = {}


def register_slot_state(type_name: str):
    def deco(fn: Callable) -> Callable:
        if type_name in slot_state_types:
            raise ValueError(f"duplicate slot state for {type_name!r}")
        slot_state_types[type_name] = fn
        return fn
    return deco


def register_layer(*type_names: str, cost: bool = False,
                   validation: bool = False):
    def deco(fn: LayerFn) -> LayerFn:
        for name in type_names:
            if name in layer_registry:
                raise ValueError(f"duplicate layer type {name!r}")
            layer_registry[name] = fn
            if cost:
                cost_layer_types.add(name)
            if validation:
                validation_layer_types.add(name)
        return fn
    return deco


def get_layer_fn(type_name: str) -> LayerFn:
    try:
        return layer_registry[type_name]
    except KeyError:
        raise NotImplementedError(
            f"layer type {type_name!r} not implemented; known: {sorted(layer_registry)}")
