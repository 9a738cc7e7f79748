from paddle_tpu.graph.builder import GraphExecutor  # noqa: F401
from paddle_tpu.graph.registry import layer_registry, register_layer  # noqa: F401

# importing the implementation modules populates the registry
from paddle_tpu.graph import layers_core  # noqa: F401
from paddle_tpu.graph import layers_cost  # noqa: F401
from paddle_tpu.graph import layers_seq  # noqa: F401
from paddle_tpu.graph import layers_conv  # noqa: F401
from paddle_tpu.graph import layers_misc  # noqa: F401
from paddle_tpu.graph import layers_attn  # noqa: F401
from paddle_tpu.graph import layers_moe  # noqa: F401
from paddle_tpu.graph import layers_kda  # noqa: F401
from paddle_tpu.graph import layers_sconv  # noqa: F401
from paddle_tpu.graph import layers_ssm  # noqa: F401
from paddle_tpu.graph import layers_mamba  # noqa: F401
from paddle_tpu.graph import layers_hc  # noqa: F401
