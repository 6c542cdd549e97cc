"""Model zoo: configs + functional transformer implementation."""
from .config import (
    MambaConfig,
    ModelConfig,
    MoEConfig,
    RWKVConfig,
    RecurrentConfig,
    SHAPES,
    ShapeConfig,
    get_shape,
    scaled_down,
    shape_applicable,
)
from .transformer import (
    cache_specs,
    decode_step,
    forward,
    init_cache,
    init_params,
    loss_and_aux,
    param_specs,
    prefill,
    rewritten_leaves,
)

__all__ = [
    "MambaConfig", "ModelConfig", "MoEConfig", "RWKVConfig", "RecurrentConfig", "SHAPES",
    "ShapeConfig", "get_shape", "scaled_down", "shape_applicable",
    "cache_specs", "decode_step", "forward", "init_cache", "init_params",
    "loss_and_aux", "param_specs", "prefill", "rewritten_leaves",
]
