"""Architecture registry — one module per assigned arch + the shape grid."""

from repro.configs.base import (
    ModelConfig,
    MoEConfig,
    MLAConfig,
    YaRNConfig,
    MambaConfig,
    XLSTMConfig,
    ShapeConfig,
    SHAPES,
    ARCH_IDS,
    LONG_CONTEXT_OK,
    get_config,
    get_smoke_config,
    input_specs,
    cell_supported,
)

__all__ = [
    "ModelConfig",
    "MoEConfig",
    "MLAConfig",
    "YaRNConfig",
    "MambaConfig",
    "XLSTMConfig",
    "ShapeConfig",
    "SHAPES",
    "ARCH_IDS",
    "LONG_CONTEXT_OK",
    "get_config",
    "get_smoke_config",
    "input_specs",
    "cell_supported",
]
