from repro.config.base import (
    SHAPES,
    ArchConfig,
    DataConfig,
    LoRAConfig,
    ModelConfig,
    ShapeConfig,
    SplitConfig,
    TrainConfig,
    reduced,
)

__all__ = [
    "SHAPES",
    "ArchConfig",
    "DataConfig",
    "LoRAConfig",
    "ModelConfig",
    "ShapeConfig",
    "SplitConfig",
    "TrainConfig",
    "reduced",
]
