from repro.configs.base import (ARCH_IDS, LM_SHAPES, ModelConfig, ShapeConfig,
                                TrainConfig, get_config, reduced)

__all__ = ["ARCH_IDS", "LM_SHAPES", "ModelConfig", "ShapeConfig", "TrainConfig",
           "get_config", "reduced"]
