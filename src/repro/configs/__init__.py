from repro.configs.base import (  # noqa: F401
    BASELINE, OPTIMIZED, SHAPES, STRATEGIES, ZERO3, MambaConfig, ModelConfig, MoEConfig,
    ShardingStrategy, TrainConfig, WorkloadShape, XLSTMConfig, replace,
    shape_applicable,
)
from repro.configs.registry import ARCH_IDS, CONFIG_IDS, EXTRA_IDS, all_configs, get  # noqa: F401
