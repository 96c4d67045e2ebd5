from repro.configs.base import (  # noqa: F401
    MeshConfig, MLAConfig, ModelConfig, MoEConfig, SHAPES, SHAPE_BY_NAME,
    SINGLE_POD, MULTI_POD, SSMConfig, ShapeSpec, TrainConfig, XLSTMConfig,
    shape_applicability,
)
from repro.configs.registry import (  # noqa: F401
    ASSIGNED_ARCHS, get_config, get_tiny, list_archs,
)
