"""Model zoo: one composable DecoderLM covering the ten assigned archs."""

from .config import ModelConfig, MoEConfig, SSMConfig, reduce_for_smoke
from .model import DecoderLM
from .params import (
    ParamSpec,
    abstract_params,
    init_params,
    make_shardings,
    param_count,
    sharding_rules,
)

__all__ = [
    "ModelConfig",
    "MoEConfig",
    "SSMConfig",
    "DecoderLM",
    "ParamSpec",
    "init_params",
    "abstract_params",
    "make_shardings",
    "param_count",
    "sharding_rules",
    "reduce_for_smoke",
]
