from repro.configs.base import ModelConfig
from repro.configs.registry import ARCHS, get_config, smoke_config

__all__ = ["ModelConfig", "ARCHS", "get_config", "smoke_config"]
