"""repro.configs — the published-model catalog."""
from .base import ModelConfig
from .registry import ARCHS, get_config

__all__ = ["ModelConfig", "ARCHS", "get_config"]
