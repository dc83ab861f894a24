"""Architecture registry: resolve a catalog name to its CONFIG."""
from __future__ import annotations

import importlib

from .base import ModelConfig

ARCHS = (
    "granite-moe-3b-a800m",
    "moonshot-v1-16b-a3b",
    "zamba2-1.2b",
    "qwen2-vl-2b",
    "deepseek-67b",
    "gemma3-27b",
    "gemma3-12b",
    "deepseek-7b",
    "rwkv6-1.6b",
    "musicgen-large",
)

_MODULES = {a: a.replace("-", "_").replace(".", "_") for a in ARCHS}


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; have {list(ARCHS)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.CONFIG
