"""Architecture registry: ``get_config("<arch-id>")`` for every assigned arch.

IDs use the assigned dashed names; module files use underscores.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (SHAPES, ModelConfig, ShapeConfig,  # noqa
                                cell_runnable, input_specs)

ARCHS: List[str] = [
    "dbrx-132b",
    "llama4-maverick-400b-a17b",
    "seamless-m4t-large-v2",
    "yi-6b",
    "qwen1.5-4b",
    "qwen2.5-3b",
    "granite-3-2b",
    "internvl2-2b",
    "mamba2-2.7b",
    "recurrentgemma-9b",
    "granite-4.0-h-small",
]


def _module_name(arch: str) -> str:
    return "repro.configs." + arch.replace("-", "_").replace(".", "_")


def get_config(arch: str) -> ModelConfig:
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; choose from {ARCHS}")
    return importlib.import_module(_module_name(arch)).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    return importlib.import_module(_module_name(arch)).SMOKE
