"""Architecture registry: the assigned architectures as selectable configs.

Use ``get_config("<arch-id>")`` / ``--arch <arch-id>`` in the launchers.
"""

from __future__ import annotations

import importlib

from repro.configs.base import SHAPES, ShapeSpec, smoke_reduce
from repro.models.transformer import ModelConfig

#: arch-id -> module name
_MODULES: dict[str, str] = {
    "internlm2-20b": "internlm2_20b",
    "gemma2-27b": "gemma2_27b",
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    "qwen3-4b": "qwen3_4b",
    "whisper-medium": "whisper_medium",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "mixtral-8x7b": "mixtral_8x7b",
    "mamba2-2.7b": "mamba2_2_7b",
    "paligemma-3b": "paligemma_3b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "granite-4.0-h-small": "granite_4_0_h_small",
}


def _module(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch]}")


def list_archs() -> list[str]:
    return list(_MODULES)


def get_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _module(arch).SMOKE


def supports_long_context(arch: str) -> bool:
    return bool(getattr(_module(arch), "SUPPORTS_LONG_CONTEXT", False))


def cells(include_skipped: bool = False) -> list[tuple[str, str]]:
    """All (arch, shape) dry-run cells; long_500k only for sub-quadratic archs."""
    out = []
    for arch in _MODULES:
        for shape in SHAPES:
            if shape == "long_500k" and not supports_long_context(arch):
                if include_skipped:
                    out.append((arch, shape + ":SKIP"))
                continue
            out.append((arch, shape))
    return out


__all__ = [
    "SHAPES",
    "ShapeSpec",
    "ModelConfig",
    "smoke_reduce",
    "list_archs",
    "get_config",
    "get_smoke_config",
    "supports_long_context",
    "cells",
]
