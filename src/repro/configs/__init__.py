"""Assigned architecture registry: ``get_config(name)`` / ``--arch <id>``."""
from __future__ import annotations

from repro.configs.base import SHAPES, ArchConfig, ShapeCell, cell_applicable


def _import_all():
    from repro.configs import (  # noqa: F401
        deepseek_7b,
        deepseek_v3_671b,
        granite_4_0_h_micro,
        granite_8b,
        granite_moe_1b_a400m,
        paper_synthetic,
        qwen2_vl_72b,
        rwkv6_7b,
        smollm_135m,
        stablelm_1_6b,
        whisper_medium,
        zamba2_1_2b,
    )

    mods = [
        deepseek_v3_671b,
        granite_moe_1b_a400m,
        whisper_medium,
        qwen2_vl_72b,
        rwkv6_7b,
        granite_8b,
        smollm_135m,
        stablelm_1_6b,
        deepseek_7b,
        zamba2_1_2b,
        granite_4_0_h_micro,
        paper_synthetic,
    ]
    return {m.CONFIG.name: m for m in mods}


_REGISTRY: dict | None = None


def registry() -> dict:
    global _REGISTRY
    if _REGISTRY is None:
        _REGISTRY = _import_all()
    return _REGISTRY


def get_config(name: str) -> ArchConfig:
    reg = registry()
    if name not in reg:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(reg)}")
    return reg[name].CONFIG


def get_smoke_config(name: str) -> ArchConfig:
    """Reduced same-family config for CPU smoke tests."""
    return registry()[name].SMOKE_CONFIG


def arch_names(include_synthetic: bool = False) -> list[str]:
    names = [n for n in registry() if n != "paper-synthetic"]
    if include_synthetic:
        names.append("paper-synthetic")
    return names


__all__ = [
    "ArchConfig",
    "SHAPES",
    "ShapeCell",
    "arch_names",
    "cell_applicable",
    "get_config",
    "get_smoke_config",
]
