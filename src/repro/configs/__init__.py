"""Assigned-architecture configs (--arch <id>).

Each module defines CONFIG (exact assigned hyperparameters) and SMOKE
(reduced same-family config for CPU tests).
"""
from __future__ import annotations

import importlib

ARCH_IDS = [
    "xlstm_125m",
    "command_r_plus_104b",
    "mistral_nemo_12b",
    "qwen3_14b",
    "qwen3_32b",
    "zamba2_7b",
    "dbrx_132b",
    "arctic_480b",
    "seamless_m4t_medium",
    "pixtral_12b",
]

ALIASES = {i.replace("_", "-"): i for i in ARCH_IDS}


def canonical(arch_id: str) -> str:
    arch_id = arch_id.replace("-", "_")
    if arch_id not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}")
    return arch_id


def load(arch_id: str):
    """Returns the config module for an arch id (accepts - or _ forms)."""
    return importlib.import_module(
        f"repro.configs.{canonical(arch_id)}"
    )


def model_config(arch_id: str, smoke: bool = False, **overrides):
    mod = load(arch_id)
    cfg = mod.SMOKE if smoke else mod.CONFIG
    return cfg.replace(**overrides) if overrides else cfg

