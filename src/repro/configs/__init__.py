"""Assigned-architecture registry (``--arch <id>``).

Each module defines ``config()`` (the exact assigned configuration, source
cited) and ``smoke_config()`` (a reduced same-family variant: ≤2 layers,
d_model ≤ 512, ≤4 experts) for CPU smoke tests. :func:`get_preset` is
what the launchers take: the smoke variant, or the published config at
its published widths with only the depth cut (``--preset full
--n-layers N``), which is what runs on a chip.
"""
from __future__ import annotations

import importlib

from repro.models.types import INPUT_SHAPES, InputShape, ModelConfig

ARCH_IDS = [
    "qwen2-moe-a2.7b",
    "internvl2-1b",
    "xlstm-125m",
    "granite-moe-1b-a400m",
    "hymba-1.5b",
    "granite-3-2b",
    "stablelm-12b",
    "command-r-35b",
    "gemma2-27b",
    "musicgen-medium",
]

_MODULES = {a: "repro.configs." + a.replace("-", "_").replace(".", "_")
            for a in ARCH_IDS}


def _module(arch_id: str):
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; choose from {ARCH_IDS}")
    return importlib.import_module(_MODULES[arch_id])


def get_config(arch_id: str) -> ModelConfig:
    return _module(arch_id).config()


def get_smoke_config(arch_id: str) -> ModelConfig:
    return _module(arch_id).smoke_config()


PRESETS = ("smoke", "full")


def get_preset(arch_id: str, preset: str = "smoke",
               n_layers: int = 0) -> tuple[ModelConfig, dict]:
    """(config, reduced) for a launcher.

    ``smoke`` is :func:`get_smoke_config`. ``full`` is :func:`get_config`
    at its published widths, with ``n_layers`` (0 = all) cutting only the
    depth. ``reduced`` maps each key cut from the published config to
    ``(published, used)``: empty for an uncut full config, ``None`` for
    the smoke preset, whose widths are not the published ones.
    """
    if preset == "smoke":
        return get_smoke_config(arch_id), None
    if preset != "full":
        raise ValueError(f"unknown preset {preset!r}; choose from {PRESETS}")
    cfg = get_config(arch_id)
    reduced = {}
    if n_layers and n_layers != cfg.n_layers:
        if not 0 < n_layers < cfg.n_layers:
            raise ValueError(f"--n-layers {n_layers} must lie in "
                             f"[1, {cfg.n_layers}] for {arch_id}")
        reduced["n_layers"] = (cfg.n_layers, n_layers)
        cfg = cfg.with_(n_layers=n_layers)
    return cfg, reduced


def get_input_shape(name: str) -> InputShape:
    return INPUT_SHAPES[name]
