"""Architecture registry: one module per assigned arch + the paper's models.

``get_arch(name)`` returns the full-size :class:`ArchConfig`;
``get_smoke(name)`` a reduced same-family config for CPU smoke tests.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.core.config import ArchConfig

_ARCH_MODULES = {
    "mamba2-2.7b": "repro.configs.mamba2_2p7b",
    "granite-moe-3b-a800m": "repro.configs.granite_moe_3b",
    "granite-moe-1b-a400m": "repro.configs.granite_moe_1b",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
    "qwen2-vl-7b": "repro.configs.qwen2_vl_7b",
    "gemma2-2b": "repro.configs.gemma2_2b",
    "chatglm3-6b": "repro.configs.chatglm3_6b",
    "phi3-mini-3.8b": "repro.configs.phi3_mini",
    "minitron-4b": "repro.configs.minitron_4b",
    "seamless-m4t-large-v2": "repro.configs.seamless_m4t",
    # the paper's own model family (Qwen-R1 distills + Llama 3.2 1B)
    "qwen-r1-1.5b": "repro.configs.qwen_r1_1p5b",
    "qwen-r1-7b": "repro.configs.qwen_r1_7b",
    "qwen-r1-32b": "repro.configs.qwen_r1_32b",
    "llama32-1b": "repro.configs.llama32_1b",
    # served on the chip as one expert-parallel share (configs/mellum2_12b)
    "mellum2-12b-a2.5b": "repro.configs.mellum2_12b",
}

ASSIGNED_ARCHS: List[str] = list(_ARCH_MODULES)[:10]
PAPER_ARCHS: List[str] = list(_ARCH_MODULES)[10:14]


def get_arch(name: str) -> ArchConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(_ARCH_MODULES[name])
    return mod.CONFIG


def get_smoke(name: str) -> ArchConfig:
    mod = importlib.import_module(_ARCH_MODULES[name])
    if hasattr(mod, "SMOKE"):
        return mod.SMOKE
    return get_arch(name).scaled_down()


def all_archs() -> Dict[str, ArchConfig]:
    return {n: get_arch(n) for n in _ARCH_MODULES}
