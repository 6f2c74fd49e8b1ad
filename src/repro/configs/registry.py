"""Config registry: ``--arch <id>`` resolution."""

from __future__ import annotations

import importlib

from repro.configs.base import ArchConfig

_ARCHS = {
    "h2o-danube-1.8b": "repro.configs.h2o_danube_1_8b",
    "granite-3-8b": "repro.configs.granite_3_8b",
    "gemma2-9b": "repro.configs.gemma2_9b",
    "qwen2-7b": "repro.configs.qwen2_7b",
    "zamba2-7b": "repro.configs.zamba2_7b",
    "phi3.5-moe-42b-a6.6b": "repro.configs.phi35_moe",
    "llama4-scout-17b-a16e": "repro.configs.llama4_scout",
    "mellum2-12b-a2.5b": "repro.configs.mellum2_12b_a2_5b",
    "whisper-large-v3": "repro.configs.whisper_large_v3",
    "internvl2-2b": "repro.configs.internvl2_2b",
    "mamba2-130m": "repro.configs.mamba2_130m",
    # the paper's vision-transformer family (§III ViT/DeiT tables)
    "vit-b16": "repro.configs.vit_b16",
    "deit-s16": "repro.configs.deit_s16",
    # the paper's own model family (benchmarks)
    "opt-125m": "repro.configs.opt",
    "opt-tiny": "repro.configs.opt",
}


def list_configs() -> list[str]:
    return sorted(_ARCHS)


def get_config(name: str) -> ArchConfig:
    key = name.lower()
    if key not in _ARCHS:
        raise ValueError(f"unknown arch {name!r}; known: {list_configs()}")
    mod = importlib.import_module(_ARCHS[key])
    return mod.get(key) if hasattr(mod, "get") else mod.CONFIG
