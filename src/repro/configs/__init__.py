"""Architecture registry: one module per assigned architecture (+ the
paper's own GPT-A / GPT-B testbed models).

Every config cites its source in ``ModelConfig.source``.  ``get_config``
returns the full-size config; ``get_smoke_config`` returns the reduced
same-family variant used by CPU smoke tests (≤2 layers, d_model ≤ 512,
≤4 experts).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List

from repro.models.modules import ModelConfig

ARCHS: List[str] = [
    "rwkv6_7b",
    "minitron_4b",
    "zamba2_2p7b",
    "granite_34b",
    "hubert_xlarge",
    "deepseek_v2_lite_16b",
    "nemotron_4_15b",
    "deepseek_coder_33b",
    "qwen2_vl_7b",
    "qwen2_moe_a2p7b",
    "gpt_a",
    "gpt_b",
]

# CLI ids (``--arch <id>``) use dashes, matching the assignment sheet
CLI_IDS = {a.replace("_", "-").replace("-2p7b", "-2.7b").replace("-a2p7b", "-a2.7b"): a for a in ARCHS}


def canon(arch: str) -> str:
    arch = arch.strip()
    if arch in ARCHS:
        return arch
    if arch in CLI_IDS:
        return CLI_IDS[arch]
    alt = arch.replace("-", "_").replace(".", "p")
    if alt in ARCHS:
        return alt
    raise KeyError(f"unknown arch {arch!r}; known: {sorted(CLI_IDS)}")


def get_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canon(arch)}")
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canon(arch)}")
    return mod.SMOKE_CONFIG


def load_config(arch: str, *, smoke: bool = False, layers: int = 0) -> ModelConfig:
    """Full (or smoke) config; ``layers`` > 0 replaces only ``num_layers``
    (a depth cut at the published widths)."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    return cfg


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCHS}
