"""Architecture registry: the assigned configs + input-shape sets.

Every entry is from public literature; source tags in each module.
``get_config(arch_id)`` returns the full-scale config; ``.smoke()``
gives the reduced same-family config used by CPU smoke tests.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass

from repro.models.config import ModelConfig

ARCH_IDS = [
    "whisper_base",
    "llama32_vision_90b",
    "qwen2_0_5b",
    "chatglm3_6b",
    "stablelm_3b",
    "yi_6b",
    "grok1_314b",
    "granite_moe_3b",
    "zamba2_2_7b",
    "falcon_mamba_7b",
    "granite4_h_small",
]

# CLI aliases (--arch accepts either form)
ALIASES = {
    "whisper-base": "whisper_base",
    "llama-3.2-vision-90b": "llama32_vision_90b",
    "qwen2-0.5b": "qwen2_0_5b",
    "chatglm3-6b": "chatglm3_6b",
    "stablelm-3b": "stablelm_3b",
    "yi-6b": "yi_6b",
    "grok-1-314b": "grok1_314b",
    "granite-moe-3b-a800m": "granite_moe_3b",
    "zamba2-2.7b": "zamba2_2_7b",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "granite-4.0-h-small": "granite4_h_small",
}


def get_config(arch_id: str) -> ModelConfig:
    arch_id = ALIASES.get(arch_id, arch_id).replace("-", "_")
    if arch_id not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{arch_id}")
    return mod.CONFIG


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

# long_500k needs sub-quadratic attention: run only for SSM/hybrid
# (falcon-mamba decode is O(1)/token; zamba2's single shared-attention
# block decodes in O(S)/token).  Pure full-attention archs skip it —
# recorded in DESIGN.md SArch-applicability and as skip rows in
# EXPERIMENTS.md.
LONG_CONTEXT_ARCHS = {"zamba2_2_7b", "falcon_mamba_7b"}


def cells(include_skips: bool = False):
    """All (arch, shape) dry-run cells; skipped cells flagged."""
    out = []
    for a in ARCH_IDS:
        for s in SHAPES.values():
            skip = s.name == "long_500k" and a not in LONG_CONTEXT_ARCHS
            if include_skips or not skip:
                out.append((a, s.name, skip))
    return out
