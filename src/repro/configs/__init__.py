"""Architecture registry: the assigned configs + their input shapes.

Every arch is selectable via ``--arch <id>`` in the launchers.  Each entry
records the exact published config (source in its module docstring), the
shape set, and per-shape execution knobs (microbatches, cache dtype).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List, Optional, Tuple

from ..models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str            # train | prefill | decode
    seq: int
    global_batch: int


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    config: ModelConfig
    microbatches: Dict[str, int] = dataclasses.field(default_factory=dict)
    kv_cache_dtype: Dict[str, str] = dataclasses.field(default_factory=dict)
    notes: str = ""

    def shape_names(self) -> List[str]:
        out = ["train_4k", "prefill_32k", "decode_32k"]
        if self.supports_long_context:
            out.append("long_500k")
        return out

    @property
    def supports_long_context(self) -> bool:
        # assignment rule: long_500k only for sub-quadratic-attention archs
        return "attn" not in self.config.block_pattern

    def config_for(self, shape: str) -> ModelConfig:
        kv = self.kv_cache_dtype.get(shape)
        if kv:
            return dataclasses.replace(self.config, kv_cache_dtype=kv)
        return self.config


_ARCH_MODULES = [
    "musicgen_large",
    "stablelm_1_6b",
    "qwen3_8b",
    "olmo_1b",
    "gemma3_27b",
    "recurrentgemma_2b",
    "falcon_mamba_7b",
    "chameleon_34b",
    "granite_moe_1b_a400m",
    "llama4_scout_17b_a16e",
    "moonlight_16b_a3b",
]

ARCHS: Dict[str, ArchSpec] = {}
for _m in _ARCH_MODULES:
    mod = importlib.import_module(f".{_m}", __name__)
    ARCHS[mod.ARCH.config.name] = mod.ARCH


def get_arch(name: str) -> ArchSpec:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(ARCHS)}")
    return ARCHS[name]


def all_cells() -> List[Tuple[str, str]]:
    """Every live (arch, shape) pair (long_500k skips already applied)."""
    return [(a, s) for a, spec in ARCHS.items() for s in spec.shape_names()]
