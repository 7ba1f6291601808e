"""Architecture registry: the assigned archs, Moonlight, and the paper's own MLP."""

from __future__ import annotations

from repro.configs import (
    granite_8b,
    internvl2_2b,
    kimi_k2_1t_a32b,
    mamba2_130m,
    minicpm3_4b,
    mnist_mlp,
    moonlight_16b_a3b,
    qwen1_5_0_5b,
    qwen2_moe_a2_7b,
    qwen3_1_7b,
    recurrentgemma_9b,
    whisper_small,
)
from repro.configs.base import SHAPES, Arch, ShapeCase, token_specs

_MODULES = [
    qwen1_5_0_5b,
    minicpm3_4b,
    qwen3_1_7b,
    granite_8b,
    qwen2_moe_a2_7b,
    kimi_k2_1t_a32b,
    moonlight_16b_a3b,
    mamba2_130m,
    internvl2_2b,
    recurrentgemma_9b,
    whisper_small,
    mnist_mlp,
]

REGISTRY: dict[str, Arch] = {m.ARCH.name: m.ARCH for m in _MODULES}

ASSIGNED: tuple[str, ...] = tuple(
    m.ARCH.name for m in _MODULES if m.ARCH.name != "mnist_mlp"
)


def get(name: str) -> Arch:
    if name not in REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(REGISTRY)}")
    return REGISTRY[name]


def list_archs() -> list[str]:
    return list(REGISTRY)


__all__ = ["Arch", "ShapeCase", "SHAPES", "REGISTRY", "ASSIGNED", "get",
           "list_archs", "token_specs"]
