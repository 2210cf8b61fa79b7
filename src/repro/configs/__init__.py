"""Assigned architecture registry: ``--arch <id>`` resolves here.

Each module defines ``CONFIG`` (the exact published figures) and ``reduced()``
(a tiny same-family config for CPU smoke tests).
"""

from __future__ import annotations

from repro.config import ModelConfig

from repro.configs import (
    phi4_mini_3_8b,
    qwen3_8b,
    smollm_360m,
    minitron_4b,
    falcon_mamba_7b,
    llava_next_mistral_7b,
    granite_moe_1b_a400m,
    deepseek_moe_16b,
    zamba2_1_2b,
    whisper_medium,
    moonlight_16b_a3b,
)

_MODULES = [
    phi4_mini_3_8b,
    qwen3_8b,
    smollm_360m,
    minitron_4b,
    falcon_mamba_7b,
    llava_next_mistral_7b,
    granite_moe_1b_a400m,
    deepseek_moe_16b,
    zamba2_1_2b,
    whisper_medium,
    moonlight_16b_a3b,
]

ARCHS: dict[str, ModelConfig] = {m.CONFIG.arch_id: m.CONFIG for m in _MODULES}
REDUCED: dict[str, ModelConfig] = {m.CONFIG.arch_id: m.reduced() for m in _MODULES}

# Natural draft/target pairings for speculative decoding: a small same-vocab
# family member drafts for the big target. Keyed by target arch id.
DRAFT_PAIRS: dict[str, str] = {
    "qwen3-8b": "smollm-360m",
    "phi4-mini-3.8b": "smollm-360m",
    "minitron-4b": "smollm-360m",
    "deepseek-moe-16b": "granite-moe-1b-a400m",
}


def get(arch_id: str, reduced: bool = False) -> ModelConfig:
    table = REDUCED if reduced else ARCHS
    if arch_id not in table:
        raise KeyError(f"unknown arch {arch_id!r}; available: {sorted(table)}")
    return table[arch_id]


def draft_for(arch_id: str, reduced: bool = False) -> ModelConfig | None:
    """The paired draft config for a target arch (None when unpaired)."""
    pair = DRAFT_PAIRS.get(arch_id)
    return get(pair, reduced=reduced) if pair else None
