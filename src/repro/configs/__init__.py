"""Architecture config registry.

``get_config("<arch-id>")`` resolves both the assigned production
architectures (by their public ids, e.g. ``--arch qwen1.5-0.5b``) and the
Galaxy paper's own evaluation models (``--arch bert-l``).
"""
from __future__ import annotations

import importlib
from typing import Dict

from repro.configs.base import (  # noqa: F401
    MLAMoEConfig, ModelConfig, reduced, reduced_mla_moe,
)

# arch-id -> module under repro.configs
_ASSIGNED = {
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "codeqwen1.5-7b": "codeqwen1_5_7b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "musicgen-medium": "musicgen_medium",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "qwen1.5-110b": "qwen1_5_110b",
    "qwen1.5-0.5b": "qwen1_5_0_5b",
    "stablelm-12b": "stablelm_12b",
    "llama-3.2-vision-90b": "llama_3_2_vision_90b",
    "xlstm-350m": "xlstm_350m",
}

ASSIGNED_ARCHS = tuple(_ASSIGNED)

# arch-id -> module: blocks only the Galaxy executor serves (core/mla_moe.py);
# their configs are not ModelConfigs and the model zoo does not build them
_GALAXY_ONLY = {
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}

GALAXY_ARCHS = tuple(_GALAXY_ONLY)


def get_config(name: str):
    """The ``ModelConfig`` of a zoo or paper arch, or the ``MLAMoEConfig``
    of a Galaxy-only arch."""
    modules = {**_ASSIGNED, **_GALAXY_ONLY}
    if name in modules:
        mod = importlib.import_module(f"repro.configs.{modules[name]}")
        return mod.CONFIG
    from repro.configs.paper_models import PAPER_MODELS

    if name in PAPER_MODELS:
        return PAPER_MODELS[name]
    raise KeyError(
        f"unknown arch {name!r}; known: {sorted(modules) + ['distilbert', 'bert-l', 'gpt2-l', 'opt-l', 'opt-xl']}"
    )


def all_configs() -> Dict[str, ModelConfig]:
    return {name: get_config(name) for name in ASSIGNED_ARCHS}
