"""Config registry: ``get_config(arch_id)`` / ``get_smoke_config(arch_id)``.

One module per assigned architecture (exact public-literature configs), plus
the paper's own eval model (llama2-13b).  Smoke configs are reduced same-
family variants for CPU tests.  Full configs are compiled as shapes by the
dry-run and by ``tests/test_chip_compile.py``; qwen3-4b also serves at full
width on a TPU (``chip_smoke.py``, ``serve(..., smoke=False)``).
"""

from __future__ import annotations

from .base import ModelConfig

_REGISTRY: dict = {}
_SMOKE: dict = {}


def register(cfg: ModelConfig, smoke: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    _SMOKE[cfg.name] = smoke
    return cfg


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch '{name}'; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def get_smoke_config(name: str) -> ModelConfig:
    _ensure_loaded()
    return _SMOKE[name]


def list_archs() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


_loaded = False


def _ensure_loaded():
    global _loaded
    if _loaded:
        return
    from . import (deepseek_v2_lite_16b, gemma3_4b, h2o_danube_1p8b,
                   hubert_xlarge, internvl2_76b, llama2_13b, mamba2_370m,
                   minicpm3_4b, phi35_moe_42b, qwen3_4b, recurrentgemma_9b)
    # imported for their registration side effect only
    _ = (deepseek_v2_lite_16b, gemma3_4b, h2o_danube_1p8b, hubert_xlarge,
         internvl2_76b, llama2_13b, mamba2_370m, minicpm3_4b, phi35_moe_42b,
         qwen3_4b, recurrentgemma_9b)
    _loaded = True
