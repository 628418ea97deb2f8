"""Config registry: one module per assigned architecture (plus smoke variants)."""
from __future__ import annotations

from repro.configs.base import (ArchConfig, MLAConfig, MoEConfig, RGLRUConfig,
                                XLSTMConfig)

from repro.configs.llama4_maverick_400b_a17b import CONFIG as LLAMA4_MAVERICK
from repro.configs.deepseek_v2_lite_16b import CONFIG as DEEPSEEK_V2_LITE
from repro.configs.olmo_1b import CONFIG as OLMO_1B
from repro.configs.phi4_mini_3_8b import CONFIG as PHI4_MINI
from repro.configs.tinyllama_1_1b import CONFIG as TINYLLAMA
from repro.configs.qwen1_5_110b import CONFIG as QWEN15_110B
from repro.configs.recurrentgemma_2b import CONFIG as RECURRENTGEMMA_2B
from repro.configs.llava_next_34b import CONFIG as LLAVA_NEXT_34B
from repro.configs.xlstm_125m import CONFIG as XLSTM_125M
from repro.configs.musicgen_large import CONFIG as MUSICGEN_LARGE

ARCHS = {
    c.name: c for c in (
        LLAMA4_MAVERICK, DEEPSEEK_V2_LITE, OLMO_1B, PHI4_MINI, TINYLLAMA,
        QWEN15_110B, RECURRENTGEMMA_2B, LLAVA_NEXT_34B, XLSTM_125M,
        MUSICGEN_LARGE,
    )
}


def get_config(name: str) -> ArchConfig:
    if name.endswith("-smoke"):
        return ARCHS[name[:-len("-smoke")]].reduced()
    return ARCHS[name]


def list_archs():
    return sorted(ARCHS)


__all__ = [
    "ArchConfig", "MLAConfig", "MoEConfig", "RGLRUConfig", "XLSTMConfig",
    "ARCHS", "get_config", "list_archs",
]
