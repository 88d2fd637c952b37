"""Architecture registry: ``--arch <id>`` resolves through ``get_config``."""
from __future__ import annotations

from repro.configs.base import (ATTN, LOCAL_ATTN, MAMBA, SHARED_ATTN, SHAPES,
                                ModelConfig, MoEConfig, ShapeConfig, SSMConfig,
                                shape_applicable, smoke_config)

from repro.configs.zamba2_2p7b import CONFIG as _zamba2
from repro.configs.gemma3_12b import CONFIG as _gemma3
from repro.configs.mistral_large_123b import CONFIG as _mistral
from repro.configs.phi4_mini_3p8b import CONFIG as _phi4
from repro.configs.gemma2_27b import CONFIG as _gemma2
from repro.configs.whisper_large_v3 import CONFIG as _whisper
from repro.configs.paligemma_3b import CONFIG as _paligemma
from repro.configs.mamba2_780m import CONFIG as _mamba2
from repro.configs.olmoe_1b_7b import CONFIG as _olmoe
from repro.configs.moonlight_16b_a3b import CONFIG as _moonlight
from repro.configs.moonlight_16b_a3b import EP4 as _moonlight_ep4

ARCHS = {
    c.name: c for c in [
        _zamba2, _gemma3, _mistral, _phi4, _gemma2,
        _whisper, _paligemma, _mamba2, _olmoe, _moonlight,
    ]
}


# one chip's share of a published model in ARCHS (not a model of its own:
# the cell grid and the registry tests iterate ARCHS alone)
SHARES = {c.name: c for c in [_moonlight_ep4]}


def get_config(name: str) -> ModelConfig:
    if name.endswith("-smoke"):
        return smoke_config(get_config(name[: -len("-smoke")]))
    return ARCHS[name] if name in ARCHS else SHARES[name]


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


def all_cells():
    """Every (arch, shape) pair with its applicability verdict."""
    for arch in ARCHS.values():
        for shape in SHAPES.values():
            ok, reason = shape_applicable(arch, shape)
            yield arch, shape, ok, reason
