"""Architecture registry: --arch <id> resolves here."""
from repro.configs import (
    deepseek_moe_16b,
    deepseek_v2_lite,
    internlm2_1_8b,
    llama3_2_3b,
    llama4_scout_17b_a16e,
    llava_next_34b,
    mistral_nemo_12b,
    phi3_mini_3_8b,
    whisper_large_v3,
    xlstm_350m,
    zamba2_7b,
)
from repro.configs.base import SHAPES, SMOKE_SHAPE, ModelConfig, ShapeConfig, shape_by_name

_MODULES = (
    mistral_nemo_12b, internlm2_1_8b, llama3_2_3b, phi3_mini_3_8b,
    deepseek_moe_16b, llama4_scout_17b_a16e, xlstm_350m, llava_next_34b,
    whisper_large_v3, zamba2_7b, deepseek_v2_lite,
)

ARCHS = {m.ARCH_ID: m for m in _MODULES}


def get_config(arch_id: str) -> ModelConfig:
    return ARCHS[arch_id].config()


def get_smoke_config(arch_id: str) -> ModelConfig:
    return ARCHS[arch_id].smoke_config()


# long_500k requires sub-quadratic attention: only the recurrent/hybrid
# archs run it (DESIGN.md §4); pure full-attention archs record a skip.
LONG_CONTEXT_ARCHS = ("xlstm-350m", "zamba2-7b")


def cell_is_runnable(arch_id: str, shape_name: str) -> bool:
    if shape_name == "long_500k":
        return arch_id in LONG_CONTEXT_ARCHS
    return True
