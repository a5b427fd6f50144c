"""Architecture registry. `load_all()` imports every per-arch module."""
from repro.configs.base import (  # noqa: F401
    ModelConfig, MoESpec, ShapeConfig, SHAPES, TRAIN_4K, PREFILL_32K,
    DECODE_32K, LONG_500K, get_config, list_archs, register,
    ATTN, ATTN_LOCAL, MLA, RWKV, MAMBA, CONV,
)

_ARCH_MODULES = (
    "stablelm_1_6b", "smollm_360m", "gemma3_1b", "minicpm3_4b", "rwkv6_1_6b",
    "whisper_base", "llama4_maverick_400b_a17b", "deepseek_moe_16b",
    "jamba_v0_1_52b", "paligemma_3b", "paper_drl", "lfm2_8b_a1b",
)

_loaded = False


def load_all():
    global _loaded
    if _loaded:
        return
    import importlib
    for m in _ARCH_MODULES:
        importlib.import_module(f"repro.configs.{m}")
    _loaded = True
