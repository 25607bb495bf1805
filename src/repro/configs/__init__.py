"""Config registry: --arch <id> resolution."""
import importlib

ARCH_IDS = (
    "jamba-1.5-large-398b",
    "granite-moe-1b-a400m",
    "qwen2-moe-a2.7b",
    "internvl2-26b",
    "minicpm-2b",
    "starcoder2-7b",
    "stablelm-1.6b",
    "deepseek-67b",
    "seamless-m4t-medium",
    "xlstm-350m",
)

_MODULES = {
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "internvl2-26b": "internvl2_26b",
    "minicpm-2b": "minicpm_2b",
    "starcoder2-7b": "starcoder2_7b",
    "stablelm-1.6b": "stablelm_1_6b",
    "deepseek-67b": "deepseek_67b",
    "seamless-m4t-medium": "seamless_m4t_medium",
    "xlstm-350m": "xlstm_350m",
    "sru_timit": "sru_timit",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
}


def get_config(arch_id: str):
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.CONFIG
