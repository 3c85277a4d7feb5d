"""Model zoo: TPU-first reference models used by Train/Serve/Data/RLlib.

The reference (Ray) delegates model code to torch/vLLM downstream; this
framework ships JAX-native models so its ML libraries have first-class
workloads (flagship: Llama).
"""

from . import hybrid_ssm, llama, moe_llama, vit
from .hybrid_ssm import HYBRID_SSM_TINY, HybridSSMConfig
from .llama import (
    LLAMA_2_7B,
    LLAMA_3_8B,
    LLAMA_3_70B,
    LLAMA_BENCH,
    LLAMA_TINY,
    LlamaConfig,
)
from .moe_llama import MIXTRAL_8X7B, MOE_TINY, MoELlamaConfig
from .vit import VIT_B_16, VIT_L_16, VIT_TINY, ViTConfig

__all__ = [
    "hybrid_ssm",
    "HybridSSMConfig",
    "HYBRID_SSM_TINY",
    "llama",
    "moe_llama",
    "vit",
    "ViTConfig",
    "VIT_B_16",
    "VIT_L_16",
    "VIT_TINY",
    "LlamaConfig",
    "LLAMA_2_7B",
    "LLAMA_3_8B",
    "LLAMA_3_70B",
    "LLAMA_BENCH",
    "LLAMA_TINY",
    "MoELlamaConfig",
    "MIXTRAL_8X7B",
    "MOE_TINY",
]
