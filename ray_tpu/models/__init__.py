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
    "parallel_moe",
    "ParallelMoEConfig",
    "PARALLEL_MOE_TINY",
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

# ``parallel_moe`` (like ``window_moe`` and ``latent_moe``, which it is
# written on) starts importing its Pallas kernels when it is imported,
# beside a replica's opening of its chip; a process that serves another
# family imports this package too and is not to pay for that, so the
# three names are looked up when they are first asked for
_LAZY = {"parallel_moe": None, "ParallelMoEConfig": "ParallelMoEConfig",
         "PARALLEL_MOE_TINY": "PARALLEL_MOE_TINY"}


def __getattr__(name):
    if name not in _LAZY:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    module = importlib.import_module(".parallel_moe", __name__)
    return module if _LAZY[name] is None else getattr(module, _LAZY[name])
