"""TPU compute kernels: flash/ring/Ulysses attention, MoE dispatch.
XLA blockwise fallbacks keep every op runnable on the CPU test mesh;
Pallas kernels take over on real TPU."""

from .attention import flash_attention
from .moe import (MoEConfig, init_moe_params, moe_ffn, moe_ffn_dropless,
                  top_k_gating)
from .ring_attention import ring_attention, ring_attention_sharded
from .ulysses import ulysses_attention

__all__ = [
    "MoEConfig",
    "flash_attention",
    "init_moe_params",
    "moe_ffn",
    "moe_ffn_dropless",
    "ring_attention",
    "ring_attention_sharded",
    "top_k_gating",
    "ulysses_attention",
]
