"""Mixtral-style MoE Llama: the flagship architecture with every dense
FFN replaced by a top-k routed expert FFN.

Second first-class model family (the reference ships none in-tree —
it serves models through vLLM; here models are in-tree and mesh-aware).
Reuses the Llama attention stack (GQA/RoPE/RMSNorm, stacked-layer scan,
flash/ring attention impls) from models/llama.py and the capacity-
bounded expert dispatch from ops/moe.py; experts shard over the
`expert` mesh axis (param_specs), tokens reach them via GSPMD
all-to-all — the §2.5 EP strategy as a real model.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import MoEConfig, moe_ffn

from .llama import (
    LlamaConfig,
    attention_sublayer,
    attn_param_count,
    init_routed_params,
    masked_ce,
    remat_policy,
    rms_norm,
    rope_table,
    unpack_batch,
)
from .llama import param_specs as dense_param_specs


@dataclasses.dataclass(frozen=True)
class MoELlamaConfig(LlamaConfig):
    n_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    router_aux_coeff: float = 0.01

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig(
            d_model=self.dim,
            d_ff=self.ffn_dim,
            n_experts=self.n_experts,
            k=self.experts_per_token,
            capacity_factor=self.capacity_factor,
        )


# Stock shapes (public Mixtral architecture table) + test-size config.
MIXTRAL_8X7B = MoELlamaConfig(
    vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    ffn_dim=14336, max_seq_len=32768, rope_theta=1e6,
    n_experts=8, experts_per_token=2,
)
MOE_TINY = MoELlamaConfig(
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=128, max_seq_len=128, rope_theta=10000.0, remat=False,
    n_experts=4, experts_per_token=2,
)


def param_specs(config: MoELlamaConfig) -> Dict[str, Any]:
    """Llama attention shardings + experts on the `expert` axis.

    Expert matrices are (L, E, D, F): E shards over `expert` (EP), and
    the per-expert matrices additionally shard fsdp/model exactly like
    the dense FFN — EP composes with TP/FSDP."""
    specs = dense_param_specs(config)
    specs["blocks"].update(
        router=P(None, "fsdp", None),                   # (L, D, E)
        w_gate=P(None, "expert", "fsdp", "model"),      # (L, E, D, F)
        w_up=P(None, "expert", "fsdp", "model"),
        w_down=P(None, "expert", "model", "fsdp"))      # (L, E, F, D)
    return specs


def init_params(rng: jax.Array, config: MoELlamaConfig) -> Dict[str, Any]:
    return init_routed_params(rng, config, config.ffn_dim)


def param_count(config: MoELlamaConfig) -> int:
    c = config
    moe = c.dim * c.n_experts + 3 * c.n_experts * c.dim * c.ffn_dim
    return (
        c.vocab_size * c.dim * 2
        + c.n_layers * (attn_param_count(c) + moe)
        + c.dim
    )


def active_param_count(config: MoELlamaConfig) -> int:
    """Params touched per token (k of E experts) — the FLOPs-relevant
    count for MFU math on MoE models."""
    c = config
    moe = c.dim * c.n_experts + 3 * c.experts_per_token * c.dim * c.ffn_dim
    return (
        c.vocab_size * c.dim * 2
        + c.n_layers * (attn_param_count(c) + moe)
        + c.dim
    )


def block_fn(config: MoELlamaConfig, x: jax.Array, layer: Dict[str, jax.Array],
             cos: jax.Array, sin: jax.Array, mask=None):
    """One MoE transformer block. Returns (x, aux_loss)."""
    c = config
    x = attention_sublayer(c, x, layer, cos, sin)

    h = rms_norm(x, layer["mlp_norm"], c.norm_eps)
    moe_params = {
        # router stays fp32 (precision-sensitive); expert matmuls — the
        # bulk of the FLOPs — run in config.dtype like the dense FFN
        "router": layer["router"],
        "w_gate": layer["w_gate"].astype(c.dtype),
        "w_up": layer["w_up"].astype(c.dtype),
        "w_down": layer["w_down"].astype(c.dtype),
    }
    out, aux = moe_ffn(moe_params, h.astype(c.dtype), c.moe, mask=mask)
    return x + out.astype(x.dtype), aux


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: MoELlamaConfig, mask=None):
    """tokens (B, S) int32 -> (logits (B, S, V) float32, aux_loss).

    Same stacked-layer lax.scan shape as the dense model; the router
    aux losses accumulate through the scan carry. ``mask`` (B, S)
    excludes padding tokens from expert capacity and balance stats."""
    c = config
    B, S = tokens.shape
    x = params["embed"].astype(c.dtype)[tokens]
    cos, sin = rope_table(c, S)

    blk = partial(block_fn, c)
    if c.remat:
        blk = jax.checkpoint(blk, policy=remat_policy())

    def scan_body(carry, layer):
        x, aux_sum = carry
        x, aux = blk(x, layer, cos, sin, mask)
        return (x, aux_sum + aux), None

    (x, aux_sum), _ = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), params["blocks"]
    )
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(c.dtype))
    logits = logits.astype(jnp.float32)
    if c.logit_softcap:
        logits = jnp.tanh(logits / c.logit_softcap) * c.logit_softcap
    return logits, aux_sum / c.n_layers


def loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array],
            config: MoELlamaConfig) -> jax.Array:
    """Next-token cross entropy + router load-balancing aux loss.

    The LOSS mask ("mask") and the ROUTING mask are different things:
    an SFT loss mask zeroes prompt positions whose tokens are still
    real input the experts must process. Routing only excludes PADDING,
    supplied as batch["input_mask"] aligned with the model inputs; when
    absent, every input position routes."""
    inputs, targets, mask = unpack_batch(batch)
    input_mask = batch.get("input_mask")
    if input_mask is not None and "tokens" in batch:
        input_mask = input_mask[:, :-1]  # align with inputs = tokens[:, :-1]
    logits, aux = forward(params, inputs, config, mask=input_mask)
    return masked_ce(logits, targets, mask) + config.router_aux_coeff * aux
