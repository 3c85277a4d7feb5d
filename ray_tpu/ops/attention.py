"""Memory-efficient causal attention (flash-attention algorithm).

Online-softmax blockwise attention: O(S) memory instead of the O(S^2)
logits tensor. Three consumers share the core accumulate step:

- ``blockwise_attention`` — `lax.scan` formulation in plain XLA. It is
  the numerical reference the kernel tests compare against, and what
  ``flash_attention`` runs on the CPU test mesh at head sizes the kernel
  does not tile.
- ``ray_tpu.ops.ring_attention`` — sequence-parallel ring schedule that
  feeds successive KV shards through the same accumulator.
- ``flash_attention`` — the Pallas TPU kernel
  (ray_tpu.ops.pallas_attention), mapped by hand over the ambient mesh
  (ops/_partition.py). On a TPU backend it is that kernel or an
  exception, never another implementation. Its three kernels (forward,
  ``bwd_dkv``, ``bwd_dq``) visit only the tile pairs a causal row can
  see, work on one (batch, kv head) a grid step with the G q heads of
  the group handled while their K and V are in VMEM, and sum dk and dv
  over the group inside ``bwd_dkv``; operands sit in VMEM whole where
  they fit and are streamed pair by pair where they do not, by their
  bytes alone. They want q, k and v head-major: the transposes around
  the call are folded by XLA's layout assignment into the projections
  and RoPE (no copy of their own in a compiled train step; PERF.md §6,
  PR 38), so they stay here in plain sight.

Supports GQA (n_kv_heads divides n_heads). Layout: q (B, S, H, hd),
k/v (B, T, KVH, hd) — the layout ray_tpu.models uses.

Reference parity note: the reference has NO sequence-parallel or
flash-attention code (SURVEY.md §5.7 — delegated to vLLM/torch); this
is TPU-native net-new capability.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ._partition import ambient_partition

_NEG_INF = -1e30


def _blockwise_accum(
    q, k, v, acc, m, l, *, causal: bool, block_q: int, block_kv: int,
    q_offset=0, kv_offset=0,
):
    """Accumulate attention of q against one K/V span into running
    online-softmax state. Shapes: q (B, Sq, KVH, G, hd), k/v
    (B, Skv, KVH, hd); acc (B, Sq, KVH, G, hd) f32, m/l (B, Sq, KVH, G)
    f32. ``q_offset``/``kv_offset`` may be tracers (ring attention
    passes the rotating shard's absolute position).

    Returns updated (acc, m, l). Fully-masked blocks are exact no-ops:
    masked probabilities are explicitly zeroed (relying on exp(-big)
    underflow is wrong when a block is masked BEFORE any visible block
    has set a finite running max).
    """
    B, Sq, KVH, G, hd = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    nq = max(1, Sq // block_q)
    nkv = max(1, Skv // block_kv)
    block_q = Sq // nq
    block_kv = Skv // nkv

    qb = q.reshape(B, nq, block_q, KVH, G, hd)
    kb = k.reshape(B, nkv, block_kv, KVH, hd)
    vb = v.reshape(B, nkv, block_kv, KVH, hd)
    accb = acc.reshape(B, nq, block_q, KVH, G, hd)
    mb = m.reshape(B, nq, block_q, KVH, G)
    lb = l.reshape(B, nq, block_q, KVH, G)

    q_pos = q_offset + jnp.arange(Sq).reshape(nq, block_q)
    kv_pos = kv_offset + jnp.arange(Skv).reshape(nkv, block_kv)

    def per_qblock(args):
        qi, q_blk, acc0, m0, l0 = args

        def body(carry, inputs):
            acc, m, l = carry
            ki, k_blk, v_blk = inputs
            logits = jnp.einsum(
                "bqkgh,btkh->bqkgt", q_blk, k_blk,
                preferred_element_type=jnp.float32,
            ) * scale
            if causal:
                mask = q_pos[qi][:, None] >= kv_pos[ki][None, :]
                logits = jnp.where(mask[None, :, None, None, :], logits, _NEG_INF)
            blk_max = jnp.max(logits, axis=-1)
            m_new = jnp.maximum(m, blk_max)
            # clamp for exp() only — fully-masked rows keep m_new=-inf
            # in the carry but compute with 0 to avoid inf/nan
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(logits - m_safe[..., None])
            if causal:
                p = jnp.where(mask[None, :, None, None, :], p, 0.0)
            corr = jnp.where(
                jnp.isfinite(m), jnp.exp(m - m_safe), 0.0
            )
            l = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bqkgt,btkh->bqkgh", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            acc = acc * corr[..., None] + pv
            return (acc, m_new, l), None

        (acc, m, l), _ = jax.lax.scan(
            body, (acc0, m0, l0),
            (jnp.arange(nkv), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)),
        )
        return acc, m, l

    out = jax.lax.map(
        per_qblock,
        (
            jnp.arange(nq),
            jnp.moveaxis(qb, 1, 0),
            jnp.moveaxis(accb, 1, 0),
            jnp.moveaxis(mb, 1, 0),
            jnp.moveaxis(lb, 1, 0),
        ),
    )
    acc2, m2, l2 = (jnp.moveaxis(t, 0, 1) for t in out)
    return (
        acc2.reshape(B, Sq, KVH, G, hd),
        m2.reshape(B, Sq, KVH, G),
        l2.reshape(B, Sq, KVH, G),
    )


def init_attention_state(B, Sq, KVH, G, hd):
    return (
        jnp.zeros((B, Sq, KVH, G, hd), jnp.float32),
        jnp.full((B, Sq, KVH, G), -jnp.inf, jnp.float32),
        jnp.zeros((B, Sq, KVH, G), jnp.float32),
    )


def finalize_attention_state(acc, l):
    return acc / jnp.maximum(l, 1e-30)[..., None]


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_kv: int = 512,
) -> jax.Array:
    """q (B, S, H, hd); k/v (B, T, KVH, hd) → (B, S, H, hd), by the
    online-softmax scan in plain XLA (any backend, any shape)."""
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    if H % KVH != 0:
        raise ValueError(f"n_heads {H} not divisible by n_kv_heads {KVH}")
    G = H // KVH
    qg = q.reshape(B, S, KVH, G, hd)
    acc, m, l = init_attention_state(B, S, KVH, G, hd)
    acc, m, l = _blockwise_accum(
        qg, k, v, acc, m, l, causal=causal, block_q=block_q, block_kv=block_kv
    )
    out = finalize_attention_state(acc, l)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_kv: int = 512,
) -> jax.Array:
    """q (B, S, H, hd); k/v (B, T, KVH, hd) → (B, S, H, hd).

    The Pallas kernel wherever its tiling fits the shapes: compiled by
    Mosaic on a TPU, interpreted on the CPU test mesh. Shapes it cannot
    tile are an error on a TPU; on the CPU, where nothing is measured,
    they run the blockwise XLA formulation. ``block_q``/``block_kv``
    apply to that formulation only; the kernel picks its own tiles
    (and whether K and V stay resident in VMEM) from the shapes.
    """
    from .pallas_attention import pallas_flash_attention, untileable

    reason = untileable(q, k, v)
    if reason is None:
        kernel = functools.partial(pallas_flash_attention, causal=causal)
        part = ambient_partition()
        if part is None:
            return kernel(q, k, v)
        # batch over (data, fsdp); heads over model when both head
        # counts divide (qkv arrive head-sharded from the model-split
        # projections), otherwise replicated over it
        H, KVH = q.shape[2], k.shape[2]
        heads = (
            "model"
            if part.tp > 1 and H % part.tp == 0 and KVH % part.tp == 0
            else None
        )
        spec = P(part.batch, None, heads, None)
        return jax.shard_map(
            kernel, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names=part.axes, check_vma=False,
        )(q, k, v)
    if jax.default_backend() != "cpu":
        raise NotImplementedError(
            f"flash attention on {jax.default_backend()}: {reason}"
        )
    return blockwise_attention(
        q, k, v, causal=causal, block_q=block_q, block_kv=block_kv
    )
