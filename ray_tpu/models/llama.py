"""Llama-family decoder-only transformer, TPU-first.

Flagship model for the framework. Design choices are deliberately
XLA-shaped rather than a torch translation:

- Parameters are a flat pytree of arrays with **stacked layers**
  (leading ``n_layers`` axis) consumed by ``lax.scan`` — one compiled
  block instead of n_layers unrolled copies, so compile time and HBM
  code size stay flat as depth grows.
- Attention/MLP matmuls are einsums in bfloat16 feeding the MXU; the
  attention inner can be swapped for the Pallas flash kernel
  (ray_tpu.ops.attention) via ``config.attention_impl``.
- Sharding is declared as PartitionSpecs per parameter (``param_specs``)
  against the canonical mesh axes (ray_tpu.parallel.mesh): fsdp shards
  the "long" dim of each matrix, model (tensor parallel) shards heads /
  ffn-hidden, Megatron-style, with XLA GSPMD inserting the collectives.
- GQA (n_kv_heads < n_heads), RoPE, RMSNorm, SwiGLU — Llama-2/3
  architecture. ``jax.checkpoint`` (remat) wraps each block when
  ``config.remat``: the backward recomputes the block's activations
  from its input, all but the flash kernel's output and row statistics,
  which are kept by name (``remat_policy``) so the forward kernel runs
  once a layer. Attention paths without the kernel keep nothing.

No reference-code lineage: the reference (Ray) ships no transformer;
this exists so the framework's Train/Serve/Data stacks have a real
workload (reference analogue: python/ray/llm delegates models to vLLM).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import decoder
from .decoder import rms_norm


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    attention_impl: str = "xla"  # "xla" | "flash" (pallas kernel) | "ring"
    ce_impl: str = "xla"  # "xla" | "fused" (pallas lm-head CE; needs
    # B*S % 128 == 0, vocab % 128 == 0, no logit softcap)
    # logits softcap (Gemma-style) kept for generality; 0 disables.
    logit_softcap: float = 0.0
    # a head's size where the heads do not add up to the hidden size;
    # None: dim // n_heads. Read it as ``head_dim``. (A field of that
    # name would be carried, resolved, through ``dataclasses.replace``
    # into a configuration of another ``dim``.)
    head_size: Optional[int] = None

    # the module whose init_params / init_cache / forward_with_cache
    # serve this configuration (llm/_internal/engine.py, llm/config.py)
    model_module = "ray_tpu.models.llama"

    @property
    def head_dim(self) -> int:
        return self.head_size or self.dim // self.n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads


# Stock configs. Sources are the public architecture tables.
LLAMA_3_8B = LlamaConfig(
    vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    ffn_dim=14336, max_seq_len=8192, rope_theta=500000.0,
)
LLAMA_3_70B = LlamaConfig(
    vocab_size=128256, dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
    ffn_dim=28672, max_seq_len=8192, rope_theta=500000.0,
)
LLAMA_2_7B = LlamaConfig(
    vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=32,
    ffn_dim=11008, max_seq_len=4096, rope_theta=10000.0,
)
# Small configs for tests / benches / CI (CPU-mesh friendly).
LLAMA_TINY = LlamaConfig(
    vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=256, max_seq_len=256, rope_theta=10000.0, remat=False,
)
LLAMA_BENCH = LlamaConfig(
    vocab_size=32000, dim=2048, n_layers=16, n_heads=16, n_kv_heads=8,
    ffn_dim=5632, max_seq_len=2048, rope_theta=10000.0,
)


def param_specs(config: LlamaConfig) -> Dict[str, Any]:
    """PartitionSpec pytree matching init_params' structure.

    fsdp shards each matrix's embedding-like dim; model (TP) shards
    heads (qkv/o) and ffn hidden — the Megatron split, expressed
    declaratively and compiled by GSPMD.
    """
    return {
        "embed": P("model", "fsdp"),              # (V, D): vocab-sharded on TP
        "blocks": {
            "attn_norm": P(None, None),            # (L, D)
            "wq": P(None, "fsdp", "model", None),  # (L, D, H, hd)
            "wk": P(None, "fsdp", "model", None),  # (L, D, KVH, hd)
            "wv": P(None, "fsdp", "model", None),
            "wo": P(None, "model", None, "fsdp"),  # (L, H, hd, D)
            "mlp_norm": P(None, None),
            "w_gate": P(None, "fsdp", "model"),    # (L, D, F)
            "w_up": P(None, "fsdp", "model"),
            "w_down": P(None, "model", "fsdp"),    # (L, F, D)
        },
        "final_norm": P(None),                     # (D,)
        "lm_head": P("fsdp", "model"),             # (D, V)
    }


def make_dense_init(config: LlamaConfig):
    """Scaled-normal initializer in config.param_dtype (shared by the
    dense and MoE model families)."""

    def dense(key, shape, fan_in):
        scale = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(
            config.param_dtype
        )

    return dense


def init_attn_params(config: LlamaConfig, keys, dense) -> Dict[str, Any]:
    """Stacked attention sublayer params (norms + qkvo) — the shared
    half of both families' block params. keys: (k_q, k_k, k_v, k_o)."""
    c = config
    hd = c.head_dim
    L = c.n_layers
    k_q, k_k, k_v, k_o = keys
    return {
        "attn_norm": jnp.ones((L, c.dim), c.param_dtype),
        "wq": dense(k_q, (L, c.dim, c.n_heads, hd), c.dim),
        "wk": dense(k_k, (L, c.dim, c.n_kv_heads, hd), c.dim),
        "wv": dense(k_v, (L, c.dim, c.n_kv_heads, hd), c.dim),
        "wo": dense(k_o, (L, c.n_heads, hd, c.dim), c.n_heads * hd),
        "mlp_norm": jnp.ones((L, c.dim), c.param_dtype),
    }


def attn_param_count(config: LlamaConfig) -> int:
    """Per-layer params of the shared attention sublayer + both norms."""
    c = config
    return (
        2 * c.dim
        + c.dim * c.n_heads * c.head_dim
        + 2 * c.dim * c.n_kv_heads * c.head_dim
        + c.n_heads * c.head_dim * c.dim
    )


def init_params(rng: jax.Array, config: LlamaConfig) -> Dict[str, Any]:
    """Initialize parameters (stacked-layer layout, param_dtype)."""
    c = config
    k_embed, k_q, k_k, k_v, k_o, k_g, k_u, k_d, k_lm = jax.random.split(rng, 9)
    dense = make_dense_init(c)
    L = c.n_layers
    return {
        "embed": dense(k_embed, (c.vocab_size, c.dim), c.dim),
        "blocks": {
            **init_attn_params(c, (k_q, k_k, k_v, k_o), dense),
            "w_gate": dense(k_g, (L, c.dim, c.ffn_dim), c.dim),
            "w_up": dense(k_u, (L, c.dim, c.ffn_dim), c.dim),
            "w_down": dense(k_d, (L, c.ffn_dim, c.dim), c.ffn_dim),
        },
        "final_norm": jnp.ones((c.dim,), c.param_dtype),
        "lm_head": dense(k_lm, (c.dim, c.vocab_size), c.dim),
    }


def init_routed_params(rng: jax.Array, config, expert_dim: int):
    """``init_params``' tree for a decoder whose every feed-forward is
    ``config.n_experts`` routed experts ``expert_dim`` wide: (L, E, ...)
    expert matrices beside a router, which stays float32 (tiny, and
    routing is precision-sensitive)."""
    c = config
    (k_embed, k_q, k_k, k_v, k_o, k_r, k_g, k_u, k_d,
     k_lm) = jax.random.split(rng, 10)
    dense = make_dense_init(c)
    L, E, D, F = c.n_layers, c.n_experts, c.dim, expert_dim
    return {
        "embed": dense(k_embed, (c.vocab_size, D), D),
        "blocks": {
            **init_attn_params(c, (k_q, k_k, k_v, k_o), dense),
            "router": jax.random.normal(
                k_r, (L, D, E), jnp.float32) / math.sqrt(D),
            "w_gate": dense(k_g, (L, E, D, F), D),
            "w_up": dense(k_u, (L, E, D, F), D),
            "w_down": dense(k_d, (L, E, F, D), F),
        },
        "final_norm": jnp.ones((D,), c.param_dtype),
        "lm_head": dense(k_lm, (D, c.vocab_size), D),
    }


def param_count(config: LlamaConfig) -> int:
    c = config
    per_layer = attn_param_count(c) + 3 * c.dim * c.ffn_dim
    return c.vocab_size * c.dim * 2 + c.n_layers * per_layer + c.dim


def rope_table(config: LlamaConfig, seq_len: int) -> Tuple[jax.Array, jax.Array]:
    hd = config.head_dim
    inv_freq = 1.0 / (
        config.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    )
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # (S, hd/2)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (S, hd/2) (or (B, S, hd/2) for shifted)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``apply_rope`` for tables that turn ADJACENT pairs: dimensions
    (2i, 2i + 1) by the angle of frequency i (the GPT-J convention),
    where ``apply_rope`` pairs i with i + hd/2. x: (B, S, H, hd);
    cos/sin: (B, S, hd/2). Each value's partner is a lane to its left or
    right (two lane rolls and a select), so nothing is de-interleaved."""
    x32 = x.astype(jnp.float32)
    cos = jnp.repeat(cos, 2, axis=-1)[:, :, None, :]
    sin = jnp.repeat(sin, 2, axis=-1)[:, :, None, :]
    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(even, -jnp.roll(x32, -1, axis=-1),
                        jnp.roll(x32, 1, axis=-1))
    return (x32 * cos + partner * sin).astype(x.dtype)


def _attention_xla(q, k, v, config: LlamaConfig, *, causal: bool = True):
    """Grouped-query causal attention via einsum — fuses cleanly in XLA.

    q: (B, S, H, hd); k/v: (B, S, KVH, hd). Computed in fp32 logits.
    """
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    q = q.reshape(B, S, KVH, G, hd)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, S, H, hd)


def _attention_ring(q, k, v, config: LlamaConfig):
    """Sequence-parallel attention: activations sharded (batch on
    data/fsdp, sequence on seq); the ring runs inside shard_map against
    the ambient mesh, rotating KV shards over ICI. Falls back to flash
    when there is no ambient mesh or the seq axis is trivial."""
    from jax.sharding import get_abstract_mesh

    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.ops.ring_attention import ring_attention

    mesh = get_abstract_mesh()
    if mesh is None or mesh.empty or dict(mesh.shape).get("seq", 1) == 1:
        return flash_attention(q, k, v, causal=True)
    # keep heads sharded over the TP axis inside the ring (qkv arrive
    # head-sharded from the model-split projections; replicating them
    # here would duplicate the whole ring per TP rank)
    tp = dict(mesh.shape).get("model", 1)
    kvh = k.shape[2]
    head_axis = "model" if (kvh % tp == 0 and q.shape[2] % tp == 0) else None
    spec = P(("data", "fsdp"), "seq", head_axis, None)
    return jax.shard_map(
        partial(ring_attention, axis_name="seq"),
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _attention(q, k, v, config: LlamaConfig):
    if config.attention_impl == "flash":
        from ray_tpu.ops.attention import flash_attention

        return flash_attention(q, k, v, causal=True)
    if config.attention_impl == "ring":
        return _attention_ring(q, k, v, config)
    if config.attention_impl != "xla":
        raise ValueError(
            f"unknown attention_impl {config.attention_impl!r}; "
            "expected 'xla', 'flash', or 'ring' (sequence parallel)"
        )
    return _attention_xla(q, k, v, config)


def attention_mix(config: LlamaConfig, h: jax.Array,
                  layer: Dict[str, jax.Array], cos, sin,
                  mixer=_attention, rotate=apply_rope) -> jax.Array:
    """The projections of GQA attention over a normed input ``h``
    (B, S, D) -> what the sublayer adds to the stream: the one spelling
    of the projections and their rotation, for train, prefill and decode
    and for every family whose layers project so. ``mixer(q, k, v,
    config)`` turns the rotated queries and keys and the values into the
    attended rows (B, S, H, hd): whole-sequence causal attention, or a
    closure over a cache. ``rotate(x, cos, sin)`` turns queries and
    keys; a layer without a position term gives ``cos`` None and
    nothing is turned."""
    c = config
    q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(c.dtype))
    k = jnp.einsum("bsd,dhk->bshk", h, layer["wk"].astype(c.dtype))
    v = jnp.einsum("bsd,dhk->bshk", h, layer["wv"].astype(c.dtype))
    if cos is not None:
        q = rotate(q, cos, sin)
        k = rotate(k, cos, sin)
    attn = mixer(q, k, v, c)
    return jnp.einsum("bshk,hkd->bsd", attn, layer["wo"].astype(c.dtype))


def attention_sublayer(config: LlamaConfig, x: jax.Array,
                       layer: Dict[str, jax.Array],
                       cos: jax.Array, sin: jax.Array,
                       mixer=_attention) -> jax.Array:
    """Pre-norm GQA attention + residual: ``attention_mix`` behind the
    layer's own norm. A block whose halves share a norm calls
    ``attention_mix`` on the normed rows and adds the residual itself
    (``models/parallel_moe.py``)."""
    c = config
    with jax.named_scope("attn"):
        h = rms_norm(x, layer["attn_norm"], c.norm_eps)
        return x + attention_mix(c, h, layer, cos, sin, mixer)


def mlp_mix(config: LlamaConfig, h: jax.Array,
            layer: Dict[str, jax.Array]) -> jax.Array:
    """The SwiGLU MLP over a normed input: what the sublayer adds."""
    c = config
    gate = jnp.einsum("bsd,df->bsf", h, layer["w_gate"].astype(c.dtype))
    up = jnp.einsum("bsd,df->bsf", h, layer["w_up"].astype(c.dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                      layer["w_down"].astype(c.dtype))


def mlp_sublayer(config: LlamaConfig, x: jax.Array,
                 layer: Dict[str, jax.Array]) -> jax.Array:
    """Pre-norm SwiGLU MLP + residual."""
    c = config
    with jax.named_scope("mlp"):
        h = rms_norm(x, layer["mlp_norm"], c.norm_eps)
        return x + mlp_mix(c, h, layer)


def block_fn(config: LlamaConfig, x: jax.Array, layer: Dict[str, jax.Array],
             cos: jax.Array, sin: jax.Array) -> jax.Array:
    """One transformer block. x: (B, S, D) in config.dtype."""
    x = attention_sublayer(config, x, layer, cos, sin)
    return mlp_sublayer(config, x, layer)


def remat_policy():
    """What a checkpointed block keeps for its backward: the two
    residuals the flash kernel's forward rule names (its output and its
    row statistics, B x S x H x (hd + 2) elements a layer), so the
    backward does not run the forward kernel a second time; everything
    else is recomputed. A block in which the kernel did not run (xla or
    ring attention, the CPU's blockwise fallback) carries no such name
    and keeps nothing."""
    from ray_tpu.ops.pallas_attention import SAVED_NAMES

    return jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES)


def forward_hidden(params: Dict[str, Any], tokens: jax.Array,
                   config: LlamaConfig) -> jax.Array:
    """tokens (B, S) int32 → final-norm hidden states (B, S, D) in
    config.dtype (everything except the lm-head projection)."""
    c = config
    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"].astype(c.dtype)[tokens]
    cos, sin = rope_table(c, S)

    blk = partial(block_fn, c)
    if c.remat:
        blk = jax.checkpoint(blk, policy=remat_policy())

    def scan_body(carry, layer):
        return blk(carry, layer, cos, sin), None

    with jax.named_scope("layers"):  # alone: the scan's own slicing
        x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    with jax.named_scope("head"):
        return rms_norm(x, params["final_norm"], c.norm_eps)


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: LlamaConfig) -> jax.Array:
    """tokens (B, S) int32 → logits (B, S, V) float32.

    Layers run under lax.scan over the stacked-params leading axis;
    each iteration optionally rematerialized.
    """
    c = config
    x = forward_hidden(params, tokens, c)
    with jax.named_scope("head"):
        logits = jnp.einsum(
            "bsd,dv->bsv", x, params["lm_head"].astype(c.dtype))
        logits = logits.astype(jnp.float32)
        if c.logit_softcap:
            logits = jnp.tanh(logits / c.logit_softcap) * c.logit_softcap
        return logits


def unpack_batch(batch: Dict[str, jax.Array]):
    """batch {"tokens": (B, S+1)} or {"inputs","targets"} [+"mask"]
    -> (inputs, targets, mask) — shared by both model families."""
    if "tokens" in batch:
        inputs = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
        mask = batch.get("mask")
        if mask is not None:
            mask = mask[:, 1:]
        return inputs, targets, mask
    return batch["inputs"], batch["targets"], batch.get("mask")


def masked_mean(nll: jax.Array, mask) -> jax.Array:
    """Masked-mean reduction shared by every CE path."""
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def masked_ce(logits: jax.Array, targets: jax.Array, mask) -> jax.Array:
    with jax.named_scope("ce"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return masked_mean(nll, mask)


def loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array],
            config: LlamaConfig) -> jax.Array:
    """Next-token cross entropy. batch: {"tokens": (B, S+1) int32} or
    {"inputs": (B,S), "targets": (B,S)} with optional "mask".

    ce_impl="fused" routes the lm-head projection + softmax-CE through
    the Pallas kernel (ops/pallas_ce.py): fp32 logits never touch HBM.
    """
    c = config
    inputs, targets, mask = unpack_batch(batch)
    B, S = inputs.shape
    if c.ce_impl == "fused":
        # an explicit "fused" request that can't be honored must FAIL,
        # not silently run XLA — a fused-kernel benchmark or live-chip
        # validation would otherwise measure the wrong implementation
        problems = []
        if c.logit_softcap:
            problems.append("logit_softcap is set")
        if (B * S) % 128 != 0:
            problems.append(f"B*S={B * S} not a multiple of 128")
        if c.vocab_size % 128 != 0:
            problems.append(f"vocab_size={c.vocab_size} not a multiple of 128")
        if problems:
            raise ValueError(
                "ce_impl='fused' not applicable: " + "; ".join(problems)
            )
        from ray_tpu.ops.pallas_ce import fused_cross_entropy

        x = forward_hidden(params, inputs, c)
        with jax.named_scope("ce"):
            nll = fused_cross_entropy(
                x.reshape(B * S, c.dim),
                params["lm_head"].astype(c.dtype),
                targets.reshape(B * S),
            ).reshape(B, S)
            return masked_mean(nll, mask)
    logits = forward(params, inputs, c)
    return masked_ce(logits, targets, mask)


# ---------------------------------------------------------------------
# KV-cache inference path (used by ray_tpu.llm — reference analogue:
# python/ray/llm delegates generation to vLLM; here generation is
# in-tree and XLA-shaped: static cache shapes, dynamic_update_slice
# writes, length-masked attention, one jitted program per bucket).
# ---------------------------------------------------------------------

def init_kv_cache(config: LlamaConfig, batch: int, max_seq: int,
                  chunk: Optional[int] = None):
    """Preallocated cache: k/v (L, B, KVH, max_seq, hd) in config.dtype.
    ``chunk`` is the most rows one call writes, which the engine tells
    every family and rows addressed by position do not need to know."""
    del chunk
    c = config
    shape = (c.n_layers, batch, c.n_kv_heads, max_seq, c.head_dim)
    return {
        "k": jnp.zeros(shape, c.dtype),
        "v": jnp.zeros(shape, c.dtype),
    }


init_cache = init_kv_cache      # the name the engine asks a family for


def attn_rows_read(config: LlamaConfig, cache, rows: int) -> int:
    """Cache rows a sequence one call reads for attention at the read
    window ``rows``, the layers' mean: every layer reads the window."""
    del config, cache
    return rows


def write_rows(stack, new, layer, first, start_pos):
    """``new`` (B, T, KVH, hd) into the stacked cache (L, B', KVH, S, hd)
    at layer ``layer``: sequence ``b``'s T rows from row ``start_pos[b]``
    of cache row ``first + b``, and nothing else."""
    new = new.astype(stack.dtype).transpose(0, 2, 1, 3)  # (B, KVH, T, hd)
    for b in range(new.shape[0]):
        stack = jax.lax.dynamic_update_slice(
            stack, new[None, b:b + 1],
            (layer, first + b, 0, start_pos[b], 0))
    return stack


def read_rows(stack, layer, first, batch: int, rows: int):
    """Layer ``layer``'s first ``rows`` rows of the ``batch`` sequences
    from cache row ``first`` on: (batch, KVH, rows, hd)."""
    _, _, kvh, _, hd = stack.shape
    return jax.lax.dynamic_slice(
        stack, (layer, first, 0, 0, 0), (1, batch, kvh, rows, hd))[0]


def _attention_cached(q, k_cache, v_cache, pos, config: LlamaConfig,
                      mask=None):
    """q (B, T, H, hd) new queries at absolute positions ``pos`` (B, T);
    k/v_cache (B, KVH, S, hd) hold all tokens written so far (including
    the new ones). Rows attend to cache slots <= their position, or to
    the slots ``mask`` (B, T, S) names where the cache's rows are not
    addressed by position."""
    B, T, H, hd = q.shape
    KVH, S = k_cache.shape[1:3]
    G = H // KVH
    qg = q.reshape(B, T, KVH, G, hd)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum(
        "btkgh,bksh->bkgts", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) * scale
    if mask is None:
        mask = jnp.arange(S)[None, None, :] <= pos[:, :, None]  # (B, T, S)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgts,bksh->btkgh", probs, v_cache)
    return out.reshape(B, T, H, hd)


def write_and_read(stacks, k, v, layer, call: decoder.Call, rows: int,
                   write=write_rows):
    """A layer's new keys and values (B, T, KVH, hd) into the ``k`` and
    ``v`` of ``stacks`` (``write(stack, new, layer, first, start_pos)``),
    then the first ``rows`` rows of the call's sequences read back out
    of them -> (k (B, KVH, rows, hd), v, the stacks)."""
    k_all, v_all = stacks["k"], stacks["v"]
    with jax.named_scope("kv_write"):
        k_all = write(k_all, k, layer, call.first, call.start_pos)
        v_all = write(v_all, v, layer, call.first, call.start_pos)
    with jax.named_scope("kv_slice"):
        k_c = read_rows(k_all, layer, call.first, call.B, rows)
        v_c = read_rows(v_all, layer, call.first, call.B, rows)
        if call.T >= 128:
            # From a chunk of 128 rows (a register's lanes) the TPU
            # compiler gives QK^T its K with the rows minor. Read
            # straight off the carry, that layout goes to the whole
            # stack, and the scan is bracketed by two transposing copies
            # of the shard. Behind the barrier only these sequences'
            # rows of this layer are laid out anew. Under 128 rows there
            # are no such copies and the barrier would only add one.
            # (tests/aot_compile_check.py compiles both sides.)
            k_c = jax.lax.optimization_barrier(k_c)
    return k_c, v_c, {"k": k_all, "v": v_all}


def forward_with_cache(
    params: Dict[str, Any],
    tokens: jax.Array,
    cache: Dict[str, jax.Array],
    start_pos: jax.Array,
    config: LlamaConfig,
    *,
    slot: Optional[jax.Array] = None,
    logits_at: Optional[jax.Array] = None,
    rows: Optional[int] = None,
):
    """Incremental forward: tokens (B, T) appended at per-sequence
    offsets ``start_pos`` (B,); ``slot``, ``logits_at`` and ``rows`` mean
    what ``decoder.Call`` says. Returns (logits fp32, (B, T, V) or with
    ``logits_at`` (B, 1, V); the updated cache).

    The cache is updated in place: the stacked k/v ride in the layer
    scan's carry (``decoder.scan_layers``), a layer writes its T new
    rows into them and reads its own rows for attention out of them.
    Under a jit that donates the cache nothing else of it moves.
    ``cache`` may be a tuple of several shards' caches, the B sequences
    theirs shard after shard (``models/decoder.py`` says what such a
    call is): the tuple comes back updated.
    """
    c = config
    caches, back = decoder.caches_of(cache)
    max_seq = caches[0]["k"].shape[3]
    call = decoder.Call(tokens, start_pos, max_seq, slot=slot,
                        logits_at=logits_at, rows=rows, shards=len(caches))
    x = decoder.embed(params, tokens, c)
    cos_full, sin_full = rope_table(c, max_seq)
    cos, sin = cos_full[call.pos], sin_full[call.pos]       # (B, T, hd/2)

    def step(x, caches, layer, i):
        def attend(part, stacks, q, k, v):
            k_c, v_c, stacks = write_and_read(
                stacks, k, v, i, part, part.window)
            with jax.named_scope("attn_cached"):
                return _attention_cached(q, k_c, v_c, part.pos, c), stacks

        def mixer(q, k, v, c):
            nonlocal caches     # the stacks with this layer's new rows
            attn, caches = call.by_shard(attend, caches, q, k, v)
            return attn

        x = attention_sublayer(c, x, layer, cos, sin, mixer)
        return mlp_sublayer(c, x, layer), caches, None

    x, caches, _ = decoder.scan_layers(step, x, caches, params["blocks"])
    return decoder.head(params, x, c, logits_at), back(caches)
