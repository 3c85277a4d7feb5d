"""Decoders with latent attention, sandwich norms and a shared expert
beside routed ones of which this chip holds its share (openPangu-Ultra-
MoE, and by its key names the DeepSeek-V3 convention). Served through
``llm/_internal/engine.py`` as the other families are; not trained.

**A layer** (``N`` an RMS norm with its own gain): ``x = x + N2(attn(
N1(x)))``, then ``x = x + N4(mlp(N3(x)))``: four gains a layer. ``mlp``
is a dense SwiGLU in the ``n_dense_layers`` leading layers and
``ops/moe.py``'s dropless expert layer after them (sigmoid scores over
all ``n_experts``, the ``experts_per_token`` largest renormalised and
scaled, the experts in ``held_experts`` computed and the others' part
left out, one shared expert added once).

**Latent attention.** ``cq = Nq(h Wdq)``; a head's query is ``[q_nope |
q_rope] = cq Wuq``; ``[ckv | k_rope] = h Wdkv``, ``c = Nkv(ckv)``;
``k_rope`` is turned by the rotary table at the row's position and is
one vector for all heads, ``q_rope`` is turned head by head; a head's
key is ``[c Wuk | k_rope]`` and its value ``c Wuv``; the score of row t
on row s is ``(q_nope_t . k_nope_s + q_rope_t . k_rope_s) / sqrt(
nope + rope)``, causal, softmax in float32.

**The cache holds ``c`` and the turned ``k_rope`` of every row**: 576
values a token a layer and nothing per head, in two leaves, ``latent``
(L, B, max_seq, kv_rank) and ``rope_key`` (L, B, rope_dim, max_seq, the
rows last, as a score's matmul takes its keys). As one leaf of 576-wide
rows the chip laid it out with the rows last (576 is no whole number of
a register's 128 lanes, 16 384 is), whichever way it was declared, and
the chunk program, which wants the latent rows' width last, was
bracketed by two transposing copies of the whole shard, 3 GB each
(PERF.md section 6, PR 48). Attention has two forms over it, and which
one runs follows from the call alone:

- a call of more than one row a sequence (a prefill chunk) **expands**:
  block by block of the cache's rows it makes the heads' keys and values
  from the latent rows, once for all the chunk's rows, which attend to
  them a tile of rows at a time, each tile with a running maximum and
  sum, so no score of the chunk's rows x the cache's rows x the heads
  ever exists, and a block past the chunk's last row is never read.
  Where the widths tile (the published ones do) that is one Pallas
  kernel a layer, ``ops/pallas_latent_attention.py``, which reads the
  cache's stacks where they lie and keeps a head's score, softmax and
  accumulator in VMEM (scope ``attn_latent_prefill``); elsewhere a
  ``jax.numpy`` loop with the same arithmetic (``latent_expand`` +
  ``attn_latent_prefill``), which is also the kernel's reference;
- a call of one row a sequence (a decode) **absorbs**: ``q_nope . (c
  Wuk) = (q_nope Wuk^T) . c`` and ``sum_s p_s (c_s Wuv) = (sum_s p_s
  c_s) Wuv``, so every head attends to the latent rows themselves, one
  576-wide key and one 512-wide value for all of them
  (``attn_latent_decode``), block by block as well. Where the widths
  tile (kv_rank in 128s, the rotary part and the heads in 16s, the
  cache's rows in 128s: the published ones do) that is one Pallas kernel
  a layer between the two foldings, ``ops/pallas_latent_attention.py``
  ``latent_decode_attention``: a lane's heads attend together to a
  block of its rows, fetched once out of the stacks where they lie,
  each lane through the blocks up to its own last row and an idle lane
  through none (its result zeros, which the engine drops); elsewhere a
  ``jax.numpy`` loop with the same arithmetic
  (``attend_absorbed_blockwise``, every lane through the longest live
  lane's blocks), which is also the kernel's reference.

Above some 170 rows a call the expanded form is the cheaper (it pays
``2 x kv_rank x heads x (nope + v)`` FLOPs once a latent row; the
absorbed form pays the wider key and value at every pair).

**Parameters are stacked by kind of layer**: ``dense`` and ``routed``
each hold their layers' attention and norms, and the MLP of their own
shape; ``decoder.scan_layers`` runs over the one and then over the
other, the cache riding in both carries. The call around the layers
(its rows, embedding, head, the counters' words) is
``models/decoder.py``'s.

**Counters** (``COUNTERS``, in the cache's ``counts``): the ``moe_*`` three of
``EngineStats`` (held experts only), ``moe_held_slabs`` (the passes
``ops/moe.py`` made over a slab of the held experts' assignments: one a
layer a chunk call whose held share fits the slab, none in a decode
call), ``moe_assignments_all`` (every live
row's ``experts_per_token``, so that the held share of the routing is
read and not assumed), and for the two attention forms
``attn_pairs_prefill`` (a chunk's live rows x the rows each attends to),
``attn_rows_prefill`` (the latent rows a chunk call attends to, each
expanded once), ``attn_rows_decode`` (the rows a live lane attends to)
and ``attn_blocks_decode`` (the rows of the blocks the decode form took
the call's lanes through, ``absorbed_blocks``: on the kernel's path
every live lane's own blocks, on the loop's the longest's for every
lane; ``attn_rows_decode`` over it is the share of the fetched rows that
some lane asked for), each summed over layers and calls.
"""

from __future__ import annotations

import dataclasses
import math
import threading
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import moe

from . import decoder
from .decoder import rms_norm
from .llama import LlamaConfig, apply_rope, make_dense_init


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig(LlamaConfig):
    # ``ffn_dim`` is the dense leading layers' width; ``n_kv_heads`` and
    # ``head_size`` mean nothing here
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    n_dense_layers: int = 1
    # the router's width, the experts a token goes to, an expert's width
    n_experts: int = 256
    experts_per_token: int = 8
    expert_dim: int = 2048
    # the ids of the experts this chip holds, in the order their weights
    # are stacked
    held_experts: Tuple[int, ...] = tuple(range(8))
    shared_dim: int = 2048      # the one shared expert's width
    norm_topk_prob: bool = True
    routed_scale: float = 2.5

    model_module = "ray_tpu.models.latent_moe"

    def __post_init__(self):
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(
                f"{self.n_dense_layers} dense layers of {self.n_layers}")
        if self.rope_dim % 2:
            raise ValueError("the rotary part turns pairs of dimensions")

    @property
    def n_routed_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def n_held(self) -> int:
        return len(self.held_experts)

    @property
    def latent_dim(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def moe(self) -> moe.MoEConfig:
        return moe.MoEConfig(
            d_model=self.dim, d_ff=self.expert_dim, n_experts=self.n_experts,
            k=self.experts_per_token, norm_topk_prob=self.norm_topk_prob,
            scoring="sigmoid", routed_scale=self.routed_scale,
            held=self.held_experts)


LATENT_MOE_TINY = LatentMoEConfig(
    vocab_size=512, dim=64, n_layers=3, n_heads=4, ffn_dim=128,
    max_seq_len=256, rope_theta=10000.0, remat=False,
    q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16,
    n_dense_layers=1, n_experts=16, experts_per_token=4, expert_dim=32,
    held_experts=(4, 5, 6, 7), shared_dim=32,
)

SHARED_WEIGHTS = ("shared_gate", "shared_up", "shared_down")
COUNTERS = ("moe_assignments", "moe_experts_touched", "moe_expert_slots",
            "moe_held_slabs", "moe_assignments_all", "attn_pairs_prefill",
            "attn_rows_prefill", "attn_rows_decode", "attn_blocks_decode")
# (the most a call counts at once, 2048 rows x 16 384 x 5 layers, is
# 2^27: under the carry of ``decoder``'s counter words)
# What the ``jax.numpy`` loops take at a time (since PR 50 and PR 62
# they are the forms of the widths the kernels cannot tile, and the
# tests' reference): cache rows a block, and (the prefill form's loop,
# ``attend_expanded_blockwise``) the rows of a chunk that attend to them
# at a time: its
# score of one tile and block is heads x PREFILL_TILE x PREFILL_BLOCK
# float32 (34 MB at 128 heads), a decode's lanes x heads x DECODE_BLOCK.
# Read on a v5e at the published widths, when the loop was the cell's
# path: a 256-row chunk call 2.3 us an attended row at blocks of 256,
# 2.6 at 128, 3.3 at 512, 4.8 at 1024; a 1024-row call 11 us in tiles of
# 256, 14 in tiles of 128, 16 in tiles of 512 and 21 as one tile; a
# decode call of 32 lanes the same at 512, 1024 and 2048 (PERF.md
# section 6, PR 48 and PR 49). The kernels (``ops/
# pallas_latent_attention.py``) size themselves: there 1024 rows at row
# 3072, a layer, read 8.1 ms at blocks of 256 rows and 2 heads a step,
# 4.2 at 512 rows and 4 heads in tiles of 512, 4.1 at 1024 rows and at
# 8 heads, 4.5 in tiles of 256 (the row maxima's lane reductions are
# paid once a tile and block whatever its width; PERF.md section 6,
# PR 50)
PREFILL_BLOCK = 256
PREFILL_TILE = 256
DECODE_BLOCK = 1024


def _attn_shapes(c: LatentMoEConfig) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """name -> (a layer's shape, fan-in) of the attention projections."""
    H = c.n_heads
    return {
        "wdq": ((c.dim, c.q_rank), c.dim),
        "wuq": ((c.q_rank, H, c.nope_dim + c.rope_dim), c.q_rank),
        "wdkv": ((c.dim, c.latent_dim), c.dim),
        "wuk": ((c.kv_rank, H, c.nope_dim), c.kv_rank),
        "wuv": ((c.kv_rank, H, c.v_dim), c.kv_rank),
        "wo": ((H, c.v_dim, c.dim), H * c.v_dim),
    }


def chunk_terms(config: LatentMoEConfig, max_seq: int) -> Dict[str, float]:
    """What ``engine.derived_prefill_chunk`` is told beside the chip,
    from the configuration and the cache's length alone. Every row
    meets the attention projections, the dense layers' MLPs, the shared
    experts and the head; a held expert is met by the rows routed to
    it, ``experts_per_token / n_experts`` of them. Whichever of the two
    holds more of the model sets what a row's work is counted in
    (``row_share``). Where that is the former (8 of 256 experts held
    beside 7680-wide latent attention: 1.74 G parameters beside 1.51 G
    and an embedding that is gathered, not multiplied), two things more
    are paid once a call:

    - the held experts' read (``read_beside``: 1.51 G parameters over
      1.74 G, 0.87). At the rows that pay for the weights every row
      meets, an expert sees a thirty-second of them and is read all
      the same;
    - the expansion of every latent row the chunk attends to into the
      heads' keys and values (``latent_expand``: 2 x kv_rank x heads x
      (nope + v) FLOPs a row a layer), before a row of the chunk's own
      is scored. A lane of ``max_seq`` holds half of them on average
      over a prompt; over a row's 2 FLOPs a parameter it meets that is
      ``once_rows`` (394 rows' worth at 16 384).

    Where the held experts hold more, their read is what the rows pay
    for, each expert seeing its share of them; the weights every row
    meets are past their own ridge by then and the expansion is small
    beside rows so many."""
    c = config
    attention = sum(math.prod(shape) for shape, _ in _attn_shapes(c).values())
    every_row = (c.n_layers * attention
                 + c.n_dense_layers * 3 * c.dim * c.ffn_dim
                 + c.n_routed_layers * 3 * c.dim * c.shared_dim
                 + c.dim * c.vocab_size)
    routed = c.n_routed_layers * c.n_held * 3 * c.dim * c.expert_dim
    if every_row < routed:
        return {"row_share": c.experts_per_token / c.n_experts}
    expand = (c.n_layers * 2 * c.kv_rank * c.n_heads
              * (c.nope_dim + c.v_dim)) * max_seq / 2
    return {"read_beside": routed / every_row,
            "once_rows": expand / (2 * every_row)}


# -- parameters --------------------------------------------------------
NORMS = {"attn_norm": "dim", "attn_post_norm": "dim", "mlp_norm": "dim",
         "mlp_post_norm": "dim", "q_norm": "q_rank", "kv_norm": "kv_rank"}


def param_specs(config: LatentMoEConfig) -> Dict[str, Any]:
    """Everything whole on every device: the family is served on one
    chip, which holds its share of a deployment's experts already."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), config))
    return jax.tree_util.tree_map(lambda a: P(*[None] * a.ndim), shapes)


def init_params(rng: jax.Array, config: LatentMoEConfig) -> Dict[str, Any]:
    """``dense`` and ``routed``: each kind's layers stacked, in
    ``param_dtype``; the router float32; every norm's gain 1."""
    c = config
    dense = make_dense_init(c)
    keys = iter(jax.random.split(rng, 32))

    def common(L):
        out = {name: jnp.ones((L, getattr(c, width)), c.param_dtype)
               for name, width in NORMS.items()}
        for name, (shape, fan_in) in _attn_shapes(c).items():
            out[name] = dense(next(keys), (L, *shape), fan_in)
        return out

    Ld, Lr, D, E = c.n_dense_layers, c.n_routed_layers, c.dim, c.n_held
    params = {
        "embed": dense(next(keys), (c.vocab_size, D), D),
        "dense": {
            **common(Ld),
            "w_gate": dense(next(keys), (Ld, D, c.ffn_dim), D),
            "w_up": dense(next(keys), (Ld, D, c.ffn_dim), D),
            "w_down": dense(next(keys), (Ld, c.ffn_dim, D), c.ffn_dim),
        },
        "routed": {
            **common(Lr),
            "router": jax.random.normal(
                next(keys), (Lr, D, c.n_experts), jnp.float32) / math.sqrt(D),
            "w_gate": dense(next(keys), (Lr, E, D, c.expert_dim), D),
            "w_up": dense(next(keys), (Lr, E, D, c.expert_dim), D),
            "w_down": dense(next(keys), (Lr, E, c.expert_dim, D),
                            c.expert_dim),
        },
        "final_norm": jnp.ones((D,), c.param_dtype),
        "lm_head": dense(next(keys), (D, c.vocab_size), D),
    }
    params["routed"].update(
        shared_gate=dense(next(keys), (Lr, D, c.shared_dim), D),
        shared_up=dense(next(keys), (Lr, D, c.shared_dim), D),
        shared_down=dense(next(keys), (Lr, c.shared_dim, D), c.shared_dim))
    return params


# -- the sublayers -----------------------------------------------------
def rope_cos_sin(c: LatentMoEConfig, pos: jax.Array):
    """cos and sin (..., rope_dim / 2) float32 at the positions ``pos``."""
    inv_freq = c.rope_theta ** -(
        np.arange(0, c.rope_dim, 2, dtype=np.float64) / c.rope_dim)
    freqs = pos[..., None].astype(jnp.float32) * jnp.asarray(
        inv_freq, jnp.float32)
    return jnp.cos(freqs), jnp.sin(freqs)


def latent_q(c: LatentMoEConfig, h, layer, cos, sin):
    """h (B, T, D) -> the heads' queries (q_nope (B, T, H, nope), q_rope
    (B, T, H, rope) turned)."""
    with jax.named_scope("latent_q"):
        cq = rms_norm(h @ layer["wdq"].astype(c.dtype), layer["q_norm"],
                      c.norm_eps)
        q = jnp.einsum("btr,rhk->bthk", cq, layer["wuq"].astype(c.dtype))
        return q[..., :c.nope_dim], apply_rope(q[..., c.nope_dim:], cos, sin)


def latent_kv(c: LatentMoEConfig, h, layer, cos, sin):
    """h (B, T, D) -> (B, T, kv_rank + rope_dim): a row's normed latent
    and its turned rotary key, as the cache keeps them."""
    with jax.named_scope("latent_kv"):
        ckv = h @ layer["wdkv"].astype(c.dtype)
        latent = rms_norm(ckv[..., :c.kv_rank], layer["kv_norm"], c.norm_eps)
        k_rope = apply_rope(ckv[..., None, c.kv_rank:], cos, sin)[:, :, 0]
        return jnp.concatenate([latent, k_rope], axis=-1)


def _blocks(rows: int, block: int) -> int:
    """The block size that divides ``rows``: ``block`` or, where the
    cache is shorter or no multiple of it, what is."""
    block = min(block, rows)
    while rows % block:
        block //= 2
    return block


def _stack_reader(stack, layer, first, B: int):
    """``read(start, size)`` over the cache's two stacks: rows ``[start,
    start + size)`` of layer ``layer`` and sequences ``first .. first +
    B`` (latent rows (B, size, kv_rank), rotary keys (B, rope, size)),
    the block alone out of the layers' stacks."""
    latents, keys = stack

    def read(start, size):
        return (jax.lax.dynamic_slice(
                    latents, (layer, first, start, 0),
                    (1, B, size, latents.shape[3]))[0],
                jax.lax.dynamic_slice(
                    keys, (layer, first, 0, start),
                    (1, B, keys.shape[2], size))[0])
    return read


def attend_expanded(c: LatentMoEConfig, q_nope, q_rope, stack, index, first,
                    S: int, start_pos, layer):
    """The prefill form. q_nope (B, T, H, nope), q_rope (B, T, H, rope),
    sequence b's T rows at the positions ``start_pos[b] ..``; ``stack``
    the cache's two stacks (latent (L, B', max_seq, kv_rank), rope_key
    (L, B', rope, max_seq)) of which layer ``index``, sequences ``first
    .. first + B`` and the rows ``[0, S)`` are read, the call's own among
    them; ``layer`` holds ``wuk`` and ``wuv`` -> (B, T, H, v) in the
    compute type. Which of the two implementations runs follows from
    the shapes alone: ``ops/pallas_latent_attention.py``'s kernel where
    it can tile them, ``attend_expanded_blockwise`` elsewhere."""
    # imported beside whatever followed this module's own import
    # (``_import_kernel``); waits here for what is left of it
    from ray_tpu.ops import pallas_latent_attention as kernel

    B, T = q_nope.shape[:2]
    wuk, wuv = layer["wuk"].astype(c.dtype), layer["wuv"].astype(c.dtype)
    with jax.named_scope("latent_q"):
        # head-major, as the kernel takes them: the turn is folded into
        # the fusions that make the queries
        by_head = q_nope.transpose(0, 2, 1, 3), q_rope.transpose(0, 2, 1, 3)
    if kernel.untileable(*by_head, *stack, wuk, wuv, S) is None:
        with jax.named_scope("attn_latent_prefill"):
            return kernel.latent_prefill_attention(
                *by_head, *stack, wuk, wuv, layer=index, slot=first,
                start_pos=start_pos, rows=S,
                scale=1.0 / math.sqrt(c.nope_dim + c.rope_dim))
    pos = start_pos[:, None] + jnp.arange(T)[None, :]
    return attend_expanded_blockwise(
        c, q_nope, q_rope, _stack_reader(stack, index, first, B), S, pos,
        layer)


def attend_expanded_blockwise(c: LatentMoEConfig, q_nope, q_rope, read,
                              S: int, pos, layer):
    """The prefill form in ``jax.numpy``, for the shapes the kernel
    cannot tile and as its numerical reference. q_nope (B, T, H, nope),
    q_rope (B, T, H, rope) at the positions ``pos`` (B, T); ``read(
    start, size)`` gives rows ``[start, start + size)`` of the
    sequences' ``S`` cache rows (latent rows (B, size, kv_rank), rotary
    keys (B, rope, size)), the call's own among them -> (B, T, H, v) in
    the compute type. ``PREFILL_BLOCK`` rows of the cache at a time:
    their keys and values are made from the latent rows once, the
    chunk's rows attend to them ``PREFILL_TILE`` at a time, each tile
    with a running maximum and sum of its own that carry its softmax;
    the loop ends with the block that holds the call's last position."""
    B, T, H, _ = q_nope.shape
    block = _blocks(S, PREFILL_BLOCK)
    tile = _blocks(T, PREFILL_TILE)
    scale = 1.0 / math.sqrt(c.nope_dim + c.rope_dim)
    wuk, wuv = layer["wuk"].astype(c.dtype), layer["wuv"].astype(c.dtype)
    # a tile's queries and positions, cut out here and not once a block
    queries = [(q_nope[:, t:t + tile], q_rope[:, t:t + tile],
                pos[:, t:t + tile, None]) for t in range(0, T, tile)]

    def step(i, carry):
        with jax.named_scope("attn_latent_prefill"), \
                jax.named_scope("kv_slice"):
            rows, k_rope = read(i * block, block)
        with jax.named_scope("latent_expand"):
            # in a call of one tile the compiler makes these inside the
            # score's fusions, which carry ``attn_latent_prefill``: a
            # trace reads the two scopes together. Behind an optimization
            # barrier they are this scope's own and read a seventh of a
            # 256-row chunk call, which then takes 14-19 % longer
            # (PERF.md section 6, PR 48)
            k_nope = jnp.einsum("bsc,chk->bshk", rows, wuk)
            v = jnp.einsum("bsc,chk->bshk", rows, wuv)
        at = i * block + jnp.arange(block)
        out = []
        for (q_nope_t, q_rope_t, pos_t), (m, l, acc) in zip(queries, carry):
            with jax.named_scope("attn_latent_prefill"):
                s = (jnp.einsum("bthk,bshk->bhts", q_nope_t, k_nope,
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("bthr,brs->bhts", q_rope_t, k_rope,
                                  preferred_element_type=jnp.float32)) * scale
                seen = at[None, None, :] <= pos_t              # (B, tile, blk)
                s = jnp.where(seen[:, None], s, -1e30)
                m_new = jnp.maximum(m, s.max(-1))
                p = jnp.exp(s - m_new[..., None])
                fade = jnp.exp(m - m_new)
                out.append((m_new, l * fade + p.sum(-1),
                            acc * fade[..., None] + jnp.einsum(
                                "bhts,bshk->bhtk", p.astype(c.dtype), v,
                                preferred_element_type=jnp.float32)))
        return tuple(out)

    # row 0 is seen by every query, so the first block sets every
    # maximum and a masked score weighs exp(-1e30 - m) = 0 exactly
    blocks = jnp.minimum(pos.max() // block + 1, S // block)
    first = (jnp.full((B, H, tile), -1e30, jnp.float32),
             jnp.zeros((B, H, tile), jnp.float32),
             jnp.zeros((B, H, tile, c.v_dim), jnp.float32))
    done = jax.lax.fori_loop(0, blocks, step, (first,) * len(queries))
    with jax.named_scope("attn_latent_prefill"):
        return jnp.concatenate(
            [(acc / l[..., None]).astype(c.dtype).transpose(0, 2, 1, 3)
             for _, l, acc in done], axis=1)


def absorbed_blocks(c: LatentMoEConfig, stack, S: int, seen):
    """What the decode form fetches for a call whose lane b attends to
    its ``seen[b]`` leading rows (B,; 0: an idle lane) at the read window
    ``S`` -> (the rows of a block, the blocks each lane is taken
    through (B,)). On the kernel's path a lane's own count, none for a
    lane that attends to nothing; on the loop's the count of the
    longest, for every lane."""
    from ray_tpu.ops import pallas_latent_attention as kernel

    if kernel.decode_untileable(c.n_heads, c.rope_dim, *stack) is None:
        block = kernel.decode_block(stack[0])
        return block, jnp.minimum(-(-seen // block),
                                  stack[0].shape[2] // block)
    block = _blocks(S, DECODE_BLOCK)
    return block, jnp.broadcast_to(
        jnp.clip(-(-seen.max() // block), 1, S // block), seen.shape)


def attend_absorbed(c: LatentMoEConfig, q_nope, q_rope, stack, index, first,
                    S: int, pos, layer, blocks):
    """The decode form, one query row a sequence. q_nope (B, 1, H,
    nope), q_rope (B, 1, H, rope) at ``pos`` (B, 1); ``stack``, ``index``,
    ``first`` and ``S`` as ``attend_expanded`` takes them; ``blocks``
    (B,) of ``absorbed_blocks``: the blocks each lane is taken through
    -> (B, 1, H, v). ``Wuk`` is folded into the query and ``Wuv`` into
    the output, so the heads attend to the latent rows themselves, a
    block at a time. Which of the two implementations runs follows from
    the shapes alone: ``ops/pallas_latent_attention.py``'s decode
    kernel where it can tile them (each lane through its own blocks, a
    lane of none given zeros), ``attend_absorbed_blockwise`` elsewhere
    (every lane through the longest's)."""
    from ray_tpu.ops import pallas_latent_attention as kernel

    with jax.named_scope("attn_latent_decode"):
        q = jnp.einsum("bhk,chk->bhc", q_nope[:, 0],
                       layer["wuk"].astype(c.dtype))
        if kernel.decode_untileable(c.n_heads, c.rope_dim, *stack) is None:
            mixed = kernel.latent_decode_attention(
                q, q_rope[:, 0], *stack, layer=index, slot=first,
                pos=pos[:, 0], blocks=blocks,
                scale=1.0 / math.sqrt(c.nope_dim + c.rope_dim))
        else:
            mixed = attend_absorbed_blockwise(
                c, q, q_rope[:, 0], _stack_reader(stack, index, first,
                                                  q.shape[0]),
                S, pos, blocks.max())
        return jnp.einsum("bhc,chk->bhk", mixed,
                          layer["wuv"].astype(c.dtype))[:, None]


def attend_absorbed_blockwise(c: LatentMoEConfig, q, q_rope, read, S: int,
                              pos, blocks):
    """The decode form's attention in ``jax.numpy``, for the shapes the
    kernel cannot tile and as its numerical reference. q (B, H, kv_rank)
    the folded queries, q_rope (B, H, rope), at ``pos`` (B, 1); ``read``
    and ``S`` as ``attend_expanded_blockwise`` takes them -> the mixed
    latent rows (B, H, kv_rank). ``DECODE_BLOCK`` rows at a time, all
    lanes through the ``blocks`` leading blocks."""
    B, H, _ = q.shape
    block = _blocks(S, DECODE_BLOCK)
    scale = 1.0 / math.sqrt(c.nope_dim + c.rope_dim)

    def step(i, carry):
        m, l, acc = carry
        with jax.named_scope("kv_slice"):
            rows, k_rope = read(i * block, block)
        s = (jnp.einsum("bhc,bsc->bhs", q, rows,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhr,brs->bhs", q_rope, k_rope,
                          preferred_element_type=jnp.float32)) * scale
        at = i * block + jnp.arange(block)
        s = jnp.where((at[None, :] <= pos)[:, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        fade = jnp.exp(m - m_new)
        l = l * fade + p.sum(-1)
        acc = acc * fade[..., None] + jnp.einsum(
            "bhs,bsc->bhc", p.astype(c.dtype), rows,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, blocks, step,
        (jnp.full((B, H), -1e30, jnp.float32),
         jnp.zeros((B, H), jnp.float32),
         jnp.zeros((B, H, c.kv_rank), jnp.float32)))
    return (acc / l[..., None]).astype(c.dtype)


def attn_out(c: LatentMoEConfig, x, attn, layer):
    """The heads' results through ``Wo``, the post-norm, the residual."""
    with jax.named_scope("attn_out"):
        out = jnp.einsum("bthk,hkd->btd", attn, layer["wo"].astype(c.dtype))
    return x + rms_norm(out, layer["attn_post_norm"], c.norm_eps)


def dense_mlp(c: LatentMoEConfig, x, layer):
    with jax.named_scope("mlp"):
        h = rms_norm(x, layer["mlp_norm"], c.norm_eps)
        gate = h @ layer["w_gate"].astype(c.dtype)
        up = h @ layer["w_up"].astype(c.dtype)
        out = (jax.nn.silu(gate) * up) @ layer["w_down"].astype(c.dtype)
        return x + rms_norm(out, layer["mlp_post_norm"], c.norm_eps)


def moe_mlp(c: LatentMoEConfig, x, layer, experts, index, live=None):
    """The expert layer between its two norms + residual -> (x, counts
    int32[5]: ``ops/moe.py``'s four and every live row's assignments,
    held or not). ``layer``: this layer's norms, router and shared
    expert; ``experts``: every routed layer's held experts, stacked, of
    which this layer is ``index`` (``ops/moe.py`` ``expert_ffn`` says
    why the stack goes whole)."""
    with jax.named_scope("moe"):
        h = rms_norm(x, layer["mlp_norm"], c.norm_eps)
        out, counts = moe.moe_ffn_dropless(
            {"router": layer["router"],
             **{k: layer[k].astype(c.dtype) for k in SHARED_WEIGHTS},
             **{k: w.astype(c.dtype) for k, w in experts.items()}},
            h, c.moe, layer=index, live=live)
        rows = (math.prod(x.shape[:-1]) if live is None
                else jnp.broadcast_to(live, x.shape[:-1]).sum())
        counts = jnp.concatenate([counts, jnp.reshape(
            rows * c.experts_per_token, (1,)).astype(jnp.int32)])
        return x + rms_norm(out, layer["mlp_post_norm"], c.norm_eps), counts


# -- the cache ---------------------------------------------------------
def init_cache(config: LatentMoEConfig, batch: int, max_seq: int,
               chunk: Optional[int] = None):
    """``latent`` (L, B, max_seq, kv_rank) and ``rope_key`` (L, B,
    rope_dim, max_seq) in the compute type, a row a position;
    ``counts``: the device words of ``COUNTERS``."""
    del chunk
    c = config
    return {"latent": jnp.zeros((c.n_layers, batch, max_seq, c.kv_rank),
                                c.dtype),
            "rope_key": jnp.zeros((c.n_layers, batch, c.rope_dim, max_seq),
                                  c.dtype),
            "counts": decoder.counter_words(len(COUNTERS))}


def attn_rows_read(config: LatentMoEConfig, cache, rows: int) -> int:
    """Cache rows a sequence one call may read for attention at the read
    window ``rows``: every layer's bound is the window (both forms stop
    at the block of a sequence's last position, which the device
    counters see and this host-side count does not)."""
    del config, cache
    return rows


# what one cache shard's programs have counted
read_counters = partial(decoder.read_counters, names=COUNTERS)


def _write_rows(stack, new, layer, first, start_pos):
    """``new`` (B, T, kv_rank + rope) into the two stacks (latent (L,
    B', S, kv_rank), rope_key (L, B', rope, S)) at layer ``layer``:
    sequence b's T rows from row ``start_pos[b]`` of cache row ``first +
    b``, and nothing else."""
    latents, keys = stack
    rank = latents.shape[3]
    rows = new[..., :rank].astype(latents.dtype)
    turned = new[..., rank:].astype(keys.dtype).swapaxes(1, 2)
    for b in range(new.shape[0]):
        latents = jax.lax.dynamic_update_slice(
            latents, rows[None, b:b + 1], (layer, first + b, start_pos[b], 0))
        keys = jax.lax.dynamic_update_slice(
            keys, turned[None, b:b + 1], (layer, first + b, 0, start_pos[b]))
    return latents, keys


def forward_with_cache(
    params: Dict[str, Any],
    tokens: jax.Array,
    cache: Dict[str, Any],
    start_pos: jax.Array,
    config: LatentMoEConfig,
    *,
    slot: Optional[jax.Array] = None,
    logits_at: Optional[jax.Array] = None,
    rows: Optional[int] = None,
):
    """``llama.forward_with_cache``'s signature and meaning (tokens
    (B, T) appended at ``start_pos`` (B,), ``slot``, ``logits_at``,
    ``rows``) over this family's cache (``init_cache``). T of 1 is a
    decode and attends in the absorbed form, T over 1 a chunk and
    attends in the expanded one; ``rows`` bounds either's read. The
    cache and the counters ride in the carries of the two layer scans
    and are updated in place under a jit that donates the cache.
    ``cache`` may be a tuple of several shards' caches
    (``models/decoder.py``); what the call counts goes into the first
    one's words."""
    c = config
    caches, back = decoder.caches_of(cache)
    call = decoder.Call(tokens, start_pos, caches[0]["latent"].shape[2],
                        slot=slot, logits_at=logits_at, rows=rows,
                        shards=len(caches))
    T, pos, first = call.T, call.pos, call.first
    x = decoder.embed(params, tokens, c)
    cos, sin = rope_cos_sin(c, pos)
    live = call.live()
    seen_by_live = jnp.where(live, pos + 1, 0)       # rows a live row sees
    zero = jnp.int32(0)
    if T == 1:
        # the blocks of rows each lane is taken through, a live lane's
        # own or the longest's for all, as the shapes decide
        block, blocks = absorbed_blocks(
            c, (caches[0]["latent"], caches[0]["rope_key"]), call.window,
            seen_by_live[:, 0])
        attended = jnp.stack([zero, zero, seen_by_live.sum(),
                              blocks.sum() * block])
    else:
        blocks = None
        attended = jnp.stack([seen_by_live.sum(),
                              seen_by_live.max(axis=1).sum(), zero, zero])
    attended = attended.astype(jnp.int32)

    def attention(x, shards, layer, i):
        """-> (x, the shards' stacks with layer i's new rows)."""
        def attend(part, latents, q_nope, q_rope, new, blocks):
            with jax.named_scope("kv_write"):
                latents = _write_rows(latents, new, i, first, part.start_pos)
            if T == 1:
                attn = attend_absorbed(c, q_nope, q_rope, latents, i, first,
                                       call.window, part.pos, layer, blocks)
            else:
                attn = attend_expanded(c, q_nope, q_rope, latents, i, first,
                                       call.window, part.start_pos, layer)
            return attn, latents

        with jax.named_scope("attn"):
            h = rms_norm(x, layer["attn_norm"], c.norm_eps)
            q_nope, q_rope = latent_q(c, h, layer, cos, sin)
            new = latent_kv(c, h, layer, cos, sin)
            attn, shards = call.by_shard(attend, shards, q_nope, q_rope, new,
                                         blocks)
            return attn_out(c, x, attn, layer), shards

    def dense_step(x, shards, layer, i):
        x, shards = attention(x, shards, layer, i)
        return dense_mlp(c, x, layer), shards, None

    scanned, experts = moe.split_experts(params["routed"])

    def routed_step(x, shards, layer, i):
        x, shards = attention(x, shards, layer, c.n_dense_layers + i)
        x, counted = moe_mlp(c, x, layer, experts, i, live)
        return x, shards, counted

    x, shards, _ = decoder.scan_layers(
        dense_step, x,
        tuple((each["latent"], each["rope_key"]) for each in caches),
        params["dense"])
    x, shards, counted = decoder.scan_layers(
        routed_step, x, shards, scanned, 5)
    with jax.named_scope("layers"):     # counted beside the scans
        counted = jnp.concatenate([counted, attended * c.n_layers])
    with jax.named_scope("head"):
        # accumulated in float32, where the other families round the
        # product to the compute type and widen (ROADMAP Queue 3 item 1)
        x = decoder.final_rows(params, x, c, logits_at)
        logits = jnp.einsum("btd,dv->btv", x, params["lm_head"].astype(c.dtype),
                            preferred_element_type=jnp.float32)
    return logits, back(tuple(
        {"latent": latents, "rope_key": keys, "counts": words}
        for (latents, keys), words in zip(
            shards, decoder.folded(caches, counted))))


def _import_kernel():
    from ray_tpu.ops import pallas_latent_attention  # noqa: F401


# Pallas takes 1.2 s to import on a replica's host, a chunk or decode
# program's first trace needs it, and ``setup_s`` is a metric with a
# bound. A process that imports this module to serve goes on to open its
# chip, nine seconds in which Python has nothing to do: the import runs
# beside that, and the two forms' own imports find it done (or wait on
# the module's lock for the rest). PERF.md section 6, PR 50.
threading.Thread(target=_import_kernel, name="import-latent-kernel",
                 daemon=True).start()
