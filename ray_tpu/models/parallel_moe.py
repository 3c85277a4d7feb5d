"""Decoders whose block is parallel: one LayerNorm a layer feeds
grouped-query attention and a routed feed-forward side by side, and both
are added to the stream the layer began with (Cohere's Command family
with experts; ``model_type`` cohere2_moe):

    h = LN(x);  x = x + Attn(h) + Moe(h)

``layer_types`` says of each layer whether its attention is **sliding**
(row i attends to rows j with 0 <= i - j < ``sliding_window``, queries
and keys turned by position over ADJACENT pairs of dimensions,
``llama.apply_rope_pairs``) or **full** (every earlier row and no
position term of any kind: nothing is turned). ``Moe(h)`` is
``ops/moe.py``'s dropless layer: sigmoid scores over all ``n_experts``,
the ``experts_per_token`` largest renormalised, the experts in
``held_experts`` computed and the others' part left out, plus the MEAN
of ``n_shared_experts`` shared experts that every row meets (their
weights side by side along the width, the sum scaled by 1 / n). The
final norm is a LayerNorm too, and the head is the embedding (tied),
times ``logit_scale``. Served through ``llm/_internal/engine.py`` as the
other families are; not trained.

**Nothing of the cache is written here.** The ring of a sliding layer,
the rows by position of a full one, the period scan and the call over
them are ``models/window_moe.py``'s (``cached_periods``), handed this
family's block in the place of its own; the projections are
``llama.attention_mix``'s and the experts ``window_moe.moe_mix``'s, each
given the one normed input. Which form a call's attention takes is
``cached_periods``' choice by the call's shapes, as for its own family:
at the published widths a chunk call attends tile by tile on the chip
(``ops/pallas_chunk_attention.py``; at 128 query heads a 1024-row
chunk's float32 scores against 8192 cache rows would be 4.3 GB a
layer).

**Counters** (``COUNTERS``, in the cache's ``counts``): the ``moe_*``
three of ``EngineStats`` (held experts only), ``moe_held_slabs`` (the
passes ``ops/moe.py`` made over a slab of the held experts'
assignments: one a layer a chunk call whose held share fits the slab,
none in a decode call), ``moe_assignments_all``
(every live row's ``experts_per_token``, so the held share of the
routing is read and not assumed), and ``window_moe.ATTN_COUNTERS``,
which ``cached_periods`` counts of the sliding layers' attention:
``attn_window_pairs_scored`` (the (live query row, ring slot) pairs a
call computed a score for) and ``attn_window_pairs_visible`` (those of
them inside the query's window: what a banded read would keep), each
summed over layers and calls.

**What the engine's chunk rule is told** (``chunk_terms``): every row
meets the attention projections and the shared experts, and the rows
that pay for reading those are the chip's ridge; the held experts are
read once a call beside them whatever its rows (a held expert sees
``experts_per_token x held / n_experts`` of a call's rows a row, one in
sixteen at 16 of 128 held: rows enough to pay for ITS read would be
3840, a ring of 8192 slots and a chunk that holds every other lane's
decode for a second), so their bytes stand as ``read_beside``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import moe

from . import decoder, window_moe
from .decoder import layer_norm
from .llama import (
    _attention_cached,
    apply_rope_pairs,
    attention_mix,
    make_dense_init,
)
from .window_moe import FULL, SLIDING, WindowMoEConfig


@dataclasses.dataclass(frozen=True)
class ParallelMoEConfig(WindowMoEConfig):
    # ``full_rope`` means nothing here (a full layer is not turned);
    # ``norm_eps`` is the LayerNorms'
    # the ids, among the router's ``n_experts``, of the experts this
    # chip holds, in the order their weights are stacked
    held_experts: Tuple[int, ...] = tuple(range(64))
    # shared experts of ``expert_dim`` each, averaged
    n_shared_experts: int = 4
    logit_scale: float = 1.0

    model_module = "ray_tpu.models.parallel_moe"

    @property
    def n_held(self) -> int:
        return len(self.held_experts)

    @property
    def shared_dim(self) -> int:
        return self.n_shared_experts * self.expert_dim

    @property
    def moe(self) -> moe.MoEConfig:
        return moe.MoEConfig(
            d_model=self.dim, d_ff=self.expert_dim, n_experts=self.n_experts,
            k=self.experts_per_token, norm_topk_prob=self.norm_topk_prob,
            scoring="sigmoid", held=self.held_experts,
            shared_scale=1.0 / self.n_shared_experts)


PARALLEL_MOE_TINY = ParallelMoEConfig(
    vocab_size=512, dim=64, n_layers=4, n_heads=8, n_kv_heads=2,
    head_size=16, ffn_dim=0, max_seq_len=256, rope_theta=10000.0,
    remat=False, sliding_window=16, n_experts=16, experts_per_token=4,
    expert_dim=32, held_experts=(4, 5, 6, 7), n_shared_experts=2,
)

COUNTERS = (*window_moe.MOE_COUNTERS, "moe_held_slabs",
            "moe_assignments_all", *window_moe.ATTN_COUNTERS)


def chunk_terms(config: ParallelMoEConfig, max_seq: int) -> Dict[str, float]:
    """What ``engine.derived_prefill_chunk`` is told beside the chip
    (the module docstring says why in this form): the held experts'
    parameters over those every row meets (attention, the shared
    experts, the tied head)."""
    del max_seq
    c = config
    attention = 2 * c.dim * c.head_dim * (c.n_heads + c.n_kv_heads)
    every_row = (c.n_layers * (attention + 3 * c.dim * c.shared_dim)
                 + c.dim * c.vocab_size)
    held = c.n_layers * c.n_held * 3 * c.dim * c.expert_dim
    return {"read_beside": held / every_row}


# -- parameters --------------------------------------------------------
def init_params(rng: jax.Array, config: ParallelMoEConfig) -> Dict[str, Any]:
    """Stacked-layer parameters in ``param_dtype``, the router float32,
    every gain 1; ``embed`` is the head too."""
    c = config
    dense = make_dense_init(c)
    keys = iter(jax.random.split(rng, 12))
    L, D, H, KVH, hd = c.n_layers, c.dim, c.n_heads, c.n_kv_heads, c.head_dim
    E, F, Fs = c.n_held, c.expert_dim, c.shared_dim
    return {
        "embed": dense(next(keys), (c.vocab_size, D), D),
        "blocks": {
            "norm": jnp.ones((L, D), c.param_dtype),
            "wq": dense(next(keys), (L, D, H, hd), D),
            "wk": dense(next(keys), (L, D, KVH, hd), D),
            "wv": dense(next(keys), (L, D, KVH, hd), D),
            "wo": dense(next(keys), (L, H, hd, D), H * hd),
            "router": jax.random.normal(
                next(keys), (L, D, c.n_experts), jnp.float32) / D ** 0.5,
            "w_gate": dense(next(keys), (L, E, D, F), D),
            "w_up": dense(next(keys), (L, E, D, F), D),
            "w_down": dense(next(keys), (L, E, F, D), F),
            "shared_gate": dense(next(keys), (L, D, Fs), D),
            "shared_up": dense(next(keys), (L, D, Fs), D),
            # fan-in of ONE shared expert: the stack is their sum
            "shared_down": dense(next(keys), (L, Fs, D), F),
        },
        "final_norm": jnp.ones((D,), c.param_dtype),
    }


def param_specs(config: ParallelMoEConfig) -> Dict[str, Any]:
    """Everything whole on every device: the family is served on one
    chip, which holds its share of a deployment's experts already."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), config))
    return jax.tree_util.tree_map(lambda a: P(*[None] * a.ndim), shapes)


# -- the block ---------------------------------------------------------
def parallel_block(c: ParallelMoEConfig, pos, kind, x, layer, mixer, experts,
                   index, live):
    """One layer: the one norm, then attention and the experts on its
    output, both added to the ``x`` the layer began with -> (x, counts
    int32[5]: ``ops/moe.py``'s four and every live row's assignments,
    held or not)."""
    with jax.named_scope("block_norm"):
        h = layer_norm(x, layer["norm"], c.norm_eps)
    with jax.named_scope("attn"):
        cos, sin = (window_moe.rope_cos_sin(c, kind, pos)
                    if kind == SLIDING else (None, None))
        attn = attention_mix(c, h, layer, cos, sin, mixer,
                             rotate=apply_rope_pairs)
    with jax.named_scope("moe"):
        out, counts = window_moe.moe_mix(c, h, layer, experts, index, live)
        rows = (x.shape[0] * x.shape[1] if live is None
                else jnp.broadcast_to(live, x.shape[:-1]).sum())
        counts = jnp.concatenate([counts, jnp.reshape(
            rows * c.experts_per_token, (1,)).astype(jnp.int32)])
    return x + attn + out, counts


def head(params, x, c: ParallelMoEConfig, logits_at=None):
    """-> float32 logits: the final LayerNorm, then the embedding as the
    head, accumulated in float32, times ``logit_scale``."""
    with jax.named_scope("head"):
        x = decoder.final_rows(params, x, c, logits_at, norm=layer_norm)
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(c.dtype),
                            preferred_element_type=jnp.float32)
        return logits if c.logit_scale == 1.0 else logits * c.logit_scale


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: ParallelMoEConfig) -> jax.Array:
    """tokens (B, S) int32 -> logits (B, S, V) float32: whole sequences,
    XLA attention under each layer's own mask, no cache."""
    c = config
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    behind = pos[:, :, None] - jnp.arange(S)[None, None, :]   # i - j
    masks = {FULL: behind >= 0,
             SLIDING: (behind >= 0) & (behind < c.sliding_window)}

    def attend(kind, i, state, q, k, v):
        return _attention_cached(
            q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), pos, c,
            mask=masks[kind]), state

    x, _, _ = window_moe.scan_periods(
        c, params["blocks"], decoder.embed(params, tokens, c), pos, attend,
        block=parallel_block, n_counted=5)
    return head(params, x, c)


# -- the cache: window_moe's, with this family's counters --------------
init_cache = partial(window_moe.init_cache, counters=len(COUNTERS))
attn_rows_read = window_moe.attn_rows_read
read_counters = partial(decoder.read_counters, names=COUNTERS)


def forward_with_cache(
    params: Dict[str, Any],
    tokens: jax.Array,
    cache: Dict[str, Any],
    start_pos: jax.Array,
    config: ParallelMoEConfig,
    *,
    slot: Optional[jax.Array] = None,
    logits_at: Optional[jax.Array] = None,
    rows: Optional[int] = None,
):
    """``window_moe.forward_with_cache``'s signature, meaning and cache,
    with this family's block and the tied head."""
    c = config
    caches, back = decoder.caches_of(cache)
    call = decoder.Call(tokens, start_pos, caches[0]["full"]["k"].shape[3],
                        slot=slot, logits_at=logits_at, rows=rows,
                        shards=len(caches))
    x, shards, counted = window_moe.cached_periods(
        c, params["blocks"], decoder.embed(params, tokens, c), call, caches,
        block=parallel_block, n_counted=5)
    return head(params, x, c, logits_at), back(
        window_moe.new_caches(caches, shards, counted))
