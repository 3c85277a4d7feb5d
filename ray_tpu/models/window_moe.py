"""Decoders whose layers follow a pattern and whose feed-forward is
routed: every layer is pre-norm GQA attention and a dropless top-k
mixture of SwiGLU experts, and ``layer_types`` says of each layer whether
its attention is **sliding** (row i attends to rows j with
0 <= i - j < ``sliding_window``, rotary table at ``rope_theta``) or
**full** (every earlier row, the table of ``full_rope``: YaRN where it
is given). Served through ``llm/_internal/engine.py`` as the llama family
is; not trained (``ops/moe.py`` has no backward pass of the dropless
layer).

Written on ``models/decoder.py`` (the call's rows, embedding and head,
the layer scan, the counters' words) and on ``models/llama.py``'s
attention sublayer, whose mixer here writes and reads this family's
cache: a full layer is the llama family's cached attention at a head
size of its own, a sliding layer the same einsums under another mask.
A prefill chunk whose shapes tile (the published widths: 8 query heads
a key/value head of 128, a ring of 3072 slots, chunks of 512 to 2048
rows) attends through ``ops/pallas_chunk_attention.py`` instead, the
same arithmetic a tile at a time with the blocks of slots no row of the
tile sees skipped (``cached_periods`` chooses by the shapes, for this
family and ``models/parallel_moe.py`` alike).

**The cache is not one pair of stacks.** A full layer keeps rows by
position, ``(Lf, B, KVH, max_seq, hd)`` as the llama family does. A
sliding layer keeps a ring of ``ring`` = ``sliding_window`` + the most
rows a call writes (rounded up to a multiple of 8): position p lives in
slot p mod ring, and which position a slot holds follows from the
call's own ``start_pos`` and rows alone (the largest position at or
before the call's last row that is congruent to the slot), so nothing a
slot held before a sequence began is ever read, and rows a padded chunk
wrote behind its real tokens fall outside every live query's window
until the sequence overwrites them (that is what the ring's extra chunk
of rows is for). One slot more, ``ring`` itself, is the scratch row of
idle decode lanes (the idle position is no live sequence's): position
mod ring would land in a live row of a lane that is mid-prefill. Beside
the rows rides ``counts``, the words of ``COUNTERS`` accumulated on the
device: the ``moe_*`` three of ``EngineStats`` and, of the sliding
layers' attention, the pairs scored and the pairs visible
(``cached_periods``).

**What the engine's chunk rule is told.** The experts' three matrices
hold most of a layer's bytes (95 % at 64 experts of 3 x 2304 x 896
beside 21 M of attention projections and router), and a row multiplies
``experts_per_token`` of the ``n_experts`` of them, so an expert sees
that share of a call's rows. ``chunk_terms`` says so from the
configuration's two counts, and ``engine.derived_prefill_chunk``
divides the ridge's rows by it: a chunk then gives each expert the
rows that pay for reading it (8 of 64 on a v5e: 2048 rows, not 256).
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
from .llama import (
    LlamaConfig,
    _attention_cached,
    attention_sublayer,
    init_routed_params,
    write_and_read,
    write_rows,
)
from .llama import param_specs as dense_param_specs
from .rope import YarnRope, yarn_inv_freq  # noqa: F401  (this family's names for them)

SLIDING, FULL = "sliding", "full"
# a layer's shared expert (or several side by side), where it has one
SHARED_WEIGHTS = ("shared_gate", "shared_up", "shared_down")


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig(LlamaConfig):
    # one entry a layer, "sliding" or "full"; a whole number of periods
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 1024
    # the full layers' rotary table; None: the default table at
    # rope_theta, which is always the sliding layers'
    full_rope: Optional[YarnRope] = None
    n_experts: int = 64
    experts_per_token: int = 8
    expert_dim: int = 896
    norm_topk_prob: bool = True

    model_module = "ray_tpu.models.window_moe"

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"n_layers is {self.n_layers}")
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}")
        if self.n_layers % len(self.period):
            raise ValueError("layer_types is not a whole number of periods")

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern whose repetition begins ``layer_types``
        (the last repetition may be cut: __post_init__ refuses that)."""
        types = self.layer_types
        for n in range(1, len(types) + 1):
            if all(types[i] == types[i % n] for i in range(len(types))):
                return types[:n]
        return types

    @property
    def moe(self) -> moe.MoEConfig:
        return moe.MoEConfig(
            d_model=self.dim, d_ff=self.expert_dim, n_experts=self.n_experts,
            k=self.experts_per_token, norm_topk_prob=self.norm_topk_prob)


WINDOW_MOE_TINY = WindowMoEConfig(
    vocab_size=512, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
    head_size=32, ffn_dim=0, max_seq_len=256, rope_theta=10000.0,
    remat=False, sliding_window=16,
    full_rope=YarnRope(theta=10000.0, factor=4.0, original_max_position=64),
    n_experts=8, experts_per_token=2, expert_dim=32,
)


def chunk_terms(config: WindowMoEConfig, max_seq: int) -> Dict[str, float]:
    """What ``engine.derived_prefill_chunk`` is told beside the chip:
    the share of a call's rows that multiply one of the weights that
    hold most of its bytes, an expert's, which a row in
    ``experts_per_token / n_experts`` meets (dropless, so every
    assignment is computed). The projections beside the experts are
    past their own ridge well before, and a call does nothing once
    whatever its rows (``models/latent_moe.py`` answers for a family
    where neither holds)."""
    del max_seq
    return {"row_share": config.experts_per_token / config.n_experts}


# -- rotary tables -----------------------------------------------------
def rope_cos_sin(config: WindowMoEConfig, kind: str, pos: jax.Array):
    """cos and sin (..., hd/2) float32 at the positions ``pos`` for a
    layer of ``kind``; a YaRN table's are scaled by its attention
    factor."""
    hd = config.head_dim
    rope = config.full_rope if kind == FULL else None
    if rope is None:
        inv_freq = config.rope_theta ** -(
            np.arange(0, hd, 2, dtype=np.float64) / hd)
        scale = 1.0
    else:
        inv_freq = yarn_inv_freq(rope, hd)
        scale = rope.cos_sin_scale
    freqs = pos[..., None].astype(jnp.float32) * jnp.asarray(
        inv_freq, jnp.float32)
    return jnp.cos(freqs) * scale, jnp.sin(freqs) * scale


# -- parameters --------------------------------------------------------
def param_specs(config: WindowMoEConfig) -> Dict[str, Any]:
    """The llama attention shardings; the experts and the routers whole
    on every device, which is how ``moe.moe_ffn_dropless`` takes them
    under a mesh (its rows are split, its weights are not)."""
    specs = dense_param_specs(config)
    whole = P(None, None, None, None)
    specs["blocks"].update(router=P(None, None, None), w_gate=whole,
                           w_up=whole, w_down=whole)
    return specs


def init_params(rng: jax.Array, config: WindowMoEConfig) -> Dict[str, Any]:
    """Stacked-layer parameters in ``param_dtype``, the router float32."""
    return init_routed_params(rng, config, config.expert_dim)


# -- the sublayers -----------------------------------------------------
def moe_mix(c: WindowMoEConfig, h, layer, experts, index, live=None):
    """The routed feed-forward over a normed input ``h`` -> (what the
    sublayer adds, counts int32[4]: ``ops/moe.py``'s). ``layer``: this
    layer's router (and shared expert, where it has one); ``experts``:
    every layer's expert weights, stacked, of which this layer is
    ``index`` (the grouped matmul takes the stack whole: ``ops/moe.py``
    ``expert_ffn`` says why); ``live`` (B, T): the rows to count."""
    shared = {k: layer[k].astype(c.dtype) for k in SHARED_WEIGHTS
              if k in layer}
    return moe.moe_ffn_dropless(
        {"router": layer["router"], **shared,
         **{k: w.astype(c.dtype) for k, w in experts.items()}},
        h, c.moe, layer=index, live=live)


def moe_sublayer(c: WindowMoEConfig, x, layer, experts, index, live=None):
    """Pre-norm routed feed-forward + residual -> (x, counts int32[3]):
    ``moe_mix`` behind the layer's own norm."""
    with jax.named_scope("moe"):
        h = rms_norm(x, layer["mlp_norm"], c.norm_eps)
        out, counts = moe_mix(c, h, layer, experts, index, live)
        # every expert is held here: no slab of a share to count passes of
        return x + out, counts[:len(MOE_COUNTERS)]


def pre_norm_block(c: WindowMoEConfig, pos, kind, x, layer, mixer, experts,
                   index, live):
    """A layer of ``kind`` as this family has it: attention and the
    experts one after the other, each behind its own norm and adding its
    own residual -> (x, counts)."""
    with jax.named_scope("attn"):   # the table is the sublayer's
        cos, sin = rope_cos_sin(c, kind, pos)
    x = attention_sublayer(c, x, layer, cos, sin, mixer)
    return moe_sublayer(c, x, layer, experts, index, live)


def scan_periods(c: WindowMoEConfig, blocks, x, pos, attend, state=None,
                 live=None, block=pre_norm_block, n_counted: int = 3):
    """The layers over ``x`` (B, T, D) at the positions ``pos`` (B, T):
    ``decoder.scan_layers`` over whole periods, a period's layers
    unrolled inside it (the stacked ``blocks`` as (periods, layers a
    period, ...), but the experts' weights, which stay stacked as they
    are). ``attend(kind, i, state, q, k, v) -> (the attended rows,
    state)`` is the mixer of layer ``i`` among the layers of its
    ``kind``; ``live`` the rows the experts count; ``block(c, pos, kind,
    x, layer, mixer, experts, index, live) -> (x, counts int32[
    n_counted])`` is one layer, ``pre_norm_block`` or another family's
    -> (x, state, counts)."""
    period = c.period
    n = len(period)
    per = {kind: period.count(kind) for kind in (FULL, SLIDING)}
    scanned, experts = moe.split_experts(blocks)
    scanned = {k: a.reshape(c.n_layers // n, n, *a.shape[1:])
               for k, a in scanned.items()}

    def step(x, state, layers, p):
        seen = {FULL: 0, SLIDING: 0}
        counted = []
        for j, kind in enumerate(period):
            i = p * per[kind] + seen[kind]
            seen[kind] += 1

            def mixer(q, k, v, _):
                nonlocal state
                attn, state = attend(kind, i, state, q, k, v)
                return attn

            layer = jax.tree_util.tree_map(lambda a: a[j], layers)
            x, counts = block(c, pos, kind, x, layer, mixer, experts,
                              p * n + j, live)
            counted.append(counts)
        return x, state, sum(counted[1:], counted[0])

    return decoder.scan_layers(step, x, state, scanned, n_counted)


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: WindowMoEConfig) -> jax.Array:
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

    x, _, _ = scan_periods(c, params["blocks"],
                           decoder.embed(params, tokens, c), pos, attend)
    return decoder.head(params, x, c)


# -- the cache ---------------------------------------------------------
MOE_COUNTERS = ("moe_assignments", "moe_experts_touched", "moe_expert_slots")
# what ``cached_periods`` counts of the sliding layers' attention, behind
# whatever the family's layers count
ATTN_COUNTERS = ("attn_window_pairs_scored", "attn_window_pairs_visible")
COUNTERS = (*MOE_COUNTERS, *ATTN_COUNTERS)
# slots a ring has past its rows: the first is the idle lanes' scratch
# row, the rest keep the rows a multiple of 8
_SCRATCH_SLOTS = 8


def init_cache(config: WindowMoEConfig, batch: int, max_seq: int,
               chunk: int, counters: int = len(COUNTERS)):
    """``full``: k/v (full layers, B, KVH, max_seq, hd) by position;
    ``ring``: k/v (sliding layers, B, KVH, ring + _SCRATCH_SLOTS, hd),
    slot ``ring`` the idle lanes' scratch row; ``counts``: the device
    words of ``COUNTERS`` (``counters`` of them). ``chunk``: the most
    rows a call will write."""
    c = config
    n_full = c.layer_types.count(FULL)
    # the window and a chunk, to a multiple of 8; no more than the cache
    ring = -(-min(c.sliding_window + chunk, max_seq) // 8) * 8

    def stacks(layers, rows):
        shape = (layers, batch, c.n_kv_heads, rows, c.head_dim)
        return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}

    return {"full": stacks(n_full, max_seq),
            "ring": stacks(c.n_layers - n_full, ring + _SCRATCH_SLOTS),
            "counts": decoder.counter_words(counters)}


def attn_rows_read(config: WindowMoEConfig, cache, rows: int) -> float:
    """Cache rows a sequence one call reads for attention at the read
    window ``rows``, the layers' mean: a full layer's the window, a
    sliding layer's its ring."""
    ring = cache["ring"]["k"].shape[3] - _SCRATCH_SLOTS
    n_full = config.layer_types.count(FULL)
    n_ring = config.n_layers - n_full
    return (n_full * rows + n_ring * ring) / config.n_layers


# what the programs that wrote one cache shard have counted
read_counters = partial(decoder.read_counters, names=COUNTERS)


# jitted, so that the keys and the values of every sliding layer of a
# period trace and lower one writer between them (a lane's update is a
# dozen operations, a decode call has 16 or 32 lanes, and a replica's
# start pays for every one it traces); the compiler inlines the calls
@partial(jax.jit, static_argnames=("ring", "idle"))
def _ring_write(stack, new, layer, first, start_pos, *, ring: int, idle: int):
    """``new`` (B, T, KVH, hd) into the ring stack at layer ``layer``:
    sequence b's row t to slot (start_pos[b] + t) mod ring of cache row
    ``first + b``. One row (a decode) is one update, an idle lane's
    (position ``idle``) to the scratch slot. T rows may wrap, so they
    go in as two blocks of T slots, each read, merged and written back:
    the block that ends at the ring's end at the latest and the block at
    its start."""
    new = new.astype(stack.dtype).transpose(0, 2, 1, 3)      # (B, KVH, T, hd)
    B, KVH, T, hd = new.shape
    if T > ring:
        raise ValueError(f"a call of {T} rows into a ring of {ring}")
    at = jnp.arange(T)[None, :, None]                        # (1, T, 1)
    for b in range(B):
        row, lane, o = new[b], first + b, start_pos[b] % ring
        if T == 1:
            slot = jnp.where(start_pos[b] == idle, ring, o)
            stack = jax.lax.dynamic_update_slice(
                stack, row[None, None], (layer, lane, 0, slot, 0))
            continue
        before_wrap = jnp.minimum(T, ring - o)
        a0 = jnp.minimum(o, ring - T)
        blocks = (
            # slots [a0, a0 + T): new row i - (o - a0) from slot o on
            (a0, jnp.roll(row, o - a0, axis=1), at >= o - a0),
            # slots [0, T): new rows before_wrap, before_wrap + 1, ...
            (0, jnp.roll(row, -before_wrap, axis=1), at < T - before_wrap))
        for slot, rolled, fresh in blocks:
            old = jax.lax.dynamic_slice(
                stack, (layer, lane, 0, slot, 0), (1, 1, KVH, T, hd))
            stack = jax.lax.dynamic_update_slice(
                stack, jnp.where(fresh, rolled, old[0, 0])[None, None],
                (layer, lane, 0, slot, 0))
    return stack


def _ring_held(start_pos, T: int, ring: int, slots: int):
    """(held (B, slots) int32, ok (B, slots) bool): slot r holds the
    largest position at or before the call's last row that is congruent
    to r mod ring; ``ok`` where that is a position of the sequence (no
    earlier than 0) and the slot is one of the ring's (the scratch slots
    past ``ring`` hold nobody's)."""
    last = (start_pos + T - 1)[:, None]                          # (B, 1)
    r = jnp.arange(slots)[None, :]
    held = last - (last - r) % ring                              # (B, slots)
    return held, (r < ring) & (held >= 0)


def _ring_mask(pos, start_pos, T: int, ring: int, slots: int, window: int):
    """(B, T, slots): which slots of its ring each query attends to:
    those that hold (``_ring_held``) a position no later than the
    query's and inside its window."""
    held, ok = _ring_held(start_pos, T, ring, slots)
    behind = pos[:, :, None] - held[:, None, :]              # (B, T, slots)
    return ok[:, None, :] & (behind >= 0) & (behind < window)


def cached_periods(c: WindowMoEConfig, blocks, x, call: decoder.Call,
                   caches: tuple, *, block=pre_norm_block,
                   n_counted: int = 3):
    """The layers of one cached call over this family's caches (the
    ``init_cache`` of each shard) -> (x, every shard's stacks by kind
    with the call's rows written, what the call counted: the layers'
    ``n_counted`` words, then ``ATTN_COUNTERS``' two). A full layer
    reads the call's read window of rows by position, a sliding layer
    its ring; the stacks and what the layers count ride in the period
    scan's carry.

    **Which form of attention a call takes follows from its shapes.** A
    call of more than one row a sequence (a chunk) whose shapes the
    kernel tiles (``pallas_chunk_attention.untileable``: every published
    width) attends tile by tile on the chip, and no score of the chunk's
    rows x the cache's rows x the heads exists in HBM. A decode call,
    and a chunk at shapes that do not tile (the toy presets of most
    tests), score their rows against every row read
    (``llama._attention_cached`` under the same masks). Whichever family
    hands in its block gets the same choice.

    The two words counted here are, of the sliding layers, the (live
    query row, ring slot) pairs the call computed a score for, visible
    or masked (every slot of the ring for the whole matrix; the slots of
    the blocks a row's tile visits for the kernel), and those of them
    inside the row's window."""
    slots = caches[0]["ring"]["k"].shape[3]
    ring = slots - _SCRATCH_SLOTS
    ring_write = partial(_ring_write, ring=ring,
                         idle=decoder.idle_position(call.max_seq))
    chunk = None
    if call.T > 1:
        # imported where a chunk is traced (the module's import thread
        # has it by then): a decode program never needs it
        from ray_tpu.ops import pallas_chunk_attention as chunk
        if chunk.untileable(call.T, c.n_heads, c.n_kv_heads, c.head_dim,
                            (ring, call.window)) is not None:
            chunk = None
    if chunk is not None:
        scale = 1.0 / math.sqrt(c.head_dim)
        # a kind's rows read, its writer, its attention's scope, the
        # position each row read holds and the window behind a query
        held, ok = _ring_held(call.start_pos, call.T, ring, ring)
        reads = {
            FULL: (call.window, write_rows, "attn_cached",
                   jnp.broadcast_to(jnp.arange(call.window),
                                    (call.B, call.window)), chunk.NO_WINDOW),
            SLIDING: (ring, ring_write, "attn_window",
                      jnp.where(ok, held, chunk.NOT_HELD), c.sliding_window)}
        scored = chunk.scored_slots(reads[SLIDING][3], call.start_pos,
                                    call.T, c.sliding_window)

        def attention(part, q, k_c, v_c, held, window):
            return chunk.chunk_attention(
                q, k_c, v_c, held, part.start_pos, window=window, scale=scale)
    else:
        # a kind's rows read, its writer, its attention's scope and mask
        reads = {FULL: (call.window, write_rows, "attn_cached", None, None),
                 SLIDING: (slots, ring_write, "attn_window",
                           _ring_mask(call.pos, call.start_pos, call.T, ring,
                                      slots, c.sliding_window), None)}
        scored = jnp.full((call.B, call.T), slots, jnp.int32)

        def attention(part, q, k_c, v_c, mask, _):
            return _attention_cached(q, k_c, v_c, part.pos, c, mask=mask)

    def attend(kind, i, shards, q, k, v):
        n, write, scope, seen, window = reads[kind]

        def one(part, stacks, q, k, v, seen):
            k_c, v_c, new = write_and_read(
                stacks[kind], k, v, i, part, n, write)
            with jax.named_scope(scope):
                attn = attention(part, q, k_c, v_c, seen, window)
            return attn, {**stacks, kind: new}

        return call.by_shard(one, shards, q, k, v, seen)

    live = call.live()
    x, shards, counted = scan_periods(
        c, blocks, x, call.pos, attend,
        tuple({FULL: each["full"], SLIDING: each["ring"]}
              for each in caches), live, block, n_counted)
    with jax.named_scope("layers"):     # counted beside the scan
        # a live row sees the rows of its window that exist: every one
        # of them is in its ring (``init_cache``)
        visible = jnp.where(live, jnp.minimum(call.pos + 1, c.sliding_window),
                            0).sum()
        pairs = jnp.stack([jnp.where(live, scored, 0).sum(), visible])
        counted = jnp.concatenate([
            counted, (pairs * c.layer_types.count(SLIDING)).astype(jnp.int32)])
    return x, shards, counted


def forward_with_cache(
    params: Dict[str, Any],
    tokens: jax.Array,
    cache: Dict[str, Any],
    start_pos: jax.Array,
    config: WindowMoEConfig,
    *,
    slot: Optional[jax.Array] = None,
    logits_at: Optional[jax.Array] = None,
    rows: Optional[int] = None,
):
    """``llama.forward_with_cache``'s signature and meaning (tokens
    (B, T) appended at ``start_pos`` (B,), ``slot``, ``logits_at``,
    ``rows``) over this family's cache (``init_cache``). ``rows`` bounds
    the full layers' read and leaves the rings alone. The layer scan runs
    over whole periods, a period's layers unrolled inside it; the stacks
    and the counters ride in its carry and are updated in place under a
    jit that donates the cache. ``cache`` may be a tuple of several
    shards' caches (``models/decoder.py``): the experts then meet all
    the shards' rows at once and count them as one call's, into the
    first cache's words."""
    c = config
    caches, back = decoder.caches_of(cache)
    call = decoder.Call(tokens, start_pos, caches[0]["full"]["k"].shape[3],
                        slot=slot, logits_at=logits_at, rows=rows,
                        shards=len(caches))
    x, shards, counted = cached_periods(
        c, params["blocks"], decoder.embed(params, tokens, c), call, caches)
    return decoder.head(params, x, c, logits_at), back(
        new_caches(caches, shards, counted))


def new_caches(caches: tuple, shards: tuple, counted) -> tuple:
    """Every shard's cache behind a call: its stacks as the call left
    them, ``counted`` folded into the first's words."""
    return tuple(
        {"full": stacks[FULL], "ring": stacks[SLIDING], "counts": words}
        for stacks, words in zip(shards, decoder.folded(caches, counted)))


def _import_kernels():
    from ray_tpu.ops import pallas_chunk_attention  # noqa: F401
    from ray_tpu.ops import pallas_grouped_matmul  # noqa: F401


# Pallas takes 1.2 s to import on a replica's host, a chunk program's
# first trace needs it (``moe.expert_ffn``'s kernel and the chunk's
# attention), and ``setup_s`` is a metric with a bound. A process that
# imports this module to serve goes on to open its chip, and the import
# runs beside that, as ``models/latent_moe.py``'s does. It hides less
# than hoped: the opening read 1.2 s longer with the import beside it
# (PERF.md section 6, PR 53); what the thread saves is the first chunk
# trace's wait.
threading.Thread(target=_import_kernels, name="import-chunk-kernels",
                 daemon=True).start()
