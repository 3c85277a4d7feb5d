"""Pallas TPU kernel for the chunk form of cached grouped-query attention.

A prefill chunk's T rows attend to the rows their sequence's cache
holds. ``llama._attention_cached`` scores every (head, query row, cache
row) in float32 in HBM, which at 128 query heads, a 1024-row chunk and
8192 cache rows is 4.3 GB a layer (0.8 GB at 32 heads, 2048 rows and a
ring of 3080 slots). This kernel is the same arithmetic with a tile's
score, its running softmax and its accumulator kept in VMEM, and
``_attention_cached`` is its numerical reference
(tests/test_parallel_block.py, tests/test_window_moe.py). Which calls
take it is ``window_moe.cached_periods``' choice, by ``untileable``
alone: every family that serves through that function, at any shapes
that tile.

**One function for both kinds of cache row.** The caller says of every
cache slot which position it holds (``held`` (B, S) int32: a row by
position holds its own index; a ring's slot holds what
``window_moe._ring_held`` says; ``NOT_HELD`` where a slot holds nothing
a query of this call may see), and query row t of sequence b, at
position ``start_pos[b] + t``, attends to slot r iff ``0 <= position -
held[b, r] < window``. Rows by position and ring slots differ in
``held`` and in ``window`` alone.

Layout: q (B, T, H, hd) as the projections give it; k, v (B, KVH, S,
hd), the sequences' cache rows as ``llama.write_and_read`` reads them
out; -> (B, T, H, hd). Query head j reads key/value head ``j // (H //
KVH)``.

Design:
- Grid (sequence, key/value head, tile of ``_TILE`` query rows, block
  of ``_BLOCK`` cache rows), the blocks innermost. A grid step holds the
  tile's queries of ALL the G = H / KVH heads of the group (G x tile x
  hd) and one block of keys and values, which those G heads share: a
  block is fetched once for the group's scores (16 heads at
  command-a-plus's published widths, 8 at Mellum2's).
- **Blocks no query of the tile can see are skipped** (above the
  diagonal; behind the window; a ring's slots outside every query's
  window): a table of the visible (tile, block) pairs, made from
  ``held`` before the call, arrives by scalar prefetch, a skipped step
  runs nothing, and its index map names the block the last visible step
  fetched, so nothing is copied for it either.
- Inside a visible pair every (query, slot) score is computed in
  float32 and masked by the rule above; the row maxima are (tile, 1)
  columns and the row sums are kept a lane apart ((tile, 128), summed
  once at the end), as ``pallas_latent_attention`` found cheapest.
- Every query sees its own row (a call's rows are written before they
  are read), so a row's maximum becomes a real score at some block; a
  block in which a row sees nothing before that adds ``exp(0)`` terms to
  a sum that the first real score's ``exp(-1e30 - m) = 0`` wipes.
- ``_interpret`` is ``pallas_attention``'s: on the CPU the kernel runs
  interpreted, so tier-1 tests it at small tileable shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_attention as _flash

_LANES = 128
_MASKED = -1e30
# what ``held`` says of a slot no query of the call may see, and the
# ``window`` of a layer that has none: a position no sequence reaches
NOT_HELD = 1 << 30
NO_WINDOW = 1 << 30

_TILE = 512
_BLOCK = 512
_VMEM_LIMIT_BYTES = 64 * 2**20

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _divisor(size: int, preferred: int) -> int:
    """The largest of ``preferred``, its halves down to 128, that
    divides ``size`` (a multiple of 128)."""
    while size % preferred:
        preferred //= 2
    return preferred


def untileable(T: int, heads: int, kv_heads: int, head_dim: int,
               slots) -> str | None:
    """Why the kernel cannot take a call of ``T`` rows a sequence over
    caches of ``slots`` rows (several: every kind of layer's), or None
    when it can."""
    if head_dim % _LANES:
        return f"head_dim={head_dim} not a multiple of {_LANES} lanes"
    if heads % kv_heads:
        return f"{heads} query heads over {kv_heads} key/value heads"
    if T % _LANES or any(s % _LANES for s in slots):
        return (f"chunk rows {T} or cache rows {tuple(slots)} not "
                f"multiples of {_LANES}")
    return None


def visible_blocks(held, start_pos, T: int, window: int, tile: int,
                   block: int):
    """(B, T // tile, S // block) bool: the blocks of cache slots in
    which some query of a tile sees a slot."""
    B, S = held.shape
    first = start_pos[:, None] + jnp.arange(0, T, tile)[None, :]  # (B, nQ)
    at = held.reshape(B, 1, S // block, block)
    seen = ((at <= (first + tile - 1)[:, :, None, None])
            & (at > (first - window)[:, :, None, None]))
    return seen.any(-1)


def scored_slots(held, start_pos, T: int, window: int):
    """(B, T) int32: the cache slots each query row of such a call is
    scored against, visible or masked: those of the blocks its tile
    visits."""
    tile, block = _divisor(T, _TILE), _divisor(held.shape[1], _BLOCK)
    vis = visible_blocks(held, start_pos, T, window, tile, block)
    return jnp.repeat(vis.sum(-1).astype(jnp.int32) * block, tile, axis=1)


def _kernel(start_ref, vis_ref, fetch_ref, q_ref, k_ref, v_ref, held_ref,
            o_ref, m_scr, l_scr, acc_scr, *, scale, window, G, tile, block,
            nQ, nK):
    del fetch_ref                         # the index maps' alone
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _MASKED)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(vis_ref[(b * nQ + i) * nK + j] != 0)
    def _visible():
        position = (start_ref[b] + i * tile
                    + jax.lax.broadcasted_iota(jnp.int32, (tile, block), 0))
        behind = position - held_ref[0]               # (tile, block)
        seen = (behind >= 0) & (behind < window)
        keys, values = k_ref[0, 0], v_ref[0, 0]

        def head(g, carry):
            s = jax.lax.dot_general(
                q_ref[0, 0, g], keys, _NT,
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, _MASKED)
            m = m_scr[g]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            fade = jnp.exp(m - m_new)
            m_scr[g] = m_new
            l_scr[g] = l_scr[g] * fade + sum(
                p[:, n:n + _LANES] for n in range(0, block, _LANES))
            acc_scr[g] = acc_scr[g] * fade + jax.lax.dot_general(
                p.astype(values.dtype), values, _NN,
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, G, head, 0, unroll=True)

    @pl.when(j == nK - 1)
    def _finish():
        for g in range(G):
            o_ref[0, 0, g] = (
                acc_scr[g] / jnp.sum(l_scr[g], axis=1, keepdims=True)
            ).astype(o_ref.dtype)


def chunk_attention(q, k, v, held, start_pos, *, window: int, scale: float):
    """The module docstring's contract -> the attended rows (B, T, H,
    hd) in the queries' type. Raises NotImplementedError for shapes the
    kernel does not tile (``untileable``)."""
    B, T, H, hd = q.shape
    KVH, S = k.shape[1:3]
    reason = untileable(T, H, KVH, hd, (S,))
    if reason is not None:
        raise NotImplementedError(reason)
    return _call(q, k, v, held.astype(jnp.int32), start_pos.astype(jnp.int32),
                 window=int(window), scale=float(scale),
                 tile=_divisor(T, _TILE), block=_divisor(S, _BLOCK),
                 interpret=_flash._interpret())


# jitted, so that the layers of a period that share a kind of cache
# trace and lower one kernel between them
@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "tile", "block", "interpret"))
def _call(q, k, v, held, start_pos, *, window, scale, tile, block,
          interpret):
    B, T, H, hd = q.shape
    KVH, S = k.shape[1:3]
    G, nQ, nK = H // KVH, T // tile, S // block
    vis = visible_blocks(held, start_pos, T, window, tile, block)
    # a skipped step names the block the last visible step fetched (the
    # first visible one before any): the pipeline then copies nothing
    at = jnp.where(vis, jnp.arange(nK)[None, None, :], -1)
    fetch = jnp.maximum(jax.lax.cummax(at, axis=2),
                        jnp.argmax(vis, axis=2)[:, :, None])
    flat = lambda a: a.astype(jnp.int32).reshape(-1)
    # head-major, the G heads of a key/value head side by side
    grouped = q.transpose(0, 2, 1, 3).reshape(B, KVH, G, T, hd)
    rows = pl.BlockSpec((1, 1, G, tile, hd),
                        lambda b, h, i, j, *_: (b, h, 0, i, 0))

    def cached(b, h, i, j, start, vis, fetch):
        return (b, h, fetch[(b * nQ + i) * nK + j], 0)

    def slots(b, h, i, j, start, vis, fetch):
        return (b, 0, fetch[(b * nQ + i) * nK + j])

    pairs = B * H * T * S // 2
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, G=G,
                          tile=tile, block=block, nQ=nQ, nK=nK),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, KVH, nQ, nK),
            in_specs=[rows,
                      pl.BlockSpec((1, 1, block, hd), cached),
                      pl.BlockSpec((1, 1, block, hd), cached),
                      pl.BlockSpec((1, 1, block), slots)],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((G, tile, 1), jnp.float32),
                            pltpu.VMEM((G, tile, _LANES), jnp.float32),
                            pltpu.VMEM((G, tile, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, T, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * pairs * hd),
            bytes_accessed=int((2 * q.size + nQ * (k.size + v.size) // 2)
                               * q.dtype.itemsize),
            transcendentals=int(pairs)),
        interpret=interpret,
        name="chunk_attention",
    )(flat(start_pos), flat(vis), flat(fetch), grouped, k, v,
      held[:, None, :])
    return out.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
