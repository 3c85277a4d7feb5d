"""Pallas TPU kernel for the prefill (expanded) form of latent attention.

``models/latent_moe.py`` keeps one latent row and one turned rotary key
a token a layer; a prefill chunk makes the heads' keys and values from
the latent rows it attends to and scores its rows against them. This
kernel is that form with the score, the running softmax and the
accumulator kept in VMEM; ``latent_moe.attend_expanded`` dispatches to
it wherever ``untileable`` finds nothing against the shapes, and its
``jax.numpy`` block loop (the arithmetic and every rounding point of
which this file repeats) is the form of the shapes Mosaic cannot tile
and the numerical reference in tests/test_pallas_latent_attention.py.

Layout contract (what ``latent_moe.forward_with_cache`` holds):
    q_nope (B, H, T, nope), q_rope (B, H, T, rope), turned: head-major;
    latents (L, B', S, kv_rank), keys (L, B', rope, S): the cache's two
    stacks as they lie, of which the call reads layer ``layer``,
    sequences ``slot .. slot + B`` and rows ``[0, rows)``;
    wuk (kv_rank, H, nope), wuv (kv_rank, H, v);
    start_pos (B,): sequence b's T rows stand at ``start_pos[b] ..``
    -> (B, T, H, v) in the queries' type.
The queries come head-major and the two up-projections are turned so
here ((H, kv_rank, .)): a head's is then a whole tile under a leading
index a rolled loop can carry. The caller turns the queries where it
makes them (``latent_moe`` under its ``latent_q`` scope: the compiler
folds the turn into the rotary fusion and the query's slice, no op of
its own); the weights' turn is two small copies a layer. The
result leaves head-major too and is turned back here: the compiler's
matmul of ``attn_out`` wants its left operand laid out (H, T, v), takes
the kernel's as it lies, and ran at half its rate (2.35 ms a layer for
1.34) on a (B, T, H x v) result that it had to turn inside its fusion.

Design notes (PERF.md section 6, PR 50, has the chip readings behind
each choice; all on a v5e at the published widths, 128 heads of 128 +
64 / 128 over latent rows of 512, one 1024-row chunk at row 3072, a
layer: 4.1 ms where the block loop took 8):
- One call a layer a chunk call, grid (sequence, group of ``_HEADS``
  heads). **Resident** a grid step, through the pipeline's BlockSpecs:
  the group's queries (T rows), its slices of ``wuk`` and ``wuv``, its
  result; in scratch its float32 accumulators (T x v a head), maxima
  and sums, and the keys and values of one block. **Streamed**, by the
  kernel's own double-buffered copies out of the stacks where they lie
  (``memory_space=pl.ANY``): a block of ``_BLOCK`` cache rows at a
  time, a (block, kv_rank) tile of latent rows and a (rope, block) tile
  of rotary keys, the next one in flight while this one is worked on.
  The layer, the first lane, each sequence's start and its count of
  blocks arrive by scalar prefetch, so no block is sliced out into a
  temporary first and the loop over blocks ends, at run time, with the
  block of the sequence's last row: nothing behind it is fetched. (A
  third grid axis over the window's blocks, clamped index maps and
  ``pl.when`` read 2 % slower at an 8192-row window and pays an empty
  grid step for every block not visited.)
- A block's keys and values are made once for each head of the group
  (``rows @ wuk[:, h]``, ``rows @ wuv[:, h]``, rounded to the compute
  type) and all T rows of the chunk attend to them there, in tiles of
  ``_TILE`` rows: the expansion is paid once a call a head, not once a
  tile.
- A (tile, block) pair wholly above the diagonal is skipped; every pair
  that is computed is masked by position. Row 0 is seen by every
  query, so block 0 sets every maximum and a masked score (-1e30)
  weighs ``exp(-1e30 - m) = 0`` exactly: whatever lies behind a
  sequence's last row never reaches a result.
- The row maxima are (T, 1) columns; the row sums are kept **a lane
  apart**, (T, 128): a block adds its score's 128-lane groups into them
  elementwise and the lanes are summed once, when the last block is
  done. A reduction over lanes runs on the transpose unit, once a row
  and block whatever the block's width, and was what bound the kernel:
  with both reductions a block, 8.1 ms at blocks of 256; with the
  maximum alone 6.8; the rest came from wider blocks (4.2 at 512, 4.1
  at 1024, where the diagonal's block computes more that is masked).
- ``_HEADS`` = 4: 4.3 ms at 2, 4.1 at 4 and at 8 (whose kernel takes
  three times as long to compile). ``_TILE`` = 512: 4.5 at 256, 4.1 at
  512 and at 1024. An unrolled loop over tiles and ``acc * (1 / l)`` in
  the place of ``acc / l`` read the same.
- What a replica's start pays (``setup_s`` is a metric with a bound):
  the loops over the group's heads are ``fori_loop``s with
  ``unroll=True``, traced once and unrolled when the kernel is lowered
  (rolled on the chip they read 11 % slower: the heads' matmuls and
  softmaxes interleave; written out in Python they read the same and
  took 0.3 s longer to trace, a chunk program, on the chip's host);
  ``_call`` is jitted so that the two layer scans of a program trace
  and lower one kernel between them; ``latent_moe`` imports this
  module in a thread its own import starts (Pallas takes 1.2 s to
  import there, which then runs beside the chip's opening).
- Left out: an unmasked path for the blocks wholly under the diagonal
  (``pallas_attention`` measured none); the rotary part's own matmul
  is 64 deep, half an MXU pass, and stays so (two heads' rotary parts
  side by side would need a block-diagonal key, twice as wide).
- ``_interpret`` is ``pallas_attention``'s: on the CPU the kernel's own
  code runs interpreted, copies and semaphores too, so tier-1 tests it
  at small tileable shapes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_attention as _flash

_LANES = 128
_MASKED = -1e30

# Cache rows a block, heads a grid step, and the rows of the chunk that
# attend at a time (the design notes have the readings that chose them)
_BLOCK = 512
_HEADS = 4
_TILE = 512
_VMEM_LIMIT_BYTES = 64 * 2**20

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _divisor(size: int, preferred: int) -> int:
    """The largest of ``preferred``, its halves down to 128, that
    divides ``size`` (a multiple of 128)."""
    while size % preferred:
        preferred //= 2
    return preferred


def untileable(q_nope, q_rope, latents, keys, wuk, wuv, rows: int):
    """Why the kernel cannot take these shapes, or None when it can."""
    _, H, T, nope = q_nope.shape
    rope, kv_rank, v = q_rope.shape[3], latents.shape[3], wuv.shape[2]
    for name, width in (("nope", nope), ("v", v), ("kv_rank", kv_rank)):
        if width % _LANES:
            return f"{name}={width} not a multiple of {_LANES} lanes"
    if rope % 16:
        return f"rope={rope} not a multiple of 16 sublanes"
    if T % _LANES or rows % _LANES or keys.shape[3] % _LANES:
        return (f"chunk rows {T}, read window {rows} or cache rows "
                f"{keys.shape[3]} not multiples of {_LANES}")
    if wuk.shape != (kv_rank, H, nope) or keys.shape[2] != rope:
        return "the up-projections or the rotary keys do not fit the queries"
    return None


def _init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, _MASKED)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _attend(first_row, start, lat_ref, key_ref, qn_ref, qr_ref, wuk_ref,
            wuv_ref, k_scr, v_scr, m_scr, l_scr, acc_scr,
            *, scale, G, T, tile, block):
    """All T rows of the chunk, the G heads of the group, on the block
    of cache rows from ``first_row``: ``lat_ref`` (block, kv_rank) its
    latent rows, ``key_ref`` (rope, block) its rotary keys. The loops
    over heads are traced once and unrolled when the kernel is lowered:
    written out in Python they read the same on the chip and took four
    times as long to trace, a second of a replica's start."""

    def expand(g, carry):
        rows = lat_ref[...]
        k_scr[g] = jnp.dot(rows, wuk_ref[g],
                           preferred_element_type=jnp.float32
                           ).astype(k_scr.dtype)
        v_scr[g] = jnp.dot(rows, wuv_ref[g],
                           preferred_element_type=jnp.float32
                           ).astype(v_scr.dtype)
        return carry

    jax.lax.fori_loop(0, G, expand, 0, unroll=True)

    def attend(i, carry):
        r0 = pl.multiple_of(i * tile, tile)
        at = pl.ds(r0, tile)

        @pl.when(first_row <= start + r0 + tile - 1)
        def _visible():
            def head(g, carry):
                s = (jax.lax.dot_general(
                        qn_ref[0, g, at, :], k_scr[g], _NT,
                        preferred_element_type=jnp.float32)
                     + jax.lax.dot_general(
                         qr_ref[0, g, at, :], key_ref[...], _NN,
                         preferred_element_type=jnp.float32)) * scale
                seen = (jax.lax.broadcasted_iota(jnp.int32, (tile, block), 1)
                        - jax.lax.broadcasted_iota(jnp.int32, (tile, block), 0)
                        <= start + r0 - first_row)
                s = jnp.where(seen, s, _MASKED)
                m = m_scr[g, at]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                fade = jnp.exp(m - m_new)
                m_scr[g, at] = m_new
                l_scr[g, at] = l_scr[g, at] * fade + sum(
                    p[:, k:k + _LANES] for k in range(0, block, _LANES))
                acc_scr[g, at] = acc_scr[g, at] * fade + jax.lax.dot_general(
                    p.astype(v_scr.dtype), v_scr[g], _NN,
                    preferred_element_type=jnp.float32)
                return carry

            jax.lax.fori_loop(0, G, head, 0, unroll=True)

        return carry

    jax.lax.fori_loop(0, T // tile, attend, 0)


def _finish(o_ref, l_scr, acc_scr, G):
    for g in range(G):
        o_ref[0, g] = (acc_scr[g] / jnp.sum(l_scr[g], axis=1, keepdims=True)
                       ).astype(o_ref.dtype)


def _kernel(meta_ref, start_ref, blocks_ref,
            qn_ref, qr_ref, lat_hbm, key_hbm, wuk_ref, wuv_ref, o_ref,
            lat_buf, key_buf, sems, k_scr, v_scr, m_scr, l_scr, acc_scr,
            *, scale, G, T, tile, block):
    b = pl.program_id(0)
    layer, lane = meta_ref[0], meta_ref[1] + b
    blocks = blocks_ref[b]

    def fetch(j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        return (pltpu.make_async_copy(lat_hbm.at[layer, lane, at, :],
                                      lat_buf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(key_hbm.at[layer, lane, :, at],
                                      key_buf.at[slot], sems.at[1, slot]))

    for copy in fetch(0, 0):
        copy.start()
    _init(m_scr, l_scr, acc_scr)

    def step(j, carry):
        slot = j % 2

        @pl.when(j + 1 < blocks)
        def _next():
            for copy in fetch(j + 1, 1 - slot):
                copy.start()

        for copy in fetch(j, slot):
            copy.wait()
        _attend(j * block, start_ref[b], lat_buf.at[slot], key_buf.at[slot],
                qn_ref, qr_ref, wuk_ref, wuv_ref, k_scr, v_scr, m_scr, l_scr,
                acc_scr, scale=scale, G=G, T=T, tile=tile, block=block)
        return carry

    jax.lax.fori_loop(0, blocks, step, 0)
    _finish(o_ref, l_scr, acc_scr, G)


def latent_prefill_attention(q_nope, q_rope, latents, keys, wuk, wuv, *,
                             layer, slot, start_pos, rows: int, scale: float):
    """The expanded form over the cache's stacks (the module docstring
    has the layout contract) -> (B, T, H, v). Raises NotImplementedError
    for shapes the kernel does not tile (see ``untileable``)."""
    reason = untileable(q_nope, q_rope, latents, keys, wuk, wuv, rows)
    if reason is not None:
        raise NotImplementedError(reason)
    H, T = q_nope.shape[1:3]
    return _call(
        q_nope, q_rope, latents, keys, wuk, wuv, layer, slot, start_pos,
        rows=rows, scale=scale, block=_divisor(rows, _BLOCK),
        tile=_divisor(T, _TILE), G=math.gcd(H, _HEADS),
        interpret=_flash._interpret())


# jitted, so that a program whose two layer scans each call it with the
# same shapes traces and lowers the kernel once (a replica's start is a
# metric, and a kernel's trace a tenth of a second of it)
@functools.partial(jax.jit, static_argnames=(
    "rows", "scale", "block", "tile", "G", "interpret"))
def _call(q_nope, q_rope, latents, keys, wuk, wuv, layer, slot, start_pos, *,
          rows, scale, block, tile, G, interpret):
    B, H, T, nope = q_nope.shape
    rope, kv_rank, v = q_rope.shape[3], latents.shape[3], wuv.shape[2]
    dtype = q_nope.dtype
    # the blocks a sequence's last row sees, the window's at most
    blocks = jnp.minimum((start_pos + T - 1) // block + 1, rows // block)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(slot, jnp.int32)])
    scratch = [pltpu.VMEM((2, block, kv_rank), dtype),
               pltpu.VMEM((2, rope, block), dtype),
               pltpu.SemaphoreType.DMA((2, 2)),
               pltpu.VMEM((G, block, nope), dtype),
               pltpu.VMEM((G, block, v), dtype),
               pltpu.VMEM((G, T, 1), jnp.float32),
               pltpu.VMEM((G, T, _LANES), jnp.float32),
               pltpu.VMEM((G, T, v), jnp.float32)]
    heads = lambda width: pl.BlockSpec((1, G, T, width),
                                       lambda b, h, *_: (b, h, 0, 0))
    weights = lambda width: pl.BlockSpec((G, kv_rank, width),
                                         lambda b, h, *_: (h, 0, 0))
    in_specs = [heads(nope), heads(rope), pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY), weights(nope), weights(v)]
    # half the window attended, as a prompt's chunks see on average
    pairs, attended = B * H * T * rows // 2, B * H * rows // 2
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, G=G, T=T, tile=tile,
                          block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, H // G), in_specs=in_specs,
            out_specs=heads(v),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, H, T, v), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * pairs * (nope + rope + v)
                      + 2 * attended * kv_rank * (nope + v)),
            bytes_accessed=int(
                (2 * q_nope.size + q_rope.size + wuk.size + wuv.size
                 + (H // G) * B * (rows // 2) * (kv_rank + rope))
                * dtype.itemsize),
            transcendentals=int(pairs)),
        interpret=interpret,
        name="latent_attention_prefill",
    )(meta, start_pos.astype(jnp.int32), blocks.astype(jnp.int32),
      q_nope, q_rope, latents, keys, wuk.transpose(1, 0, 2),
      wuv.transpose(1, 0, 2))
    return out.transpose(0, 2, 1, 3)
