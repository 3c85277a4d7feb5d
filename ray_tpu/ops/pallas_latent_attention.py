"""Pallas TPU kernels for the two forms of latent attention: the prefill
(expanded) form, and since PR 62 the decode (absorbed) form (the second
half of this note and of the file).

``models/latent_moe.py`` keeps one latent row and one turned rotary key
a token a layer; a prefill chunk makes the heads' keys and values from
the latent rows it attends to and scores its rows against them. The
first kernel is that form with the score, the running softmax and the
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

**The decode form** (``latent_decode_attention``, kernel
``latent_attention_decode``; ``latent_moe.attend_absorbed`` dispatches
to it between its two foldings wherever ``decode_untileable`` finds
nothing against the shapes, and ``latent_moe.attend_absorbed_blockwise``
is the form of the other shapes and the reference). One query row a
lane, ``Wuk`` folded into it, so all H heads of a lane attend to the
latent rows themselves:
    q (B, H, kv_rank), q_rope (B, H, rope); the cache's two stacks as
    above, of which lane b reads layer ``layer``, cache row ``slot +
    b`` and its ``blocks[b]`` leading blocks of ``decode_block`` rows;
    pos (B,): the lane's row, which masks its last block
    -> the mixed latent rows (B, H, kv_rank), which the caller folds
    through ``Wuv``; zeros for a lane of no blocks.
Design notes (PERF.md section 6, PR 62, has the chip readings; all on a
v5e at the published widths, 16 lanes of 128 heads over latent rows of
512 + 64, a layer-call with the query's folding (23 us), all lanes at
6144 rows unless said, where the reader's count allows 139 us: 1152
bytes and 2 x 128 x 1088 FLOPs a row, the two bounds equal; the
``jax.numpy`` loop took 369 us there and 540 on the cell's mix of
lengths, 15 lanes of 2048 to 8960 rows and one idle):
- Grid (lane,); a lane's queries and result ride the pipeline's
  BlockSpecs, its running maximum (H, 1), its sums a lane apart (H,
  128) and its float32 accumulator (H, kv_rank) live in scratch. A
  block of ``_DECODE_BLOCK`` rows is fetched **once**, a (block,
  kv_rank) tile of latent rows and a (rope, block) tile of rotary keys
  by the kernel's own copies out of the stacks where they lie, and
  serves the score of all heads (two products, 512 and 64 deep, summed)
  and then, the same tile, the value. Rounding points are the loop's:
  score and softmax in float32, ``p`` rounded to the compute type
  before the value's product, ``acc / l`` rounded once.
- **Each lane through its own blocks**: the per-lane counts arrive by
  scalar prefetch (``latent_moe.absorbed_blocks``: from the rows a live
  lane sees, none for an idle lane, whose position is the scratch row
  far up the cache), so nothing behind a lane's last block is fetched
  or scored. The call's blocks are one sequence, lane after lane (the
  lane and block of each by scalar prefetch too), and every block's
  step starts the fetch of the next one **whichever lane's it is**: a
  lane's first block is in flight while the lane before it finishes.
- What bounds it, as read: the fetches alone, nothing computed, take
  165 us (686 GB/s); a third buffer read the same as two (239.7 against
  242.7 us at blocks of 1024, 287.3 against 288.0 at 512), so the
  fetch is hidden and the rest is the block's own chain (score, row
  maximum, exp, value, the accumulator's update): 197 us + 0.47 us a
  block-step over blocks of 256 / 512 / 1024 / 2048 rows (381 / 288 /
  243 / 220 us).
- ``_DECODE_BLOCK`` = 1024 in tiles of ``_DECODE_TILE`` = 512, each
  tile with the running update of its own and **every tile's score
  before the first tile's softmax** in program order: a tile's score
  needs nothing of the tile before it, and the scheduler runs its
  matmuls beside that one's exp: 223.5 us (224.4 in tiles of 256)
  where the block as one tile read 242.8; score and softmax tile by
  tile in turn 266.8; one maximum a block and the tiles after it 240.5.
  Blocks of 2048 in tiles of 512 read 208.6 at equal lanes and no
  better than 1024 on the mix (a lane's count is rounded up to a block:
  a lane of 6100 rows wastes 512 on average at 1024, 1024 at 2048);
  512 in tiles of 256 280.6. On the mix: 235 us.
- Which operand stands still in the MXU made no difference: the score
  as ``rows @ q^T`` (the 128 x 512 query the stationary one, 1024 rows
  streamed) transposed back read 241.6 us against 243.2 for ``q @
  rows^T``. The value's product has the cache rows stationary either
  way (they are what is contracted over).
- Masking by position in every block (``-1e30``; row 0 is seen by every
  lane): masking the lane's last block alone through ``lax.cond`` read
  265 us against 243, the branch dearer than the compare and select.
- Left out: a last block fetched and scored in tiles (would take half
  the mix's rounding waste, 4 % of the rows); the step's chain
  overlapped across blocks by hand (the 0.3 us a step that is left);
  the value's folding inside the kernel (``Wuv``'s einsum outside is 50
  us a layer-call, twice what its 17 MB cost to read).
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
            *, scale, G, T, tile, block, allowed_ref=None):
    """All T rows of the chunk, the G heads of the group, on the block
    of cache rows from ``first_row``: ``lat_ref`` (block, kv_rank) its
    latent rows, ``key_ref`` (rope, block) its rotary keys,
    ``allowed_ref`` (T, block) int8, where the call brings a selection:
    which of the block's rows each of the chunk's may attend to. The loops
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
                if allowed_ref is not None:
                    seen &= allowed_ref[at, :].astype(jnp.int32) != 0
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
            qn_ref, qr_ref, lat_hbm, key_hbm, wuk_ref, wuv_ref, *rest,
            scale, G, T, tile, block, selected):
    # a call with a selection brings it behind the weights, (B, T, rows)
    # int8 in HBM, and a buffer for a block's columns of it
    allowed_hbm, rest = (rest[0], rest[1:]) if selected else (None, rest)
    (o_ref, lat_buf, key_buf, sems, k_scr, v_scr, m_scr, l_scr, acc_scr,
     *allowed_buf) = rest
    b = pl.program_id(0)
    layer, lane = meta_ref[0], meta_ref[1] + b
    blocks = blocks_ref[b]

    def fetch(j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        copies = (pltpu.make_async_copy(lat_hbm.at[layer, lane, at, :],
                                        lat_buf.at[slot], sems.at[0, slot]),
                  pltpu.make_async_copy(key_hbm.at[layer, lane, :, at],
                                        key_buf.at[slot], sems.at[1, slot]))
        if selected:
            copies += (pltpu.make_async_copy(
                allowed_hbm.at[b, :, at], allowed_buf[0].at[slot],
                sems.at[2, slot]),)
        return copies

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
                acc_scr, scale=scale, G=G, T=T, tile=tile, block=block,
                allowed_ref=allowed_buf[0].at[slot] if selected else None)
        return carry

    jax.lax.fori_loop(0, blocks, step, 0)
    _finish(o_ref, l_scr, acc_scr, G)


def latent_prefill_attention(q_nope, q_rope, latents, keys, wuk, wuv, *,
                             layer, slot, start_pos, rows: int, scale: float,
                             allowed=None):
    """The expanded form over the cache's stacks (the module docstring
    has the layout contract) -> (B, T, H, v). ``allowed`` (B, T, rows)
    bool, where the caller has selected rows: row t of sequence b
    attends to the cache rows at or before its own that it marks, and
    to no other (one of them at least, for every row whose result is
    kept: the first block no longer sets every maximum, and what a row
    gathered before its first marked row fades to 0 exactly when that
    row's score arrives). Raises NotImplementedError for shapes the
    kernel does not tile (see ``untileable``)."""
    reason = untileable(q_nope, q_rope, latents, keys, wuk, wuv, rows)
    if reason is not None:
        raise NotImplementedError(reason)
    H, T = q_nope.shape[1:3]
    return _call(
        q_nope, q_rope, latents, keys, wuk, wuv, layer, slot, start_pos,
        rows=rows, scale=scale, block=_divisor(rows, _BLOCK),
        tile=_divisor(T, _TILE), G=math.gcd(H, _HEADS),
        interpret=_flash._interpret(),
        **({} if allowed is None else {"allowed": allowed.astype(jnp.int8)}))


# jitted, so that a program whose two layer scans each call it with the
# same shapes traces and lowers the kernel once (a replica's start is a
# metric, and a kernel's trace a tenth of a second of it)
@functools.partial(jax.jit, static_argnames=(
    "rows", "scale", "block", "tile", "G", "interpret"))
def _call(q_nope, q_rope, latents, keys, wuk, wuv, layer, slot, start_pos, *,
          rows, scale, block, tile, G, interpret, allowed=None):
    B, H, T, nope = q_nope.shape
    rope, kv_rank, v = q_rope.shape[3], latents.shape[3], wuv.shape[2]
    dtype = q_nope.dtype
    # the blocks a sequence's last row sees, the window's at most
    blocks = jnp.minimum((start_pos + T - 1) // block + 1, rows // block)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(slot, jnp.int32)])
    scratch = [pltpu.VMEM((2, block, kv_rank), dtype),
               pltpu.VMEM((2, rope, block), dtype),
               pltpu.SemaphoreType.DMA((2 if allowed is None else 3, 2)),
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
    selection = ()
    if allowed is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch.append(pltpu.VMEM((2, T, block), jnp.int8))
        selection = (allowed,)
    # half the window attended, as a prompt's chunks see on average
    pairs, attended = B * H * T * rows // 2, B * H * rows // 2
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, G=G, T=T, tile=tile,
                          block=block, selected=allowed is not None),
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
      wuv.transpose(1, 0, 2), *selection)
    return out.transpose(0, 2, 1, 3)


# -- the decode (absorbed) form -----------------------------------------
# Cache rows a block of the decode form (what is fetched at a time, and
# what a lane's count is rounded up to) and the rows of a block that
# are scored and weighed at a time
_DECODE_BLOCK = 1024
_DECODE_TILE = 512


def decode_block(latents) -> int:
    """Cache rows a block of the decode kernel over these stacks."""
    return _divisor(latents.shape[2], _DECODE_BLOCK)


def decode_untileable(H: int, rope: int, latents, keys):
    """Why the decode kernel cannot take ``H`` heads with rotary parts
    of ``rope`` over the cache's two stacks, or None when it can."""
    kv_rank = latents.shape[3]
    if kv_rank % _LANES:
        return f"kv_rank={kv_rank} not a multiple of {_LANES} lanes"
    if rope % 16 or H % 16:
        return f"rope={rope} or heads={H} not a multiple of 16 sublanes"
    if latents.shape[2] % _LANES:
        return f"cache rows {latents.shape[2]} not a multiple of {_LANES}"
    if keys.shape[2:] != (rope, latents.shape[2]):
        return "the rotary keys do not fit the queries or the latent rows"
    return None


def _decode_kernel(meta_ref, blocks_ref, pos_ref, base_ref, lane_ref, block_ref,
                   q_ref, qr_ref, lat_hbm, key_hbm, o_ref,
                   lat_buf, key_buf, sems, m_scr, l_scr, acc_scr,
                   *, scale, H, block, tile):
    b = pl.program_id(0)
    layer, first, total = meta_ref[0], meta_ref[1], meta_ref[2]
    blocks, base, pos = blocks_ref[b], base_ref[b], pos_ref[b]

    def fetch(g):
        """The copies of the call's g-th block, all lanes' blocks in
        one sequence, into the buffer of its turn."""
        slot = g % 2
        lane = first + lane_ref[g]
        at = pl.ds(pl.multiple_of(block_ref[g] * block, block), block)
        return (pltpu.make_async_copy(lat_hbm.at[layer, lane, at, :],
                                      lat_buf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(key_hbm.at[layer, lane, :, at],
                                      key_buf.at[slot], sems.at[1, slot]))

    def start(g):
        @pl.when(g < total)
        def _():
            for copy in fetch(g):
                copy.start()

    # the first grid step starts the call's first fetch; from there
    # every block's step starts the next one's, whichever lane's it is
    @pl.when(b == 0)
    def _first():
        start(0)

    @pl.when(blocks == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(blocks > 0)
    def _live():
        _init(m_scr, l_scr, acc_scr)
        q, qr = q_ref[0], qr_ref[0]
        column = jax.lax.broadcasted_iota(jnp.int32, (H, tile), 1)

        def step(j, carry):
            g = base + j
            slot = g % 2
            start(g + 1)
            for copy in fetch(g):
                copy.wait()
            # every tile's score first, written out: none needs a tile
            # before it, so its matmuls run beside that one's softmax
            scores = [
                (jax.lax.dot_general(q, lat_buf[slot, k:k + tile, :], _NT,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr, key_buf[slot, :, k:k + tile], _NN,
                                       preferred_element_type=jnp.float32)
                 ) * scale for k in range(0, block, tile)]
            for i, s in enumerate(scores):
                rows = lat_buf[slot, i * tile:(i + 1) * tile, :]
                s = jnp.where(column <= pos - (j * block + i * tile), s,
                              _MASKED)
                m = m_scr[...]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                fade = jnp.exp(m - m_new)
                m_scr[...] = m_new
                l_scr[...] = l_scr[...] * fade + sum(
                    p[:, k:k + _LANES] for k in range(0, tile, _LANES))
                acc_scr[...] = acc_scr[...] * fade + jax.lax.dot_general(
                    p.astype(rows.dtype), rows, _NN,
                    preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, blocks, step, 0)
        o_ref[0] = (acc_scr[...] / jnp.sum(l_scr[...], axis=1, keepdims=True)
                    ).astype(o_ref.dtype)


def latent_decode_attention(q, q_rope, latents, keys, *, layer, slot, pos,
                            blocks, scale: float):
    """The absorbed form over the cache's stacks: q (B, H, kv_rank) the
    queries folded through ``wuk``, q_rope (B, H, rope), lane b's one
    row at ``pos[b]`` attending to the ``blocks[b]`` leading blocks
    (``decode_block`` rows each) of layer ``layer``, cache row ``slot +
    b`` -> the mixed latent rows (B, H, kv_rank); zeros for a lane of no
    blocks. Raises NotImplementedError for shapes the kernel does not
    tile (``decode_untileable``)."""
    reason = decode_untileable(*q_rope.shape[1:], latents, keys)
    if reason is None and q.shape[2] != latents.shape[3]:
        reason = "the folded queries are not as wide as the latent rows"
    if reason is not None:
        raise NotImplementedError(reason)
    block = decode_block(latents)
    return _call_decode(q, q_rope, latents, keys, layer, slot, pos, blocks,
                        scale=scale, block=block,
                        tile=_divisor(block, _DECODE_TILE),
                        interpret=_flash._interpret())


# jitted for the reason ``_call`` is; the read window is no argument, so
# the engine's decode variants trace and lower one kernel between them
@functools.partial(jax.jit, static_argnames=(
    "scale", "block", "tile", "interpret"))
def _call_decode(q, q_rope, latents, keys, layer, slot, pos, blocks, *,
                 scale, block, tile, interpret):
    B, H, kv_rank = q.shape
    rope, S = keys.shape[2:]
    dtype = q.dtype
    blocks = jnp.minimum(blocks, S // block).astype(jnp.int32)
    # the call's blocks as one sequence, lane after lane: the lane and
    # the block of each, and where each lane's begin
    ends = jnp.cumsum(blocks)
    base = ends - blocks
    turn = jnp.arange(B * (S // block), dtype=jnp.int32)
    # (the lanes that end at or before a turn, counted: a search by
    # halves is a loop of its own in every layer of the scan)
    lane_of = jnp.minimum((ends[None, :] <= turn[:, None]).sum(1), B - 1)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(slot, jnp.int32), ends[-1]])
    lane = lambda width: pl.BlockSpec((1, H, width), lambda b, *_: (b, 0, 0))
    scratch = [pltpu.VMEM((2, block, kv_rank), dtype),
               pltpu.VMEM((2, rope, block), dtype),
               pltpu.SemaphoreType.DMA((2, 2)),
               pltpu.VMEM((H, 1), jnp.float32),
               pltpu.VMEM((H, _LANES), jnp.float32),
               pltpu.VMEM((H, kv_rank), jnp.float32)]
    # half the cache attended, as a lane holds on average
    attended = B * H * S // 2
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, H=H, block=block,
                          tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(B,),
            in_specs=[lane(kv_rank), lane(rope),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=lane(kv_rank), scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, H, kv_rank), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * attended * (2 * kv_rank + rope)),
            bytes_accessed=int((2 * q.size + q_rope.size
                                + B * (S // 2) * (kv_rank + rope))
                               * dtype.itemsize),
            transcendentals=int(attended)),
        interpret=interpret,
        name="latent_attention_decode",
    )(meta, blocks, pos.astype(jnp.int32), base.astype(jnp.int32),
      lane_of.astype(jnp.int32), turn - base[lane_of].astype(jnp.int32),
      q, q_rope, latents, keys)
