"""Pallas TPU kernel for the grouped SwiGLU of a prefill chunk's experts.

``ops/moe.py`` ``expert_ffn`` sorts a call's assignments by expert and
multiplies each expert's rows by that expert's three matrices. This is
that grouped matmul as a kernel of the program's own; ``expert_ffn``
dispatches to it wherever ``untileable`` finds nothing against the
shapes, and ``jax.lax.ragged_dot`` (the compiler's grouped kernel) stays
for the shapes it refuses and as the form the tests compare with.

Layout contract (what ``ops/moe.py`` holds):
    xs (N, D) rows sorted by expert; group_sizes (E,) int32, the rows of
    each expert in that order, rows behind their sum no expert's;
    w_gate, w_up (E, D, F), w_down (E, F, D), or a model's stacks
    (L, E, D, F) / (L, E, F, D) with ``layer`` a traced index into them
    -> (N, D) float32. Rows behind the last group are not written: what
    they hold is not a number to keep (where a share of the experts is
    held ``ops/moe.py`` puts 0 there: ``_held_slabs`` behind each slab
    of the held assignments it passes here, ``_dropless_rows`` behind a
    call it passes whole).

Two ``pallas_call``s a layer, the same kernel body twice: the first
takes a tile of rows through ``w_gate`` and ``w_up`` and writes
``silu(gate) * up`` (both products and the SwiGLU in float32, rounded to
the rows' type once, for the second matmul); the second takes that
through ``w_down`` into float32. The (N, F) between them goes through
HBM: 59 MB there and back at Mellum2's 16 384 assignments of 896, 0.07
ms of a layer's 2.

- **Tiles follow the groups.** The grid's inner axis walks *visits*:
  (row tile, expert) pairs that hold a row, in the order of the rows. A
  tile that two experts share is visited once by each, and each stores
  only its own rows (a tile's first visit zeroes the rest). The pairs
  are made on the device from ``group_sizes`` (``_visits``: an expert's
  tiles run from the tile of its first row to the tile of its last; an
  expert with no row has none) and reach the index maps by scalar
  prefetch, with the layer. At most ``N / tile + E - 1`` visits can be;
  the grid's extent is their count, a traced number, so what is not
  visited costs no step: openPangu's 8 held experts take about 245 of a
  call's 8192 assignments, 9 or 10 visits (since PR 59 the call hands
  over a slab of 512 of them, 11 visits at the most).
- **An expert's weights are read once a call.** The weight block's
  index is (layer, expert of the visit, 0, column tile): consecutive
  visits of one expert keep it and the pipeline fetches nothing. The
  column tiles are the grid's *outer* axis, so a second column tile
  walks the visits again (and reads the row tiles again, which is the
  cheaper of the two: rows x K beside E x K x N).
- **Which of row tile, K and N are tiled.** Rows in tiles of
  ``_ROW_TILE``; K (the contraction: D for gate and up, F for down) is
  never tiled, so no accumulator is carried between grid steps; N (the
  matmul's columns: F for gate and up, D for down) is tiled only where
  a whole (K, N) matrix is over ``_WEIGHT_BLOCK_BYTES``: the largest
  multiple of 128 lanes that divides N and keeps a block under it
  (``_column_tile``). Mellum2 (2304 x 896, bf16): 4.1 MB a matrix, whole;
  gate and up double-buffered 16.5 MB, down 8.3. openPangu (7680 x 2048):
  gate and up in 8 column tiles of 256 (3.9 MB a block, 15.7 MB the two
  double-buffered), down in 5 of 1536 (6.3 MB, 12.6). The VMEM limit
  is stated a call, from its blocks: the pipeline's two buffers of the
  row tile in, the weight blocks and the tile out, a step's float32
  products (two and their SwiGLU, tile x columns each) and
  ``_VMEM_SLACK_BYTES`` for what the compiler keeps beside them: 26
  MiB for Mellum2's gate and up, 27 MiB for openPangu's, over the
  compiler's default of 16 and well under a v5e's 128.
- **The stack goes in whole; ``layer`` is a scalar.** The weights'
  BlockSpec squeezes (layer, expert) and the index map reads the layer
  from scalar prefetch: no slice is copied out (0.8 GB a layer a call at
  Mellum2's widths) and no ``L * E`` groups are made.
- ``_interpret`` is ``pallas_attention``'s: on the CPU the kernel's own
  code runs interpreted, so tier-1 tests it at small tileable shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_attention as _flash

_LANES = 128

# Rows a visit, and the most bytes of one weight block (the module
# docstring has the sums behind them)
_ROW_TILE = 128
_WEIGHT_BLOCK_BYTES = 6 * 2**20
_VMEM_SLACK_BYTES = 8 * 2**20


def untileable(xs, w_gate, w_down):
    """Why the kernel cannot take these shapes, or None when it can."""
    N, D = xs.shape
    F = w_gate.shape[-1]
    if w_gate.shape[-2:] != (D, F) or w_down.shape[-2:] != (F, D):
        return "the experts' matrices do not fit the rows"
    for name, width in (("D", D), ("F", F)):
        if width % _LANES:
            return f"{name}={width} not a multiple of {_LANES} lanes"
    if N % _ROW_TILE:
        return f"{N} rows not a multiple of the row tile {_ROW_TILE}"
    if xs.dtype != w_gate.dtype or xs.dtype not in (jnp.bfloat16, jnp.float32):
        return f"rows {xs.dtype} beside weights {w_gate.dtype}"
    return None


def _column_tile(K: int, N: int, itemsize: int) -> int:
    """The widest multiple of 128 lanes that divides ``N`` and keeps a
    (K, tile) block within ``_WEIGHT_BLOCK_BYTES``; 128 where none does."""
    fits = [t for t in range(_LANES, N + 1, _LANES)
            if N % t == 0 and K * t * itemsize <= _WEIGHT_BLOCK_BYTES]
    return max(fits, default=_LANES)


def _visits(group_sizes, rows: int, tile: int):
    """The (row tile, expert) pairs that hold a row, in the rows' order,
    from ``group_sizes`` (E,) int32, all int32
    -> (offsets (E + 1,): expert e's rows are ``offsets[e] ..
    offsets[e + 1]``; experts, tiles (rows / tile + E - 1,): visit v is
    expert ``experts[v]`` on row tile ``tiles[v]``; their count)."""
    E = group_sizes.shape[0]
    most = rows // tile + E - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tile
    # an expert visits the tiles from its first row's to its last row's
    n_tiles = jnp.where(group_sizes > 0, (ends - 1) // tile - first + 1, 0)
    before = jnp.cumsum(n_tiles) - n_tiles
    experts = jnp.repeat(jnp.arange(E, dtype=jnp.int32), n_tiles,
                         total_repeat_length=most)
    tiles = first[experts] + jnp.arange(most, dtype=jnp.int32) - before[experts]
    # behind the last visit nothing is read; the indices stay in bounds
    tiles = jnp.clip(tiles, 0, rows // tile - 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return offsets, experts, tiles, n_tiles.sum()


def _kernel(offsets_ref, experts_ref, tiles_ref, layer_ref, x_ref, *refs,
            tile):
    """One visit: the tile's rows through the expert's block of one
    matrix (the product) or of two (``silu(first) * second``), float32;
    the expert's own rows of it stored."""
    *w_refs, o_ref = refs
    v = pl.program_id(1)
    x = x_ref[...]
    ys = [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
          for w in w_refs]
    y = ys[0] if len(ys) == 1 else jax.nn.silu(ys[0]) * ys[1]
    e, t = experts_ref[v], tiles_ref[v]
    row = t * tile + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    own = (row >= offsets_ref[e]) & (row < offsets_ref[e + 1])
    # a tile's first visit finds whatever the buffer held
    fresh = (v == 0) | (tiles_ref[jnp.maximum(v - 1, 0)] != t)
    kept = jnp.where(fresh, 0.0, o_ref[...].astype(jnp.float32))
    o_ref[...] = jnp.where(own, y, kept).astype(o_ref.dtype)


def _grouped(xs, weights, visits, layer, out_dtype, *, tile, interpret,
             name):
    """``xs`` (N, K) through the (L, E, K, M) ``weights`` (one: the
    product; two: the SwiGLU of the two products) of the visits'
    experts in layer ``layer`` -> (N, M) ``out_dtype``."""
    offsets, experts, tiles, count = visits
    N, K = xs.shape
    E, M = weights[0].shape[1], weights[0].shape[3]
    cols = _column_tile(K, M, weights[0].dtype.itemsize)
    in_specs = [pl.BlockSpec((tile, K), lambda n, v, o, e, t, l: (t[v], 0))]
    in_specs += [pl.BlockSpec((None, None, K, cols),
                              lambda n, v, o, e, t, l: (l[0], e[v], 0, n))
                 ] * len(weights)
    itemsize, out_itemsize = xs.dtype.itemsize, jnp.dtype(out_dtype).itemsize
    # the pipeline's two buffers of every block, a step's float32
    # products and their SwiGLU, and room for the compiler's own
    vmem = (2 * (tile * K * itemsize + len(weights) * K * cols * itemsize
                 + tile * cols * out_itemsize)
            + 3 * tile * cols * 4 + _VMEM_SLACK_BYTES)
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(M // cols, count),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tile, cols),
                                   lambda n, v, o, e, t, l: (t[v], n))),
        out_shape=jax.ShapeDtypeStruct((N, M), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * K * M * len(weights),
            bytes_accessed=(len(weights) * E * K * M + (M // cols) * N * K
                            ) * itemsize + N * M * out_itemsize,
            transcendentals=N * M * (len(weights) - 1)),
        interpret=interpret,
        name=name,
    )(offsets, experts, tiles, layer, xs, *weights)


def grouped_swiglu(xs, w_gate, w_up, w_down, group_sizes, layer=None):
    """The grouped SwiGLU (the module docstring has the layout contract)
    -> (N, D) float32. Raises NotImplementedError for shapes the kernel
    does not tile (see ``untileable``)."""
    reason = untileable(xs, w_gate, w_down)
    if reason is not None:
        raise NotImplementedError(reason)
    if layer is None:
        layer = 0
        w_gate, w_up, w_down = (w[None] for w in (w_gate, w_up, w_down))
    return _call(xs, w_gate, w_up, w_down, group_sizes, layer,
                 tile=_ROW_TILE, interpret=_flash._interpret())


# jitted, so that a program whose layer scans each call it with the same
# shapes traces and lowers the two kernels once
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _call(xs, w_gate, w_up, w_down, group_sizes, layer, *, tile, interpret):
    visits = _visits(group_sizes.astype(jnp.int32), xs.shape[0], tile)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    h = _grouped(xs, (w_gate, w_up), visits, layer, xs.dtype, tile=tile,
                 interpret=interpret, name="grouped_swiglu_gate_up")
    return _grouped(h, (w_down,), visits, layer, jnp.float32, tile=tile,
                    interpret=interpret, name="grouped_swiglu_down")
