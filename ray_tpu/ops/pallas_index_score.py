"""Pallas TPU kernel for the indexer's score of a chunk's rows
(``ops/index_select.py`` says what the score is and dispatches here):

    q (B, T, Hi, di) the index queries, w (B, T, Hi) float32 their
    heads' weights, keys (B, S, di) the cache's index keys of the rows
    read, start_pos (B,): sequence b's T rows stand at ``start_pos[b]
    ..`` -> (B, T, S) float32, ``sum_j w[t, j] relu(q[t, j] . keys[s])``.

The plain einsum makes ``Hi x T x S`` float32 before the sum over the
heads (64 x 1024 x 36 864 x 4 B = 9.7 GB a layer of the widths this was
written for), and block by block in ``jax.numpy`` that intermediate
still goes out to HBM and back, 270 MB a block of 512 rows. Here a
(tile of query rows, block of cache rows) score is accumulated in VMEM
over the heads and written once.

- Grid (sequence, tile of ``_TILE`` query rows, block of ``_BLOCK``
  cache rows), the blocks innermost: a tile's queries (all heads, head-
  major, so that a head's is a whole tile under a leading index) and
  weights stay resident while its blocks stream through the pipeline's
  BlockSpecs.
- A step is ``Hi`` products ``(tile, di) @ (di, block)``, each through
  ``relu``, times its head's weight (a column of the resident weights,
  broadcast along the lanes) and into the sum; the loop over heads is
  written out (static lane slices of the weights).
- A block wholly behind the tile's last row is skipped and its scores
  left at 0: no query of the tile may attend to it, and the selection
  masks by position.
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
_TILE = 256
_BLOCK = 512
_VMEM_LIMIT_BYTES = 64 * 2**20
_NT = (((1,), (1,)), ((), ()))   # a @ b.T


def _divisor(size: int, preferred: int) -> int:
    while size % preferred:
        preferred //= 2
    return preferred


def untileable(q, keys):
    """Why the kernel cannot take these shapes, or None when it can."""
    _, T, _, di = q.shape
    if di % _LANES:
        return f"index head of {di}, not a multiple of {_LANES} lanes"
    if T % _LANES or keys.shape[1] % _LANES:
        return (f"chunk rows {T} or rows read {keys.shape[1]} not multiples "
                f"of {_LANES}")
    return None


def _kernel(start_ref, q_ref, w_ref, k_ref, o_ref, *, Hi, tile, block):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last = start_ref[b] + (i + 1) * tile - 1     # the tile's last position

    @pl.when(j * block <= last)
    def _seen():
        keys = k_ref[0]
        acc = jnp.zeros((tile, block), jnp.float32)
        for h in range(Hi):
            s = jax.lax.dot_general(q_ref[0, h], keys, _NT,
                                    preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w_ref[0, :, h:h + 1]
        o_ref[0] = acc

    @pl.when(j * block > last)
    def _behind():
        o_ref[0] = jnp.zeros((tile, block), jnp.float32)


def index_score(q, w, keys, start_pos):
    """The score over the shapes ``untileable`` finds nothing against
    (the module docstring has the contract) -> (B, T, S) float32."""
    reason = untileable(q, keys)
    if reason is not None:
        raise NotImplementedError(reason)
    return _call(q, w, keys, start_pos, tile=_divisor(q.shape[1], _TILE),
                 block=_divisor(keys.shape[1], _BLOCK),
                 interpret=_flash._interpret())


@functools.partial(jax.jit, static_argnames=("tile", "block", "interpret"))
def _call(q, w, keys, start_pos, *, tile, block, interpret):
    B, T, Hi, di = q.shape
    S = keys.shape[1]
    return pl.pallas_call(
        functools.partial(_kernel, Hi=Hi, tile=tile, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, T // tile, S // block),
            in_specs=[
                pl.BlockSpec((1, Hi, tile, di), lambda b, i, j, *_: (b, 0, i, 0)),
                pl.BlockSpec((1, tile, Hi), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block, di), lambda b, i, j, *_: (b, j, 0))],
            out_specs=pl.BlockSpec((1, tile, block),
                                   lambda b, i, j, *_: (b, i, j))),
        out_shape=jax.ShapeDtypeStruct((B, T, S), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * B * T * S * Hi * di // 2),
            bytes_accessed=int(q.size * q.dtype.itemsize
                               + (T // tile) * keys.size * keys.dtype.itemsize
                               + 4 * B * T * S),
            transcendentals=0),
        interpret=interpret,
        name="index_score",
    )(start_pos.astype(jnp.int32), q.transpose(0, 2, 1, 3),
      w.astype(jnp.float32), keys)
