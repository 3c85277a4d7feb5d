"""Learned sparse attention's two steps before attention itself: **the
indexer's score** of every cache row for every query row, and **the
exact selection** of the ``k`` rows a query attends to.

*The score.* An index query has ``Hi`` small heads, an index key is one
vector a row for all of them, and a query's weight for each head is a
scalar: ``I(t, s) = sum_j w(t, j) relu(q(t, j) . k(s))``, float32. A
chunk's rows score through ``ops/pallas_index_score.py`` where the
shapes tile (no ``heads x rows x cache rows`` intermediate ever exists)
and through ``index_scores_blockwise`` elsewhere, which is also the
kernel's reference; one row a lane (a decode) is two fused einsums.

*The selection* is exact: the ``k`` largest scores of a row among the
cache rows it may attend to (``valid``), every one of them where there
are ``k`` or fewer, and **of equal scores the row of the lower index
first**, which is ``jax.lax.top_k``'s rule and the reference's. Two
spellings of the same set:

- ``select_mask`` (a chunk: rows x cache rows is tens of millions of
  scores, and a sort of that is not cheap on this chip): the k-th
  largest score of each row is found bit by bit. A float32's bits, the
  sign bit flipped for a positive number and all bits for a negative
  one, order as unsigned integers the way the numbers do, so the
  threshold is built from the top bit down: a bit stays set where ``k``
  or more of the row's keys are at or over the candidate. That is 32
  passes of a compare and a row sum over the keys, each a read of the
  matrix and nothing written. The rows over the threshold are taken,
  and of those AT it the first ``k - (rows over it)`` by index: where no
  row of the call has more rows at its threshold than it needs (the
  usual case: a sum of 64 float32 products ties with nothing), that is
  all of them and the running count over the equal ones is never made.
- ``select_rows`` (a decode: a few lanes, and what follows wants the
  rows' indices to gather them): ``jax.lax.top_k`` of the scores with
  the rows a lane may not attend to at minus infinity.

No ``approx_max_k`` anywhere: an approximate set where the model's is
exact is a different result.

*What was selected, as bits* (``mask_as_bits``, ``rows_as_bits``), for a
program that says what it chose: ``said_words(S)`` uint32 words a set
over S cache rows. 32 planes of ``_PLANE`` words make a group of 32 x
``_PLANE`` rows, and row r of a group is the bit ``r // _PLANE`` of word
``r % _PLANE``: a mask is cut into planes of whole lanes and none is
split.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# cache rows a step of the ``jax.numpy`` score loop: its heads x rows x
# block float32 intermediate is what the kernel never makes
SCORE_BLOCK = 512


def index_scores_blockwise(q, w, keys):
    """q (B, T, Hi, di), w (B, T, Hi) float32, keys (B, S, di) ->
    (B, T, S) float32: ``sum_j w[t, j] relu(q[t, j] . keys[s])``, the
    products accumulated in float32, ``SCORE_BLOCK`` cache rows at a
    time. Every (query row, cache row) pair is scored; the caller masks
    those a row may not attend to."""
    B, T = q.shape[:2]
    S = keys.shape[1]
    block = min(SCORE_BLOCK, S)
    while S % block:
        block //= 2

    def step(i, out):
        rows = jax.lax.dynamic_slice_in_dim(keys, i * block, block, axis=1)
        s = jnp.einsum("bthd,bsd->bths", q, rows,
                       preferred_element_type=jnp.float32)
        s = (jnp.maximum(s, 0.0) * w[..., None]).sum(2)
        return jax.lax.dynamic_update_slice_in_dim(out, s, i * block, axis=2)

    return jax.lax.fori_loop(0, S // block, step,
                             jnp.zeros((B, T, S), jnp.float32))


def index_scores(q, w, keys, start_pos):
    """The indexer's score of a call's rows: q (B, T, Hi, di), w (B, T,
    Hi) float32, keys (B, S, di), sequence b's rows at ``start_pos[b]
    ..`` -> (B, T, S) float32. Which implementation runs follows from
    the shapes alone. A pair behind the causal diagonal may hold
    anything (the kernel skips blocks no row of a tile sees)."""
    if q.shape[1] == 1:
        s = jnp.einsum("bhd,bsd->bhs", q[:, 0], keys,
                       preferred_element_type=jnp.float32)
        return (jnp.maximum(s, 0.0) * w[:, 0, :, None]).sum(1)[:, None]
    from . import pallas_index_score as kernel

    if kernel.untileable(q, keys) is None:
        return kernel.index_score(q, w, keys, start_pos)
    return index_scores_blockwise(q, w, keys)


def _ordered_bits(scores):
    """float32 -> uint32 that order as the numbers do; at least 1, so
    that 0 is under every score. ``-0.0`` is taken as ``0.0``."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.uint32)
    flip = jnp.where(bits >> 31 == 1, jnp.uint32(0xFFFFFFFF),
                     jnp.uint32(0x80000000))
    return jnp.maximum(bits ^ flip, jnp.uint32(1))


def select_mask(scores, valid, k: int):
    """scores (..., S) float32, valid (..., S) bool -> (..., S) bool:
    each row's ``k`` largest scores among its valid entries, all of them
    where they are ``k`` or fewer; of equal scores the lower index
    first."""
    keys = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (keys >= cand[..., None]).sum(-1) >= k
        return jnp.where(enough, cand, thr)

    # the k-th largest key of each row; 0 where the row has under k
    # valid entries (every valid key is over 0)
    thr = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(keys.shape[:-1], jnp.uint32))
    over = keys > thr[..., None]
    at = (keys == thr[..., None]) & valid
    need = k - over.sum(-1)

    def first_of_the_equal(_):
        return over | (at & (jnp.cumsum(at, axis=-1) <= need[..., None]))

    return jax.lax.cond(jnp.any(at.sum(-1) > need), first_of_the_equal,
                        lambda _: over | at, None)


def select_rows(scores, valid, k: int):
    """scores (B, S) float32, valid (B, S) bool -> (rows (B, k) int32,
    chosen (B, k) bool): each lane's ``k`` largest scores among its
    valid rows, by index; where a lane has fewer, ``chosen`` is False at
    the places that name no row of it. Of equal scores the lower index
    first (``jax.lax.top_k``)."""
    k = min(k, scores.shape[-1])
    # ``-0.0`` taken as ``0.0``, as ``select_mask`` takes it
    top, rows = jax.lax.top_k(jnp.where(valid, scores + 0.0, -jnp.inf), k)
    return rows.astype(jnp.int32), top > -jnp.inf


_PLANE = 128


def said_words(rows: int) -> int:
    """The words of a set over ``rows`` cache rows."""
    return -(-rows // (32 * _PLANE)) * _PLANE


def mask_as_bits(mask, words: int):
    """mask (..., S) bool -> (..., words) uint32, ``words`` at least
    ``said_words(S)`` (the module docstring has the order of the bits;
    zeros behind the rows' own words)."""
    lead, S = mask.shape[:-1], mask.shape[-1]
    mine = said_words(S)
    mask = jnp.pad(mask, [(0, 0)] * len(lead) + [(0, 32 * mine - S)])
    bit = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    bits = jnp.where(mask.reshape(*lead, -1, 32, _PLANE), bit[:, None],
                     jnp.uint32(0)).sum(-2, dtype=jnp.uint32)
    return jnp.pad(bits.reshape(*lead, mine),
                   [(0, 0)] * len(lead) + [(0, words - mine)])


def rows_as_bits(rows, chosen, words: int):
    """``select_rows``' two results (B, k) -> (B, words) uint32: the
    same set as ``mask_as_bits`` would give of its mask."""
    B = rows.shape[0]
    group, r = rows // (32 * _PLANE), rows % (32 * _PLANE)
    bit = jnp.where(chosen, jnp.uint32(1) << (r // _PLANE).astype(
        jnp.uint32), jnp.uint32(0))
    return jnp.zeros((B, words), jnp.uint32).at[
        jnp.arange(B)[:, None], group * _PLANE + r % _PLANE].add(bit)
