"""Pallas TPU flash-attention kernel (forward + FlashAttention-2 backward).

Hand-tiled MXU implementation of the online-softmax attention in
``ray_tpu.ops.attention`` — same semantics (causal, GQA), O(S) memory,
logits never materialized in HBM. ``ops.attention.flash_attention``
dispatches to this kernel; the XLA blockwise formulation there is the
numerical reference in tests/test_pallas_attention.py.

Reference parity note: the reference (Ray) has no attention kernels at
all (SURVEY.md §5.7 — delegated to vLLM/torch); this is TPU-native
net-new capability.

Layout contract (matches ray_tpu.models):
    q (B, S, H, hd); k/v (B, T, KVH, hd), H = G * KVH.
Internally transposed to head-major (B, H, S, hd) so the kernel tiles
(S, hd) blocks onto the MXU with hd on the 128-lane axis.

Design notes (the tile schedule is PR 38's; PERF.md §6 has the chip
readings behind each choice):
- Three kernels, `flash_attention_fwd`, `flash_attention_bwd_dkv` and
  `flash_attention_bwd_dq`, one call of each per attention per pass
  (FlashAttention-2: saved (o, lse), p recomputed per tile). Each grid
  step works on one (batch, kv head) and handles the G q heads of that
  group while their K and V (in `bwd_dkv`: their q and dO) are in
  VMEM, so K and V are fetched once a kv head, not once a q head.
- Only the (q tile, kv tile) pairs a causal row can see are visited.
  *Resident* form: the operand a kernel sweeps over (K and V for `fwd`
  and `bwd_dq`; q, dO and the row statistics for `bwd_dkv`) sits in
  VMEM whole, and the sweep is a `fori_loop` inside the kernel whose
  bounds follow from the tile's own index. *Streamed* form: the grid
  enumerates the visible pairs through scalar prefetch, so a tile
  above the diagonal costs no grid step and no fetch. ``_resident``
  picks by bytes, what would sit in VMEM against ``_RESIDENT_BYTES``;
  no option chooses. On a v5e the resident `fwd` takes 40 % less time
  than the streamed one, the two backward kernels 3-9 % less.
- Every visited tile of a causal call is masked (one iota difference,
  one compare, one select); non-causal calls never mask. Splitting
  the sweep into an unmasked interior and a masked diagonal read no
  faster in the backward kernels and 5-7 % slower in `fwd` on the
  chip (the vector ALU has room; a second loop has a cost), so it
  was left out. Every sweep starts at column 0, which every row sees:
  the running maximum is finite after the first tile and nothing
  guards against ``-inf - -inf``.
- `bwd_dkv` computes the transposed tile, ``K Q^T`` (bkv, bq): the row
  statistics broadcast along sublanes as dense (1, bq) rows, and
  ``dV += P^T dO`` / ``dK += dS^T Q`` are plain matmuls with no
  transpose of a (bq, bkv) tile. dK and dV accumulate over the q
  tiles *and* over the G heads of the group in VMEM scratch and are
  written once as (B, KVH, T, hd): no per-q-head partials, no XLA sum.
- Row statistics (lse, delta) are float32, carried dense as
  (B, KVH, G, 1, S): a (1, bq) row a tile instead of a lane-broadcast
  (bq, 128) one, a 128th of the bytes. `fwd` and `bwd_dq`, whose tiles
  want them as columns, turn them once a (q tile, head), a (bq, 128)
  transpose on the XLU.
- ``scale`` multiplies the float32 scores as before, so they are what
  they were; the backward's second ``* scale`` (on dS, a (bq, bkv)
  operand) moved onto the accumulators, (bq, hd) and (bkv, hd).
- The head-major transposes stay in ``pallas_flash_attention``:
  PERF.md §6 (PR 38) has the trace reading behind that.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ``jax.ad_checkpoint.checkpoint_name`` tags of the forward kernel's two
# results, o and lse, which the backward kernels take as residuals
SAVED_NAMES = ("flash_out", "flash_lse")

_LANES = 128
_NEG_INF = float("-inf")

# What a kernel may hold resident in VMEM, its double buffers counted,
# and what it asks Mosaic for in all. The smallest VMEM of the chips
# this runs on is a v5e's 128 MiB; the two train cells' shapes hold at
# most 4 and 16 MiB resident (`bwd_dkv`'s q and dO of a group).
_RESIDENT_BYTES = 24 * 2**20
_VMEM_LIMIT_BYTES = 64 * 2**20

# Rows of a tile, q and kv alike, in all three kernels, where the
# lengths and the caller's cap allow them: the best of 14 tile shapes a
# kernel on a v5e at the two train cells' shapes (PERF.md §6, PR 38)
_TILE_ROWS = 512

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _interpret() -> bool:
    # CPU has no Mosaic; interpret mode keeps the kernel testable on the
    # virtual device mesh. A worker that holds a chip runs with
    # JAX_PLATFORMS=tpu (_private/accelerators/tpu.py), so it can never
    # find itself here on "cpu".
    return jax.default_backend() == "cpu"


def _pick_block(size: int, preferred: int) -> int:
    for b in (preferred, 512, 256, 128):
        if b <= preferred and size % b == 0:
            return b
    raise NotImplementedError(f"sequence length {size} not a multiple of 128")


def _tiles(S: int, T: int, block_q: int, block_kv: int):
    """(q rows, kv rows) of a tile: the kernels' preference under the
    caller's cap."""
    return (_pick_block(S, min(block_q, _TILE_ROWS)),
            _pick_block(T, min(block_kv, _TILE_ROWS)))


def _resident(rows: int, hd: int, itemsize: int) -> bool:
    """Whether the two operands of (rows, hd) a kernel sweeps over fit
    in VMEM whole, each with the pipeline's second buffer."""
    return 4 * rows * hd * itemsize <= _RESIDENT_BYTES


def untileable(q, k, v):
    """Why the kernel cannot take these shapes, or None when it can."""
    B, S, H, hd = q.shape
    if k.shape != v.shape:
        return "k/v shape mismatch"
    Bk, T, KVH, hdk = k.shape
    if Bk != B or hdk != hd:
        return "q/k shape mismatch"
    if H % KVH != 0:
        return f"H={H} not divisible by KVH={KVH}"
    if hd % _LANES != 0:
        return f"head_dim={hd} not a multiple of {_LANES} (MXU lane width)"
    if S % _LANES != 0 or T % _LANES != 0:
        return f"sequence lengths {S}/{T} not multiples of {_LANES}"
    return None


# ----------------------------------------------------------------------
# the visible pairs
# ----------------------------------------------------------------------
# Row r sees column c when r >= c (absolute positions). A q tile sees
# the kv tiles [0, _kv_end); a kv tile is seen by the q tiles
# [_q_begin, nq). The same formulas run on the host (the streamed forms'
# pair lists) and on traced scalars inside the kernels.

def _kv_end(q_start, bq, bkv, nk, causal, lib=jnp):
    return lib.minimum((q_start + bq - 1) // bkv + 1, nk) if causal else nk


def _q_begin(kv_start, bq, nq, causal, lib=jnp):
    return lib.minimum(kv_start // bq, nq) if causal else 0


def _visible(q_start, kv_start, shape, q_dim, causal):
    """Where the tile's rows see its columns, or None where they all
    do. ``q_dim`` is the axis of ``shape`` that runs over q positions."""
    if not causal:
        return None
    d = (jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
         - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim))
    return d >= kv_start - q_start


def _to_row(col):
    """(n, 1) -> (1, n): through a lane-broadcast (n, 128) transpose,
    the relayout Mosaic has for it."""
    n = col.shape[0]
    return jnp.transpose(jnp.broadcast_to(col, (n, _LANES)))[:1]


def _to_col(row):
    """(1, n) -> (n, 1)."""
    n = row.shape[1]
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, n)))[:, :1]


# ----------------------------------------------------------------------
# one tile of each kernel
# ----------------------------------------------------------------------

def _fwd_tile(q, k, v, m, l, acc, scale, mask):
    s = jax.lax.dot_general(
        q, k, _NT, preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))   # (bq, 1)
    p = jnp.exp(s - m_new)                 # masked columns: exp(-inf) = 0
    corr = jnp.exp(m - m_new)              # first tile: exp(-inf) = 0
    l = l * corr + jnp.sum(p, axis=1, keepdims=True)
    acc = acc * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
    return m_new, l, acc


def _dq_tile(q, k, v, do, lse, delta, scale, mask):
    """The tile's share of dq, without its last ``* scale``."""
    s = jax.lax.dot_general(
        q, k, _NT, preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, v, _NT, preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    return jax.lax.dot_general(
        ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)


def _dkv_tile(q, k, v, do, lse, delta, scale, mask):
    """The tile's shares of (dk, dv), dk without its last ``* scale``.
    Everything (bkv, bq): ``lse`` and ``delta`` are (1, bq) rows."""
    st = jax.lax.dot_general(
        k, q, _NT, preferred_element_type=jnp.float32) * scale
    if mask is not None:
        st = jnp.where(mask, st, _NEG_INF)
    pt = jnp.exp(st - lse)
    dv = jax.lax.dot_general(
        pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
    dpt = jax.lax.dot_general(
        v, do, _NT, preferred_element_type=jnp.float32)
    dst = pt * (dpt - delta)
    dk = jax.lax.dot_general(
        dst.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)
    return dk, dv


def _rows(ref, start, size):
    """``size`` rows of a (rows, hd) block from ``start``, a multiple
    of ``size``."""
    return ref[pl.ds(pl.multiple_of(start, size), size), :]


def _each_head(G, body):
    """``body(g)`` for the G q heads of the group, as one rolled loop."""
    def step(g, carry):
        body(g)
        return carry

    jax.lax.fori_loop(0, G, step, 0)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

def _fwd_finish(o_ref, lse_ref, g, m, l, acc):
    o_ref[0, g] = (acc * (1.0 / l)).astype(o_ref.dtype)
    lse_ref[0, 0, g] = _to_row(m + jnp.log(l))


def _fwd_resident_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                         *, scale, causal, bq, bkv, nk, G):
    q_start = pl.program_id(2) * bq

    def head(g):
        q = q_ref[0, g]                       # (bq, hd)

        def tile(j, ml):
            kv_start = j * bkv
            m, l, acc = _fwd_tile(
                q, _rows(k_ref.at[0, 0], kv_start, bkv),
                _rows(v_ref.at[0, 0], kv_start, bkv), *ml, acc_ref[...],
                scale, _visible(q_start, kv_start, (bq, bkv), 0, causal))
            acc_ref[...] = acc
            return m, l

        acc_ref[...] = jnp.zeros_like(acc_ref)
        m, l = jax.lax.fori_loop(
            0, _kv_end(q_start, bq, bkv, nk, causal), tile,
            (jnp.full((bq, 1), _NEG_INF, jnp.float32),
             jnp.zeros((bq, 1), jnp.float32)))
        _fwd_finish(o_ref, lse_ref, g, m, l, acc_ref[...])

    _each_head(G, head)


def _fwd_streamed_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                         acc_ref, m_ref, l_ref,
                         *, scale, causal, bq, bkv, nk, G):
    p = pl.program_id(2)
    q_start, kv_start = qi_ref[p] * bq, ki_ref[p] * bkv

    @pl.when(kv_start == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    mask = _visible(q_start, kv_start, (bq, bkv), 0, causal)

    def head(g):
        m_ref[g], l_ref[g], acc_ref[g] = _fwd_tile(
            q_ref[0, g], k_ref[0, 0], v_ref[0, 0],
            m_ref[g], l_ref[g], acc_ref[g], scale, mask)

    _each_head(G, head)

    @pl.when(ki_ref[p] == _kv_end(q_start, bq, bkv, nk, causal) - 1)
    def _finalize():
        _each_head(G, lambda g: _fwd_finish(
            o_ref, lse_ref, g, m_ref[g], l_ref[g], acc_ref[g]))


def _pairs(nq, nk, bq, bkv, causal, by_kv=False):
    """The visible (q tile, kv tile) pairs as two int32 lists, for the
    streamed forms' scalar prefetch. A q tile's pairs together and its
    kv tiles ascending (the order `fwd` and `bwd_dq` accumulate in), or
    with ``by_kv`` a kv tile's together (`bwd_dkv`'s). A kv tile no row
    sees (causal, T > S) keeps one pair there, masked to nothing, so
    that its zeros are written."""
    if by_kv:
        pairs = [(i, j) for j in range(nk)
                 for i in range(min(_q_begin(j * bkv, bq, nq, causal, np),
                                    nq - 1), nq)]
    else:
        pairs = [(i, j) for i in range(nq)
                 for j in range(_kv_end(i * bq, bq, bkv, nk, causal, np))]
    return tuple(np.asarray(x, np.int32) for x in zip(*pairs))


def _call(kernel, name, *, grid, in_specs, out_specs, out_shape, scratch,
          prefetch=(), cost=None):
    """One of the three kernels: ``grid`` over (batch, kv head, tile or
    pair), the last axis the one scratch is carried along."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=cost,
        interpret=_interpret(),
        name=name,
    )


def _specs(resident, G, bq, bkv, hd, S, T, sweep_kv):
    """BlockSpecs of a q-like operand, a k-like one and a row statistic,
    for the grid (batch, kv head, tile) of the resident form, whose
    swept operands (k-like if ``sweep_kv``, else q-like) are whole, or
    (batch, kv head, pair) of the streamed form."""
    if not resident:
        q_at = lambda b, h, p, qi, ki: (b, h, qi[p], 0)
        kv_at = lambda b, h, p, qi, ki: (b, h, ki[p], 0)
        row_at = lambda b, h, p, qi, ki: (b, h, 0, 0, qi[p])
    elif sweep_kv:
        q_at = lambda b, h, i: (b, h, i, 0)
        kv_at = lambda b, h, i: (b, h, 0, 0)
        row_at = lambda b, h, i: (b, h, 0, 0, i)
        bkv = T
    else:
        q_at = lambda b, h, j: (b, h, 0, 0)
        kv_at = lambda b, h, j: (b, h, j, 0)
        row_at = lambda b, h, j: (b, h, 0, 0, 0)
        bq = S
    return (pl.BlockSpec((1, G, bq, hd), q_at),
            pl.BlockSpec((1, 1, bkv, hd), kv_at),
            pl.BlockSpec((1, 1, G, 1, bq), row_at))


def _fwd(q, k, v, causal, block_q, block_kv):
    """q (B,H,S,hd), k/v (B,KVH,T,hd) -> o (B,H,S,hd) and lse
    (B,KVH,G,1,S) f32, head h = kvh * G + g."""
    B, H, S, hd = q.shape
    KVH, T = k.shape[1], k.shape[2]
    G = H // KVH
    bq, bkv = _tiles(S, T, block_q, block_kv)
    nq, nk = S // bq, T // bkv
    static = dict(scale=1.0 / math.sqrt(hd), causal=causal,
                  bq=bq, bkv=bkv, nk=nk, G=G)
    share = 0.5 if causal else 1.0
    resident = _resident(T, hd, k.dtype.itemsize)
    q_spec, kv_spec, row_spec = _specs(resident, G, bq, bkv, hd, S, T, True)
    common = dict(
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
            jax.ShapeDtypeStruct((B, KVH, G, 1, S), jnp.float32),
        ],
        cost=pl.CostEstimate(
            flops=int(4 * B * H * S * T * hd * share),
            bytes_accessed=int(2 * q.size * q.dtype.itemsize
                               + 2 * k.size * k.dtype.itemsize),
            transcendentals=int(B * H * S * T * share),
        ),
    )
    if resident:
        return _call(
            functools.partial(_fwd_resident_kernel, **static),
            "flash_attention_fwd", grid=(B, KVH, nq),
            scratch=[pltpu.VMEM((bq, hd), jnp.float32)], **common,
        )(q, k, v)
    pairs = _pairs(nq, nk, bq, bkv, causal)
    return _call(
        functools.partial(_fwd_streamed_kernel, **static),
        "flash_attention_fwd", grid=(B, KVH, len(pairs[0])), prefetch=pairs,
        scratch=[pltpu.VMEM((G, bq, hd), jnp.float32),
                 pltpu.VMEM((G, bq, 1), jnp.float32),
                 pltpu.VMEM((G, bq, 1), jnp.float32)], **common,
    )(*pairs, q, k, v)


# ----------------------------------------------------------------------
# backward (FlashAttention-2)
# ----------------------------------------------------------------------

def _dkv_finish(dk_ref, dv_ref, dk_acc, dv_acc, scale):
    dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dkv_resident_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_acc, dv_acc,
                         *, scale, causal, bq, bkv, nq, G):
    kv_start = pl.program_id(2) * bkv
    k = k_ref[0, 0]                           # (bkv, hd)
    v = v_ref[0, 0]
    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)

    def head(g):
        def tile(i, _):
            q_start = pl.multiple_of(i * bq, bq)
            dk, dv = _dkv_tile(
                _rows(q_ref.at[0, g], q_start, bq), k, v,
                _rows(do_ref.at[0, g], q_start, bq),
                lse_ref[0, 0, g, :, pl.ds(q_start, bq)],
                delta_ref[0, 0, g, :, pl.ds(q_start, bq)], scale,
                _visible(q_start, kv_start, (bkv, bq), 1, causal))
            dk_acc[...] += dk
            dv_acc[...] += dv
            return 0

        jax.lax.fori_loop(_q_begin(kv_start, bq, nq, causal), nq, tile, 0)

    _each_head(G, head)
    _dkv_finish(dk_ref, dv_ref, dk_acc, dv_acc, scale)


def _dkv_streamed_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, do_ref,
                         lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                         *, scale, causal, bq, bkv, nq, G):
    p = pl.program_id(2)
    qi = qi_ref[p]
    q_start, kv_start = qi * bq, ki_ref[p] * bkv

    @pl.when(qi == jnp.minimum(_q_begin(kv_start, bq, nq, causal), nq - 1))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    mask = _visible(q_start, kv_start, (bkv, bq), 1, causal)

    def head(g):
        dk, dv = _dkv_tile(
            q_ref[0, g], k_ref[0, 0], v_ref[0, 0], do_ref[0, g],
            lse_ref[0, 0, g], delta_ref[0, 0, g], scale, mask)
        dk_acc[...] += dk
        dv_acc[...] += dv

    _each_head(G, head)
    pl.when(qi == nq - 1)(
        lambda: _dkv_finish(dk_ref, dv_ref, dk_acc, dv_acc, scale))


def _dq_resident_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_acc, *, scale, causal, bq, bkv, nk, G):
    q_start = pl.program_id(2) * bq

    def head(g):
        q = q_ref[0, g]
        do = do_ref[0, g]
        lse = _to_col(lse_ref[0, 0, g])       # (bq, 1)
        delta = _to_col(delta_ref[0, 0, g])

        def tile(j, _):
            kv_start = j * bkv
            dq_acc[...] += _dq_tile(
                q, _rows(k_ref.at[0, 0], kv_start, bkv),
                _rows(v_ref.at[0, 0], kv_start, bkv), do, lse, delta, scale,
                _visible(q_start, kv_start, (bq, bkv), 0, causal))
            return 0

        dq_acc[...] = jnp.zeros_like(dq_acc)
        jax.lax.fori_loop(0, _kv_end(q_start, bq, bkv, nk, causal), tile, 0)
        dq_ref[0, g] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    _each_head(G, head)


def _dq_streamed_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, do_ref,
                        lse_ref, delta_ref, dq_ref, dq_acc,
                        *, scale, causal, bq, bkv, nk, G):
    p = pl.program_id(2)
    q_start, kv_start = qi_ref[p] * bq, ki_ref[p] * bkv

    @pl.when(kv_start == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    mask = _visible(q_start, kv_start, (bq, bkv), 0, causal)

    def head(g):
        dq_acc[g] += _dq_tile(
            q_ref[0, g], k_ref[0, 0], v_ref[0, 0], do_ref[0, g],
            _to_col(lse_ref[0, 0, g]), _to_col(delta_ref[0, 0, g]),
            scale, mask)

    _each_head(G, head)

    @pl.when(ki_ref[p] == _kv_end(q_start, bq, bkv, nk, causal) - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv(q, k, v, do, lse, delta, causal, block_q, block_kv):
    B, H, S, hd = q.shape
    KVH, T = k.shape[1], k.shape[2]
    G = H // KVH
    bq, bkv = _tiles(S, T, block_q, block_kv)
    nq, nk = S // bq, T // bkv
    static = dict(scale=1.0 / math.sqrt(hd), causal=causal,
                  bq=bq, bkv=bkv, nq=nq, G=G)
    resident = _resident(G * S, hd, q.dtype.itemsize)
    q_spec, kv_spec, row_spec = _specs(resident, G, bq, bkv, hd, S, T, False)
    common = dict(
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch=[pltpu.VMEM((bkv, hd), jnp.float32),
                 pltpu.VMEM((bkv, hd), jnp.float32)],
    )
    if resident:
        return _call(
            functools.partial(_dkv_resident_kernel, **static),
            "flash_attention_bwd_dkv", grid=(B, KVH, nk), **common,
        )(q, k, v, do, lse, delta)
    pairs = _pairs(nq, nk, bq, bkv, causal, by_kv=True)
    return _call(
        functools.partial(_dkv_streamed_kernel, **static),
        "flash_attention_bwd_dkv", grid=(B, KVH, len(pairs[0])),
        prefetch=pairs, **common,
    )(*pairs, q, k, v, do, lse, delta)


def _bwd_dq(q, k, v, do, lse, delta, causal, block_q, block_kv):
    B, H, S, hd = q.shape
    KVH, T = k.shape[1], k.shape[2]
    G = H // KVH
    bq, bkv = _tiles(S, T, block_q, block_kv)
    nq, nk = S // bq, T // bkv
    static = dict(scale=1.0 / math.sqrt(hd), causal=causal,
                  bq=bq, bkv=bkv, nk=nk, G=G)
    resident = _resident(T, hd, k.dtype.itemsize)
    q_spec, kv_spec, row_spec = _specs(resident, G, bq, bkv, hd, S, T, True)
    common = dict(
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )
    if resident:
        return _call(
            functools.partial(_dq_resident_kernel, **static),
            "flash_attention_bwd_dq", grid=(B, KVH, nq),
            scratch=[pltpu.VMEM((bq, hd), jnp.float32)], **common,
        )(q, k, v, do, lse, delta)
    pairs = _pairs(nq, nk, bq, bkv, causal)
    return _call(
        functools.partial(_dq_streamed_kernel, **static),
        "flash_attention_bwd_dq", grid=(B, KVH, len(pairs[0])),
        prefetch=pairs, scratch=[pltpu.VMEM((G, bq, hd), jnp.float32)],
        **common,
    )(*pairs, q, k, v, do, lse, delta)


def _bwd(q, k, v, o, lse, do, causal, block_q, block_kv):
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise reduce, XLA
    # fuses it; laid out as lse is
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).reshape(lse.shape)
    dk, dv = _bwd_dkv(q, k, v, do, lse, delta, causal, block_q, block_kv)
    dq = _bwd_dq(q, k, v, do, lse, delta, causal, block_q, block_kv)
    return dq, dk, dv


# ----------------------------------------------------------------------
# public API
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, block_q, block_kv):
    o, _ = _fwd(q, k, v, causal, block_q, block_kv)
    return o


def _flash_fwd(q, k, v, causal, block_q, block_kv):
    # named so that a caller's ``jax.checkpoint`` can keep the two and
    # its backward not run ``_fwd`` again
    o, lse = map(checkpoint_name, _fwd(q, k, v, causal, block_q, block_kv),
                 SAVED_NAMES)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, block_q, block_kv, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, causal, block_q, block_kv)


_flash.defvjp(_flash_fwd, _flash_bwd)


def pallas_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    *,
    block_q: int = 512,
    block_kv: int = 512,
) -> jax.Array:
    """Flash attention on TPU via Pallas. q (B,S,H,hd), k/v (B,T,KVH,hd)
    -> (B,S,H,hd). Raises NotImplementedError for shapes the kernel does
    not tile (see ``untileable``). ``block_q``/``block_kv`` cap the tile
    sizes; under the cap each kernel picks its own."""
    reason = untileable(q, k, v)
    if reason is not None:
        raise NotImplementedError(reason)
    qt = q.transpose(0, 2, 1, 3)          # (B, H, S, hd)
    kt = k.transpose(0, 2, 1, 3)          # (B, KVH, T, hd)
    vt = v.transpose(0, 2, 1, 3)
    o = _flash(qt, kt, vt, causal, block_q, block_kv)
    return o.transpose(0, 2, 1, 3)
