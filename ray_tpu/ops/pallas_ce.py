"""Pallas TPU fused lm-head cross-entropy (forward + custom VJP).

The final-projection loss is the classic HBM hog: XLA materializes
(N, V) fp32 logits (N = B*S tokens, V = vocab) for softmax-CE — at
N=16k, V=128k that's an 8 GiB round trip per step. This kernel fuses
x @ W with an online logsumexp over vocab tiles, so only (N,) outputs
(lse, target logit) ever leave VMEM; the backward recomputes each
logits tile (one extra matmul each for dx and dW — FLOPs for
bandwidth, the flash-attention trade).

Reference parity note: the reference (Ray) ships no kernels (losses are
torch's, downstream); this is TPU-native net-new, same role as
ops/pallas_attention.py for the MFU bar.

Contract:
    x (N, D) bf16/f32, w (D, V), targets (N,) int32
    -> per-token losses (N,) f32 = lse_i - logit_i[target_i]
Masking/averaging stay with the caller (models.llama.masked_ce shape).
N must divide by the row block (128), V by the vocab block (512|256|128),
D is kept whole (fits VMEM alongside one vocab tile in bf16 for
D <= 8192).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ._partition import ambient_partition

_LANES = 128
_NEG_INF = float("-inf")


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pick_block(size: int, preferred: int) -> int:
    for b in (preferred, 512, 256, 128):
        if b <= preferred and size % b == 0:
            return b
    raise NotImplementedError(f"dimension {size} not a multiple of 128")


# ----------------------------------------------------------------------
# forward: online logsumexp over vocab tiles + target-logit gather
# ----------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, t_ref, lse_ref, tgt_ref, m_ref, l_ref, g_ref,
                *, block_n, block_v, nv):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        g_ref[:] = jnp.zeros_like(g_ref)

    x = x_ref[:]                                   # (block_n, D)
    w = w_ref[:]                                   # (D, block_v)
    s = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                              # (block_n, block_v) f32

    # online logsumexp
    m_prev = m_ref[:]                              # (block_n, LANES)
    blk_max = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(blk_max, m_prev.shape))
    p_sum = jnp.sum(jnp.exp(s - m_new[:, :1]), axis=1, keepdims=True)
    corr = jnp.exp(m_prev - m_new)
    l_ref[:] = l_ref[:] * corr + jnp.broadcast_to(p_sum, corr.shape)
    m_ref[:] = m_new

    # target logit: the one column (if any) matching this tile
    t = t_ref[:]                                   # (block_n, 1) int32
    cols = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)
    hit = cols == t                                # (block_n, block_v)
    g_ref[:] = g_ref[:] + jnp.broadcast_to(
        jnp.sum(jnp.where(hit, s, 0.0), axis=1, keepdims=True),
        g_ref.shape,
    )

    @pl.when(vi == nv - 1)
    def _finish():
        # per-row scalars leave lane-broadcast as (block_n, LANES):
        # Mosaic tiles a 1-D f32 operand by 128 where XLA lays it out
        # by 1024, and refuses the mismatch
        lse_ref[:] = m_ref[:] + jnp.log(l_ref[:])
        tgt_ref[:] = g_ref[:]


def _fwd_call(x, w, targets, block_n, block_v):
    N, D = x.shape
    V = w.shape[1]
    if N % block_n != 0:
        # silent floor-division here would drop tail rows
        raise NotImplementedError(
            f"N={N} not a multiple of the row block ({block_n}); pad the "
            "token dimension"
        )
    nv = V // block_v
    grid = (N // block_n, nv)
    kernel = functools.partial(
        _fwd_kernel, block_n=block_n, block_v=block_v, nv=nv
    )
    lse, tgt = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, D), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((D, block_v), lambda ni, vi: (0, vi)),
            pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, _LANES), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((block_n, _LANES), lambda ni, vi: (ni, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((N, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_n, _LANES), jnp.float32),  # running sum
            pltpu.VMEM((block_n, _LANES), jnp.float32),  # target logit
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="fused_ce_fwd",
    )(x, w, targets[:, None].astype(jnp.int32))
    return lse[:, 0], tgt[:, 0]


# ----------------------------------------------------------------------
# backward: recompute each logits tile; dlogits = (softmax - onehot) * g
# ----------------------------------------------------------------------

def _dx_kernel(x_ref, w_ref, t_ref, lse_ref, gin_ref, dx_ref, acc_ref,
               *, block_n, block_v, nv):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:]
    w = w_ref[:]                                   # (D, block_v)
    s = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    p = jnp.exp(s - lse_ref[:, :1])                # softmax tile
    t = t_ref[:]
    cols = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)
    dlog = (p - jnp.where(cols == t, 1.0, 0.0)) * gin_ref[:, :1]
    acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
        dlog.astype(w.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                              # (block_n, D)

    @pl.when(vi == nv - 1)
    def _finish():
        dx_ref[:] = acc_ref[:].astype(dx_ref.dtype)


def _dw_kernel(x_ref, w_ref, t_ref, lse_ref, gin_ref, dw_ref, acc_ref,
               *, block_n, block_v, nn):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:]                                   # (block_n, D)
    w = w_ref[:]                                   # (D, block_v)
    s = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    p = jnp.exp(s - lse_ref[:, :1])
    t = t_ref[:]
    vi = pl.program_id(0)
    cols = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], s.shape[1]), 1)
    dlog = (p - jnp.where(cols == t, 1.0, 0.0)) * gin_ref[:, :1]
    acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
        x, dlog.astype(x.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                              # (D, block_v)

    @pl.when(ni == nn - 1)
    def _finish():
        dw_ref[:] = acc_ref[:].astype(dw_ref.dtype)


def _bwd_call(x, w, targets, lse, g, block_n, block_v):
    N, D = x.shape
    V = w.shape[1]
    nv = V // block_v
    nn = N // block_n
    t2 = targets[:, None].astype(jnp.int32)
    # lane-broadcast the per-row inputs (see _fwd_kernel._finish)
    lse = jnp.broadcast_to(lse[:, None], (N, _LANES))
    g = jnp.broadcast_to(g[:, None], (N, _LANES))

    dx = pl.pallas_call(
        functools.partial(
            _dx_kernel, block_n=block_n, block_v=block_v, nv=nv
        ),
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((D, block_v), lambda ni, vi: (0, vi)),
            pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((block_n, _LANES), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((block_n, _LANES), lambda ni, vi: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, D), lambda ni, vi: (ni, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="fused_ce_bwd_dx",
    )(x, w, t2, lse, g)

    dw = pl.pallas_call(
        functools.partial(
            _dw_kernel, block_n=block_n, block_v=block_v, nn=nn
        ),
        grid=(nv, nn),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda vi, ni: (ni, 0)),
            pl.BlockSpec((D, block_v), lambda vi, ni: (0, vi)),
            pl.BlockSpec((block_n, 1), lambda vi, ni: (ni, 0)),
            pl.BlockSpec((block_n, _LANES), lambda vi, ni: (ni, 0)),
            pl.BlockSpec((block_n, _LANES), lambda vi, ni: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((D, block_v), lambda vi, ni: (0, vi)),
        out_shape=jax.ShapeDtypeStruct((D, V), w.dtype),
        scratch_shapes=[pltpu.VMEM((D, block_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="fused_ce_bwd_dw",
    )(x, w, t2, lse, g)
    return dx, dw


# ----------------------------------------------------------------------
# public API with custom VJP
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused(x, w, targets, block_n, block_v):
    lse, tgt = _fwd_call(x, w, targets, block_n, block_v)
    return lse - tgt


def _vjp_fwd(x, w, targets, block_n, block_v):
    lse, tgt = _fwd_call(x, w, targets, block_n, block_v)
    return lse - tgt, (x, w, targets, lse)


def _vjp_bwd(block_n, block_v, res, g):
    x, w, targets, lse = res
    dx, dw = _bwd_call(x, w, targets, lse, g.astype(jnp.float32),
                       block_n, block_v)
    return dx, dw, None


_fused.defvjp(_vjp_fwd, _vjp_bwd)


def fused_cross_entropy(x, w, targets, block_n: int = 128,
                        block_v: int = 512):
    """Per-token losses (N,) f32 for logits = x @ w against targets.

    Out-of-range targets are clamped into [0, V) to match the XLA
    path's gather semantics (jnp.take_along_axis clamps under jit);
    without the clamp the kernel's one-hot match would silently miss
    and return lse instead of a real loss.

    Under an ambient mesh the rows are split over (data, fsdp) and
    ``w`` is gathered whole onto every shard (ops/_partition.py); the
    transpose of that gather sums dW over the shards."""
    targets = jnp.clip(targets, 0, w.shape[1] - 1)
    kernel = functools.partial(
        _fused, block_n=block_n, block_v=_pick_block(w.shape[1], block_v)
    )
    part = ambient_partition()
    if part is None:
        return kernel(x, w, targets)
    rows = P(part.batch)
    return jax.shard_map(
        kernel, in_specs=(P(part.batch, None), P(None, None), rows),
        out_specs=rows, axis_names=part.axes, check_vma=False,
    )(x, w, targets)


def xla_cross_entropy(x, w, targets):
    """Reference path: materialized logits + log_softmax (what XLA does
    for models.llama.loss_fn today)."""
    logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(
        logp, targets[:, None].astype(jnp.int32), axis=1
    )[:, 0]
