"""Pallas TPU flash-attention kernel (forward + FlashAttention-2 backward).

Hand-tiled MXU implementation of the online-softmax attention in
``ray_tpu.ops.attention`` — same semantics (causal, GQA), O(S) memory,
logits never materialized in HBM. ``ops.attention.flash_attention``
dispatches to this kernel; the XLA blockwise formulation there is the
numerical reference in tests/test_pallas_attention.py.

Reference parity note: the reference (Ray) has no attention kernels at
all (SURVEY.md §5.7 — delegated to vLLM/torch); this is TPU-native
net-new capability, required to hit the BASELINE.md MFU bar.

Layout contract (matches ray_tpu.models):
    q (B, S, H, hd); k/v (B, T, KVH, hd), H = G * KVH.
Internally transposed to head-major (B, H, S, hd) so the kernel tiles
(S, hd) blocks onto the MXU with hd on the 128-lane axis.

Design notes:
- Grid (B, H, q_blocks, kv_blocks), kv innermost and "arbitrary"; the
  online-softmax state (m, l, acc) lives in VMEM scratch carried across
  kv steps; output written once on each row's last visible kv block.
- Causal blocks strictly above the diagonal are skipped with pl.when —
  ~2x fewer MXU ops at long seq, same skip the backward kernels use.
- Backward follows FlashAttention-2: saved (o, lse) + recomputed p per
  tile; dkv kernel accumulates over q blocks, dq kernel over kv blocks.
  GQA group-summing of dk/dv happens outside the kernel (per-q-head
  partials), trading a small HBM buffer for race-free accumulation.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_NEG_INF = float("-inf")


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
# forward
# ----------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_kv, nk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_start = qi * block_q
    kv_start = ki * block_kv

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: a block strictly above the diagonal contributes nothing
    visible = (q_start + block_q - 1 >= kv_start) if causal else True

    @pl.when(visible)
    def _accumulate():
        q = q_ref[0, 0]                       # (block_q, hd)
        k = k_ref[0, 0]                       # (block_kv, hd)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                             # (block_q, block_kv) f32
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            cols = kv_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_ref[:]                     # (block_q, LANES)
        blk_max = jnp.max(s, axis=1, keepdims=True)      # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(blk_max, m_prev.shape))
        # rows with nothing visible yet: compute exp against 0, carry -inf
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[:, :1])        # masked cols: exp(-inf)=0
        corr = jnp.where(
            jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_ref[:] = l_ref[:] * corr + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), corr.shape)
        acc_ref[:] = acc_ref[:] * corr[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    # last kv block whose columns any row of this q block can see
    if causal:
        last_ki = jnp.minimum(nk - 1, (q_start + block_q - 1) // block_kv)
    else:
        last_ki = nk - 1

    @pl.when(ki == last_ki)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # lane-broadcast (bq, LANES) layout — Mosaic requires the last
        # two block dims to tile (8, 128), so scalar-per-row stats ride
        # a full lane vector (same layout the stock jax kernel uses)
        lse_ref[0, 0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _fwd(q, k, v, causal, block_q, block_kv):
    """q (B,H,S,hd), k/v (B,KVH,T,hd) -> o (B,H,S,hd), lse (B,H,S) f32."""
    B, H, S, hd = q.shape
    KVH, T = k.shape[1], k.shape[2]
    G = H // KVH
    bq = _pick_block(S, block_q)
    bkv = _pick_block(T, block_kv)
    nq, nk = S // bq, T // bkv
    scale = 1.0 / math.sqrt(hd)

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_kv=bkv, nk=nk,
    )
    flops_per_bh = 4 * S * T * hd * (0.5 if causal else 1.0)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, _LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(B * H * flops_per_bh),
            bytes_accessed=int(
                q.size * q.dtype.itemsize + 2 * k.size * k.dtype.itemsize
                + q.size * q.dtype.itemsize),
            transcendentals=int(B * H * S * T * (0.5 if causal else 1.0)),
        ),
        interpret=_interpret(),
        name="flash_attention_fwd",
    )(q, k, v)
    return o, lse


# ----------------------------------------------------------------------
# backward (FlashAttention-2)
# ----------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, block_q, block_kv, nq):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    q_start = qi * block_q
    kv_start = ki * block_kv

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    visible = (q_start + block_q - 1 >= kv_start) if causal else True

    @pl.when(visible)
    def _accumulate():
        q = q_ref[0, 0]                       # (block_q, hd)
        k = k_ref[0, 0]                       # (block_kv, hd)
        v = v_ref[0, 0]
        do = do_ref[0, 0]                     # (block_q, hd)
        lse = lse_ref[0, 0][:, :1]            # (block_q, 1)
        delta = delta_ref[0, 0][:, :1]        # (block_q, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            cols = kv_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)                  # (block_q, block_kv)
        # dv += p^T @ do
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dp = do @ v^T ; ds = p * (dp - delta) * scale
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc,
                   *, scale, causal, block_q, block_kv, nk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_start = qi * block_q
    kv_start = ki * block_kv

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    visible = (q_start + block_q - 1 >= kv_start) if causal else True

    @pl.when(visible)
    def _accumulate():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            cols = kv_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        last_ki = jnp.minimum(nk - 1, (q_start + block_q - 1) // block_kv)
    else:
        last_ki = nk - 1

    @pl.when(ki == last_ki)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd(q, k, v, o, lse, do, causal, block_q, block_kv):
    B, H, S, hd = q.shape
    KVH, T = k.shape[1], k.shape[2]
    G = H // KVH
    bq = _pick_block(S, block_q)
    bkv = _pick_block(T, block_kv)
    nq, nk = S // bq, T // bkv
    scale = 1.0 / math.sqrt(hd)

    # delta_i = rowsum(dO_i * O_i) — cheap elementwise reduce, XLA
    # fuses it; lane-broadcast to match the lse layout
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                keepdims=True),
        lse.shape,
    )

    common_in_specs = [
        pl.BlockSpec((1, 1, bq, hd), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bkv, hd), lambda b, h, j, i: (b, h // G, j, 0)),
        pl.BlockSpec((1, 1, bkv, hd), lambda b, h, j, i: (b, h // G, j, 0)),
        pl.BlockSpec((1, 1, bq, hd), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bq, _LANES), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bq, _LANES), lambda b, h, j, i: (b, h, i, 0)),
    ]
    # dk/dv accumulated per q-head (B, H, T, hd); summed over the GQA
    # group below — keeps the kernel write sets disjoint
    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=bq, block_kv=bkv, nq=nq,
        ),
        grid=(B, H, nk, nq),
        in_specs=common_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, hd), k.dtype),
            jax.ShapeDtypeStruct((B, H, T, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, hd), jnp.float32),
            pltpu.VMEM((bkv, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=_interpret(),
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse, delta)
    if G > 1:
        dk = dk_h.reshape(B, KVH, G, T, hd).sum(axis=2).astype(k.dtype)
        dv = dv_h.reshape(B, KVH, G, T, hd).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_h, dv_h

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            block_q=bq, block_kv=bkv, nk=nk,
        ),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, _LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, _LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=_interpret(),
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ----------------------------------------------------------------------
# public API
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, block_q, block_kv):
    o, _ = _fwd(q, k, v, causal, block_q, block_kv)
    return o


def _flash_fwd(q, k, v, causal, block_q, block_kv):
    o, lse = _fwd(q, k, v, causal, block_q, block_kv)
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
    not tile (see ``untileable``)."""
    reason = untileable(q, k, v)
    if reason is not None:
        raise NotImplementedError(reason)
    qt = q.transpose(0, 2, 1, 3)          # (B, H, S, hd)
    kt = k.transpose(0, 2, 1, 3)          # (B, KVH, T, hd)
    vt = v.transpose(0, 2, 1, 3)
    o = _flash(qt, kt, vt, causal, block_q, block_kv)
    return o.transpose(0, 2, 1, 3)
