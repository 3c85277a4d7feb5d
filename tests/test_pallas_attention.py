"""Numerical equivalence of the Pallas flash-attention kernel against
the XLA blockwise reference (ops.attention) and against naive softmax
attention — forward and gradients. Runs in Pallas interpret mode on the
CPU mesh; the same kernel compiles via Mosaic on TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import pallas_attention
from ray_tpu.ops.attention import blockwise_attention
from ray_tpu.ops.pallas_attention import pallas_flash_attention


def _naive(q, k, v, causal=True):
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    qg = q.reshape(B, S, KVH, G, hd).astype(jnp.float32)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, k.astype(jnp.float32))
    logits /= jnp.sqrt(hd).astype(jnp.float32)
    if causal:
        T = k.shape[1]
        mask = jnp.arange(S)[:, None] >= jnp.arange(T)[None, :]
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)


@pytest.mark.parametrize("causal,kvh", [(True, 4), (True, 1), (False, 2)])
def test_forward_matches_reference(causal, kvh):
    B, S, H, hd = 2, 256, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand((B, S, H, hd), ks[0])
    k = _rand((B, S, kvh, hd), ks[1])
    v = _rand((B, S, kvh, hd), ks[2])
    out = pallas_flash_attention(q, k, v, causal, block_q=128, block_kv=128)
    ref = _naive(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    blockwise = blockwise_attention(q, k, v, causal=causal,
                                    block_q=128, block_kv=128)
    np.testing.assert_allclose(out, blockwise, atol=2e-5, rtol=2e-5)


def test_grads_match_reference():
    B, S, H, hd = 1, 256, 4, 128
    kvh = 2
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand((B, S, H, hd), ks[0])
    k = _rand((B, S, kvh, hd), ks[1])
    v = _rand((B, S, kvh, hd), ks[2])

    def loss_pallas(q, k, v):
        o = pallas_flash_attention(q, k, v, True, block_q=128, block_kv=128)
        return jnp.sum(o * jnp.cos(o))

    def loss_naive(q, k, v):
        o = _naive(q, k, v, True)
        return jnp.sum(o * jnp.cos(o))

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gn, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


def test_bf16_close_to_fp32():
    B, S, H, hd = 1, 256, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q32 = _rand((B, S, H, hd), ks[0])
    k32 = _rand((B, S, H, hd), ks[1])
    v32 = _rand((B, S, H, hd), ks[2])
    out16 = pallas_flash_attention(
        q32.astype(jnp.bfloat16), k32.astype(jnp.bfloat16),
        v32.astype(jnp.bfloat16), True, block_q=128, block_kv=128)
    ref = _naive(q32, k32, v32, True)
    assert out16.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        out16.astype(jnp.float32), ref, atol=4e-2, rtol=4e-2)


def test_rejects_untileable_shapes():
    q = jnp.zeros((1, 256, 2, 64))  # head_dim 64 < lane width
    with pytest.raises(NotImplementedError):
        pallas_flash_attention(q, q, q, True)
    q = jnp.zeros((1, 100, 2, 128))  # seq not a multiple of 128
    with pytest.raises(NotImplementedError):
        pallas_flash_attention(q, q, q, True)


def test_uneven_q_kv_lengths():
    # cross-attention style: T != S (non-causal)
    B, S, T, H, hd = 1, 128, 384, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand((B, S, H, hd), ks[0])
    k = _rand((B, T, H, hd), ks[1])
    v = _rand((B, T, H, hd), ks[2])
    out = pallas_flash_attention(q, k, v, False, block_q=128, block_kv=128)
    ref = _naive(q, k, v, False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------------
# the tile schedule (PR 38): which pairs are visited, where the mask
# runs, the group reduction inside bwd_dkv, resident and streamed forms
# ----------------------------------------------------------------------

@pytest.fixture
def form(request, monkeypatch):
    """Both forms of every kernel at shapes the resident one would take:
    with no VMEM to hold an operand whole, ``_resident`` streams."""
    if request.param == "streamed":
        monkeypatch.setattr(pallas_attention, "_RESIDENT_BYTES", 0)
    return request.param


both_forms = pytest.mark.parametrize(
    "form", ["resident", "streamed"], indirect=True)


def _check(q, k, v, causal, block_q, block_kv):
    """Forward and all three gradients against ``_naive``, at the
    tolerances of the tests above."""
    def loss(attend):
        def f(q, k, v):
            o = attend(q, k, v)
            return jnp.sum(o * jnp.cos(o)), o
        return f

    kernel = loss(lambda q, k, v: pallas_flash_attention(
        q, k, v, causal, block_q=block_q, block_kv=block_kv))
    naive = loss(lambda q, k, v: _naive(q, k, v, causal))
    gp, out = jax.grad(kernel, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    gn, ref = jax.grad(naive, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    for a, b, name in zip(gp, gn, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


def _qkv(seed, B, S, T, H, kvh, hd=128):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (_rand((B, S, H, hd), ks[0]), _rand((B, T, kvh, hd), ks[1]),
            _rand((B, T, kvh, hd), ks[2]))


@both_forms
@pytest.mark.parametrize("block_q,block_kv",
                         [(128, 128), (256, 128), (128, 256)])
def test_causal_tile_schedule(form, block_q, block_kv):
    # 4 x 4 tiles of 128 rows: tiles below the diagonal run unmasked,
    # the diagonal's masked, those above it are never visited; uneven
    # tiles put two kv tiles, or half of one, on a q tile's diagonal
    _check(*_qkv(4, 1, 512, 512, 2, 1), True, block_q, block_kv)


@both_forms
@pytest.mark.parametrize("heads,kvh", [(8, 2), (4, 2), (2, 2), (4, 1)])
def test_group_summed_inside_dkv(form, heads, kvh):
    # dk and dv of a kv head are the sum over its G = 4, 2, 1, 4 q
    # heads, accumulated in the kernel's scratch and written once
    _check(*_qkv(5, 2, 256, 256, heads, kvh), True, 128, 128)


@both_forms
@pytest.mark.parametrize("causal,S,T", [
    (False, 128, 384), (False, 384, 128), (True, 128, 384), (True, 384, 128),
])
def test_uneven_lengths_all_gradients(form, causal, S, T):
    # causal with T > S: kv tiles no row sees get zero dk and dv
    _check(*_qkv(6, 1, S, T, 4, 2), causal, 128, 128)


def test_streamed_by_shape():
    # 8 MB of K, V, q and dO a head: more than a kernel holds resident,
    # so all three kernels enumerate their visible pairs instead
    B, S, H, hd = 1, 2048, 1, 1024
    assert not pallas_attention._resident(S, hd, 4)
    assert pallas_attention._resident(2048, 128, 2)
    _check(*_qkv(7, B, S, S, H, H, hd), True, 512, 512)


# ----------------------------------------------------------------------
# the forward rule names its two residuals (PR 43): a caller's
# jax.checkpoint may keep them and spare the second forward kernel
# ----------------------------------------------------------------------

@both_forms
def test_named_residuals_spare_the_second_forward(form):
    from jaxpr_kernels import kernel_calls

    policies = jax.checkpoint_policies
    q, k, v = _qkv(8, 1, 256, 256, 4, 2)

    def grads(policy):
        attend = jax.checkpoint(
            lambda q, k, v: pallas_flash_attention(q, k, v, True),
            policy=policy)
        grad = jax.grad(lambda *qkv: jnp.sum(jnp.sin(attend(*qkv))),
                        argnums=(0, 1, 2))
        return kernel_calls(jax.make_jaxpr(grad)(q, k, v)), grad(q, k, v)

    calls, kept = grads(
        policies.save_only_these_names(*pallas_attention.SAVED_NAMES))
    calls_all, recomputed = grads(policies.nothing_saveable)
    backward = {"flash_attention_bwd_dkv": 1, "flash_attention_bwd_dq": 1}
    assert calls == {"flash_attention_fwd": 1, **backward}
    assert calls_all == {"flash_attention_fwd": 2, **backward}
    # the kept o and lse are the ones a second forward would produce
    for a, b in zip(kept, recomputed):
        np.testing.assert_array_equal(a, b)
    # each name alone keeps one residual of the two: the kernel runs again
    for name in pallas_attention.SAVED_NAMES:
        calls_one, _ = grads(policies.save_only_these_names(name))
        assert calls_one["flash_attention_fwd"] == 2, name
