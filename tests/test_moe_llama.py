"""MoE Llama model family (models/moe_llama.py): routed-FFN transformer
with expert-parallel shardings, trained and sharded on the virtual
8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import MOE_TINY, moe_llama


@pytest.fixture(scope="module")
def params():
    return moe_llama.init_params(jax.random.PRNGKey(0), MOE_TINY)


def test_forward_shapes_and_finiteness(params):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, MOE_TINY.vocab_size)
    logits, aux = jax.jit(
        lambda p, t: moe_llama.forward(p, t, MOE_TINY)
    )(params, tokens)
    assert logits.shape == (2, 16, MOE_TINY.vocab_size)
    assert jnp.isfinite(logits).all()
    assert jnp.isfinite(aux) and aux > 0  # load-balance loss is positive


def test_param_counts():
    total = moe_llama.param_count(MOE_TINY)
    active = moe_llama.active_param_count(MOE_TINY)
    leaves = jax.tree.leaves(moe_llama.init_params(jax.random.PRNGKey(0), MOE_TINY))
    assert total == sum(int(np.prod(l.shape)) for l in leaves)
    # top-2 of 4 experts: active params strictly fewer than total
    assert active < total


def test_training_reduces_loss(params):
    import optax

    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 33), 0, MOE_TINY.vocab_size)
    batch = {"tokens": tokens}
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, s):
        loss, grads = jax.value_and_grad(
            lambda p_: moe_llama.loss_fn(p_, batch, MOE_TINY)
        )(p)
        updates, s = opt.update(grads, s)
        return optax.apply_updates(p, updates), s, loss

    p = params
    first = None
    for _ in range(12):
        p, opt_state, loss = step(p, opt_state)
        if first is None:
            first = float(loss)
    assert float(loss) < first - 0.3, (first, float(loss))


def test_expert_parallel_sharded_forward(params):
    """Experts sharded over a real `expert` mesh axis; GSPMD inserts the
    dispatch all-to-all. Output must match the unsharded forward."""
    import dataclasses

    # float32 activations: sharding must be value-preserving, and fp32
    # keeps GSPMD's different reduction orders within tight tolerance
    # (bf16 reordering noise would swamp the comparison)
    cfg = dataclasses.replace(MOE_TINY, dtype=jnp.float32)
    devices = np.asarray(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devices, ("fsdp", "expert"))
    # MOE_TINY has 4 experts -> 1 per expert-mesh column
    specs = moe_llama.param_specs(cfg)

    def shard_spec(spec):
        # drop axes this 2-axis test mesh doesn't have
        return P(*(
            ax if ax in ("fsdp", "expert") else None
            for ax in (tuple(spec) if spec else ())
        ))

    sharded = jax.tree.map(
        lambda arr, spec: jax.device_put(
            arr, NamedSharding(mesh, shard_spec(spec))
        ),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, P) or not isinstance(x, dict),
    )
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, cfg.vocab_size)
    ref_logits, ref_aux = jax.jit(
        lambda p, t: moe_llama.forward(p, t, cfg)
    )(params, tokens)
    with mesh:
        out_logits, out_aux = jax.jit(
            lambda p, t: moe_llama.forward(p, t, cfg)
        )(sharded, tokens)
    np.testing.assert_allclose(
        np.asarray(out_logits), np.asarray(ref_logits), atol=2e-4
    )
    np.testing.assert_allclose(float(out_aux), float(ref_aux), rtol=1e-4)


def test_pad_tokens_excluded_from_moe():
    """Masked tokens get no expert (zero output) and are excluded from
    the load-balance statistics."""
    from ray_tpu.ops import MoEConfig, init_moe_params, moe_ffn

    cfg = MoEConfig(d_model=16, d_ff=32, n_experts=4, k=2)
    p = init_moe_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 16), jnp.float32)
    mask = jnp.asarray([[1, 1, 1, 1, 0, 0, 0, 0]], jnp.float32)
    out, aux = moe_ffn(p, x, cfg, mask=mask)
    np.testing.assert_allclose(np.asarray(out[0, 4:]), 0.0)
    # balance stats are pre-drop means over REAL tokens: identical to
    # running the unpadded prefix alone
    _, aux_ref = moe_ffn(p, x[:, :4], cfg)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)


def test_remat_keeps_flash_residuals_and_the_gradients():
    """The MoE block is checkpointed under the dense model's policy
    (``llama.remat_policy``): with the flash kernel in the shared
    attention sublayer its backward runs no second forward kernel, and
    loss and gradients are those of the program that keeps everything
    and of the one that keeps nothing."""
    import dataclasses

    from jaxpr_kernels import kernel_calls
    from ray_tpu.models import llama

    # head_dim 128, S = 128: the smallest shapes the kernel tiles
    cfg = dataclasses.replace(
        MOE_TINY, dim=256, n_heads=2, n_kv_heads=1, remat=True,
        attention_impl="flash", dtype=jnp.float32)
    params = moe_llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 129), 0, cfg.vocab_size)

    def grad_fn(c):
        return jax.value_and_grad(
            lambda p: moe_llama.loss_fn(p, {"tokens": tokens}, c))

    calls = kernel_calls(jax.make_jaxpr(grad_fn(cfg))(params))
    assert calls["flash_attention_fwd"] == calls["flash_attention_bwd_dkv"] == 1
    loss, grads = jax.jit(grad_fn(cfg))(params)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe_llama, "remat_policy",
                      lambda: jax.checkpoint_policies.nothing_saveable)
        calls = kernel_calls(jax.make_jaxpr(grad_fn(cfg))(params))
        assert calls["flash_attention_fwd"] == 2
        others = [jax.jit(grad_fn(cfg))(params)]
    others.append(jax.jit(grad_fn(dataclasses.replace(cfg, remat=False)))(params))
    for other_loss, other in others:
        np.testing.assert_allclose(loss, other_loss, rtol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
            grads, other)
    assert moe_llama.remat_policy is llama.remat_policy
