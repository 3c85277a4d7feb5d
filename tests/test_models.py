"""Model zoo tests (CPU, tiny configs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama


@pytest.fixture(scope="module")
def tiny_params():
    return llama.init_params(jax.random.PRNGKey(0), llama.LLAMA_TINY)


def test_forward_shapes(tiny_params):
    cfg = llama.LLAMA_TINY
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.forward(tiny_params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_param_count_matches(tiny_params):
    n = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(tiny_params))
    assert n == llama.param_count(llama.LLAMA_TINY)


def test_loss_near_uniform_at_init(tiny_params):
    cfg = llama.LLAMA_TINY
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size)
    loss = llama.loss_fn(tiny_params, {"tokens": tokens}, cfg)
    # random init ⇒ loss ≈ ln(vocab)
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.5


def test_loss_mask(tiny_params):
    cfg = llama.LLAMA_TINY
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab_size)
    mask = jnp.ones_like(tokens, jnp.float32)
    full = llama.loss_fn(tiny_params, {"tokens": tokens, "mask": mask}, cfg)
    half_mask = mask.at[:, 9:].set(0.0)
    half = llama.loss_fn(tiny_params, {"tokens": tokens, "mask": half_mask}, cfg)
    assert full.shape == () and half.shape == ()
    assert float(full) != float(half)


def test_causality(tiny_params):
    """Changing a future token must not change past logits."""
    cfg = llama.LLAMA_TINY
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0, cfg.vocab_size)
    logits_a = llama.forward(tiny_params, tokens, cfg)
    tokens_b = tokens.at[0, -1].set((tokens[0, -1] + 1) % cfg.vocab_size)
    logits_b = llama.forward(tiny_params, tokens_b, cfg)
    np.testing.assert_allclose(
        np.asarray(logits_a[0, :-1]), np.asarray(logits_b[0, :-1]),
        rtol=2e-2, atol=2e-2,
    )


def test_gqa_vs_mha_shapes():
    cfg = llama.LlamaConfig(
        vocab_size=64, dim=32, n_layers=1, n_heads=4, n_kv_heads=4,
        ffn_dim=64, remat=False, dtype=jnp.float32,
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    out = llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    assert out.shape == (1, 8, 64)


def test_training_reduces_loss():
    import optax
    cfg = llama.LlamaConfig(
        vocab_size=32, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
        ffn_dim=64, remat=False, dtype=jnp.float32,
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 17), 0, 32)
    batch = {"tokens": tokens}

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(llama.loss_fn)(params, batch, cfg)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    first = None
    for i in range(30):
        params, opt_state, loss = step(params, opt_state)
        if first is None:
            first = float(loss)
    assert float(loss) < first - 0.5, (first, float(loss))


def test_flash_attention_matches_xla():
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.models.llama import _attention_xla, LlamaConfig
    cfg = LlamaConfig(n_heads=4, n_kv_heads=2, dim=32)
    rng = jax.random.PRNGKey(0)
    B, S, H, KVH, hd = 2, 64, 4, 2, 16
    q = jax.random.normal(rng, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KVH, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KVH, hd), jnp.float32)
    ref = _attention_xla(q, k, v, cfg)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_kv=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_attention_in_model():
    import dataclasses
    cfg = dataclasses.replace(llama.LLAMA_TINY, attention_impl="flash", dtype=jnp.float32)
    cfg_ref = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)
    a = llama.forward(params, tokens, cfg)
    b = llama.forward(params, tokens, cfg_ref)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3)


def test_unknown_attention_impl_raises():
    import dataclasses
    cfg = dataclasses.replace(llama.LLAMA_TINY, attention_impl="bogus")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="attention_impl"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)


def test_ring_attention_impl_matches_xla():
    """attention_impl='ring' without a seq mesh falls back to flash and
    matches the xla einsum path; with a seq mesh it runs the ring (the
    multi-axis case is tests/test_chip_path.py)."""
    import dataclasses

    cfg = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
    cfg_ring = dataclasses.replace(cfg, attention_impl="ring")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    ref = llama.forward(params, toks, cfg)
    out = llama.forward(params, toks, cfg_ring)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-3)
