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


# ----------------------------------------------------------------------
# remat keeps the flash kernel's output and row statistics by name
# (PR 43): the backward does not run the forward kernel again
# ----------------------------------------------------------------------

# the smallest shapes the kernel tiles: head_dim 128, S = 128; G = 2 so
# that k and v (B, KVH, S, hd) differ in shape from q and o (B, H, S, hd)
_REMAT_CFG = llama.LlamaConfig(
    vocab_size=256, dim=512, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=256, max_seq_len=128, rope_theta=10000.0, remat=True,
    dtype=jnp.float32,
)
_NOTHING = jax.checkpoint_policies.nothing_saveable


def _kept(fn, *args):
    """Elements of each array ``fn``'s backward is handed besides
    ``fn``'s own arguments, from ``print_saved_residuals``' lines
    (``f32[4,4,128,128] named ...``). A residual kept inside a
    shard_map is listed with its shards stacked: the same elements."""
    import contextlib
    import io
    import math

    from jax.ad_checkpoint import print_saved_residuals

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        print_saved_residuals(fn, *args)
    return sorted(
        math.prod(map(int, line[line.index("[") + 1:line.index("]")].split(",")))
        for line in out.getvalue().splitlines()
        if "from the argument" not in line)


@pytest.mark.parametrize("impl,meshed", [
    ("flash", False),
    ("flash", True),   # the kernel inside shard_map, as fsdp runs it
    ("ring", False),   # no seq axis: falls back to the flash kernel
    ("xla", False),
    ("xla", True),
])
def test_remat_keeps_flash_residuals_by_name(impl, meshed, monkeypatch):
    import contextlib
    import dataclasses
    from functools import partial

    from jaxpr_kernels import kernel_calls, scans
    from ray_tpu import parallel

    cfg = dataclasses.replace(_REMAT_CFG, attention_impl=impl)
    B, S, H, hd = 4, 128, cfg.n_heads, cfg.head_dim
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab_size)
    mesh = parallel.make_mesh(fsdp=4, model=2) if meshed else None
    if meshed:  # the program's devices are those its arguments live on
        from jax.sharding import NamedSharding, PartitionSpec as P

        params = jax.device_put(params, NamedSharding(mesh, P()))
        tokens = jax.device_put(tokens, parallel.batch_sharding(mesh))

    def ambient():
        return (jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
                if meshed else contextlib.nullcontext())

    def grad_fn(c):
        def f(params, tokens):
            with ambient():
                return jax.value_and_grad(
                    lambda p: llama.loss_fn(p, {"tokens": tokens}, c))(params)
        return f

    def block(policy):
        blk = jax.checkpoint(partial(llama.block_fn, cfg), policy=policy)

        def f(x, layer, cos, sin):
            with ambient():
                return blk(x, layer, cos, sin)
        return f

    def readings():
        """[forward kernels, backward kernels] in each scan of the
        gradient program that calls a kernel, what one checkpointed
        block keeps, and the loss and gradients."""
        f = grad_fn(cfg)
        calls = [kernel_calls(body)
                 for body in scans(jax.make_jaxpr(f)(params, tokens))]
        calls = [[c["flash_attention_fwd"], c["flash_attention_bwd_dkv"]]
                 for c in calls if c]
        layer = jax.tree.map(lambda a: a[0], params["blocks"])
        x = jnp.zeros((B, S, cfg.dim), cfg.dtype)
        kept = _kept(block(llama.remat_policy()), x, layer,
                     *llama.rope_table(cfg, S))
        return calls, kept, jax.jit(f)(params, tokens)

    calls, kept, (loss, grads) = readings()
    monkeypatch.setattr(llama, "remat_policy", lambda: _NOTHING)
    calls_parent, kept_parent, (loss_parent, grads_parent) = readings()
    loss_plain, grads_plain = jax.jit(
        grad_fn(dataclasses.replace(cfg, remat=False)))(params, tokens)

    assert kept_parent == []  # the parent's block keeps its arguments alone
    if impl != "xla":
        # the forward scan, then the backward's: the parent's backward
        # runs the forward kernel a second time
        assert calls == [[1, 0], [0, 1]]
        assert calls_parent == [[1, 0], [1, 1]]
        # lse and o: one array of q's size, not two, none of k's and v's
        assert kept == [B * H * S, B * H * S * hd]
    else:
        assert calls == calls_parent == []
        assert kept == kept_parent

    for other_loss, other in ((loss_parent, grads_parent), (loss_plain, grads_plain)):
        np.testing.assert_allclose(loss, other_loss, rtol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
            grads, other)
