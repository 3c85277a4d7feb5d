"""reference.py against models/llama.py at a toy size: the full
forward, the loss, and prefill then decode through the KV cache."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference, spec, traffic
from ray_tpu.models import llama

HP = {**spec.load_cell("mistral-7b-v0.3.serve-docbatch", True)["hp"],
      "num_hidden_layers": 3}
KW = dict(theta=HP["rope_theta"], eps=HP["rms_norm_eps"])


@pytest.fixture(scope="module")
def model():
    # float32 everywhere, so that what is left is the equations
    cfg = dataclasses.replace(spec.llama_config(HP, remat=False),
                              param_dtype=jnp.float32, dtype=jnp.float32)
    params = llama.init_params(spec.prng_key(2**31 + 7), cfg)
    return cfg, params


def test_forward_and_loss_agree_with_the_model(model):
    cfg, params = model
    tokens = traffic.probe_sequence(1, 65, HP["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = llama.forward(params, jnp.asarray(tokens[None, :-1]), cfg)[0]
        want_loss = llama.loss_fn(params, {"tokens": jnp.asarray(tokens[None])}, cfg)
    got = reference.logits(params, jnp.asarray(tokens[:-1]), **KW)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    got_loss = reference.loss(params, jnp.asarray(tokens), rows=16, **KW)
    assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-5)
    last = reference.logits(params, jnp.asarray(tokens[:-1]), last=3, **KW)
    np.testing.assert_allclose(last, got[-3:], atol=1e-5)


def test_prefill_then_decode_through_the_cache_agrees(model):
    cfg, params = model
    tokens = traffic.probe_sequence(2, 40, HP["vocab_size"])
    cache = llama.init_kv_cache(cfg, 1, 64)
    with jax.default_matmul_precision("highest"):
        logits, cache = llama.forward_with_cache(
            params, jnp.asarray(tokens[None, :32]), cache,
            jnp.zeros((1,), jnp.int32), cfg)
        got = [logits[0, -1]]
        for i in range(32, 40):  # one token at a time through the cache
            logits, cache = llama.forward_with_cache(
                params, jnp.asarray(tokens[None, i:i + 1]), cache,
                jnp.asarray([i], jnp.int32), cfg)
            got.append(logits[0, 0])
    want = reference.logits(params, jnp.asarray(tokens), last=9, **KW)
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=2e-4)


def test_a_missing_term_fails(model):
    """The comparison is tight enough to see a defect: without RoPE
    (theta so large that no position rotates) the logits differ by far
    more than the serving tolerance."""
    cfg, params = model
    tokens = jnp.asarray(traffic.probe_sequence(3, 48, HP["vocab_size"]))
    good = reference.logits(params, tokens, **KW)
    bad = reference.logits(params, tokens, theta=1e30, eps=HP["rms_norm_eps"])
    rel = float(jnp.sqrt(jnp.mean((good - bad) ** 2) / jnp.mean(good ** 2)))
    assert rel > 0.05
