"""``models/decoder.py``, what every served family's cached forward is
written on: the call's rows, the head on one row, the layer scan, the
device counters' words, and the idle position the engine dispatches.
Small, on the CPU; the families' own parity tests are the net for the
programs themselves."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import GenRequest, LlamaEngine
from ray_tpu.models import decoder, latent_moe, llama, window_moe

NAMES = {3: window_moe.COUNTERS, 7: latent_moe.COUNTERS}


@pytest.mark.parametrize("n", sorted(NAMES))
def test_counters_carry_into_the_high_word_and_read_back_the_sum(n):
    """Calls that count just under 2^30 each: the low words wrap, the
    high ones take the carry, ``read_counters`` returns the sum under
    each family's names (the two layouts in use, 3 and 7 counters)."""
    fold = jax.jit(decoder.fold_counts)
    words = decoder.counter_words(n)
    assert words.shape == (n, 2) and words.dtype == jnp.int32
    each = (1 << 30) - 1 - np.arange(n, dtype=np.int32)     # a call's counts
    for _ in range(5):
        words = fold(words, jnp.asarray(each))
    assert (np.asarray(words)[:, 1] < 1 << 30).all()
    assert (np.asarray(words)[:, 0] == 4).all()             # carried
    got = decoder.read_counters({"counts": words}, NAMES[n])
    assert list(got) == list(NAMES[n])
    assert list(got.values()) == [5 * int(e) for e in each]


@pytest.mark.parametrize("case", ["decode", "padded_chunk", "whole"])
def test_live_rows_of_a_call(case):
    """A decode lane at the idle position is nobody's; a padded chunk's
    rows behind ``logits_at`` are nobody's; otherwise every row is
    live. Positions, first row and window come from the same call."""
    max_seq = 32
    idle = decoder.idle_position(max_seq)
    assert idle == max_seq - 1
    if case == "decode":
        start = jnp.asarray([3, idle, 0, idle], jnp.int32)
        call = decoder.Call(jnp.zeros((4, 1), jnp.int32), start, max_seq)
        want = [[True], [False], [True], [False]]
    elif case == "padded_chunk":
        call = decoder.Call(
            jnp.zeros((2, 8), jnp.int32), jnp.asarray([0, 16], jnp.int32),
            max_seq, slot=jnp.int32(2), logits_at=jnp.asarray([7, 2]),
            rows=16)
        want = [[True] * 8, [True] * 3 + [False] * 5]
        assert call.window == 16 and int(call.first) == 2
        np.testing.assert_array_equal(call.pos[1], np.arange(16, 24))
    else:
        call = decoder.Call(jnp.zeros((2, 4), jnp.int32),
                            jnp.zeros(2, jnp.int32), max_seq)
        want = [[True] * 4] * 2
        assert call.window == max_seq and call.first == 0
    assert (call.B, call.T) == np.shape(want)
    np.testing.assert_array_equal(call.live(), want)


def test_head_on_the_kept_row_is_that_row_of_the_head_over_all():
    c = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), c)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, c.dim), jnp.float32)
    at = jnp.asarray([7, 0, 4])
    every = decoder.head(params, x, c)
    kept = decoder.head(params, x, c, at)
    assert every.shape == (3, 8, c.vocab_size) and every.dtype == jnp.float32
    assert kept.shape == (3, 1, c.vocab_size)
    np.testing.assert_allclose(
        kept[:, 0], every[np.arange(3), np.asarray(at)], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        decoder.final_rows(params, x, c, at)[:, 0],
        decoder.final_rows(params, x, c)[np.arange(3), np.asarray(at)],
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n_counted", [0, 2])
def test_scan_layers_is_a_python_loop_over_the_layers(n_counted):
    """With and without counters: the carry's ``x`` and state and the
    summed counts are what stepping through the layers one by one
    gives, and the step sees each layer's index."""
    layers = {"w": jnp.arange(1.0, 5.0).reshape(4, 1),
              "b": jnp.arange(4.0).reshape(4, 1)}

    def step(x, state, layer, i):
        x = x * layer["w"] + layer["b"]
        state = state.at[i].set(x.sum())
        counted = (jnp.stack([i, jnp.int32(1)]) if n_counted else None)
        return x, state, counted

    x0, state0 = jnp.ones((2, 3)), jnp.zeros(4)
    x, state, counts = jax.jit(
        lambda x, s: decoder.scan_layers(step, x, s, layers, n_counted))(
            x0, state0)
    want_x, want_state, want_counts = x0, state0, np.zeros(2, np.int32)
    for i in range(4):
        layer = jax.tree_util.tree_map(lambda a: a[i], layers)
        want_x, want_state, counted = step(want_x, want_state, layer,
                                           jnp.int32(i))
        if n_counted:
            want_counts = want_counts + np.asarray(counted)
    np.testing.assert_allclose(x, want_x)
    np.testing.assert_allclose(state, want_state)
    if n_counted:
        np.testing.assert_array_equal(counts, want_counts)     # [6, 4]
    else:
        assert counts is None


FAMILIES = {"llama": (llama, llama.LLAMA_TINY),
            "window_moe": (window_moe, window_moe.WINDOW_MOE_TINY),
            "latent_moe": (latent_moe, latent_moe.LATENT_MOE_TINY)}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_engine_dispatches_idle_lanes_at_the_position_decoder_defines(family):
    """One request in an engine of three lanes: every decode call hands
    the two idle lanes ``decoder.idle_position(max_seq)`` for a length,
    which is what the programs' ``Call.live`` leaves out."""
    model, c = FAMILIES[family]
    # float32: the CPU has no bf16 matmul that accumulates in float32
    c = dataclasses.replace(c, remat=False, dtype=jnp.float32,
                            param_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0), c)
    eng = LlamaEngine(c, params, max_batch=3, max_seq=64, prefill_chunk=16)
    idle = decoder.idle_position(eng.max_seq)
    seen, decode = [], eng._decode

    def recorded(params, cache, last, lens, temps, rng):
        seen.append(np.array(lens))
        return decode(params, cache, last, lens, temps, rng)

    eng._decode = recorded
    req = GenRequest("one", [5, 17, 99, 3, 8], max_tokens=4)
    assert eng.add_request(req)
    while not req.done:
        eng.step()
    assert len(req.generated) == 4 and seen
    for lens in seen:
        assert sorted(lens)[1:] == [idle, idle] and min(lens) < idle - 1
        call = decoder.Call(np.zeros((3, 1), np.int32), jnp.asarray(lens),
                            eng.max_seq)
        np.testing.assert_array_equal(call.live()[:, 0], lens != idle)
        assert eng.decode_window(lens) == eng.windows[0]
