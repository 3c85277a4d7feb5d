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
from ray_tpu.models import decoder, hybrid_ssm, latent_moe, llama, window_moe

NAMES = {5: window_moe.COUNTERS, 9: latent_moe.COUNTERS}


@pytest.mark.parametrize("n", sorted(NAMES))
def test_counters_carry_into_the_high_word_and_read_back_the_sum(n):
    """Calls that count just under 2^30 each: the low words wrap, the
    high ones take the carry, ``read_counters`` returns the sum under
    each family's names (two of the layouts in use, 5 and 9 counters)."""
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


# ----------------------------------------- a call over several shards
ALL_FAMILIES = {**FAMILIES, "hybrid_ssm": (hybrid_ssm,
                                           hybrid_ssm.HYBRID_SSM_TINY)}
SHARD_SEQ, SHARD_CHUNK = 64, 16
# rows each lane holds when the decode comes. The second shard: a lane
# that is nobody's, a lane mid-prefill (16 of its prompt's rows written;
# it rides at the idle position) and a lane of 40 rows, which has gone
# round the window family's ring of 32
SHARD_ROWS = [(5, 20, 33), (0, 16, 40)]
SHARD_LIVE = [(True, True, True), (False, False, True)]


def _float32(c):
    return dataclasses.replace(c, remat=False, dtype=jnp.float32,
                               param_dtype=jnp.float32)


def _filled_shard(model, c, params, rows, seed):
    """A shard's cache with ``rows[b]`` rows of lane b written, chunk by
    chunk as the engine's prefill program writes them."""
    prefill = jax.jit(lambda cache, tokens, pos, lane, at: (
        model.forward_with_cache(params, tokens, cache, pos, c, slot=lane,
                                 logits_at=at)[1]))
    cache = model.init_cache(c, len(rows), SHARD_SEQ, SHARD_CHUNK)
    rng = np.random.default_rng(seed)
    for lane, n in enumerate(rows):
        prompt = rng.integers(1, c.vocab_size, n)
        for pos in range(0, n, SHARD_CHUNK):
            part = prompt[pos:pos + SHARD_CHUNK]
            tokens = np.zeros((1, SHARD_CHUNK), np.int32)
            tokens[0, :len(part)] = part
            cache = prefill(cache, jnp.asarray(tokens),
                            jnp.asarray([pos], jnp.int32), jnp.int32(lane),
                            jnp.asarray([len(part) - 1]))
    return cache


def _rows_of(cache):
    """The cache's leaves by name, but the counters' words."""
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf
            in jax.tree_util.tree_leaves_with_path(cache)
            if "counts" not in jax.tree_util.keystr(path)}


@pytest.mark.parametrize("rows", [None, 48])
@pytest.mark.parametrize("family", sorted(ALL_FAMILIES))
def test_a_call_over_two_shards_is_a_call_a_shard(family, rows):
    """``forward_with_cache`` with a tuple of two caches against two
    calls of one: each shard's logits and each cache's written rows to
    1e-5 (a matmul over twice the rows rounds otherwise), every row no
    call wrote untouched to the bit, an idle lane's state as it was, the
    second cache's counters left alone and the call's folded into the
    first's. At the whole cache and at a read window that just holds
    the longest lane."""
    model, c = ALL_FAMILIES[family]
    c = _float32(c)
    params = model.init_params(jax.random.PRNGKey(0), c)
    idle = decoder.idle_position(SHARD_SEQ)
    before = [_filled_shard(model, c, params, n, seed)
              for seed, n in enumerate(SHARD_ROWS)]
    start = [np.where(live, n, idle).astype(np.int32)
             for n, live in zip(SHARD_ROWS, SHARD_LIVE)]
    tokens = [np.asarray([[7], [11], [13]], np.int32),
              np.asarray([[0], [0], [17]], np.int32)]

    forward = jax.jit(lambda cache, tokens, start: model.forward_with_cache(
        params, tokens, cache, start, c, rows=rows))
    apart = [forward(cache, jnp.asarray(t), jnp.asarray(s))
             for t, cache, s in zip(tokens, before, start)]
    logits, caches = forward(tuple(before), jnp.asarray(np.concatenate(tokens)),
                             jnp.asarray(np.concatenate(start)))
    assert isinstance(caches, tuple) and len(caches) == 2
    assert logits.shape == (6, 1, c.vocab_size)
    for s, (want_logits, want_cache) in enumerate(apart):
        live = np.asarray(SHARD_LIVE[s])
        np.testing.assert_allclose(
            np.asarray(logits[3 * s:3 * s + 3])[live],
            np.asarray(want_logits)[live], rtol=1e-5, atol=1e-5)
        was, want, got = (_rows_of(x) for x in
                          (before[s], want_cache, caches[s]))
        assert list(got) == list(want)
        wrote = False
        for name in want:
            np.testing.assert_allclose(got[name], want[name], rtol=1e-5,
                                       atol=1e-5, err_msg=name)
            untouched = want[name] == was[name]
            np.testing.assert_array_equal(got[name][untouched],
                                          was[name][untouched], err_msg=name)
            wrote |= not untouched.all()
        assert wrote                    # the comparison saw a written row
    if hasattr(model, "read_counters"):
        counted = [model.read_counters(x) for x in (*before, *caches)]
        apart_counted = [model.read_counters(x) for _, x in apart]
        assert counted[3] == counted[1]
        for name, n in counted[2].items():
            both = sum(a[name] - b[name]
                       for a, b in zip(apart_counted, counted[:2]))
            if name in ("moe_experts_touched", "moe_expert_slots"):
                # an expert with a live row of either shard, once a call
                assert 0 < n - counted[0][name] <= both
            else:
                assert n - counted[0][name] == both, name


def test_several_caches_leave_no_place_for_a_slot():
    tokens, start = jnp.zeros((4, 1), jnp.int32), jnp.zeros(4, jnp.int32)
    with pytest.raises(ValueError, match="over 2 caches"):
        decoder.Call(tokens, start, 32, slot=jnp.int32(1), shards=2)
    with pytest.raises(ValueError, match="over 3 caches"):
        decoder.Call(tokens, start, 32, shards=3)
    caches, back = decoder.caches_of({"k": 1})
    assert caches == ({"k": 1},) and back(caches) == {"k": 1}
    caches, back = decoder.caches_of(({"k": 1}, {"k": 2}))
    assert len(caches) == 2 and back(list(caches)) == caches


def _drain(eng, reqs, calls):
    """Admit ``reqs`` and step the engine dry -> what each step's decode
    calls were: ("one" | "pair", the lengths they were handed)."""
    for r in reqs:
        assert eng.add_request(r)
    steps = []
    while eng.num_active():
        del calls[:]
        eng.step()
        steps.append(list(calls))
    return steps


@pytest.mark.parametrize("family", sorted(ALL_FAMILIES))
def test_engine_decodes_two_live_shards_in_one_call(family):
    """max_batch 2, at most 4 slots, float32. Four greedy requests (two
    shards, both with lanes to decode from the first step on; one ends
    on its ``eos_id`` with its next lane dispatched), then three: every
    request's tokens are ``generate()``'s one request at a time, a step
    makes one decode call (over the pair while both shards have a lane to
    decode, ``_decode`` itself once one has emptied), and the counters
    say so."""
    model, c = ALL_FAMILIES[family]
    c = _float32(c)
    params = model.init_params(jax.random.PRNGKey(0), c)
    kw = dict(max_batch=2, max_seq=64, prefill_chunk=16, max_slots=4)
    eng, alone = LlamaEngine(c, params, **kw), LlamaEngine(c, params, **kw)
    idle = decoder.idle_position(eng.max_seq)
    rng = np.random.default_rng(7)
    prompt = lambda n: [int(t) for t in rng.integers(1, c.vocab_size, n)]
    # (prompt rows, max_tokens): slots go 0, 0, 1, 1 by shard, and the
    # second shard's requests end first
    waves = [[(5, 24), (12, 30), (9, 6), (3, 8)], [(7, 9), (16, 12), (4, 5)]]
    calls, one, pair = [], eng._decode, eng._decode_pair

    def watched_one(params, cache, last, lens, temps, rng):
        calls.append(("one", np.array(lens)))
        return one(params, cache, last, lens, temps, rng)

    def watched_pair(params, caches, last, lens, temps, rng):
        calls.append(("pair", np.array(lens)))
        assert len(caches) == 2 and len(last) == 2 and len(lens) == 4
        return pair(params, caches, last, lens, temps, rng)

    eng._decode, eng._decode_pair = watched_one, watched_pair
    decoded = ended_early = 0
    for wave in waves:
        reqs = [GenRequest(f"r{i}", prompt(n), max_tokens=m)
                for i, (n, m) in enumerate(wave)]
        want = [alone.generate(r.prompt_ids, max_tokens=r.max_tokens)
                for r in reqs]
        # the first request ends on the token it would emit third
        eos = want[0][2]
        if eos not in want[0][:2]:
            reqs[0].eos_id, want[0] = eos, want[0][:3]
            ended_early += 1
        before = eng.stats.snapshot()
        steps = _drain(eng, reqs, calls)
        for r, tokens in zip(reqs, want):
            assert r.done and r.generated == tokens, r.request_id
        # one call a step; the pair while both shards have a live lane
        assert all(len(step) <= 1 for step in steps)
        kinds = [step[0][0] for step in steps if step]
        n_pair = kinds.count("pair")
        assert 0 < n_pair < len(kinds)
        assert kinds == ["pair"] * n_pair + ["one"] * (len(kinds) - n_pair)
        for step in steps:
            for kind, lens in step:
                live = (lens != idle).reshape(-1, 2).any(axis=1)
                assert live.all(), (kind, lens)
        s = eng.stats.snapshot()
        delta = {k: s[k] - before[k] for k in (
            "decode_calls", "decode_shards", "decode_lanes_total",
            "decode_lanes_active", "tokens_emitted")}
        assert delta["decode_calls"] == len(kinds)
        assert delta["decode_shards"] == len(kinds) + n_pair
        assert delta["decode_lanes_total"] == 2 * delta["decode_shards"]
        # every token but a request's first came off a decode lane
        decoded += sum(len(r.generated) - 1 for r in reqs)
        assert s["decode_lanes_active"] == decoded + s["lanes_discarded"]
    assert ended_early and eng.stats.lanes_discarded == ended_early
    assert len(eng.shards) == 2 and eng.peak_active == 4
    assert eng._pair_run and eng._decodes_run
    programs = eng.compiled_programs()
    assert {k for k in programs if k.startswith("decode_")} == (
        {f"decode_{w}" for w in eng._decodes_run}
        | {"decode_64_x2"})


def test_sampled_requests_over_a_pair_draw_from_the_engines_key():
    """Four sampled requests on two shards: the decode over the pair
    draws all its lanes at once from the engine's key, so two engines of
    one seed emit the same tokens, another seed's differ, the key moves
    with every call (no request repeats one token) and every token is
    in the vocabulary; a greedy request beside them is not disturbed."""
    c = _float32(llama.LLAMA_TINY)
    params = llama.init_params(jax.random.PRNGKey(0), c)

    def run(seed):
        eng = LlamaEngine(c, params, max_batch=2, max_seq=64,
                          prefill_chunk=16, max_slots=4, seed=seed)
        reqs = [GenRequest(f"r{i}", [3 + i, 40, 7], max_tokens=12,
                           temperature=0.0 if i == 3 else 1.5)
                for i in range(4)]
        _drain(eng, reqs, [])
        assert eng.stats.decode_shards == 2 * eng.stats.decode_calls
        return [r.generated for r in reqs]

    first, again, other = run(0), run(0), run(1)
    assert first == again and first[:3] != other[:3]
    assert first[3] == other[3]                       # the greedy one
    for tokens in first[:3]:
        assert len(tokens) == 12 and len(set(tokens)) > 3
        assert all(0 <= t < c.vocab_size for t in tokens)
    assert first[0] != first[1] != first[2]
