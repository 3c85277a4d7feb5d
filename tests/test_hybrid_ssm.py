"""``models/hybrid_ssm.py``, the two forms of ``ops/selective_scan.py``
and the engine serving that family: toy sizes on the CPU, float32 where
a tight limit needs it, against the float32 reference of
``benchmarks/families/jamba_reference.py`` (which imports nothing of
``ray_tpu``) and against counts made by hand here."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.families import jamba_reference  # noqa: E402
from benchmarks.scopes import words as scope_words  # noqa: E402
from ray_tpu._private.jax_utils import scope_map  # noqa: E402
from ray_tpu.llm import GenRequest, LlamaEngine  # noqa: E402
from ray_tpu.models import decoder  # noqa: E402
from ray_tpu.models import hybrid_ssm as hs  # noqa: E402
from ray_tpu.ops import selective_scan as ss  # noqa: E402

F32 = dataclasses.replace(hs.HYBRID_SSM_TINY, dtype=jnp.float32,
                          param_dtype=jnp.float32)
# the attention layer first in its period, last in it, and a period of
# two: the state layers before or behind it are none
SHAPES = {
    "tiny": F32,
    "attention_first": dataclasses.replace(F32, attn_offset=0),
    "attention_last": dataclasses.replace(F32, attn_offset=3),
    "period_of_two": dataclasses.replace(F32, n_layers=4, attn_period=2,
                                         attn_offset=1),
}
# what the serving cell's comparison allows a row at its rehearsal
# sizes (benchmarks/workloads/ai21-jamba2-3b.serve-reason.json)
REL_RMS_TOL = 0.03
# what float32 against float32 reads here, with room
SOUND = 2e-5


def hp_of(c: hs.HybridSSMConfig) -> dict:
    """The config.json keys the reference reads, from a configuration
    object of the program's."""
    return {"rms_norm_eps": c.norm_eps, "num_hidden_layers": c.n_layers,
            "attn_layer_period": c.attn_period,
            "attn_layer_offset": c.attn_offset, "mamba_dt_rank": c.dt_rank,
            "mamba_d_state": c.d_state, "num_experts": 1,
            "tie_word_embeddings": True}


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def seeded(c, seed, length):
    params = hs.init_params(jax.random.PRNGKey(seed), c)
    tokens = jax.random.randint(jax.random.PRNGKey(100 + seed), (length,), 0,
                                c.vocab_size)
    return params, tokens


cached = jax.jit(hs.forward_with_cache, static_argnames=("config", "rows"))


def prefill(c, params, cache, tokens, slot, bucket=16, start=0):
    """``tokens`` into ``slot`` from row ``start`` on as the engine
    sends a prompt: whole chunks of ``bucket`` rows, the last one padded
    with zeros behind its tokens -> (each chunk's logits at its last
    token, the cache)."""
    last = []
    for at in range(0, len(tokens), bucket):
        real = tokens[at:at + bucket]
        padded = jnp.zeros((1, bucket), jnp.int32).at[0, :len(real)].set(real)
        logits, cache = cached(
            params, padded, cache, jnp.array([start + at]), c,
            slot=jnp.int32(slot), logits_at=jnp.array([len(real) - 1]))
        last.append(logits[0, 0])
    return last, cache


def decode(c, params, cache, lane_tokens: dict, lengths: dict, lanes: int,
           max_seq: int):
    """One decode call: ``lane_tokens`` {lane: token} at ``lengths``
    {lane: rows it holds}; every other lane rides idle."""
    tokens = np.zeros((lanes, 1), np.int32)
    at = np.full(lanes, decoder.idle_position(max_seq), np.int32)
    for lane, token in lane_tokens.items():
        tokens[lane, 0], at[lane] = token, lengths[lane]
    return cached(params, jnp.asarray(tokens), cache, jnp.asarray(at), c)


def lane_of(cache, lane):
    """What the state layers hold of one lane."""
    return (np.asarray(cache["state"][:, lane]),
            np.asarray(cache["tail"][:, lane]))


# ------------------------------------------------- against the reference
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_whole_sequence_in_one_call_equals_the_reference(shape, seed):
    c = SHAPES[shape]
    params, tokens = seeded(c, seed, 40)
    got = hs.forward(params, tokens[None], c)[0]
    want = jamba_reference.logits(params, tokens, hp_of(c))
    assert rel_rms(got, want) < SOUND
    # the head is the embedding's transpose and nothing else
    assert "lm_head" not in params


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_chunks_then_decodes_through_the_cache_equal_the_reference(
        shape, seed):
    """A prompt of 43 tokens in buckets of 16 (two whole chunks and a
    padded one of 11) into lane 2 of 4, then 9 decodes beside idle
    lanes: every row read equals the reference's one pass."""
    c = SHAPES[shape]
    params, tokens = seeded(c, seed, 52)
    want = jamba_reference.logits(params, tokens, hp_of(c))
    last, cache = prefill(c, params, hs.init_cache(c, 4, 64), tokens[:43], 2)
    for logits, row in zip(last, (15, 31, 42)):
        assert rel_rms(logits, want[row]) < SOUND
    for row in range(43, 52):
        logits, cache = decode(c, params, cache, {2: int(tokens[row])},
                               {2: row}, 4, 64)
        assert rel_rms(logits[2, 0], want[row]) < SOUND
    # 3 chunk calls of 16 rows and 9 decode calls of 4 lanes in every
    # state layer; 43 and 9 of them were somebody's
    assert hs.read_counters(cache) == {
        "ssm_rows": (3 * 16 + 9 * 4) * c.n_state_layers,
        "ssm_rows_live": (43 + 9) * c.n_state_layers}


def test_a_read_window_means_nothing_to_a_state_layer():
    c = F32
    params, tokens = seeded(c, 0, 32)
    _, cache = prefill(c, params, hs.init_cache(c, 2, 64), tokens[:16], 0)
    whole, _ = cached(params, tokens[None, 16:], cache, jnp.array([16]), c,
                      slot=jnp.int32(0))
    half, _ = cached(params, tokens[None, 16:], cache, jnp.array([16]), c,
                     slot=jnp.int32(0), rows=32)
    np.testing.assert_allclose(np.asarray(half), np.asarray(whole),
                               rtol=0, atol=1e-6)
    assert hs.attn_rows_read(c, cache, 32) == 32 * 2 / 8


# ------------------------------------------------ the scan's two forms
def scan_inputs(seed, T, E, n, dead=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (T, E))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (T, E)) - 3.0)
    Bm, Cm = (jax.random.normal(k, (T, n)) for k in keys[2:4])
    A = -jnp.broadcast_to(jnp.arange(1.0, n + 1)[:, None], (n, E))
    h = jax.random.normal(keys[4], (n, E))
    live = jnp.arange(T) < T - dead
    return x, dt, Bm, Cm, A, jnp.ones((E,)), h, live


@pytest.mark.parametrize("dead", [0, 5])
@pytest.mark.parametrize("seed", [0, 1])
def test_the_chunk_form_equals_the_step_form_row_by_row(seed, dead):
    x, dt, Bm, Cm, A, D, h, live = scan_inputs(seed, 24, 96, 16, dead)
    y, h_out = ss.scan_chunk_rows(x, dt, Bm, Cm, A, D, h, live)
    lanes = h[None]
    for t in range(24):
        y_t, lanes = ss.scan_step(x[t][None], dt[t][None], Bm[t][None],
                                  Cm[t][None], A, D, lanes, live[t][None])
        if live[t]:
            np.testing.assert_allclose(np.asarray(y_t[0]), np.asarray(y[t]),
                                       rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lanes[0]), np.asarray(h_out),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("T, E, n, dead", [
    (8, 128, 16, 0), (32, 256, 16, 5), (136, 128, 8, 8), (16, 1152, 16, 3)])
def test_the_kernel_equals_the_loop_over_rows(T, E, n, dead):
    """The Pallas form, interpreted, against ``scan_chunk_rows``: one
    and several row blocks, one and several channel tiles, a tile that
    is no power of two, dead rows behind the live ones."""
    args = scan_inputs(T + E, T, E, n, dead)
    assert ss.untileable(T, E, n) is None
    want_y, want_h = ss.scan_chunk_rows(*args)
    got_y, got_h = ss.scan_chunk(*args)
    live = np.asarray(args[-1])
    np.testing.assert_allclose(np.asarray(got_y)[live],
                               np.asarray(want_y)[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("T, E, n, why", [
    (12, 128, 16, "rows"), (16, 96, 16, "lanes"), (16, 128, 4, "sublanes")])
def test_shapes_the_kernel_cannot_tile_are_refused(T, E, n, why):
    assert why in ss.untileable(T, E, n)
    with pytest.raises(ValueError, match=why):
        ss.scan_chunk(*scan_inputs(0, T, E, n, 2))


@pytest.mark.parametrize("form", ["chunk", "step"])
def test_dead_rows_leave_the_state_bit_for_bit(form):
    x, dt, Bm, Cm, A, D, h, _ = scan_inputs(3, 16, 128, 16)
    if form == "chunk":
        _, out = ss.scan_chunk(x, dt, Bm, Cm, A, D, h, jnp.zeros(16, bool))
    else:
        lanes = jnp.broadcast_to(h, (16, *h.shape))
        _, out = ss.scan_step(x, dt, Bm, Cm, A, D, lanes, jnp.zeros(16, bool))
        out = out[7]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(h))


# ------------------------------------- what a state owes the engine
def test_a_padded_chunk_leaves_what_an_exact_chunk_leaves():
    """8 tokens in a bucket of 16, whatever stands behind them (zeros,
    or other tokens), leave lane 1 bit for bit as 8 tokens of a bucket
    whose other rows are dead leave it, and to rounding as an exact
    chunk of 8 rows does (a call of another shape: one group of rows in
    the kernel, matmuls of 8 rows)."""
    c = F32
    params, tokens = seeded(c, 0, 16)
    fresh = hs.init_cache(c, 2, 64)
    at = dict(slot=jnp.int32(1), logits_at=jnp.array([7]))
    zeros = jnp.zeros((1, 16), jnp.int32).at[0, :8].set(tokens[:8])
    logits_a, padded = cached(params, zeros, fresh, jnp.array([0]), c, **at)
    logits_b, others = cached(params, tokens[None], fresh, jnp.array([0]), c,
                              **at)
    logits_c, exact = cached(params, tokens[None, :8], fresh, jnp.array([0]),
                             c, **at)
    for a, b in zip(lane_of(padded, 1), lane_of(others, 1)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(logits_a), np.asarray(logits_b))
    for a, b in zip(lane_of(padded, 1), lane_of(exact, 1)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert rel_rms(logits_a, logits_c) < SOUND
    # and nothing of another lane moved
    for a in lane_of(padded, 0):
        assert not a.any()


def test_decodes_between_a_lanes_chunks_leave_its_state_and_tail():
    """Lane 2 is mid-prefill and rides three decode calls at the idle
    position while lane 0 decodes: its state and tail stay bit for bit,
    and its next chunk gives the reference's row."""
    c = F32
    params, tokens = seeded(c, 1, 32)
    _, other = seeded(c, 2, 20)
    want = jamba_reference.logits(params, tokens, hp_of(c))
    _, cache = prefill(c, params, hs.init_cache(c, 4, 64), other[:16], 0)
    _, cache = prefill(c, params, cache, tokens[:16], 2)
    held = lane_of(cache, 2)
    before = lane_of(cache, 0)
    for row in range(16, 19):
        _, cache = decode(c, params, cache, {0: int(other[row])}, {0: row},
                          4, 64)
    for a, b in zip(lane_of(cache, 2), held):
        np.testing.assert_array_equal(a, b)
    assert all((a != b).any() for a, b in zip(lane_of(cache, 0), before))
    last, _ = prefill(c, params, cache, tokens[16:], 2, start=16)
    assert rel_rms(last[0], want[31]) < SOUND


def test_a_slot_reused_after_a_longer_sequence_is_a_fresh_one():
    c = F32
    params, long = seeded(c, 0, 40)
    _, short = seeded(c, 5, 13)
    _, used = prefill(c, params, hs.init_cache(c, 2, 64), long, 1)
    for row in (40, 41):
        _, used = decode(c, params, used, {1: 7}, {1: row}, 2, 64)
    assert all(a.any() for a in lane_of(used, 1))
    got, used = prefill(c, params, used, short, 1)
    want, fresh = prefill(c, params, hs.init_cache(c, 2, 64), short, 1)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for a, b in zip(lane_of(used, 1), lane_of(fresh, 1)):
        np.testing.assert_array_equal(a, b)
    got, _ = decode(c, params, used, {1: 3}, {1: 13}, 2, 64)
    want, _ = decode(c, params, fresh, {1: 3}, {1: 13}, 2, 64)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_a_read_only_call_on_a_copy_changes_nothing_of_the_real_cache():
    """As ``benchmarks/server.py``'s probe reads a row: the call runs on
    a device copy of every leaf, under a jit that donates it."""
    c = F32
    params, tokens = seeded(c, 0, 32)
    donating = jax.jit(hs.forward_with_cache, static_argnames=("config",),
                       donate_argnums=(2,))
    _, cache = prefill(c, params, hs.init_cache(c, 2, 64), tokens[:16], 0)
    held = jax.tree_util.tree_map(np.asarray, cache)
    for length in (5, 9):
        copy = jax.tree_util.tree_map(jnp.copy, cache)
        _, scratch = donating(params, tokens[None, 16:], copy, jnp.array([16]),
                              c, slot=jnp.int32(0),
                              logits_at=jnp.array([length - 1]))
        del scratch
    for a, b in zip(jax.tree_util.tree_leaves(cache),
                    jax.tree_util.tree_leaves(held)):
        assert not a.is_deleted()
        np.testing.assert_array_equal(np.asarray(a), b)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("dropped", ["state", "tail"])
def test_what_is_dropped_at_a_chunks_edge_reads_ten_times_the_limit(
        dropped, seed):
    """The planted faults the comparison has to see in this family: the
    state, or the convolution's tail, zeroed between a prompt's first
    and second chunk. The row behind the edge and those behind it read
    over the cell's limit, the worst ten times over it; the rows before
    the edge, and every row of the sound pass, read float32's rounding."""
    c = F32
    params, tokens = seeded(c, seed, 48)
    want = jamba_reference.logits(params, tokens, hp_of(c))

    def rows_read(fault):
        cache, rows = hs.init_cache(c, 2, 64), []
        for start in (0, 16, 32):
            if fault and start == 16:
                cache = {**cache, fault: jnp.zeros_like(cache[fault])}
            logits, cache = cached(params, tokens[None, start:start + 16],
                                   cache, jnp.array([start]), c,
                                   slot=jnp.int32(1))
            rows.extend(rel_rms(logits[0, t], want[start + t])
                        for t in range(16))
        return rows

    assert max(rows_read(None)) < SOUND
    faulty = rows_read(dropped)
    assert max(faulty[:16]) < SOUND
    assert max(faulty[16:]) >= 10 * REL_RMS_TOL
    assert min(faulty[16:20]) > REL_RMS_TOL


# ----------------------------------------------------------- the engine
def engine(c, params, **kw):
    return LlamaEngine(c, params, max_seq=64, prefill_chunk=16, **kw)


def test_the_engine_serves_more_requests_than_lanes_as_one_at_a_time():
    """Seven prompts of mixed lengths through three lanes (chunks of 16
    with padded last ones, lanes joining and leaving, a lane mid-prefill
    beside decoding ones, slots reused): every request's greedy tokens
    are those it gets alone in an engine of its own, and the first's
    those of a loop over the reference."""
    c = F32
    params = hs.init_params(jax.random.PRNGKey(0), c)
    eng = engine(c, params, max_batch=3, max_slots=3)
    assert eng.windows == [32, 64] and eng.buckets == [16]
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(1, c.vocab_size, n)]
               for n in (37, 5, 20, 9, 33, 16, 2)]
    reqs = [GenRequest(f"r{i}", p, max_tokens=6)
            for i, p in enumerate(prompts)]
    pending = list(reqs)
    while pending or eng.num_active():
        while pending and eng.add_request(pending[0]):
            pending.pop(0)
        eng.step()
    alone = engine(c, params, max_batch=1, max_slots=1)
    for req, prompt in zip(reqs, prompts):
        assert req.generated == alone.generate(prompt, max_tokens=6)
    seq = list(prompts[0])
    for _ in range(6):
        logits = jamba_reference.logits(params, jnp.asarray(seq), hp_of(c),
                                        last=1)[-1]
        seq.append(int(jnp.argmax(logits)))
    assert reqs[0].generated == seq[len(prompts[0]):]
    stats = eng.stats.snapshot()
    # every prompt token and every decoded token but a request's first
    # went through each state layer once, live
    assert stats["ssm_rows_live"] == (
        (sum(map(len, prompts)) + 7 * 5) * c.n_state_layers)
    assert stats["ssm_rows"] == (
        (stats["prefill_rows"] + stats["decode_lanes_total"])
        * c.n_state_layers)
    assert stats["ssm_rows"] > stats["ssm_rows_live"]


MIXER = {"ssm", "ssm_in", "ssm_conv", "ssm_x", "ssm_dt", "ssm_out",
         "state_slice", "state_write", "attn", "attn_cached", "kv_write",
         "mlp", "embed", "layers", "head"}
PROGRAM_SCOPES = {"prefill_16_64": MIXER | {"ssm_scan", "kv_slice"},
                  "decode_64": MIXER | {"ssm_step", "sample"}}


@pytest.fixture(scope="module")
def programs():
    eng = engine(hs.HYBRID_SSM_TINY,
                 hs.init_params(jax.random.PRNGKey(0), hs.HYBRID_SSM_TINY),
                 max_batch=2)
    eng.generate(list(range(1, 40)), max_tokens=2)
    return eng.compiled_programs()


@pytest.mark.parametrize("program", sorted(PROGRAM_SCOPES))
def test_the_engines_programs_carry_the_mixers_scopes(programs, program):
    found = set()
    for path in scope_map(programs[program]).values():
        found |= scope_words(path)
    want = PROGRAM_SCOPES[program]
    assert want <= found, sorted(want - found)
    # a chunk has no step and a decode no chunk scan
    assert not ({"ssm_scan", "ssm_step"} - want) & found


# ------------------------------------------------------ the configuration
def test_the_state_space_parameters_are_seeded_as_the_paper_does():
    c = F32
    mamba = hs.init_params(jax.random.PRNGKey(3), c)["mamba"]
    A = -np.exp(np.asarray(mamba["a_log"]))
    np.testing.assert_allclose(
        A, -np.broadcast_to(np.arange(1.0, c.d_state + 1)[None, :, None],
                            A.shape), rtol=1e-6)
    step = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert 0.001 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    assert np.median(step) == pytest.approx(0.01, rel=0.3)   # log-uniform
    assert (np.asarray(mamba["d"]) == 1).all()
    for name in ("a_log", "d", "dt_bias"):
        assert mamba[name].dtype == jnp.float32
    assert mamba["a_log"].shape == (c.n_state_layers, c.d_state, c.inner)


@pytest.mark.parametrize("change, why", [
    ({"n_layers": 6}, "whole periods"), ({"attn_offset": 4}, "outside"),
    ({"attn_offset": -1}, "outside")])
def test_a_pattern_that_is_no_whole_number_of_periods_is_refused(change, why):
    with pytest.raises(ValueError, match=why):
        dataclasses.replace(F32, **change)


def test_the_cache_holds_a_state_a_tail_and_rows_by_position():
    c = hs.HYBRID_SSM_TINY
    cache = hs.init_cache(c, 3, 64, 16)
    shapes = {k: (v.shape, v.dtype) for k, v in cache.items()}
    assert shapes == {
        "state": ((6, 3, 16, 128), jnp.float32),
        "tail": ((6, 3, 3 * 128), jnp.bfloat16),
        "k": ((2, 3, 1, 64, 16), jnp.bfloat16),
        "v": ((2, 3, 1, 64, 16), jnp.bfloat16),
        "counts": ((2, 2), jnp.int32)}
    assert (c.n_state_layers, c.n_attn_layers, c.inner) == (6, 2, 128)
