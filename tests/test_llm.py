"""LLM library: KV-cache engine correctness vs the full forward,
continuous batching, serving (handle + HTTP + streaming), Data batch
inference, and TP x PP placement sizing (reference:
python/ray/llm/_internal/serve/.../vllm_models.py:123-142)."""

import contextlib
import dataclasses
import math

import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm import (
    GenRequest,
    LLMConfig,
    LlamaEngine,
    build_llm_app,
    build_llm_processor,
    save_params_npz,
)
from ray_tpu.models import llama


def tiny_cfg():
    return dataclasses.replace(llama.LLAMA_TINY, remat=False)


@pytest.fixture(scope="module")
def engine_setup():
    import jax

    cfg = tiny_cfg()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_cached_decode_matches_full_forward(engine_setup):
    """Greedy generation with the KV cache must equal naive re-forward
    generation (the engine's correctness invariant)."""
    import jax.numpy as jnp

    cfg, params = engine_setup
    prompt = [5, 17, 99, 3]
    steps = 6

    # naive: full forward each step
    ids = list(prompt)
    for _ in range(steps):
        logits = llama.forward(params, jnp.asarray([ids]), cfg)
        ids.append(int(logits[0, -1].argmax()))
    expected = ids[len(prompt):]

    eng = LlamaEngine(cfg, params, max_batch=2, max_seq=64)
    got = eng.generate(prompt, max_tokens=steps)
    assert got == expected, (got, expected)


def test_continuous_batching_interleaves(engine_setup):
    cfg, params = engine_setup
    eng = LlamaEngine(cfg, params, max_batch=4, max_seq=64)
    reqs = [
        GenRequest(request_id=str(i), prompt_ids=[i + 1, i + 2],
                   max_tokens=4 + i)
        for i in range(6)  # more requests than slots
    ]
    pending = list(reqs)
    while pending or eng.num_active():
        while pending and eng.has_capacity():
            eng.add_request(pending.pop(0))
        eng.step()
    for i, r in enumerate(reqs):
        assert r.done and len(r.generated) == 4 + i

    # single-request result must match the batched run (slot isolation)
    solo = LlamaEngine(cfg, params, max_batch=1, max_seq=64)
    assert solo.generate([1, 2], max_tokens=4) == reqs[0].generated


def test_chunked_prefill_decodes_while_prefilling(engine_setup):
    """A long prompt prefills chunk-by-chunk inside step(); an
    already-active short request keeps emitting tokens DURING that
    prefill (no head-of-line blocking — VERDICT r3 Weak #7)."""
    cfg, params = engine_setup
    eng = LlamaEngine(cfg, params, max_batch=2, max_seq=256,
                      prefill_chunk=16)
    short = GenRequest(request_id="short", prompt_ids=[1, 2],
                       max_tokens=40)
    assert eng.add_request(short)
    # let the short prompt finish prefilling and start decoding
    while not short.generated:
        eng.step()
    long = GenRequest(
        request_id="long", prompt_ids=list(range(1, 200)), max_tokens=4
    )
    assert eng.add_request(long)
    # 199 tokens / 16-token chunks => >= 13 steps of prefill; the short
    # request must make decode progress across those same steps
    decoded_during_prefill = 0
    while long.prefill_pos < len(long.prompt_ids) and not long.done:
        before = len(short.generated)
        eng.step()
        decoded_during_prefill += len(short.generated) - before
    assert decoded_during_prefill >= 10, (
        f"short request starved during long prefill "
        f"({decoded_during_prefill} tokens)"
    )
    while not (short.done and long.done):
        eng.step()
    # chunked prefill must produce the same tokens as one-shot prefill
    solo = LlamaEngine(cfg, params, max_batch=1, max_seq=256,
                       prefill_chunk=256)
    assert solo.generate(list(range(1, 200)), max_tokens=4) == long.generated


DENSE_CHUNKS = [
    # peak FLOP/s over HBM bytes/s, times bytes a parameter over 2, to
    # the nearest power of two: 240 rows of bf16 on a v5e
    ("TPU v5 lite", "bfloat16", 2048, 256),
    ("TPU v5 lite", "bfloat16", 4096, 256),
    ("TPU v5 lite", "float32", 4096, 512),    # twice the bytes to read
    ("TPU v5 lite", "int8", 4096, 128),
    ("TPU v4", "bfloat16", 2048, 256),        # 224
    ("TPU v5p", "bfloat16", 8192, 128),       # 166
    ("TPU v6 lite", "bfloat16", 8192, 512),   # 560
    ("cpu", "bfloat16", 2048, 128),           # unknown: the smallest ratio
    ("some later chip", "float32", 2048, 256),
    ("TPU v5 lite", "bfloat16", 64, 64),      # capped by max_seq
    ("TPU v5 lite", "bfloat16", 384, 128),    # halved until it divides
]


@pytest.mark.parametrize("kind, dtype, max_seq, share, want", [
    # every row meets every weight: the three-argument call, and a share
    # of 1 stated, give the same table to the row
    *[(*row[:3], share, row[3]) for share in (None, 1.0)
      for row in DENSE_CHUNKS],
    # a row meets 8 of 64 experts, which hold the bytes: an expert sees
    # an eighth of a call's rows, so the ridge's 240 are 1920 of the call
    ("TPU v5 lite", "bfloat16", 8192, 8 / 64, 2048),
    ("TPU v5 lite", "bfloat16", 4096, 8 / 64, 2048),
    ("TPU v5 lite", "bfloat16", 1024, 8 / 64, 1024),  # capped by max_seq
    ("TPU v5 lite", "bfloat16", 3072, 8 / 64, 1024),  # until it divides
    ("TPU v4", "bfloat16", 8192, 8 / 64, 2048),       # 1791
    ("TPU v5p", "bfloat16", 8192, 8 / 64, 1024),      # 1328
    ("TPU v6 lite", "bfloat16", 8192, 8 / 64, 4096),  # 4478
    ("TPU v5 lite", "bfloat16", 8192, 2 / 8, 1024),   # 962
    ("TPU v5 lite", "float32", 8192, 1 / 2, 1024),    # 962
])
def test_prefill_chunk_is_derived_from_the_chip(kind, dtype, max_seq, share,
                                                want):
    from ray_tpu.llm._internal.engine import derived_prefill_chunk

    import jax.numpy as jnp

    chunk = derived_prefill_chunk(kind, jnp.dtype(dtype).itemsize, max_seq,
                                  *(() if share is None else (share,)))
    assert chunk == want and max_seq % chunk == 0


@pytest.mark.parametrize("kind, llama_chunk", [
    ("TPU v4", 256), ("TPU v5 lite", 256), ("TPU v5", 128), ("TPU v5p", 128),
    ("TPU v6 lite", 512)])
@pytest.mark.parametrize("terms, times", [
    # nothing told, or told that nothing is paid beside the weights'
    # read: the ridge's rows, as before a model could say more
    ({}, 1), ({"read_beside": 0.0, "once_rows": 0.0}, 1),
    # as much again read beside the counted weights: twice the rows
    ({"read_beside": 1.0}, 2),
    # work done once that is three ridges' worth of a row's: four times
    ({"once_rows": 3.0}, 4),
    # both, behind an expert's share of a quarter of the rows
    ({"row_share": 1 / 4, "read_beside": 1.0, "once_rows": 8.0}, 16),
])
def test_what_a_call_pays_once_is_counted_in_rows(kind, llama_chunk, terms,
                                                  times):
    """Over every kind of the chip's table: the llama answer is the
    parent's, and each term a model may state moves it as the rule says
    (``once_rows`` given in ridges here, so that a case holds on every
    kind)."""
    from ray_tpu._private.accelerators.tpu import (CHIP_PEAKS,
                                                   flops_per_hbm_byte)
    from ray_tpu.llm._internal.engine import derived_prefill_chunk

    assert set(CHIP_PEAKS) == {"TPU v4", "TPU v5 lite", "TPU v5", "TPU v5p",
                               "TPU v6 lite"}
    terms = dict(terms)
    if "once_rows" in terms:
        terms["once_rows"] *= flops_per_hbm_byte(kind)
    max_seq = 1 << 16
    chunk = derived_prefill_chunk(kind, 2, max_seq, **terms)
    assert chunk == llama_chunk * times and max_seq % chunk == 0
    for short in (64, 3 * 64, 3 * 1024, 5 * 4096):
        fitted = derived_prefill_chunk(kind, 2, short, **terms)
        assert fitted == (math.gcd(chunk, short) if chunk < short else short)
        assert fitted <= short and short % fitted == 0


@pytest.mark.parametrize("asked, chunk, buckets, windows", [
    # float32 weights, a kind not in the table; no window under a chunk
    (None, 256, [64, 128, 256], [256]),
    (64, 64, [16, 32, 64], [128, 256]),         # an explicit size wins
    (16, 16, [16], [128, 256]),                 # nor under max_seq / 2
    (512, 256, [64, 128, 256], [256]),  # and is fitted to max_seq as before
])
def test_engine_chunk_and_its_three_buckets(engine_setup, asked, chunk,
                                            buckets, windows):
    import jax

    from ray_tpu.llm._internal.engine import derived_prefill_chunk

    cfg, params = engine_setup
    kw = {} if asked is None else {"prefill_chunk": asked}
    eng = LlamaEngine(cfg, params, max_batch=2, max_seq=256, **kw)
    assert (eng.prefill_chunk, eng.buckets) == (chunk, buckets)
    assert eng.windows == windows
    if asked is None:
        assert chunk == derived_prefill_chunk(
            jax.devices()[0].device_kind, 4, 256)
    # decode and the whole chunk at every read window, the smaller
    # buckets at the top one, and the first token's few instructions;
    # max_slots is four shards' here, so the decode over a pair as well
    # (at the top window alone)
    eng.warm_up()
    assert sorted(eng.compiled_programs()) == sorted(
        ["first_token"] + [f"decode_{w}" for w in windows]
        + ["decode_256_x2"]
        + [f"prefill_{chunk}_{w}" for w in windows]
        + [f"prefill_{b}_256" for b in buckets[:-1]])


@pytest.mark.parametrize("chunk", [16, 64, None])
def test_chunked_prefill_matches_one_full_forward(engine_setup, chunk):
    """A prompt prefilled through the engine's program in chunks of 16,
    of 64 and of the derived size: every call's logits, which the head
    computes for the one row it returns, equal that row of the logits
    over all the chunk's rows to the last bit; the first token and the
    cache rows are those of the whole prompt in one call."""
    import jax.numpy as jnp

    cfg, params = engine_setup
    prompt = [1 + (7 * j) % 500 for j in range(150)]
    kw = dict(max_batch=2, max_seq=256)
    eng = LlamaEngine(cfg, params, prefill_chunk=chunk, **kw)
    onehot = np.zeros(2, np.float32)
    onehot[1] = 1.0
    all_rows = eng._jax.jit(
        lambda cache, tokens, start, rows: llama.forward_with_cache(
            params, tokens, cache, start, cfg, slot=jnp.int32(1),
            rows=rows)[0], static_argnames="rows")

    def prefill(eng):
        shard = eng.shards[0]
        for pos in range(0, len(prompt), eng.prefill_chunk):
            part = prompt[pos:pos + eng.prefill_chunk]
            bucket = next(b for b in eng.buckets if b >= len(part))
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :len(part)] = part
            start = np.asarray([pos], np.int32)
            rows = eng.prefill_window(start, bucket)
            want = all_rows(shard.cache, tokens, start, rows)[0, len(part) - 1]
            got, shard.cache = eng._prefill(
                eng.params, shard.cache, tokens, onehot, start, len(part),
                bucket=bucket)
            assert got.dtype == jnp.float32 and got.shape == (cfg.vocab_size,)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        return np.asarray(got), shard.cache

    got, cache = prefill(eng)
    whole, whole_cache = prefill(
        LlamaEngine(cfg, params, prefill_chunk=256, **kw))
    full = llama.forward(params, jnp.asarray([prompt]), cfg)[0, -1]
    assert got.argmax() == whole.argmax() == int(full.argmax())
    np.testing.assert_allclose(got, whole, atol=0.1)
    for name in ("k", "v"):
        rows, whole_rows = (np.asarray(c[name][:, 1, :, :len(prompt)],
                                       np.float32) for c in (cache, whole_cache))
        assert np.abs(whole_rows).max() > 0.5
        np.testing.assert_allclose(rows, whole_rows, atol=0.06)
        # the other slot was never written
        assert not np.asarray(cache[name][:, 0], np.float32).any()


def test_warm_up_runs_every_program_and_changes_no_answer(engine_setup):
    cfg, params = engine_setup
    kw = dict(max_batch=2, max_seq=128)
    eng, fresh = LlamaEngine(cfg, params, **kw), LlamaEngine(cfg, params, **kw)
    eng.warm_up()
    assert (eng.stats.prefill_chunks, eng.stats.decode_calls) == (0, 0)
    assert not eng.num_active()
    prompt = list(range(1, 100))
    # the sampling key is as it was: sampled answers agree too
    warm, cold = ([e.generate(prompt[:n], max_tokens=5, temperature=t)
                   for n, t in [(99, 0.0), (3, 0.0), (40, 0.7)]]
                  for e in (eng, fresh))
    assert warm == cold
    assert eng.add_request(GenRequest("r", prompt, max_tokens=5))
    with pytest.raises(RuntimeError, match="idle"):
        eng.warm_up()


def test_slot_growth_beyond_max_batch(engine_setup):
    """More concurrent requests than max_batch: the engine grows by
    cache shards (same compiled programs) up to max_slots."""
    cfg, params = engine_setup
    eng = LlamaEngine(cfg, params, max_batch=2, max_seq=64, max_slots=6)
    reqs = [
        GenRequest(request_id=str(i), prompt_ids=[i + 1], max_tokens=3)
        for i in range(6)
    ]
    for r in reqs:
        assert eng.add_request(r)  # all 6 admitted concurrently
    assert len(eng.shards) == 3
    overflow = GenRequest(request_id="x", prompt_ids=[9], max_tokens=3)
    assert not eng.add_request(overflow)  # max_slots cap holds
    while any(not r.done for r in reqs):
        eng.step()
    solo = LlamaEngine(cfg, params, max_batch=1, max_seq=64)
    for i, r in enumerate(reqs):
        assert r.generated == solo.generate([i + 1], max_tokens=3)


def _bits(cache):
    """The cache on the host, as integers: bit-for-bit comparison."""
    return {n: np.asarray(a).view(np.uint16) for n, a in cache.items()}


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("slot", [0, 3])
def test_prefill_writes_only_its_slot_among_decoding_sequences(
        engine_setup, slot, chunk):
    """A prompt prefilled into one slot of a shard whose other slots are
    decoding: a prefill call changes nothing but its chunk's rows of its
    own slot, a decode call nothing of the half-prefilled slot but the
    scratch row (where the inactive lanes write), and every sequence
    gets the tokens it gets alone."""
    cfg, params = engine_setup
    kw = dict(max_batch=4, max_seq=256, prefill_chunk=chunk, max_slots=4)
    eng = LlamaEngine(cfg, params, **kw)
    shard = eng.shards[0]
    # slots are handed out from the end of the list: `slot` goes last
    shard.free_slots = [slot] + [s for s in range(4) if s != slot]
    live = [GenRequest(f"live{i}", [7 + i, 3, 11 + 2 * i][:2 + i % 2],
                       max_tokens=24) for i in range(3)]
    late = GenRequest("late", [1 + (5 * j) % 500 for j in range(5 * chunk // 2)],
                      max_tokens=6)
    for r in live:
        assert eng.add_request(r)
    while len(shard.active) < 3:
        eng.step()
    assert eng.add_request(late) and late.slot == slot
    others = [s for s in range(4) if s != slot]
    seen = {"prefill": 0, "decode_between_chunks": 0}
    prefill, decode = eng._prefill, eng._decode

    def checked_prefill(params, cache, tokens, onehot, start, length, bucket):
        before = _bits(cache)
        logits, cache = prefill(
            params, cache, tokens, onehot, start, length, bucket=bucket)
        seen["prefill"] += 1
        lo = int(start[0])
        for name, after in _bits(cache).items():
            was = before[name]            # (L, B, KVH, S, hd)
            np.testing.assert_array_equal(after[:, others], was[:, others])
            own, own_was = after[:, slot], was[:, slot]
            np.testing.assert_array_equal(own[:, :, :lo], own_was[:, :, :lo])
            np.testing.assert_array_equal(
                own[:, :, lo + bucket:], own_was[:, :, lo + bucket:])
            assert (own[:, :, lo:lo + length] != 0).any()
        return logits, cache

    def checked_decode(params, cache, last, lens, temps, rng):
        before = _bits(cache)
        toks, cache, rng = decode(params, cache, last, lens, temps, rng)
        if 0 < late.prefill_pos < len(late.prompt_ids):
            seen["decode_between_chunks"] += 1
            assert lens[slot] == eng.max_seq - 1
            for name, after in _bits(cache).items():
                np.testing.assert_array_equal(
                    after[:, slot, :, :-1], before[name][:, slot, :, :-1])
        return toks, cache, rng

    eng._prefill, eng._decode = checked_prefill, checked_decode
    while not all(r.done for r in live + [late]):
        eng.step()
    assert seen["prefill"] == 3 and seen["decode_between_chunks"] == 2
    alone = LlamaEngine(cfg, params, **kw)
    for r in live + [late]:
        assert r.generated == alone.generate(
            r.prompt_ids, max_tokens=r.max_tokens), r.request_id


def test_engine_programs_update_the_cache_in_place():
    """Both cache leaves are donated to decode and to prefill and come
    back as outputs under the same buffers; no instruction copies a
    whole shard, a layer of it or a slot of it. A float32 cache, because
    the CPU compiler widens a bfloat16 update to float32 and back over
    the whole array, which the TPU's does not."""
    import re

    import jax
    import jax.numpy as jnp

    cfg = dataclasses.replace(tiny_cfg(), dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = LlamaEngine(cfg, params, max_batch=4, max_seq=128, prefill_chunk=16)
    eng.generate(list(range(1, 20)), max_tokens=2)     # runs bucket 16
    old = eng.shards[0].cache
    assert eng.add_request(GenRequest("r", [1, 2, 3], max_tokens=3))
    eng.step()                                         # prefill, then decode
    assert all(leaf.is_deleted() for leaf in old.values())
    assert not any(
        leaf.is_deleted() for leaf in eng.shards[0].cache.values())

    first = len(jax.tree.leaves(params))   # k and v follow the parameters
    shape = old["k"].shape
    moved = {",".join(map(str, dims))
             for dims in (shape, shape[1:], (1, *shape[2:]))}
    instruction = re.compile(
        r"^\s+(?:ROOT\s+)?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", re.M)
    programs = eng.compiled_programs()
    # 19 tokens and then 3, all inside the cache's first half
    assert sorted(programs) == ["decode_64", "first_token", "prefill_16_64"]
    del programs["first_token"]            # takes no cache
    for name, compiled in programs.items():
        text = compiled.as_text()
        header = text.split("\n", 1)[0]   # input_output_alias={ {1}: (12, ...
        # output 0 is the tokens or the logits, 1 and 2 the cache
        assert {int(o): int(p) for o, p in re.findall(
            r"\{(\d+)\}: \((\d+), \{\}", header)} == {
                1: first, 2: first + 1}, (name, header[:300])
        copies = [m.group(0).strip() for m in instruction.finditer(text)
                  if m.group(2) == "copy" and m.group(1) in moved]
        assert not copies, (name, copies)


@pytest.mark.parametrize("fault", ["before_dispatch", "after_dispatch"])
def test_engine_serves_on_after_a_fault_and_abort_all(engine_setup, fault):
    """LLMServer's loop catches an engine fault, calls abort_all() and
    keeps serving. A call that failed after it was dispatched has taken
    the donated cache with it."""
    cfg, params = engine_setup
    kw = dict(max_batch=2, max_seq=64, prefill_chunk=16)
    eng = LlamaEngine(cfg, params, **kw)
    victim = GenRequest("victim", [4, 5, 6], max_tokens=8)
    assert eng.add_request(victim)
    decode = eng._decode

    def failing(params, cache, *rest):
        if fault == "after_dispatch":
            decode(params, cache, *rest)
        raise RuntimeError("injected")

    eng._decode = failing
    with pytest.raises(RuntimeError, match="injected"):
        while True:
            eng.step()
    eng._decode = decode
    lost = fault == "after_dispatch"
    assert all(leaf.is_deleted() == lost
               for leaf in eng.shards[0].cache.values())
    assert eng.abort_all() == [victim] and victim.done
    assert not eng.num_active()
    assert not any(leaf.is_deleted() for leaf in eng.shards[0].cache.values())
    prompt = list(range(1, 30))
    fresh = LlamaEngine(cfg, params, **kw)
    assert eng.generate(prompt, max_tokens=6) == fresh.generate(
        prompt, max_tokens=6)


# ---------------------------------------- the host one decode behind
AHEAD_KW = dict(max_batch=2, max_seq=128, prefill_chunk=16, max_slots=6)


def plain_loop(eng, prompt, max_tokens, eos_id=None):
    """Greedy tokens of one prompt through the engine's two programs in
    slot 0 of its first shard, every call read on the host before the
    next is dispatched: what ``step()`` did before it ran ahead."""
    shard = eng.shards[0]
    onehot = np.zeros(eng.max_batch, np.float32)
    onehot[0] = 1.0
    for pos in range(0, len(prompt), eng.prefill_chunk):
        part = prompt[pos:pos + eng.prefill_chunk]
        bucket = next(b for b in eng.buckets if b >= len(part))
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(part)] = part
        logits, shard.cache = eng._prefill(
            eng.params, shard.cache, tokens, onehot,
            np.asarray([pos], np.int32), len(part), bucket=bucket)
    out = [int(np.asarray(logits).argmax())]
    lens = np.full(eng.max_batch, eng.max_seq - 1, np.int32)
    temps = np.zeros(eng.max_batch, np.float32)
    while (len(out) < max_tokens and out[-1] != eos_id
           and len(prompt) + len(out) < eng.max_seq - 1):
        last = np.zeros(eng.max_batch, np.int32)
        last[0], lens[0] = out[-1], len(prompt) + len(out) - 1
        toks, shard.cache, _ = eng._decode(
            eng.params, shard.cache, last, lens, temps, eng._rng)
        out.append(int(np.asarray(toks)[0]))
    return out


def _prompt(n, k=0):
    return [1 + (5 * j + 11 * k) % 500 for j in range(n)]


def _run_dry(eng):
    while eng.num_active():
        eng.step()


def _three_shards_of_mixed_lengths(eng, plain):
    reqs = [GenRequest(f"r{i}", _prompt(n, i), max_tokens=5 + i)
            for i, n in enumerate([3, 20, 40, 7, 33, 17])]
    for r in reqs:
        assert eng.add_request(r)
    assert len(eng.shards) == 3
    _run_dry(eng)
    s = eng.stats
    # every decode found one before it unread but the first shard's
    # first (its 3 tokens are in while the others' 40 and 33 prefill) and
    # the third's (alone: the second's first rides the call of the pair)
    assert s.decode_ahead == s.decode_calls - 2 and s.lanes_discarded == 0
    assert s.decode_calls < s.decode_shards < 2 * s.decode_calls
    # up to the scratch row too
    long = GenRequest("long", _prompt(100), max_tokens=64)
    assert eng.add_request(long)
    _run_dry(eng)
    assert len(long.generated) == eng.max_seq - 1 - 100
    return reqs + [long]


def _eos_while_the_next_lane_is_in_flight(eng, plain):
    stays = GenRequest("stays", _prompt(9, 1), max_tokens=20)
    free = plain_loop(plain, _prompt(5), 6)
    eos = free[2]
    assert eos not in free[:2]
    ends = GenRequest("ends", _prompt(5), max_tokens=20, eos_id=eos)
    assert eng.add_request(stays) and eng.add_request(ends)
    emitted = []
    while not ends.done:
        emitted += [tok for req, tok in eng.step() if req is ends]
    # its lane rode one decode more: that token is nobody's
    assert emitted == ends.generated == free[:3]
    assert eng.stats.lanes_discarded == 1
    # the freed slot's next prompt queues behind that decode at once; its
    # own rows pass the row the discarded lane wrote (5 + 2)
    reuses = GenRequest("reuses", _prompt(3, 2), max_tokens=10)
    assert eng.add_request(reuses)
    assert (reuses.shard, reuses.slot) == (ends.shard, ends.slot)
    _run_dry(eng)
    assert eng.stats.lanes_discarded == 1
    # alone on its shard: the decode it rode last is dropped whole
    alone = GenRequest("alone", _prompt(5), max_tokens=20, eos_id=eos)
    assert eng.add_request(alone)
    _run_dry(eng)
    assert eng.stats.lanes_discarded == 2
    return [stays, ends, reuses, alone]


def _max_tokens(n):
    def case(eng, plain):
        reqs = [GenRequest(f"r{i}", _prompt(4 + 13 * i, i), max_tokens=n)
                for i in range(3)]
        for r in reqs:
            assert eng.add_request(r)
        _run_dry(eng)
        assert [len(r.generated) for r in reqs] == [n] * 3
        assert eng.stats.decode_lanes_active == 3 * (n - 1)
        return reqs
    return case


def _last_chunk_lands_while_a_decode_is_in_flight(eng, plain):
    runs = GenRequest("runs", _prompt(6), max_tokens=30)
    assert eng.add_request(runs)
    while len(runs.generated) < 3:
        eng.step()
    late = GenRequest("late", _prompt(40, 3), max_tokens=8)
    assert eng.add_request(late) and late.shard == runs.shard
    shard = eng.shards[0]
    while late.prefill_pos < len(late.prompt_ids):
        in_flight = shard.unread
        eng.step()
    # the chunk went behind a decode nobody had read, and its first token
    # into the lane of the decode dispatched in the same call
    assert in_flight is not None and late.generated and shard.unread
    assert late in [req for _, req in shard.unread[1]]
    _run_dry(eng)
    return [runs, late]


def _abort_all_with_results_unread(eng, plain):
    reqs = [GenRequest(f"r{i}", _prompt(5 + i, i), max_tokens=20)
            for i in range(3)]
    for r in reqs:
        assert eng.add_request(r)
    while not all(s.unread for s in eng.shards):
        eng.step()
    had = [list(r.generated) for r in reqs]
    assert eng.abort_all() == reqs and not eng.num_active()
    assert all(s.unread is None and s.first is None for s in eng.shards)
    assert eng.step() == [] and [r.generated for r in reqs] == had
    fresh = GenRequest("fresh", _prompt(21, 4), max_tokens=7)
    assert eng.add_request(fresh)
    _run_dry(eng)
    return [fresh]


AHEAD_CASES = {
    "three_shards_of_mixed_lengths": _three_shards_of_mixed_lengths,
    "eos_while_the_next_lane_is_in_flight":
        _eos_while_the_next_lane_is_in_flight,
    "max_tokens_1": _max_tokens(1),
    "max_tokens_2": _max_tokens(2),
    "max_tokens_3": _max_tokens(3),
    "last_chunk_lands_while_a_decode_is_in_flight":
        _last_chunk_lands_while_a_decode_is_in_flight,
    "abort_all_with_results_unread": _abort_all_with_results_unread,
}


@pytest.mark.parametrize("case", list(AHEAD_CASES))
def test_engine_a_step_ahead_gives_the_plain_loops_tokens(engine_setup, case):
    """``step()`` dispatches every shard's programs before it reads a
    token and feeds a decode the device's own last tokens. Every greedy
    request still gets, token for token, what a loop gets that reads each
    call of the same two programs before it dispatches the next."""
    cfg, params = engine_setup
    eng = LlamaEngine(cfg, params, **AHEAD_KW)
    plain = LlamaEngine(cfg, params, **AHEAD_KW)
    reqs = AHEAD_CASES[case](eng, plain)
    assert not eng.num_active()
    for r in reqs:
        assert r.done and r.generated == plain_loop(
            plain, r.prompt_ids, r.max_tokens, r.eos_id), r.request_id


def test_an_idle_engine_has_nothing_in_flight(engine_setup):
    """``num_active() == 0`` means no result is unread, also behind a
    request that an eos_id ended with its next lane dispatched: so
    ``warm_up()`` (and the benchmark's comparison through the same
    programs) may follow traffic at once."""
    cfg, params = engine_setup
    eng = LlamaEngine(cfg, params, **AHEAD_KW)
    plain = LlamaEngine(cfg, params, **AHEAD_KW)
    eos = plain_loop(plain, _prompt(5), 2)[1]
    reqs = [GenRequest("eos", _prompt(5), max_tokens=9, eos_id=eos),
            GenRequest("count", _prompt(30, 1), max_tokens=4),
            GenRequest("other_shard", _prompt(8, 2), max_tokens=6)]
    for r in reqs:
        assert eng.add_request(r)
    was_busy = False
    while eng.num_active():
        eng.step()
        was_busy |= any(s.unread is not None for s in eng.shards)
        if not eng.num_active():
            assert all(s.unread is None and s.first is None
                       for s in eng.shards)
    assert was_busy and eng.stats.lanes_discarded == 1
    eng.warm_up()
    prompt = _prompt(19, 3)
    assert eng.generate(prompt, max_tokens=5) == plain_loop(plain, prompt, 5)


# ------------------------------------------------ the read windows
WINDOW_KW = dict(max_batch=4, max_seq=128, prefill_chunk=16, max_slots=4)
SCRATCH = WINDOW_KW["max_seq"] - 1      # an idle lane's length


def _every_row(cfg, params, **kw):
    """An engine with the one window that is the whole cache: every
    call reads all ``max_seq`` rows, as every call did before there were
    windows."""
    eng = LlamaEngine(cfg, params, **kw)
    eng.windows = [eng.max_seq]
    return eng


@contextlib.contextmanager
def _programs_lowered():
    """The programs lowered inside the block, one entry each (the event
    the benchmark's ``nothing_compiled_in_window`` counts; it fires on a
    cache hit too)."""
    import jax.monitoring

    lowered = []

    def on_event(event, duration, **kwargs):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield lowered
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


# tokens, start_pos, slot; the rows a live query attends to; live rows
WINDOW_CALLS = {
    # lanes 0 and 2 alive at 20 and 5 rows, the others on the scratch row
    "decode_among_idle_lanes": dict(
        tokens=np.asarray([[7], [0], [9], [0]], np.int32),
        start=np.asarray([20, SCRATCH, 5, SCRATCH], np.int32), slot=None,
        need=21, live=[0, 2]),
    # 16 rows at offset 32 of slot 2, which attend to the 32 before them
    "chunk_behind_an_offset": dict(
        tokens=np.asarray([[1 + (3 * j) % 500 for j in range(16)]], np.int32),
        start=np.asarray([32], np.int32), slot=2, need=48, live=[0]),
}


@pytest.mark.parametrize("call, rows", [
    ("decode_among_idle_lanes", 32), ("decode_among_idle_lanes", 64),
    ("decode_among_idle_lanes", 128),
    ("chunk_behind_an_offset", 64), ("chunk_behind_an_offset", 128),
])
def test_a_window_that_holds_the_sequence_reads_what_all_rows_read(
        engine_setup, call, rows):
    """``forward_with_cache(rows=r)``, for windows of 32, 64 and 128
    rows where they hold the rows a live query attends to: the logits of
    the live sequences and the cache (but an idle lane's scratch row, which is
    written from what that lane computed and never attended to) equal
    the full read's within float32 rounding (a masked row weighs 0), and
    ``rows=max_seq`` is the full read to the last bit."""
    import jax
    import jax.numpy as jnp

    cfg, params = engine_setup
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    cache = llama.init_kv_cache(cfg, 4, 128)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    # rows other calls wrote: every slot full of them up to row 100
    cache = {n: a.at[:, :, :, :100].set(jax.random.normal(
        k, a[:, :, :, :100].shape, a.dtype))
        for (n, a), k in zip(cache.items(), keys)}
    c = WINDOW_CALLS[call]
    assert rows >= c["need"]
    tol = dict(rtol=0, atol=0) if rows == 128 else dict(rtol=1e-5, atol=1e-5)

    def run(rows):
        slot = None if c["slot"] is None else jnp.int32(c["slot"])
        return llama.forward_with_cache(
            params, c["tokens"], cache, c["start"], cfg, slot=slot,
            rows=rows)

    logits, new = run(rows)
    full_logits, full = run(None)
    assert np.abs(np.asarray(full_logits)).max() > 0.1
    np.testing.assert_allclose(
        np.asarray(logits)[c["live"]], np.asarray(full_logits)[c["live"]],
        **tol)
    for name in ("k", "v"):
        got, want = np.asarray(new[name]), np.asarray(full[name])
        assert (got != np.asarray(cache[name])).any()     # rows were written
        np.testing.assert_allclose(
            got[..., :SCRATCH, :], want[..., :SCRATCH, :], **tol)


@pytest.mark.parametrize("call, args, rows", [
    # a chunk reads the least window that holds start + bucket
    ("prefill", (0, 32), 64), ("prefill", (32, 32), 64),
    ("prefill", (64, 32), 128), ("prefill", (96, 32), 128),
    # a bucket under the chunk every row, wherever it lies
    ("prefill", (0, 16), 128), ("prefill", (32, 16), 128),
    # a decode the longest live lane and the row it writes
    ("decode", [0], 64), ("decode", [62], 64), ("decode", [63], 64),
    ("decode", [64], 128), ("decode", [3, 63, 8], 64),
    ("decode", [3, 64, 8], 128),
    # idle lanes are not counted, whatever their number
    ("decode", [SCRATCH, 10, SCRATCH, SCRATCH], 64),
    # two rows under the scratch row: the last a live lane is dispatched at
    ("decode", [SCRATCH - 2, 2], 128), ("decode", [2, SCRATCH, SCRATCH - 2], 128),
    # nothing alive (warm_up's call): every row
    ("decode", [SCRATCH] * 4, 128),
])
def test_the_host_picks_the_least_window_that_holds_the_call(
        engine_setup, call, args, rows):
    cfg, params = engine_setup
    eng = LlamaEngine(cfg, params, **{**WINDOW_KW, "prefill_chunk": 32})
    assert (eng.buckets, eng.windows) == ([16, 32], [64, 128])
    if call == "prefill":
        start = np.asarray([args[0]], np.int32)
        assert eng.prefill_window(start, args[1]) == rows
    else:
        lengths = np.full(4, SCRATCH, np.int32)
        lengths[:len(args)] = args
        assert eng.decode_window(lengths) == rows


@pytest.mark.parametrize("n, tokens", [
    (SCRATCH, 1), (SCRATCH - 1, 1),     # the longest prompt admitted
    (SCRATCH - 2, 2), (SCRATCH - 3, 3),
])
def test_a_prompt_up_to_the_scratch_row_beside_a_short_live_lane(
        engine_setup, n, tokens):
    """A lane is idle in a decode call iff its length is the scratch
    row's number: a prompt that ends on that row or the one before gets
    its first token and no decode, so a live lane never carries it, and
    a long prompt decoded beside a short live lane of its shard (whose
    rows alone would take the half window) gets the tokens of the engine
    that reads every row in every call."""
    cfg, params = engine_setup

    def run(make):
        eng = make(cfg, params, **WINDOW_KW)
        short = GenRequest("short", _prompt(5, 1), max_tokens=40)
        long = GenRequest("long", _prompt(n), max_tokens=4)
        assert eng.add_request(short) and eng.add_request(long)
        assert short.slot != long.slot and len(eng.shards) == 1
        decode, seen = eng._decode, []

        def watched(params, cache, last, lens, temps, rng):
            if lens[long.slot] != SCRATCH:      # the long lane is decoded
                assert lens[short.slot] < 63    # beside the short one
                seen.append(eng.decode_window(lens))
            return decode(params, cache, last, lens, temps, rng)

        eng._decode = watched
        _run_dry(eng)
        assert seen == [128] * (tokens - 1)
        return short.generated, long.generated

    got = run(LlamaEngine)
    assert got == run(_every_row)
    assert (len(got[0]), len(got[1])) == (40, tokens)


def test_a_sequence_that_outgrows_its_windows_gets_the_full_reads_tokens(
        engine_setup):
    """Greedy ``generate()`` over a prompt whose decode crosses from
    one window size to the other (64 -> 128 rows) beside the engine that
    reads every row in every call: the same tokens, and both were run."""
    cfg, params = engine_setup
    eng = LlamaEngine(cfg, params, **WINDOW_KW)
    prompt = _prompt(28)
    got = eng.generate(prompt, max_tokens=50)
    assert got == _every_row(cfg, params, **WINDOW_KW).generate(
        prompt, max_tokens=50)
    assert len(got) == 50
    assert eng._decodes_run == {64, 128}
    assert eng._prefills_run == {(16, 64)}


@pytest.mark.parametrize("max_slots", [4, 8])
def test_a_warm_engine_lowers_nothing_in_any_window(engine_setup, max_slots):
    """After ``warm_up()`` a mixed run of ``step()`` (prompts of one to
    six chunks, decodes that pass every window) lowers no program, and
    ``compiled_programs()`` names every variant that ran. An engine of
    one shard for good warms no decode over a pair; one that may grow a
    second warms it, at the top window, and runs it."""
    cfg, params = engine_setup
    eng = LlamaEngine(cfg, params, **{**WINDOW_KW, "prefill_chunk": 32,
                                      "max_slots": max_slots})
    assert (eng.buckets, eng.windows) == ([16, 32], [64, 128])
    with _programs_lowered() as warmed:
        eng.warm_up()
    pair = max_slots == 8
    # the whole chunk at every window, the bucket under it at the top,
    # the first token, decode at every window; over a pair at the top
    assert len(warmed) == 3 + 1 + 2 + pair
    assert eng._prefills_run == {(32, 64), (32, 128), (16, 128)}
    assert eng._decodes_run == set(eng.windows)
    assert eng._pair_run == pair
    assert eng.stats.snapshot()["decode_shards"] == 0   # counts nothing
    eng._prefills_run.clear(), eng._decodes_run.clear()
    eng._pair_run = False
    reqs = [GenRequest(f"r{i}", _prompt(n, i), max_tokens=m)
            for i, (n, m) in enumerate(
                [(3, 40), (100, 20), (40, 30), (70, 8), (17, 12), (90, 30)])]
    pending = list(reqs)
    with _programs_lowered() as lowered:
        while pending or eng.num_active():
            while pending and eng.has_capacity():
                assert eng.add_request(pending.pop(0))
            eng.step()
    assert lowered == [] and all(r.done for r in reqs)
    # the run reached every window of both programs
    assert eng._decodes_run == set(eng.windows)
    assert {w for _, w in eng._prefills_run} == set(eng.windows)
    assert eng._pair_run == pair and len(eng.shards) == max_slots // 4
    s = eng.stats
    assert (s.decode_shards > s.decode_calls) == pair
    assert sorted(eng.compiled_programs()) == sorted(
        ["first_token"] + [f"decode_{w}" for w in eng._decodes_run]
        + ["decode_128_x2"] * pair
        + [f"prefill_{b}_{w}" for b, w in eng._prefills_run])


@pytest.mark.parametrize("make, read, full", [
    # 70 tokens: four chunks end inside the first 64 rows, the fifth
    # and the decode behind it do not
    (LlamaEngine, 4 * 64 + 128 + 128, 6 * 128),
    (_every_row, 6 * 128, 6 * 128),
])
def test_engine_stats_count_the_rows_the_calls_read(engine_setup, make, read,
                                                    full):
    """``attn_rows_read`` is the read window of every prefill and decode
    call, ``attn_rows_full`` ``max_seq`` a call: their quotient is the
    share of the cache's length attention read, 1 where every call ran
    the top window."""
    cfg, params = engine_setup
    eng = make(cfg, params, **WINDOW_KW)
    eng.warm_up()                                  # counts nothing
    assert (eng.stats.attn_rows_read, eng.stats.attn_rows_full) == (0, 0)
    eng.generate(_prompt(70), max_tokens=2)
    s = eng.stats.snapshot()
    assert (s["prefill_chunks"], s["decode_calls"]) == (5, 1)
    assert (s["attn_rows_read"], s["attn_rows_full"]) == (read, full)


@pytest.fixture(scope="module")
def started_server():
    """An in-process LLMServer, and the programs lowered since it
    started."""
    from ray_tpu.llm.serve import LLMServer

    server = LLMServer(LLMConfig(
        model_config=tiny_cfg(), max_batch_size=2, max_seq_len=64))
    with _programs_lowered() as lowered:
        yield server, lowered
    server.shutdown()


def test_a_started_server_lowers_no_program_for_any_prompt(started_server):
    """LLMServer runs every engine program before it takes a request:
    prompts of every length up to max_seq - 1 reach every chunk bucket
    and the decode program, and nothing is lowered for them."""
    server, lowered = started_server
    eng = server.engine
    assert eng.buckets == [16, 32, 64] and eng.stats.prefill_chunks == 0
    for n in range(1, eng.max_seq):
        out = server.generate([1 + i % 7 for i in range(n)], max_tokens=3)
        # a sequence ends before the cache's scratch row
        assert len(out) == 3 or (n > eng.max_seq - 6 and out)
    assert lowered == []
    assert eng._prefills_run == {(b, 64) for b in eng.buckets}
    assert eng._decodes_run == set(eng.windows) == {64}


def test_engine_stats_count_rows_beside_tokens(started_server):
    """``prefill_rows`` is what the calls computed (their buckets),
    ``prefill_tokens`` what of it was prompt: their quotient is how full
    the chunks were."""
    server, _ = started_server
    server.generate(list(range(1, 40)), max_tokens=2)   # 39 tokens in 64 rows
    stats = server.engine_stats()["engine"]
    assert stats["prefill_rows"] >= stats["prefill_tokens"] > 0
    assert stats["prefill_rows"] % 16 == 0
    before = stats
    server.generate(list(range(1, 18)), max_tokens=2)   # 17 tokens in 32 rows
    after = server.engine_stats()["engine"]
    assert after["prefill_tokens"] - before["prefill_tokens"] == 17
    assert after["prefill_rows"] - before["prefill_rows"] == 32
    assert after["prefill_chunks"] - before["prefill_chunks"] == 1


@pytest.mark.parametrize("steps_a_call", [1, 2])
@pytest.mark.parametrize("n", [1, 2, 3])
def test_server_generate_returns_every_token_asked_for(
        started_server, monkeypatch, n, steps_a_call):
    """"done" goes on a request's queue behind every token of the step,
    its last too: also where the first and the last token leave the
    engine in one call (two steps made one here; before the engine ran a
    step ahead, ``max_tokens=2`` did that by itself and lost a token)."""
    server, _ = started_server
    step = server.engine.step
    monkeypatch.setattr(
        server.engine, "step",
        lambda: [pair for _ in range(steps_a_call) for pair in step()])
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    got = server.generate(prompt, max_tokens=n)
    monkeypatch.undo()
    assert len(got) == n and got == server.generate(prompt, max_tokens=3)[:n]


def test_generation_from_checkpoint(engine_setup, tmp_path):
    cfg, params = engine_setup
    path = str(tmp_path / "model.npz")
    save_params_npz(params, path)
    llm_cfg = LLMConfig(model_config=cfg, checkpoint_path=path, max_seq_len=64)
    loaded = llm_cfg.load_params()
    eng = LlamaEngine(cfg, loaded, max_batch=1, max_seq=64)
    ref = LlamaEngine(cfg, params, max_batch=1, max_seq=64)
    assert eng.generate([7, 8, 9], max_tokens=5) == ref.generate(
        [7, 8, 9], max_tokens=5
    )


def test_placement_bundles_tp_pp():
    one = LLMConfig(tensor_parallel_size=4)
    bundles, strategy = one.placement_bundles()
    assert strategy == "PACK" and bundles == [{"TPU": 4.0, "CPU": 1.0}]
    pp = LLMConfig(tensor_parallel_size=4, pipeline_parallel_size=2)
    bundles, strategy = pp.placement_bundles()
    assert strategy == "SPREAD"
    assert bundles == [{"TPU": 4.0, "CPU": 1.0}] * 2


@pytest.fixture(scope="module")
def serve_llm(engine_setup, tmp_path_factory):
    import ray_tpu

    ray_tpu.init(num_cpus=4, max_workers=4, ignore_reinit_error=True)
    tmp_path = tmp_path_factory.mktemp("llmserve")
    from ray_tpu import serve

    cfg, params = engine_setup
    path = str(tmp_path / "m.npz")
    save_params_npz(params, path)
    llm_cfg = LLMConfig(
        model_config=cfg, checkpoint_path=path,
        max_batch_size=4, max_seq_len=64, accelerator_type="",
    )
    app = build_llm_app(llm_cfg)
    handle = serve.run(
        app, name="llm", route_prefix="/llm",
        http_options={"port": 18931},
    )
    yield handle, cfg, params
    serve.shutdown()
    ray_tpu.shutdown()


def test_serve_generate_and_stream(serve_llm):
    handle, cfg, params = serve_llm
    out = handle.remote({"prompt_ids": [5, 17, 99, 3], "max_tokens": 6}).result()
    assert out["num_generated"] == 6
    # must match local greedy generation (same checkpoint)
    local = LlamaEngine(cfg, params, max_batch=1, max_seq=64)
    assert out["token_ids"] == local.generate([5, 17, 99, 3], max_tokens=6)

    # token-by-token streaming through serve's streaming path
    toks = list(
        handle.options(method_name="generate_stream", stream=True).remote(
            [5, 17, 99, 3], 6
        )
    )
    assert toks == out["token_ids"]


def test_http_endpoint_generates(serve_llm):
    import json
    import urllib.request

    from ray_tpu import serve

    import time

    handle, cfg, params = serve_llm
    body = json.dumps({"prompt_ids": [1, 2, 3], "max_tokens": 4}).encode()
    req = urllib.request.Request(
        "http://127.0.0.1:18931/llm", data=body,
        headers={"Content-Type": "application/json"},
    )
    out = None
    deadline = time.time() + 20
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                out = json.loads(resp.read())
            break
        except Exception:
            time.sleep(0.3)
    assert out is not None, "HTTP endpoint never came up"
    assert out["num_generated"] == 4
    assert len(out["token_ids"]) == 4


def test_batch_inference_processor(ray_start_4_cpus, engine_setup, tmp_path):
    import ray_tpu.data as rdata

    cfg, params = engine_setup
    path = str(tmp_path / "m.npz")
    save_params_npz(params, path)
    llm_cfg = LLMConfig(
        model_config=cfg, checkpoint_path=path,
        max_batch_size=4, max_seq_len=64, accelerator_type="",
    )
    prompts = [[i + 1, i + 2, i + 3] for i in range(8)]
    ds = rdata.from_items([{"prompt_ids": np.array(p)} for p in prompts])
    processor = build_llm_processor(
        llm_cfg, concurrency=1, batch_size=4, max_tokens=5
    )
    out = processor(ds).materialize()
    rows = list(out.iter_rows())
    assert len(rows) == 8
    local = LlamaEngine(cfg, params, max_batch=1, max_seq=64)
    for row in rows[:2]:
        p = [int(x) for x in row["prompt_ids"]]
        got = [int(t) for t in row["generated_ids"][: row["num_generated"]]]
        assert got == local.generate(p, max_tokens=5)
