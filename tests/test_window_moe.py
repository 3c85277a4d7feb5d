"""``models/window_moe.py``, ``ops/moe.py``'s dropless layer and the
engine serving that family: toy sizes on the CPU, float32 parameters
where a tight limit needs them, against the float32 reference of
``benchmarks/families/mellum_reference.py`` (which imports nothing of
``ray_tpu``) and against per-token loops written here."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import spec  # noqa: E402
from benchmarks.families import mellum_reference  # noqa: E402
from ray_tpu.llm import GenRequest, LlamaEngine  # noqa: E402
from ray_tpu.models import llama, window_moe as wm  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops import pallas_chunk_attention as chunk_kernel  # noqa: E402

CELL = "mellum2-12b-a2.5b.serve-ide-mix"


# ------------------------------------------------ the dropless layer
MOE = moe.MoEConfig(d_model=32, d_ff=16, n_experts=8, k=2)


def moe_params(seed, config=MOE):
    return moe.init_moe_params(jax.random.PRNGKey(seed), config,
                               dtype=jnp.float32)


def per_token_loop(params, x, config):
    """Every token through each of its chosen experts, one at a time."""
    weights, experts = moe.route_top_k(x, params["router"], config)
    x, out = np.asarray(x, np.float64), np.zeros(x.shape, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in params.items()}
    for t in range(x.shape[0]):
        for weight, e in zip(np.asarray(weights[t]), np.asarray(experts[t])):
            gate, up = x[t] @ w["w_gate"][e], x[t] @ w["w_up"][e]
            silu = gate / (1 + np.exp(-gate))
            out[t] += weight * (silu * up) @ w["w_down"][e]
    return out, np.asarray(experts)


@pytest.fixture(params=[0, 64], ids=["grouped", "every_expert"])
def path(request, monkeypatch):
    """Both ways the layer multiplies: the grouped matmul over each
    expert's own rows, and (calls of few rows whose assignments reach
    every expert) every row through every expert."""
    monkeypatch.setattr(moe, "EVERY_EXPERT_ROWS", request.param)
    return request.param


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_dropless_layer_equals_a_per_token_loop(seed, path):
    params = moe_params(seed)
    x = jax.random.normal(jax.random.PRNGKey(100 + seed), (37, 32))
    out, counts = jax.jit(lambda p, x: moe.moe_ffn_dropless(p, x, MOE))(
        params, x)
    want, experts = per_token_loop(params, x, MOE)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    assert list(counts) == [37 * 2, len(np.unique(experts)), 8, 0]


def test_the_two_paths_are_chosen_by_the_rows_of_the_call():
    """Every expert over every row only where the call's assignments are
    as many as the experts and its rows are few; the grouped matmul
    otherwise (one row; a chunk of rows): ``ragged_dot`` at widths or
    rows the program's own kernel does not tile, the kernel where it
    does (rows of 128 lanes, assignments a multiple of its row tile)."""
    def path(config, rows):
        params = moe_params(0, config)
        x = jnp.ones((rows, config.d_model))
        text = str(jax.make_jaxpr(
            lambda p, x: moe.moe_ffn_dropless(p, x, config))(params, x))
        found = [name for name, word in (("ragged_dot", "ragged_dot"),
                                         ("kernel", "grouped_swiglu"))
                 if word in text]
        assert len(found) <= 1
        return found[0] if found else "every_expert"

    assert [path(MOE, n) for n in (1, 3, 4, 16, 64, 65, 256)] == [
        "ragged_dot", "ragged_dot", "every_expert", "every_expert",
        "every_expert", "ragged_dot", "ragged_dot"]
    wide = dataclasses.replace(MOE, d_model=128, d_ff=128)
    assert [path(wide, n) for n in (1, 4, 64, 65, 128, 192, 200, 256)] == [
        "ragged_dot", "every_expert", "every_expert", "ragged_dot", "kernel",
        "kernel", "ragged_dot", "kernel"]


def test_rows_that_are_nobodys_are_computed_and_not_counted(path):
    """``live``: an idle lane or a padded chunk's rows come out as any
    row does and count for nothing; an expert only they chose is not
    touched."""
    params = moe_params(5)
    x = jax.random.normal(jax.random.PRNGKey(11), (12, 32))
    live = np.arange(12) < 5
    out, counts = moe.moe_ffn_dropless(params, x, MOE, live=jnp.asarray(live))
    want, experts = per_token_loop(params, x, MOE)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    assert list(counts) == [5 * 2, len(np.unique(experts[:5])), 8, 0]
    assert len(np.unique(experts[:5])) < len(np.unique(experts))
    none, counts = moe.moe_ffn_dropless(params, x, MOE,
                                        live=jnp.zeros(12, bool))
    np.testing.assert_allclose(np.asarray(none), want, atol=2e-5)
    assert list(counts) == [0, 0, 8, 0]


def test_weights_as_the_softmax_gives_them_where_not_renormalised(path):
    """``norm_topk_prob`` False: the chosen experts keep their share of
    the softmax over all experts, which sums to under 1."""
    plain = dataclasses.replace(MOE, norm_topk_prob=False)
    params = moe_params(6)
    x = jax.random.normal(jax.random.PRNGKey(12), (10, 32))
    weights, _ = moe.route_top_k(x, params["router"], plain)
    assert float(weights.sum(-1).max()) < 1.0
    out, _ = moe.moe_ffn_dropless(params, x, plain)
    want, _ = per_token_loop(params, x, plain)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    normed, _ = moe.moe_ffn_dropless(params, x, MOE)
    assert float(jnp.abs(normed - out).max()) > 1e-3


def test_every_token_at_the_same_experts_drops_none(path):
    """All rows at two experts (a capacity would drop most of them): the
    layer still equals the per-token loop, and six experts have no row."""
    params = moe_params(3)
    router = np.zeros((32, 8), np.float32)
    router[:, 2], router[:, 5] = 0.5, 0.4     # on a positive input
    params = {**params, "router": jnp.asarray(router)}
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (64, 32))) + 0.1
    out, counts = moe.moe_ffn_dropless(params, x, MOE)
    want, experts = per_token_loop(params, x, MOE)
    assert set(np.unique(experts)) == {2, 5}
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    assert list(counts) == [128, 2, 8, 0]
    assert float(jnp.abs(out).min(axis=-1).max()) > 0   # no row left at 0


def test_an_expert_with_no_row_and_a_single_row(path):
    params = moe_params(4)
    router = np.array(params["router"])
    router[:, 0] = -1e3 * np.sign(router[:, 0].sum() or 1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (9, 32)))
    params = {**params, "router": jnp.asarray(
        np.where(np.arange(8)[None] == 0, -10.0, router))}   # never expert 0
    out, counts = moe.moe_ffn_dropless(params, x, MOE)
    want, experts = per_token_loop(params, x, MOE)
    assert 0 not in experts
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    one, counts = moe.moe_ffn_dropless(params, x[:1], MOE)     # one row
    np.testing.assert_allclose(np.asarray(one), want[:1], atol=2e-5)
    assert list(counts) == [2, 2, 8, 0]


def test_stacked_weights_take_the_layers_own_experts(path):
    """``layer``: the whole stack goes to the grouped matmul, as L * E
    groups of which only the layer's have rows; the batched matmul over
    every expert takes the layer's slice."""
    layers = [moe_params(10 + i) for i in range(3)]
    stacked = {k: jnp.stack([p[k] for p in layers])
               for k in ("w_gate", "w_up", "w_down")}
    x = jax.random.normal(jax.random.PRNGKey(6), (21, 32))
    for i, own in enumerate(layers):
        got, _ = jax.jit(lambda i: moe.moe_ffn_dropless(
            {"router": own["router"], **stacked}, x, MOE, layer=i))(
                jnp.int32(i))
        want, _ = moe.moe_ffn_dropless(own, x, MOE)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)


def test_dropless_layer_under_a_mesh_splits_the_rows_by_hand(path):
    """More than one device: the rows are split over the batch axes in a
    shard_map, every shard against the whole of the experts."""
    from jax.sharding import Mesh

    params, x = moe_params(7), jax.random.normal(
        jax.random.PRNGKey(8), (64, 32))
    want, counts = moe.moe_ffn_dropless(params, x, MOE)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "fsdp"))
    with jax.sharding.set_mesh(mesh):
        got, split = jax.jit(lambda p, x: moe.moe_ffn_dropless(p, x, MOE))(
            params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # four shards of 16 rows: the assignments are all there, the slots
    # are counted a shard
    assert int(split[0]) == int(counts[0]) and int(split[2]) == 4 * 8


# -------------------------------------------------- the rotary tables
def _formula(theta, factor, original, beta_fast, beta_slow, hd):
    """YaRN's inverse frequencies from the six numbers, written out."""
    def dim(rotations):
        return hd * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))
    low, high = max(math.floor(dim(beta_fast)), 0), min(
        math.ceil(dim(beta_slow)), hd - 1)
    out = []
    for i in range(hd // 2):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        plain = theta ** (-2 * i / hd)
        out.append(plain * (1 - ramp) + plain / factor * ramp)
    return np.array(out)


@pytest.mark.parametrize("numbers,hd,attention", [
    ((500000.0, 16.0, 8192, 32.0, 1.0), 128, 1.2772588722239782),
    ((10000.0, 4.0, 64, 32.0, 1.0), 64, None),
])
def test_yarn_table_against_the_six_numbers(numbers, hd, attention):
    theta, factor, original, fast, slow = numbers
    rope = wm.YarnRope(theta, factor, original, fast, slow, attention)
    want = _formula(*numbers, hd)
    np.testing.assert_allclose(wm.yarn_inv_freq(rope, hd), want, rtol=1e-12)
    # the ends: the fastest dimensions as published, the slowest over factor
    assert want[0] == 1.0 and want[-1] == pytest.approx(
        theta ** (-(hd - 2) / hd) / factor)
    # the reference's own table (float32) and the attention factor
    inv, scale = mellum_reference.inv_freq_and_scale(
        ("yarn", theta, factor, float(original), fast, slow, attention), hd)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=2e-6)
    assert scale == pytest.approx(0.1 * math.log(factor) + 1.0)
    cfg = dataclasses.replace(wm.WINDOW_MOE_TINY, head_size=hd, full_rope=rope)
    cos, _ = wm.rope_cos_sin(cfg, wm.FULL, jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(np.asarray(cos), scale, rtol=1e-6)
    plain, _ = wm.rope_cos_sin(cfg, wm.SLIDING, jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(np.asarray(plain), 1.0)


# ------------------------------------- the model against the reference
@pytest.fixture(scope="module")
def toy():
    """The cell's rehearsal preset in float32: (hp, config, params)."""
    hp = spec.load_cell(CELL, rehearse=True)["hp"]
    cfg = dataclasses.replace(
        spec.family_of(hp).model_config(hp), dtype=jnp.float32,
        param_dtype=jnp.float32)
    return hp, cfg, wm.init_params(jax.random.PRNGKey(0), cfg)


def reference(toy, tokens, last=0):
    hp, _, params = toy
    return np.asarray(mellum_reference.logits(params, tokens, hp, last=last))


def test_whole_sequence_forward_matches_the_reference(toy):
    _, cfg, params = toy
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (90,), 0, cfg.vocab_size))
    got = wm.forward(params, tokens[None], cfg)[0]
    np.testing.assert_allclose(np.asarray(got), reference(toy, tokens),
                               atol=2e-4)


def test_forward_under_a_mesh_with_the_parameters_where_param_specs_puts_them(
        toy):
    """``param_specs`` on a (data, fsdp, model) mesh: the attention's
    weights split as the llama family's, the experts whole on every
    device, which is how the expert layer's shard_map takes them; the
    logits are the one-device ones."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    _, cfg, params = toy
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 24), 0,
                                cfg.vocab_size)
    want = wm.forward(params, tokens, cfg)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "fsdp", "model"))
    specs = wm.param_specs(cfg)
    assert jax.tree.structure(specs) == jax.tree.structure(params)
    placed = jax.tree.map(
        lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec)),
        params, specs)
    assert placed["blocks"]["w_gate"].sharding.is_fully_replicated
    assert not placed["blocks"]["wq"].sharding.is_fully_replicated
    rows = jax.device_put(tokens, NamedSharding(
        mesh, PartitionSpec(("data", "fsdp"), None)))
    with jax.sharding.set_mesh(mesh):
        got = jax.jit(lambda p, t: wm.forward(p, t, cfg))(placed, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_full_layers_turn_with_the_default_table_where_no_yarn_is_given(toy):
    """``full_rope`` None: every layer turns with the table at
    ``rope_theta``, and the logits are not the YaRN configuration's."""
    _, cfg, params = toy
    plain = dataclasses.replace(cfg, full_rope=None)
    pos = jnp.arange(5)
    for got, want in zip(wm.rope_cos_sin(plain, wm.FULL, pos),
                         wm.rope_cos_sin(plain, wm.SLIDING, pos)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 40), 0,
                                cfg.vocab_size)
    assert float(jnp.abs(wm.forward(params, tokens, plain)
                         - wm.forward(params, tokens, cfg)).max()) > 1e-3


def through_buckets(cfg, params, cache, tokens, buckets, slot, lanes):
    """The prompt by calls of ``buckets`` [(rows, real tokens)] into
    ``slot`` (a call of fewer real tokens than rows is padded), then the
    rest one by one through the decode path beside idle lanes:
    ([(position, logits)], cache)."""
    pre = jax.jit(lambda p, t, c, s, at: wm.forward_with_cache(
        p, t, c, s, cfg, slot=jnp.int32(slot), logits_at=at),
        donate_argnums=(2,))
    dec = jax.jit(lambda p, t, c, s: wm.forward_with_cache(p, t, c, s, cfg),
                  donate_argnums=(2,))
    max_seq, got, pos = cache["full"]["k"].shape[3], [], 0
    for rows, n in buckets:
        padded = np.zeros((1, rows), np.int32)
        padded[0, :n] = tokens[pos:pos + n]
        logits, cache = pre(params, padded, cache, np.array([pos], np.int32),
                            np.array([n - 1], np.int32))
        got.append((pos + n - 1, logits[0, 0]))
        pos += n
    while pos < len(tokens):
        last = np.zeros((lanes, 1), np.int32)
        last[slot, 0] = tokens[pos]
        starts = np.full(lanes, max_seq - 1, np.int32)    # idle: scratch row
        starts[slot] = pos
        logits, cache = dec(params, last, cache, starts)
        got.append((pos, logits[slot, 0]))
        pos += 1
    return got, cache


def through_the_cache(cfg, params, cache, tokens, prompt, chunk, slot, lanes):
    """``prompt`` tokens by chunks of ``chunk`` rows (``through_buckets``,
    the last one padded)."""
    buckets = [(chunk, min(chunk, prompt - pos))
               for pos in range(0, prompt, chunk)]
    return through_buckets(cfg, params, cache, tokens, buckets, slot, lanes)


@pytest.mark.parametrize("chunk", [8, 16])
def test_prefill_by_chunks_then_decode_through_the_ring(toy, chunk, path):
    """A sequence over six windows long (the ring wraps three or four
    times), prefilled by chunks and decoded through the cache, against
    the reference's whole-sequence logits; then the slot is reused by a
    shorter prompt, which reads nothing the long one left."""
    _, cfg, params = toy
    window, lanes, max_seq = cfg.sliding_window, 3, 128
    cache = wm.init_cache(cfg, lanes, max_seq, chunk)
    ring = cache["ring"]["k"].shape[3] - 8
    assert ring == window + chunk and cache["full"]["k"].shape[3] == max_seq
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (110,), 0, cfg.vocab_size))
    assert len(tokens) > 3 * ring
    want = reference(toy, tokens)
    got, cache = through_the_cache(cfg, params, cache, tokens, 61, chunk, 1,
                                   lanes)
    assert len(got) == -(-61 // chunk) + 49
    for pos, logits in got:
        np.testing.assert_allclose(np.asarray(logits), want[pos], atol=3e-4)
    short = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (29,), 0, cfg.vocab_size))
    want = reference(toy, short)
    got, _ = through_the_cache(cfg, params, cache, short, 21, chunk, 1, lanes)
    for pos, logits in got:
        np.testing.assert_allclose(np.asarray(logits), want[pos], atol=3e-4)


def test_a_padded_call_at_any_start_wraps_the_ring(toy):
    """What the benchmark's probe does: one real token in a bucket of
    rows at a start that is no multiple of anything, so the rows wrap
    the ring in the middle of the call, on a copy of the cache."""
    _, cfg, params = toy
    chunk, max_seq = 16, 128
    cache = wm.init_cache(cfg, 2, max_seq, chunk)
    ring = cache["ring"]["k"].shape[3] - 8
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (100,), 0, cfg.vocab_size))
    want = reference(toy, tokens)
    pre = jax.jit(lambda p, t, c, s, at: wm.forward_with_cache(
        p, t, c, s, cfg, slot=jnp.int32(0), logits_at=at))
    _, cache = through_the_cache(cfg, params, cache, tokens[:48], 48, chunk,
                                 0, 2)
    wrapped = 0
    for pos in range(48, 100):
        padded = np.zeros((1, chunk), np.int32)
        padded[0, 0] = tokens[pos]
        wrapped += pos % ring + chunk > ring
        logits, cache = pre(params, padded, cache, np.array([pos], np.int32),
                            np.array([0], np.int32))
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[pos],
                                   atol=3e-4)
    assert wrapped >= 10


def test_the_read_window_bounds_the_full_layers_alone(toy):
    _, cfg, params = toy
    cache = wm.init_cache(cfg, 1, 128, 40)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (1, 40), 0, cfg.vocab_size))
    whole, _ = wm.forward_with_cache(params, tokens, cache,
                                     jnp.zeros(1, jnp.int32), cfg)
    half, _ = wm.forward_with_cache(params, tokens, cache,
                                    jnp.zeros(1, jnp.int32), cfg, rows=64)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(half))
    assert wm.attn_rows_read(cfg, cache, 64) == (64 + 3 * 56) / 4


# ----------------------------------- a chunk's attention in the kernel
# Widths the kernel tiles, Mellum2's in kind: 8 query heads over one
# key/value head of 128, YaRN full layers beside default sliding ones
TILEABLE = dataclasses.replace(
    wm.WINDOW_MOE_TINY, dim=128, n_heads=8, n_kv_heads=1, head_size=128,
    sliding_window=128, max_seq_len=1024, dtype=jnp.float32,
    param_dtype=jnp.float32)
# 256, 128, 256, 128 rows and a padded 256 (60 real): the ring of 384
# slots wraps twice; then six decodes
BUCKETS = [(256, 256), (128, 128), (256, 256), (128, 128), (256, 60)]
PROMPT, DECODES, LANES = sum(n for _, n in BUCKETS), 6, 2


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def _small_tiles(patch):
    """Tiles of 128 rows and blocks of 128 slots, so that calls this
    small have blocks to skip (on the chip they are 512)."""
    patch.setattr(chunk_kernel, "_TILE", 128)
    patch.setattr(chunk_kernel, "_BLOCK", 128)


@pytest.fixture(scope="module")
def served():
    """(tokens, a shorter prompt's, params, {form: (logits of the long
    sequence, then of the short one served in the slot it left)}): the
    same calls with the kernel interpreted and with it refused
    (``_attention_cached`` under the masks)."""
    c = TILEABLE
    assert c.full_rope is not None and c.n_heads // c.n_kv_heads == 8
    params = wm.init_params(jax.random.PRNGKey(3), c)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (PROMPT + DECODES,), 0, c.vocab_size))
    short = tokens[300:300 + 170]
    got = {}
    for form in ("kernel", "refused"):
        with pytest.MonkeyPatch.context() as patch:
            _small_tiles(patch)
            if form == "refused":
                patch.setattr(chunk_kernel, "untileable",
                              lambda *a: "refused")
            cache = wm.init_cache(c, LANES, 1024, 256)
            assert cache["ring"]["k"].shape[3] - 8 == 384
            with jax.default_matmul_precision("highest"):
                long, cache = through_buckets(c, params, cache, tokens,
                                              BUCKETS, 1, LANES)
                again, _ = through_buckets(
                    c, params, cache, short, [(128, 128), (128, 40)], 1,
                    LANES)
            got[form] = (long, again)
    return tokens, short, params, got


@pytest.mark.parametrize("against", ["refused", "forward"])
def test_chunks_through_the_kernel_equal_the_whole_matrix(served, against):
    """Unequal buckets past the ring's wrap, a padded last chunk, decodes
    beside an idle lane, then the slot reused by a shorter prompt: the
    kernel's logits against the same calls with the kernel refused, and
    against ``forward``'s one pass over the whole sequence."""
    tokens, short, params, got = served
    long, again = got["kernel"]
    assert len(long) == len(BUCKETS) + DECODES and len(again) == 2 + 2
    if against == "refused":
        for mine, theirs in zip(got["kernel"], got["refused"]):
            for (pos, a), (at, b) in zip(mine, theirs):
                assert pos == at and rel_rms(a, b) < 1e-5, pos
        return
    with jax.default_matmul_precision("highest"):
        for rows, sequence in ((long, tokens), (again, short)):
            want = np.asarray(wm.forward(params, sequence[None], TILEABLE)[0])
            for pos, logits in rows:
                assert rel_rms(logits, want[pos]) < 1e-5, pos


@pytest.mark.parametrize("form", ["kernel", "refused", "decode"])
def test_the_pairs_scored_and_visible_equal_a_count_made_by_hand(
        form, monkeypatch):
    """One call's two counters. A chunk of 256 rows at position 256,
    200 of them live, window 128, in a ring of 384 slots held in blocks
    of 128: a tile of 128 rows visits the block of its own rows and the
    one before, 256 slots; refused the kernel, every row is scored
    against all 392 slots; a decode's live lane against 392, and at
    position 5 it sees 6 rows. Each in every sliding layer."""
    c = TILEABLE
    _small_tiles(monkeypatch)
    if form == "refused":
        monkeypatch.setattr(chunk_kernel, "untileable", lambda *a: "refused")
    params = wm.init_params(jax.random.PRNGKey(0), c)
    cache = wm.init_cache(c, 2, 1024, 256)
    sliding = c.layer_types.count(wm.SLIDING)
    assert cache["counts"].shape == (len(wm.COUNTERS), 2) and sliding == 3
    if form == "decode":
        _, cache = wm.forward_with_cache(
            params, np.zeros((2, 1), np.int32), cache,
            jnp.asarray([1023, 5]), c)
        live, scored, visible = 1, 392, 6
    else:
        _, cache = wm.forward_with_cache(
            params, np.zeros((1, 256), np.int32), cache, jnp.asarray([256]),
            c, slot=jnp.int32(1), logits_at=jnp.asarray([199]))
        live, scored, visible = 200, 256 if form == "kernel" else 392, 128
    got = wm.read_counters(cache)
    assert list(got) == list(wm.COUNTERS)
    assert got["attn_window_pairs_scored"] == live * scored * sliding
    assert got["attn_window_pairs_visible"] == live * visible * sliding
    assert got["moe_assignments"] == live * c.experts_per_token * c.n_layers


@pytest.mark.parametrize("T, start, blocks", [
    (2048, 2048, 3), (1024, 3072, 3), (512, 1536, 3), (2048, 0, 1),
    (512, 1700, 4)], ids=["2048", "1024", "512", "first_chunk", "unaligned"])
def test_the_slots_a_published_chunk_is_scored_against(T, start, blocks):
    """Mellum2's ring of 3072 slots behind a window of 1024, in blocks of
    512: a tile of 512 rows at an aligned start visits its own block and
    the two before it, two thirds of what it scores visible; a first
    chunk's first tile only its own; one that starts inside a block a
    fourth."""
    held, ok = wm._ring_held(jnp.asarray([start]), T, 3072, 3072)
    scored = chunk_kernel.scored_slots(
        jnp.where(ok, held, chunk_kernel.NOT_HELD), jnp.asarray([start]), T,
        1024)
    assert scored.shape == (1, T)
    assert int(scored[0, 0]) == blocks * 512
    if start >= 1024 and start % 512 == 0:
        assert (np.asarray(scored) == 3 * 512).all()


def test_layer_types_are_held_to_whole_periods():
    with pytest.raises(ValueError, match="layer_types names"):
        dataclasses.replace(wm.WINDOW_MOE_TINY, n_layers=3)
    with pytest.raises(ValueError, match="whole number of periods"):
        dataclasses.replace(wm.WINDOW_MOE_TINY, n_layers=6, layer_types=(
            "sliding", "sliding", "sliding", "full", "sliding", "sliding"))
    two = dataclasses.replace(wm.WINDOW_MOE_TINY, n_layers=8,
                              layer_types=wm.WINDOW_MOE_TINY.layer_types * 2)
    assert two.period == wm.WINDOW_MOE_TINY.layer_types
    assert two.head_dim == 32 and two.model_module.endswith("window_moe")


# ---------------------------------------------------------- the engine
def greedy_by_the_reference(toy, prompt, generated):
    """How far each generated token's logit lies under the reference's
    best at its position."""
    rows = reference(toy, np.asarray(prompt + generated[:-1]))
    rows = rows[len(prompt) - 1:]
    return rows.max(-1) - rows[np.arange(len(generated)), generated]


def test_engine_serves_the_family_under_continuous_batching(toy):
    """Short and long lanes side by side, slots reused, two shards, the
    long prompts several rings long: every token is the reference's
    greedy choice, and the device's counters are what the calls did."""
    _, cfg, params = toy
    eng = LlamaEngine(cfg, params, max_batch=3, max_seq=256,
                      prefill_chunk=16, max_slots=6)
    assert eng.buckets == [16] and eng.windows == [128, 256]
    ring = eng.shards[0].cache["ring"]["k"].shape[3] - 8
    assert ring == cfg.sliding_window + 16
    rng = np.random.default_rng(0)
    sizes = [(150, 20), (5, 30), (33, 10), (200, 25), (17, 5), (90, 12),
             (64, 8)]
    reqs = [GenRequest(f"r{i}", [int(t) for t in rng.integers(
        0, cfg.vocab_size, n)], max_tokens=m) for i, (n, m) in
        enumerate(sizes)]
    pending = list(reqs)
    while pending or eng.num_active():
        while pending and eng.add_request(pending[0]):
            pending.pop(0)
        eng.step()
    assert len(eng.shards) == 2 and eng.peak_active >= 2
    for req, (_, m) in zip(reqs, sizes):
        assert len(req.generated) == m
        assert greedy_by_the_reference(toy, req.prompt_ids,
                                       req.generated).max() == 0.0
    s = eng.stats.snapshot()
    calls = s["prefill_chunks"] + s["decode_calls"]
    # a decode over the pair of shards reads each shard's rows
    reads = s["prefill_chunks"] + s["decode_shards"]
    assert s["decode_calls"] < s["decode_shards"] < 2 * s["decode_calls"]
    # the rows that were somebody's: no padding, no idle lane
    rows = s["prefill_tokens"] + s["decode_lanes_active"]
    assert rows < s["prefill_rows"] + s["decode_lanes_total"]
    assert s["moe_assignments"] == rows * cfg.experts_per_token * cfg.n_layers
    assert s["moe_expert_slots"] == calls * cfg.n_experts * cfg.n_layers
    assert 0 < s["moe_experts_touched"] <= s["moe_expert_slots"]
    assert s["attn_rows_full"] == reads * 256
    # a sliding layer's rows are its ring's whatever the window
    assert 3 / 4 * ring * reads < s["attn_rows_read"] < (
        3 / 4 * ring + 256 / 4) * reads + 1
    assert eng.stats.snapshot()["moe_assignments"] == s["moe_assignments"]
    # the toy widths do not tile: every live row of every call was scored
    # against all of its ring and its scratch slots, in 3 layers of 4
    assert s["attn_window_pairs_scored"] == rows * (ring + 8) * 3
    assert 0 < s["attn_window_pairs_visible"] <= rows * cfg.sliding_window * 3


@pytest.mark.parametrize("family, chunk", [("llama", 256),
                                           ("window_moe", 1024)])
def test_engine_sizes_its_chunk_by_the_rows_its_heaviest_weights_see(
        family, chunk):
    """Built with no ``prefill_chunk``: a llama engine has the ridge's
    rows as before; the routed family's has them over the share of a
    call's rows an expert sees (2 of 8 here), with the buckets, the
    windows and the ring that follow, and serves the tokens a 16-row
    chunk does."""
    from ray_tpu.llm._internal.engine import derived_prefill_chunk

    f32 = {"dtype": jnp.float32, "param_dtype": jnp.float32, "remat": False}
    if family == "llama":
        cfg = dataclasses.replace(llama.LLAMA_TINY, **f32)
        params, share = llama.init_params(jax.random.PRNGKey(0), cfg), 1.0
        assert not hasattr(llama, "chunk_terms")
    else:
        cfg = dataclasses.replace(wm.WINDOW_MOE_TINY, **f32)
        params, share = wm.init_params(jax.random.PRNGKey(0), cfg), 2 / 8
        assert wm.chunk_terms(cfg, 2048) == {"row_share": share}
    kind = jax.devices()[0].device_kind
    assert chunk == derived_prefill_chunk(kind, 4, 2048, share)
    eng = LlamaEngine(cfg, params, max_batch=2, max_seq=2048)
    assert eng.prefill_chunk == chunk
    assert eng.buckets == [chunk // 4, chunk // 2, chunk]
    assert eng.windows == [1024, 2048]
    assert len(eng._prefill_variants()) == 4
    if family == "llama":
        return
    ring = eng.shards[0].cache["ring"]["k"].shape[3] - 8
    assert ring == cfg.sliding_window + chunk
    small = LlamaEngine(cfg, params, max_batch=2, max_seq=2048,
                        prefill_chunk=16)
    rng = np.random.default_rng(1)
    # a bucket under the chunk, a whole chunk and a tail, a few rows
    for n in (300, 1100, 7):
        prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, n)]
        assert (eng.generate(prompt, max_tokens=6)
                == small.generate(prompt, max_tokens=6))
    assert eng.stats.prefill_chunks == 4 < small.stats.prefill_chunks


@pytest.mark.parametrize("kind, chunk", [
    # 8 of 64 experts a row: an expert sees an eighth of a call's rows,
    # and nothing else is told, so the sizes are the parent's (PR 47)
    ("TPU v5 lite", 2048),   # 240 x 8 = 1924
    ("TPU v4", 2048),        # 1792
    ("TPU v5", 1024),        # 1328
    ("TPU v5p", 1024),
    ("TPU v6 lite", 4096),   # 4478
])
def test_the_cells_chunk_is_what_an_experts_rows_alone_give(kind, chunk):
    from ray_tpu._private.accelerators.tpu import (CHIP_PEAKS,
                                                   flops_per_hbm_byte)
    from ray_tpu.llm._internal.engine import derived_prefill_chunk

    assert kind in CHIP_PEAKS
    cell = spec.load_cell(CELL, rehearse=False)
    cfg = spec.family_of(cell["hp"]).model_config(cell["hp"])
    max_seq = cell["serve"]["max_seq_len"]
    terms = wm.chunk_terms(cfg, max_seq)
    assert terms == {"row_share": 8 / 64}
    got = derived_prefill_chunk(kind, 2, max_seq, **terms)
    # the parent's rule, to the operation: the ridge over the share
    rows = flops_per_hbm_byte(kind) * 2 / 2 / (8 / 64)
    assert got == chunk == 2 ** round(np.log2(rows)) and max_seq % got == 0


def test_abort_all_takes_a_cache_of_any_leaves(toy):
    _, cfg, params = toy
    eng = LlamaEngine(cfg, params, max_batch=2, max_seq=64, prefill_chunk=16)
    eng.add_request(GenRequest("a", list(range(1, 20)), max_tokens=4))
    eng.step()
    counted = eng.stats.snapshot()["moe_assignments"]
    assert counted > 0
    for leaf in jax.tree_util.tree_leaves(eng.shards[0].cache):
        leaf.delete()           # a failed call took the donated cache
    assert eng.stats.snapshot()["moe_assignments"] == counted   # still read
    assert [r.request_id for r in eng.abort_all()] == ["a"]
    assert eng.stats.snapshot()["moe_assignments"] == counted   # never falls
    assert eng.generate(list(range(1, 10)), max_tokens=3)
    assert eng.stats.snapshot()["moe_assignments"] > counted


def _texts(eng):
    return {k: c.as_text() for k, c in eng.compiled_programs().items()}


def test_llama_programs_do_not_change_with_the_optional_head_size():
    """``LlamaConfig.head_size`` stated as today's quotient compiles the
    engine's programs to the text they compile to without it; a llama
    engine's snapshot shows the routed family's counters at 0."""
    cfg = dataclasses.replace(llama.LLAMA_TINY, remat=False)
    stated = dataclasses.replace(cfg, head_size=cfg.dim // cfg.n_heads)
    assert cfg.head_size is None and cfg.head_dim == stated.head_dim == 32
    assert dataclasses.replace(cfg, dim=256).head_dim == 64    # no stale size
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    texts = []
    for c in (cfg, stated):
        eng = LlamaEngine(c, params, max_batch=2, max_seq=64, prefill_chunk=16)
        out = eng.generate(list(range(1, 12)), max_tokens=4)
        texts.append((_texts(eng), out))
        s = eng.stats.snapshot()
        assert [s[k] for k in ("moe_assignments", "moe_experts_touched",
                               "moe_expert_slots")] == [0, 0, 0]
        calls = s["prefill_chunks"] + s["decode_calls"]
        assert 32 * calls <= s["attn_rows_read"] <= s["attn_rows_full"]
        assert s["attn_rows_full"] == 64 * calls
    assert texts[0] == texts[1]
    assert set(texts[0][0]) == {"first_token", "decode_32", "prefill_16_32"}
    # a head of its own size: the cache and the projections follow it
    wide = dataclasses.replace(cfg, head_size=48)
    cache = llama.init_kv_cache(wide, 2, 64)
    assert cache["k"].shape == (cfg.n_layers, 2, cfg.n_kv_heads, 64, 48)
    assert llama.init_params(jax.random.PRNGKey(0), wide)["blocks"][
        "wq"].shape == (cfg.n_layers, cfg.dim, cfg.n_heads, 48)
