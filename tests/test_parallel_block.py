"""``models/parallel_moe.py`` (one LayerNorm feeding attention and the
experts side by side; sliding layers turned over adjacent pairs beside
full layers that are not turned; a held share of sigmoid-routed experts
beside shared experts averaged; a tied head), the tiled chunk attention
of ``ops/pallas_chunk_attention.py`` and the engine serving the family:
toy sizes on the CPU in float32, against the plain reference of
``benchmarks/families/cohere_reference.py`` (which imports nothing of
``ray_tpu``)."""

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
from benchmarks.families import cohere_reference as ref  # noqa: E402
from ray_tpu._private.jax_utils import scope_map  # noqa: E402
from ray_tpu.llm import GenRequest, LlamaEngine  # noqa: E402
from ray_tpu.models import llama, window_moe as wm  # noqa: E402
from ray_tpu.models import parallel_moe as pm  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops import pallas_chunk_attention as chunk_kernel  # noqa: E402

CELL = "command-a-plus-05-2026.serve-rag"
TOL = 1e-5          # float32 rounding, as a relative RMS of logits

F32 = dataclasses.replace(pm.PARALLEL_MOE_TINY, dtype=jnp.float32,
                          param_dtype=jnp.float32)


def hp_of(c, **over):
    """The configuration file's keys for a program configuration."""
    kinds = {"sliding": "sliding_attention", "full": "full_attention"}
    return {"layer_norm_eps": c.norm_eps, "rope_theta": c.rope_theta,
            "layer_types": [kinds[k] for k in c.layer_types],
            "num_hidden_layers": c.n_layers,
            "sliding_window": c.sliding_window,
            "num_experts_per_tok": c.experts_per_token,
            "norm_topk_prob": c.norm_topk_prob,
            "num_shared_experts": c.n_shared_experts,
            "logit_scale": c.logit_scale,
            "share": {"held_experts": list(c.held_experts)}, **over}


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.fixture(scope="module")
def toy():
    params = pm.init_params(jax.random.PRNGKey(0), F32)
    return hp_of(F32), F32, params


def reference(toy, tokens):
    hp, _, params = toy
    return np.asarray(ref.logits(params, np.asarray(tokens), hp))


def program(c, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(pm.forward(params, jnp.asarray(tokens)[None], c)[0])


# ------------------------------------------------- the plain forward
@pytest.mark.parametrize("seed", [0, 1])
def test_whole_sequence_forward_matches_the_reference(toy, seed):
    """Five windows long; the held block is not the first (4-7 of 16)."""
    _, c, params = toy
    assert c.held_experts[0] != 0 and c.n_held < c.n_experts
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(10 + seed), (90,), 0, c.vocab_size))
    assert rel_rms(program(c, params, tokens), reference(toy, tokens)) < TOL


def test_adjacent_pairs_are_turned_where_halves_are_not():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 3, 8))
    pos = jnp.arange(5)[None]
    cos, sin = wm.rope_cos_sin(
        dataclasses.replace(F32, head_size=8), wm.SLIDING, pos)
    got = np.asarray(llama.apply_rope_pairs(x, cos, sin))
    want = np.asarray(ref.rope_adjacent(x[0], F32.rope_theta))
    np.testing.assert_allclose(got[0], want, atol=1e-6)
    # by hand: dimensions (2i, 2i + 1) at position p
    p, i = 3, 2
    w = F32.rope_theta ** (-2 * i / 8)
    a, b = np.asarray(x[0, p, 1, 2 * i]), np.asarray(x[0, p, 1, 2 * i + 1])
    assert got[0, p, 1, 2 * i] == pytest.approx(
        a * math.cos(p * w) - b * math.sin(p * w), abs=1e-6)
    assert got[0, p, 1, 2 * i + 1] == pytest.approx(
        b * math.cos(p * w) + a * math.sin(p * w), abs=1e-6)
    assert np.abs(got - np.asarray(llama.apply_rope(x, cos, sin))).max() > 0.1


# ---------------------------------------------------- planted faults
def _full_layers_turned(monkeypatch):
    block = pm.parallel_block       # a layer's kind only chooses its table
    monkeypatch.setattr(
        pm, "parallel_block",
        lambda c, pos, kind, *rest: block(c, pos, wm.SLIDING, *rest))


def _turned_by_halves(monkeypatch):
    monkeypatch.setattr(pm, "apply_rope_pairs", llama.apply_rope)


def _experts_behind_attention(monkeypatch):
    def sequential(c, pos, kind, x, layer, mixer, experts, index, live):
        h = pm.layer_norm(x, layer["norm"], c.norm_eps)
        cos, sin = (wm.rope_cos_sin(c, kind, pos) if kind == wm.SLIDING
                    else (None, None))
        attn = pm.attention_mix(c, h, layer, cos, sin, mixer,
                                rotate=llama.apply_rope_pairs)
        h = pm.layer_norm(x + attn, layer["norm"], c.norm_eps)
        out, counts = wm.moe_mix(c, h, layer, experts, index, live)
        return x + attn + out, jnp.concatenate([counts, counts[:1]])
    monkeypatch.setattr(pm, "parallel_block", sequential)


def _shared_experts_summed(monkeypatch):
    sound = pm.ParallelMoEConfig.moe
    monkeypatch.setattr(
        pm.ParallelMoEConfig, "moe", property(
            lambda self: dataclasses.replace(sound.fget(self),
                                             shared_scale=1.0)))


FAULTS = {"full_layers_turned": _full_layers_turned,
          "turned_by_halves": _turned_by_halves,
          "experts_read_the_norm_behind_attention": _experts_behind_attention,
          "shared_experts_summed": _shared_experts_summed}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_reads_ten_times_the_tolerance(toy, fault,
                                                       monkeypatch):
    _, c, params = toy
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(20), (90,), 0, c.vocab_size))
    want = reference(toy, tokens)
    assert rel_rms(program(c, params, tokens), want) < TOL
    FAULTS[fault](monkeypatch)
    # the fault is in the trace: ``forward`` looks its block up by name
    got = np.asarray(pm.forward(params, jnp.asarray(tokens)[None], c)[0])
    # ten times the tolerance, and over a cell's limit on the chip too
    assert rel_rms(got, want) > max(10 * TOL, 0.03)


# ------------------------------------------------------ the held share
@pytest.mark.parametrize("seed", [0, 1])
def test_the_eight_shares_add_up_to_the_uncut_layer(seed):
    """The routed parts of the eight shares (experts 0-15, ..., 112-127)
    and the shared part counted once equal the uncut reference's expert
    layer: four shared experts computed one by one and averaged."""
    D, F, E, k, n_shared = 32, 16, 128, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda key, *s: jax.random.normal(key, s, jnp.float32) / np.sqrt(s[-2])
    layer = {"router": n(keys[0], D, E), "w_gate": n(keys[1], E, D, F),
             "w_up": n(keys[2], E, D, F), "w_down": n(keys[3], E, F, D),
             "shared_gate": n(keys[4], D, n_shared * F),
             "shared_up": n(keys[5], D, n_shared * F),
             "shared_down": n(keys[6], n_shared * F, D)}
    x = jax.random.normal(jax.random.PRNGKey(seed + 5), (40, D))
    config = moe.MoEConfig(d_model=D, d_ff=F, n_experts=E, k=k,
                           scoring="sigmoid", shared_scale=1 / n_shared)
    shared = {k_: layer[k_] for k_ in wm.SHARED_WEIGHTS}
    routed, assignments = jnp.zeros_like(x), 0
    for share in range(8):
        held = tuple(range(16 * share, 16 * share + 16))
        part = {"router": layer["router"],
                **{k_: layer[k_][jnp.asarray(held)]
                   for k_ in moe.EXPERT_WEIGHTS}}
        out, counts = moe.moe_ffn_dropless(
            part, x, dataclasses.replace(config, held=held))
        routed, assignments = routed + out, assignments + int(counts[0])
        if share == 1:      # every chip computes the shared experts alike
            with_shared, _ = moe.moe_ffn_dropless(
                {**part, **shared}, x, dataclasses.replace(config, held=held))
            once = with_shared - out
    assert assignments == 40 * k
    want = ref.experts(x, layer, layer, None, None, held=tuple(range(E)),
                       top_k=k, norm_topk=True, n_shared=n_shared)
    assert rel_rms(routed + once, want) < TOL
    # the mean, by hand, of the four experts' outputs
    each = [ref.swiglu(x, layer["shared_gate"][:, j * F:(j + 1) * F],
                       layer["shared_up"][:, j * F:(j + 1) * F],
                       layer["shared_down"][j * F:(j + 1) * F])
            for j in range(n_shared)]
    assert rel_rms(once, sum(each) / n_shared) < TOL


# -------------------------------------------- through the cached calls
def through_the_cache(c, params, cache, tokens, buckets, slot, lanes):
    """The prompt by chunks of ``buckets`` rows (the last padded) into
    ``slot``, then the rest one by one through the decode path beside
    idle lanes: [(position, logits)]."""
    pre = jax.jit(lambda p, t, k, s, at: pm.forward_with_cache(
        p, t, k, s, c, slot=jnp.int32(slot), logits_at=at),
        donate_argnums=(2,))
    dec = jax.jit(lambda p, t, k, s: pm.forward_with_cache(p, t, k, s, c),
                  donate_argnums=(2,))
    max_seq, got, pos = cache["full"]["k"].shape[3], [], 0
    for bucket, n in buckets:
        padded = np.zeros((1, bucket), np.int32)
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


@pytest.mark.parametrize("every_expert_rows", [0, 64],
                         ids=["grouped", "every_expert"])
def test_unequal_buckets_past_the_wrap_then_decodes(toy, every_expert_rows,
                                                    monkeypatch):
    """Chunks of 16, 16, 8, 16 and a padded 16 (5 real rows), past the
    ring's wrap (a ring of 32 slots), then 30 decodes beside idle lanes,
    against the reference's one pass; then the slot is reused."""
    monkeypatch.setattr(moe, "EVERY_EXPERT_ROWS", every_expert_rows)
    _, c, params = toy
    lanes, max_seq = 3, 128
    cache = pm.init_cache(c, lanes, max_seq, 16)
    assert cache["ring"]["k"].shape[3] - 8 == c.sliding_window + 16
    assert cache["counts"].shape == (len(pm.COUNTERS), 2)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (91,), 0, c.vocab_size))
    want = reference(toy, tokens)
    buckets = [(16, 16), (16, 16), (8, 8), (16, 16), (16, 5)]
    with jax.default_matmul_precision("highest"):
        got, cache = through_the_cache(c, params, cache, tokens, buckets, 1,
                                       lanes)
        assert len(got) == 5 + 30
        for pos, logits in got:
            assert rel_rms(logits, want[pos]) < TOL, pos
        short = tokens[40:69]
        want = reference(toy, short)
        got, _ = through_the_cache(c, params, cache, short, [(16, 16), (8, 5)],
                                   1, lanes)
        for pos, logits in got:
            assert rel_rms(logits, want[pos]) < TOL, pos


def test_the_counters_equal_a_count_made_by_hand(toy):
    """A padded chunk's rows behind ``logits_at`` and an idle decode lane
    are not counted; the sliding layers' pairs are the slots scored and,
    of them, those inside a row's window."""
    _, c, params = toy
    cache = pm.init_cache(c, 2, 128, 16)
    slots = cache["ring"]["k"].shape[3]
    tokens = np.zeros((1, 16), np.int32)
    _, cache = pm.forward_with_cache(
        params, tokens, cache, jnp.asarray([20]), c, slot=jnp.int32(1),
        logits_at=jnp.asarray([9]))
    got = pm.read_counters(cache)
    live, sliding = 10, c.layer_types.count("sliding")
    assert got["moe_assignments_all"] == live * c.experts_per_token * 4
    assert 0 < got["moe_assignments"] < got["moe_assignments_all"]
    assert got["moe_expert_slots"] == 4 * c.n_held
    assert got["attn_window_pairs_scored"] == live * slots * sliding
    # rows 20..29 each see their window's 16 rows
    assert got["attn_window_pairs_visible"] == live * 16 * sliding
    _, cache = pm.forward_with_cache(
        params, np.zeros((2, 1), np.int32), cache, jnp.asarray([127, 5]), c)
    after = pm.read_counters(cache)
    assert (after["attn_window_pairs_visible"]
            - got["attn_window_pairs_visible"]) == 6 * sliding
    assert (after["attn_window_pairs_scored"]
            - got["attn_window_pairs_scored"]) == slots * sliding
    assert (after["moe_assignments_all"] - got["moe_assignments_all"]
            == c.experts_per_token * 4)


# --------------------------------------------- the tiled chunk attention
@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 128 rows and blocks of 128 slots, so that calls this
    small have blocks to skip (on the chip they are 512)."""
    monkeypatch.setattr(chunk_kernel, "_TILE", 128)
    monkeypatch.setattr(chunk_kernel, "_BLOCK", 128)


def _attention_case(seed, B, T, S, start):
    H, KVH, hd = 4, 2, 128
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, T, H, hd))
    k = jax.random.normal(keys[1], (B, KVH, S, hd))
    v = jax.random.normal(keys[2], (B, KVH, S, hd))
    return q, k, v, jnp.asarray(start, jnp.int32)


@pytest.mark.parametrize("start, window", [
    ((0,), 128), ((200,), 128), ((640,), 100), ((1000, 77), 128)],
    ids=["first_chunk", "before_the_wrap", "wrapped", "two_sequences"])
def test_tiled_attention_on_ring_slots_equals_the_whole_matrix(
        start, window, small_tiles):
    """A ring of 384 slots (a window of 128 rows or fewer and chunks of
    256), the call's rows anywhere in it: against ``_attention_cached``
    under ``_ring_mask``. Slots that hold nothing of the sequence (a
    first chunk) and slots a padded chunk wrote behind the window are
    masked alike in both."""
    B, T, ring = len(start), 256, 384
    q, k, v, start_pos = _attention_case(0, B, T, ring, start)
    pos = start_pos[:, None] + jnp.arange(T)[None]
    held, ok = wm._ring_held(start_pos, T, ring, ring)
    got = chunk_kernel.chunk_attention(
        q, k, v, jnp.where(ok, held, chunk_kernel.NOT_HELD), start_pos,
        window=window, scale=1 / math.sqrt(128))
    want = llama._attention_cached(
        q, k, v, pos, dataclasses.replace(F32, head_size=128),
        mask=wm._ring_mask(pos, start_pos, T, ring, ring, window))
    assert rel_rms(got, want) < TOL
    # of the three blocks of 128 slots a tile of 128 rows visits two
    scored = chunk_kernel.scored_slots(
        jnp.where(ok, held, chunk_kernel.NOT_HELD), start_pos, T, window)
    assert scored.shape == (B, T) and int(scored.max()) <= ring
    if start == (640,):
        assert int(scored.min()) < ring         # a block was skipped


@pytest.mark.parametrize("start, rows", [((0,), 512), ((256,), 512),
                                         ((700, 128), 1024)],
                         ids=["first_chunk", "second_chunk", "two_sequences"])
def test_tiled_attention_on_rows_by_position_equals_the_whole_matrix(
        start, rows, small_tiles):
    """Rows by position under the read window: causal, no window; the
    blocks past the call's last row are never visited."""
    B, T = len(start), 256
    q, k, v, start_pos = _attention_case(1, B, T, rows, start)
    pos = start_pos[:, None] + jnp.arange(T)[None]
    held = jnp.broadcast_to(jnp.arange(rows), (B, rows))
    got = chunk_kernel.chunk_attention(
        q, k, v, held, start_pos, window=chunk_kernel.NO_WINDOW,
        scale=1 / math.sqrt(128))
    want = llama._attention_cached(
        q, k, v, pos, dataclasses.replace(F32, head_size=128))
    assert rel_rms(got, want) < TOL
    scored = chunk_kernel.scored_slots(held, start_pos, T,
                                       chunk_kernel.NO_WINDOW)
    assert int(scored[0, 0]) < rows or start[0] + T >= rows


TILEABLE = dataclasses.replace(
    F32, dim=128, n_heads=4, n_kv_heads=2, head_size=128,
    sliding_window=128, max_seq_len=1024)


def test_a_chunk_call_goes_through_the_kernel_and_equals_the_whole_matrix(
        monkeypatch, small_tiles):
    """Widths the kernel tiles: chunks of 128 rows into a ring of 256
    slots, past its wrap, the last one padded, beside an idle lane's
    decode; the same calls with the kernel refused (``_attention_cached``
    under the masks) give the same logits and the same cache."""
    c = TILEABLE
    params = pm.init_params(jax.random.PRNGKey(3), c)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (600,), 0, c.vocab_size))
    buckets = [(128, 128)] * 4 + [(128, 60)]

    def run():
        cache = pm.init_cache(c, 2, 1024, 128)
        with jax.default_matmul_precision("highest"):
            got, _ = through_the_cache(c, params, cache, tokens[:580],
                                       buckets, 1, 2)
        return got

    tiled = run()
    text = jax.jit(lambda p, t, k, s: pm.forward_with_cache(
        p, t, k, s, c, slot=jnp.int32(0), logits_at=jnp.zeros(1, jnp.int32))
    ).lower(params, np.zeros((1, 128), np.int32),
            pm.init_cache(c, 2, 1024, 128), np.zeros(1, np.int32)).as_text()
    # no score of heads x chunk rows x cache rows, of either kind of row
    assert "4x128x256x" not in text and "4x128x1024x" not in text
    assert "2x2x128x256x" not in text and "2x2x128x1024x" not in text
    monkeypatch.setattr(chunk_kernel, "untileable", lambda *a: "refused")
    jax.clear_caches()
    whole = run()
    assert len(tiled) == len(whole) == 5 + 8
    for (pos, a), (_, b) in zip(tiled, whole):
        assert rel_rms(a, b) < TOL, pos
    want = np.asarray(ref.logits(params, tokens[:580], hp_of(c)))
    for pos, logits in tiled:
        assert rel_rms(logits, want[pos]) < TOL, pos


WINDOW_TILEABLE = dataclasses.replace(
    wm.WINDOW_MOE_TINY, dim=128, n_heads=4, n_kv_heads=2, head_size=128,
    sliding_window=128, max_seq_len=1024, full_rope=None)


@pytest.mark.parametrize("c, T, chunk, kernel, scores", [
    (WINDOW_TILEABLE, 128, 128, True, "1x2x2x128x264xf32"),
    (WINDOW_TILEABLE, 1, 128, False, "1x2x2x1x264xf32"),
    (wm.WINDOW_MOE_TINY, 16, 16, False, "1x2x2x16x40xf32")],
    ids=["tileable_chunk", "one_row", "toy_chunk"])
def test_mellum2s_calls_take_the_kernel_where_their_shapes_tile(
        c, T, chunk, kernel, scores):
    """``window_moe.forward_with_cache`` passes no flag: a chunk whose
    shapes tile holds ``chunk_attention`` and no float32 buffer of heads
    x chunk rows x ring slots (``scores``, which the whole matrix makes);
    a one-row call at the same widths, and a chunk at the toy preset's,
    hold the whole matrix and no kernel."""
    params = jax.eval_shape(lambda: wm.init_params(jax.random.PRNGKey(0), c))
    cache = jax.eval_shape(lambda: wm.init_cache(c, 1, 1024, chunk))
    slot = {} if T == 1 else {"slot": jnp.int32(0),
                              "logits_at": jnp.zeros(1, jnp.int32)}
    # with the operations' sources: interpreted, the kernel is inlined,
    # and its name stands in where its operations come from
    text = jax.jit(lambda p, t, k, s: wm.forward_with_cache(
        p, t, k, s, c, **slot)).lower(
            params, jax.ShapeDtypeStruct((1, T), jnp.int32), cache,
            jax.ShapeDtypeStruct((1,), jnp.int32)).as_text(debug_info=True)
    assert ("chunk_attention" in text) == kernel
    assert (scores in text) != kernel
    if kernel:      # nor a score against the rows by position
        assert "1x2x2x128x1024xf32" not in text


# ---------------------------------------------------------- the engine
def test_engine_serves_more_requests_than_lanes_as_one_at_a_time(toy):
    """Seven requests on three lanes (two shards), long prompts several
    rings long: greedy tokens equal to each request served alone, and
    every one the reference's greedy choice."""
    _, c, params = toy
    rng = np.random.default_rng(0)
    sizes = [(150, 12), (5, 20), (33, 10), (200, 15), (17, 5), (90, 8),
             (64, 8)]
    prompts = [[int(t) for t in rng.integers(0, c.vocab_size, n)]
               for n, _ in sizes]

    def serve(which):
        eng = LlamaEngine(c, params, max_batch=3, max_seq=256,
                          prefill_chunk=16, max_slots=6)
        reqs = [GenRequest(f"r{i}", prompts[i], max_tokens=sizes[i][1])
                for i in which]
        pending = list(reqs)
        while pending or eng.num_active():
            while pending and eng.add_request(pending[0]):
                pending.pop(0)
            eng.step()
        return eng, [r.generated for r in reqs]

    eng, together = serve(range(len(sizes)))
    assert len(eng.shards) == 2 and eng.peak_active >= 2
    for i, generated in enumerate(together):
        assert len(generated) == sizes[i][1]
        rows = reference(toy, np.asarray(prompts[i] + generated[:-1]))
        rows = rows[len(prompts[i]) - 1:]
        assert (rows.max(-1) - rows[np.arange(len(generated)), generated]
                ).max() < 1e-4
    for i in (0, 3, 4):
        assert serve([i])[1][0] == together[i]
    s = eng.stats.snapshot()
    rows = s["prefill_tokens"] + s["decode_lanes_active"]
    assert s["moe_assignments_all"] == rows * c.experts_per_token * c.n_layers
    assert 0 < s["moe_assignments"] < s["moe_assignments_all"]
    assert 0 < s["attn_window_pairs_visible"] < s["attn_window_pairs_scored"]


def test_the_new_scopes_and_counters_are_in_the_programs_scope_maps(toy):
    _, c, params = toy
    eng = LlamaEngine(c, params, max_batch=2, max_seq=128, prefill_chunk=16)
    eng.warm_up()
    programs = eng.compiled_programs()
    assert any(k.startswith("prefill_") for k in programs) and any(
        k.startswith("decode_") for k in programs)
    for name, compiled in programs.items():
        if name == "first_token":
            continue
        words = set()
        for path in scope_map(compiled).values():
            words |= set(path.replace("(", "/").replace(")", "/").split("/"))
        assert {"block_norm", "attn", "attn_window", "attn_cached", "moe",
                "moe_shared", "moe_experts", "moe_dispatch", "moe_router",
                "head", "kv_write"} <= words, (name, sorted(words))
    stats = eng.stats.snapshot()
    assert {"attn_window_pairs_scored", "attn_window_pairs_visible",
            "moe_assignments_all"} <= set(stats)


def test_the_chunk_rule_is_told_what_is_read_beside_every_rows_weights():
    hp = spec.load_cell(CELL, False)["hp"]
    cfg = spec.family_of(hp).model_config(hp)
    terms = pm.chunk_terms(cfg, 16384)
    # 16 held experts of 3 x 4096 x 4096 in 4 layers beside attention,
    # the shared four and the head's slice
    assert terms == {"read_beside": pytest.approx(
        4 * 16 * 3 * 4096 * 4096 / (4 * (142.6e6 + 201.3e6) + 134.2e6),
        rel=1e-3)}
    from ray_tpu.llm._internal.engine import derived_prefill_chunk
    assert derived_prefill_chunk("TPU v5 lite", 2.0, 16384, **terms) == 1024


# ------------------------------ the four families' programs, as they were
def _lowered_text(module, config, T):
    params = jax.eval_shape(
        lambda: module.init_params(jax.random.PRNGKey(0), config))
    cache = jax.eval_shape(lambda: module.init_cache(config, 2, 64, 16))
    slot = {} if T == 1 else {"slot": jnp.int32(0),
                              "logits_at": jnp.zeros(1, jnp.int32)}
    B = 2 if T == 1 else 1
    return jax.jit(lambda p, t, k, s: module.forward_with_cache(
        p, t, k, s, config, **slot)).lower(
            params, jax.ShapeDtypeStruct((B, T), jnp.int32), cache,
            jax.ShapeDtypeStruct((B,), jnp.int32)).as_text()


@pytest.mark.parametrize("T", [1, 16], ids=["decode", "chunk"])
@pytest.mark.parametrize("family", ["llama", "window_moe", "latent_moe",
                                    "hybrid_ssm"])
def test_taking_the_sublayers_apart_left_the_families_programs(family, T):
    """The lowered text of each family's cached programs at its tiny
    preset, with ``attention_sublayer`` / ``mlp_sublayer`` /
    ``moe_sublayer`` as they are now (a mix behind a norm) and as they
    were before they were taken apart (written out here): the same
    operations in the same order."""
    import importlib

    from ray_tpu import models

    module = importlib.import_module(f"ray_tpu.models.{family}")
    config = {"llama": models.LLAMA_TINY, "window_moe": wm.WINDOW_MOE_TINY,
              "latent_moe": getattr(module, "LATENT_MOE_TINY", None),
              "hybrid_ssm": models.HYBRID_SSM_TINY}[family]
    now = _lowered_text(module, config, T)

    def attention_sublayer(c, x, layer, cos, sin, mixer=llama._attention):
        with jax.named_scope("attn"):
            h = llama.rms_norm(x, layer["attn_norm"], c.norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(c.dtype))
            k = jnp.einsum("bsd,dhk->bshk", h, layer["wk"].astype(c.dtype))
            v = jnp.einsum("bsd,dhk->bshk", h, layer["wv"].astype(c.dtype))
            q = llama.apply_rope(q, cos, sin)
            k = llama.apply_rope(k, cos, sin)
            attn = mixer(q, k, v, c)
            return x + jnp.einsum(
                "bshk,hkd->bsd", attn, layer["wo"].astype(c.dtype))

    def mlp_sublayer(c, x, layer):
        with jax.named_scope("mlp"):
            h = llama.rms_norm(x, layer["mlp_norm"], c.norm_eps)
            gate = jnp.einsum("bsd,df->bsf", h, layer["w_gate"].astype(c.dtype))
            up = jnp.einsum("bsd,df->bsf", h, layer["w_up"].astype(c.dtype))
            return x + jnp.einsum(
                "bsf,fd->bsd", jax.nn.silu(gate) * up,
                layer["w_down"].astype(c.dtype))

    def moe_sublayer(c, x, layer, experts, index, live=None):
        with jax.named_scope("moe"):
            h = llama.rms_norm(x, layer["mlp_norm"], c.norm_eps)
            out, counts = moe.moe_ffn_dropless(
                {"router": layer["router"],
                 **{k: w.astype(c.dtype) for k, w in experts.items()}},
                h, c.moe, layer=index, live=live)
            return x + out, counts[:3]

    patched = pytest.MonkeyPatch()
    try:
        for mod in (llama, wm, module):
            for name, fn in (("attention_sublayer", attention_sublayer),
                             ("mlp_sublayer", mlp_sublayer),
                             ("moe_sublayer", moe_sublayer)):
                if hasattr(mod, name):
                    patched.setattr(mod, name, fn)
        before = _lowered_text(module, config, T)
    finally:
        patched.undo()
    strip = lambda text: [line.split(" loc(")[0] for line in text.splitlines()
                          if not line.lstrip().startswith("#loc")]
    assert strip(now) == strip(before)
