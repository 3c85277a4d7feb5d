"""``models/latent_moe.py``, the held share of ``ops/moe.py``'s dropless
layer and the engine serving that family: toy sizes on the CPU, float32
parameters where a tight limit needs them, against the float32
reference of ``benchmarks/families/pangu_reference.py`` (which imports
nothing of ``ray_tpu``) and against counts made by hand here."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.families import pangu_reference  # noqa: E402
from ray_tpu.llm import GenRequest, LlamaEngine  # noqa: E402
from ray_tpu.models import latent_moe as lm  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

F32 = dataclasses.replace(lm.LATENT_MOE_TINY, dtype=jnp.float32,
                          param_dtype=jnp.float32)


def hp_of(c: lm.LatentMoEConfig) -> dict:
    """The config.json keys the reference reads, from a configuration
    object of the program's."""
    return {"rms_norm_eps": c.norm_eps, "rope_theta": c.rope_theta,
            "kv_lora_rank": c.kv_rank, "qk_nope_head_dim": c.nope_dim,
            "first_k_dense_replace": c.n_dense_layers,
            "num_hidden_layers": c.n_layers,
            "num_experts_per_tok": c.experts_per_token,
            "norm_topk_prob": c.norm_topk_prob,
            "routed_scaling_factor": c.routed_scale,
            "share": {"router_experts": c.n_experts,
                      "held_experts": list(c.held_experts)}}


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


@pytest.fixture(params=[0, 64], ids=["grouped", "every_expert"])
def path(request, monkeypatch):
    """Both ways the expert layer multiplies (``test_window_moe.py``)."""
    monkeypatch.setattr(moe, "EVERY_EXPERT_ROWS", request.param)
    return request.param


# ------------------------------------------------- against the reference
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("config", [
    F32, dataclasses.replace(F32, n_layers=4, n_dense_layers=2),
    dataclasses.replace(F32, held_experts=(9, 2, 15)),
    dataclasses.replace(F32, held_experts=tuple(range(16)))],
    ids=["tiny", "two_dense", "scattered_ids", "all_held"])
def test_a_whole_sequence_in_one_call_equals_the_reference(config, seed):
    params = lm.init_params(jax.random.PRNGKey(seed), config)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 10), (48,), 0,
                                config.vocab_size)
    got, _ = lm.forward_with_cache(
        params, tokens[None], lm.init_cache(config, 1, 64),
        jnp.zeros(1, jnp.int32), config)
    got = got[0]
    want = pangu_reference.logits(params, tokens, hp_of(config))
    assert rel_rms(got, want) < 2e-5
    tail = pangu_reference.logits(params, tokens, hp_of(config), last=5)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(want[-5:]))


@pytest.mark.parametrize("seed", [0, 1])
def test_chunks_then_decodes_through_the_cache_equal_the_reference(seed, path):
    """Prefill in chunks of unequal size into a slot that is not the
    first, then decodes of every lane, against the reference's one pass
    over each lane's tokens: on logits."""
    c = F32
    params = lm.init_params(jax.random.PRNGKey(seed), c)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + 20), (3, 60), 0, c.vocab_size))
    cache = lm.init_cache(c, 3, 64)
    got = {b: [] for b in range(3)}
    for b in (2, 0, 1):
        start = 0
        for size in (16, 24, 8):
            chunk = tokens[b, start:start + size]
            logits, cache = lm.forward_with_cache(
                params, jnp.asarray(chunk)[None], cache,
                jnp.asarray([start], jnp.int32), c, slot=jnp.int32(b))
            got[b].append(logits[0])
            start += size
    for t in range(48, 60):
        logits, cache = lm.forward_with_cache(
            params, jnp.asarray(tokens[:, t:t + 1]), cache,
            jnp.full(3, t, jnp.int32), c)
        for b in range(3):
            got[b].append(logits[b])
    for b in range(3):
        want = pangu_reference.logits(params, tokens[b], hp_of(c))
        assert rel_rms(jnp.concatenate(got[b]), want) < 2e-5


def test_a_read_window_that_holds_the_rows_gives_the_full_reads_logits():
    """To rounding, not to the last bit: a window shorter than a block
    is taken in shorter blocks, and the running sums round otherwise."""
    c = F32
    params = lm.init_params(jax.random.PRNGKey(0), c)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 20), 0,
                                c.vocab_size)
    out = {}
    for rows in (32, 128):
        cache = lm.init_cache(c, 2, 128)
        _, cache = lm.forward_with_cache(
            params, tokens[:, :19], cache, jnp.zeros(2, jnp.int32), c,
            rows=rows)
        out[rows], _ = lm.forward_with_cache(
            params, tokens[:, 19:], cache, jnp.full(2, 19, jnp.int32), c,
            rows=rows)
    assert rel_rms(out[32], out[128]) < 1e-6


# ------------------------------------------- the two forms of attention
def rows_of(c, rows):
    """``read(start, size)`` over cache rows given as one array (B, S,
    kv_rank + rope), each block as the cache keeps it: latent rows (B,
    size, kv_rank), rotary keys (B, rope, size)."""
    def read(start, size):
        block = jax.lax.dynamic_slice_in_dim(rows, start, size, axis=1)
        return block[..., :c.kv_rank], block[..., c.kv_rank:].swapaxes(1, 2)
    return read


def attend_whole(c, q_nope, q_rope, latent, pos, layer):
    """The expanded form with the whole score at once: the oracle of
    both blockwise forms, at sizes at which the score fits."""
    k_nope = jnp.einsum("bsc,chk->bshk", latent[..., :c.kv_rank],
                        layer["wuk"])
    v = jnp.einsum("bsc,chk->bshk", latent[..., :c.kv_rank], layer["wuv"])
    s = (jnp.einsum("bthk,bshk->bhts", q_nope, k_nope)
         + jnp.einsum("bthr,bsr->bhts", q_rope, latent[..., c.kv_rank:])
         ) / np.sqrt(c.nope_dim + c.rope_dim)
    seen = jnp.arange(latent.shape[1])[None, None, :] <= pos[:, :, None]
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhts,bshk->bthk", p, v)


def attention_inputs(c, seed, batch, rows_held, T, start):
    """Queries of T rows a sequence at positions start.. and a cache of
    ``rows_held`` latent rows a sequence (garbage behind the call's last
    row, as a slot's earlier tenant leaves it)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    layer = {"wuk": jax.random.normal(keys[0], (c.kv_rank, c.n_heads,
                                                c.nope_dim)) / 6,
             "wuv": jax.random.normal(keys[1], (c.kv_rank, c.n_heads,
                                                c.v_dim)) / 6}
    q_nope = jax.random.normal(keys[2], (batch, T, c.n_heads, c.nope_dim))
    q_rope = jax.random.normal(keys[3], (batch, T, c.n_heads, c.rope_dim))
    latent = jax.random.normal(keys[4], (batch, rows_held, c.latent_dim))
    pos = start[:, None] + jnp.arange(T)[None, :]
    return layer, q_nope, q_rope, latent, pos


@pytest.mark.parametrize("block, tile", [(8, 256), (16, 256), (64, 256),
                                         (8, 4), (16, 8), (64, 16)])
def test_blockwise_prefill_attention_equals_the_whole_matrix(
        block, tile, monkeypatch):
    """A chunk that starts mid-cache, two sequences at different
    starts, blocks smaller than, equal to and larger than the chunk, its
    rows attending in one tile, in two and in four."""
    c = F32
    monkeypatch.setattr(lm, "PREFILL_BLOCK", block)
    monkeypatch.setattr(lm, "PREFILL_TILE", tile)
    layer, q_nope, q_rope, latent, pos = attention_inputs(
        c, 3, 2, 64, 16, jnp.asarray([24, 37]))
    want = attend_whole(c, q_nope, q_rope, latent, pos, layer)
    got = lm.attend_expanded_blockwise(
        c, q_nope, q_rope, rows_of(c, latent), 64, pos, layer)
    assert rel_rms(got, want) < 1e-5
    # nothing behind the call's last row was read: other garbage there,
    # the same result to the last bit
    other = latent.at[:, 53:].set(1e6)
    again = lm.attend_expanded_blockwise(
        c, q_nope, q_rope, rows_of(c, other), 64, pos, layer)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))


def stack_of(c, rows):
    """Cache rows given as one array (B, S, kv_rank + rope) as the two
    stacks of a one-layer cache: latent (1, B, S, kv_rank), rope_key
    (1, B, rope, S)."""
    return (rows[None, ..., :c.kv_rank],
            rows[None, ..., c.kv_rank:].swapaxes(2, 3))


@pytest.mark.parametrize("block", [8, 64])
def test_the_absorbed_form_equals_the_expanded_form_on_the_same_cache(
        block, monkeypatch):
    """One query row a lane at its own length, lane 2 idle at the
    scratch row: the decode form, which never makes a key or a value,
    against the prefill form's whole-matrix arithmetic. At widths the
    decode kernel cannot tile, so in the ``jax.numpy`` loop, every lane
    through the longest live lane's blocks."""
    c = F32
    monkeypatch.setattr(lm, "DECODE_BLOCK", block)
    start = jnp.asarray([40, 7, 63, 22])
    layer, q_nope, q_rope, latent, pos = attention_inputs(c, 4, 4, 64, 1,
                                                          start)
    want = attend_whole(c, q_nope, q_rope, latent, pos, layer)
    live = np.asarray([0, 1, 3])
    stack = stack_of(c, latent)
    rows, blocks = lm.absorbed_blocks(c, stack, 64,
                                      jnp.asarray([41, 8, 0, 23]))
    assert rows == block
    assert blocks.tolist() == [-(-41 // block)] * 4
    got = lm.attend_absorbed(c, q_nope, q_rope, stack, 0, 0, 64, pos, layer,
                             blocks)
    assert rel_rms(got[live], want[live]) < 1e-5


# widths the prefill kernel can tile (``ops/pallas_latent_attention.py``;
# ``test_pallas_latent_attention.py`` has the kernel's own cases)
TILEABLE = dataclasses.replace(F32, n_heads=2, nope_dim=128, rope_dim=64,
                               v_dim=128, kv_rank=128)


def chunk_program(c, T, S):
    """The chunk call of T rows over a cache of one lane of S rows,
    lowered from shapes alone."""
    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), c))
    cache = jax.eval_shape(lambda: lm.init_cache(c, 1, S))
    return cache, jax.jit(lambda p, t, k, s: lm.forward_with_cache(
        p, t, k, s, c, logits_at=jnp.zeros(1, jnp.int32))).lower(
            params, jax.ShapeDtypeStruct((1, T), jnp.int32), cache,
            jax.ShapeDtypeStruct((1,), jnp.int32))


@pytest.mark.parametrize("form", ["block_loop", "kernel"])
def test_no_program_holds_a_score_of_chunk_by_cache_by_heads(form):
    """The chunk call at a cache of 4096 rows, in the ``jax.numpy`` form
    (widths the kernel cannot tile: a block's score is its largest
    float32 buffer) and in the kernel's (no float32 buffer of heads x
    rows x block at all: a score is a tile's, a head at a time)."""
    c, T = (F32, 64) if form == "block_loop" else (TILEABLE, 128)
    c = dataclasses.replace(c, max_seq_len=4096)
    cache, lowered = chunk_program(c, T, 4096)
    text = lowered.as_text()
    H, S = c.n_heads, 4096
    assert f"{H}x{T}x{S}x" not in text and f"{T}x{S}x" not in text
    held = f"{H}x{T}x{lm.PREFILL_BLOCK}xf32" in text
    assert held == (form == "block_loop")
    # kv_rank + rope values a token a layer, nothing per head
    assert {k: v.shape for k, v in cache.items() if k != "counts"} == {
        "latent": (3, 1, 4096, c.kv_rank),
        "rope_key": (3, 1, c.rope_dim, 4096)}


def test_the_kernels_instructions_carry_the_prefill_forms_scope():
    """``scope_map`` of a compiled chunk program at widths the kernel
    tiles: whatever the kernel became (on the CPU its interpreted ops,
    on a TPU one custom call) lies under ``attn_latent_prefill``, which
    the benchmark's two readers of that scope join a trace with; and no
    block is sliced out of the cache for it (``kv_slice``)."""
    from ray_tpu._private.jax_utils import scope_map

    _, lowered = chunk_program(TILEABLE, 128, 256)
    scopes = scope_map(lowered.compile())
    kernels = [s for s in scopes.values() if "latent_attention_prefill" in s]
    # (a few instructions the CPU compiler makes of the interpreted
    # kernel's reductions keep the path from the kernel's name on only)
    placed = [s for s in kernels
              if not s.startswith("latent_attention_prefill")]
    assert len(placed) > 0.9 * len(kernels) > 0
    assert all("attn/attn_latent_prefill/" in s for s in placed)
    assert not any("kv_slice" in s for s in scopes.values())


def test_the_control_reaches_the_kernel_through_the_layers_weights():
    """``benchmarks/tests/control_pangu.py`` patches ``attend_expanded``
    by name and rounds ``wuk`` and ``wuv`` in the ``layer`` it finds
    among the arguments: on the kernel's path the result changes with
    them, by fp8's rounding and not by nothing."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "tests"))
    import control_pangu

    c = TILEABLE
    keys = jax.random.split(jax.random.PRNGKey(8), 6)
    stack = (jax.random.normal(keys[0], (2, 2, 256, c.kv_rank)),
             jax.random.normal(keys[1], (2, 2, c.rope_dim, 256)))
    layer = {"wuk": jax.random.normal(keys[2], (c.kv_rank, 2, 128)) / 6,
             "wuv": jax.random.normal(keys[3], (c.kv_rank, 2, 128)) / 6}
    q_nope = jax.random.normal(keys[4], (1, 128, 2, 128))
    q_rope = jax.random.normal(keys[5], (1, 128, 2, 64))
    args = (c, q_nope, q_rope, stack, 1, 1, 256, jnp.asarray([100]), layer)
    from ray_tpu.ops import pallas_latent_attention as kernel
    assert kernel.untileable(
        q_nope.transpose(0, 2, 1, 3), q_rope.transpose(0, 2, 1, 3), *stack,
        layer["wuk"], layer["wuv"], 256) is None
    sound = lm.attend_expanded(*args)
    with control_pangu.fp8():
        rounded = lm.attend_expanded(*args)
    assert lm.attend_expanded(*args) is not None      # the patch is gone
    assert 0.01 < rel_rms(rounded, sound) < 0.2


# widths and a cache the decode kernel can tile as well (16 heads: a
# lane's heads are the rows of its matmuls); ``test_pallas_latent_
# attention.py`` has the kernel's own cases
DECODABLE = dataclasses.replace(TILEABLE, n_heads=16)


def prefilled(c, params, tokens, max_seq):
    """A cache of ``max_seq`` rows a lane with lane b's leading
    ``len(tokens[b])`` tokens in it, a lane a call."""
    cache = lm.init_cache(c, len(tokens), max_seq)
    for b, row in enumerate(tokens):
        _, cache = lm.forward_with_cache(
            params, jnp.asarray(row)[None], cache, jnp.zeros(1, jnp.int32),
            c, slot=jnp.int32(b))
    return cache


def test_a_decode_call_through_the_kernel_gives_the_loops_logits(monkeypatch):
    """``forward_with_cache`` at T = 1 on caches of 256 rows (which the
    decode kernel tiles, in blocks of 128) and of 192 (which it does
    not: the ``jax.numpy`` loop), the same parameters and tokens, lane 1
    idle: the live lanes' logits agree to the prefill kernel's
    tolerance, ``attn_rows_decode`` is the same count on both paths, and
    ``attn_blocks_decode`` is what each path's bounds say."""
    from ray_tpu.ops import pallas_latent_attention as kernel

    monkeypatch.setattr(kernel, "_DECODE_BLOCK", 128)
    c = DECODABLE
    params = lm.init_params(jax.random.PRNGKey(5), c)
    lengths = (150, 3, 128)
    rng = np.random.default_rng(5)
    tokens = [rng.integers(1, c.vocab_size, n) for n in lengths]
    step = jnp.asarray([[7], [0], [9]])
    logits, counts = {}, {}
    for max_seq in (256, 192):
        shapes = jax.eval_shape(lambda: lm.init_cache(c, 3, max_seq))
        assert (kernel.decode_untileable(
            c.n_heads, c.rope_dim, shapes["latent"], shapes["rope_key"])
            is None) == (max_seq == 256)
        cache = prefilled(c, params, tokens, max_seq)
        before = lm.read_counters(cache)
        logits[max_seq], cache = lm.forward_with_cache(
            params, step, cache, jnp.asarray([150, max_seq - 1, 128]), c)
        after = lm.read_counters(cache)
        counts[max_seq] = {k: after[k] - before[k] for k in after}
    live = np.asarray([0, 2])
    assert rel_rms(logits[256][live], logits[192][live]) < 1e-5
    for max_seq in (256, 192):
        assert counts[max_seq]["attn_rows_decode"] == 3 * (151 + 129)
    # the kernel: lanes of 151 and 129 rows two blocks of 128 each, the
    # idle lane none; the loop: all three lanes through 192 rows
    assert counts[256]["attn_blocks_decode"] == 3 * (2 + 0 + 2) * 128
    assert counts[192]["attn_blocks_decode"] == 3 * 3 * 192


# ------------------------------------------------------ the held share
SHARE = moe.MoEConfig(d_model=32, d_ff=16, n_experts=16, k=4,
                      scoring="sigmoid", routed_scale=2.5)


def share_params(seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda k, *s: jax.random.normal(k, s, jnp.float32) / np.sqrt(s[-2])
    return {"router": n(keys[0], 32, 16),
            "w_gate": n(keys[1], 16, 32, 16), "w_up": n(keys[2], 16, 32, 16),
            "w_down": n(keys[3], 16, 16, 32),
            "shared_gate": n(keys[4], 32, 24), "shared_up": n(keys[5], 32, 24),
            "shared_down": n(keys[6], 24, 32)}


SHARES = ((0, 1, 2, 3), (4, 5, 6, 7), (8, 9, 10, 11), (12, 13, 14, 15))
SCATTERED = ((5, 0, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13), (14, 9, 4, 3))


@pytest.mark.parametrize("shares", [SHARES, SCATTERED],
                         ids=["blocks", "scattered"])
@pytest.mark.parametrize("seed", [0, 1])
def test_the_shares_parts_add_up_to_the_uncut_layer(seed, shares, path):
    """Over all four shares of 4 of 16 experts: the routed parts summed,
    and the shared expert counted once, equal the uncut reference's
    expert layer, taken before the post-norm."""
    params = share_params(seed)
    x = jax.random.normal(jax.random.PRNGKey(seed + 5), (24, 32))
    shared = {k: params[k] for k in lm.SHARED_WEIGHTS}
    routed = jnp.zeros_like(x)
    assignments = 0
    for i, held in enumerate(shares):
        ids = jnp.asarray(held)
        part = {"router": params["router"],
                **{k: params[k][ids] for k in moe.EXPERT_WEIGHTS}}
        out, counts = moe.moe_ffn_dropless(
            part, x, dataclasses.replace(SHARE, held=held))
        routed = routed + out
        assignments += int(counts[0])
        assert int(counts[2]) == 4
        if i == 0:      # every chip computes the shared expert alike
            with_shared, _ = moe.moe_ffn_dropless(
                {**part, **shared}, x, dataclasses.replace(SHARE, held=held))
            once = with_shared - out
    assert assignments == 24 * 4
    layer = {"router": params["router"], **{
        k: params[k] for k in moe.EXPERT_WEIGHTS + lm.SHARED_WEIGHTS}}
    with jax.default_matmul_precision("highest"):
        want = pangu_reference.routed_part(
            x, layer, held=tuple(range(16)), top_k=4, norm_topk=True,
            scale=2.5) + pangu_reference._swiglu(
                x, shared["shared_gate"], shared["shared_up"],
                shared["shared_down"])
    assert rel_rms(routed + once, want) < 1e-5
    # and the whole layer in one piece, every expert held, says the same
    whole, _ = moe.moe_ffn_dropless(
        {k: layer[k] for k in layer}, x, SHARE)
    assert rel_rms(whole, want) < 1e-5


def test_the_counters_equal_a_count_made_by_hand(path):
    """Held ids that are not the first block; a padded chunk's rows
    behind ``logits_at`` and an idle decode lane are not counted."""
    c = F32                     # holds experts 4..7 of 16, top-4
    params = lm.init_params(jax.random.PRNGKey(3), c)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 32), 0,
                                c.vocab_size)
    cache = lm.init_cache(c, 2, 64)
    _, cache = lm.forward_with_cache(
        params, tokens, cache, jnp.zeros(1, jnp.int32), c,
        slot=jnp.int32(1), logits_at=jnp.asarray([19]))      # 20 live rows
    # lane 0 idle at the scratch row, lane 1 at row 20
    _, cache = lm.forward_with_cache(
        params, jnp.asarray([[0], [5]]), cache, jnp.asarray([63, 20]), c)
    got = lm.read_counters(cache)

    # by hand: the routed layers' inputs through the reference's router
    hp = hp_of(c)
    seq = jnp.concatenate([tokens[0, :20], jnp.asarray([5])])
    x = params["embed"][seq].astype(jnp.float32)
    held, assignments, touched = set(c.held_experts), 0, 0
    attn = lambda x, layer: pangu_reference._attention(
        x, layer, eps=c.norm_eps, theta=c.rope_theta, kv_rank=c.kv_rank,
        nope=c.nope_dim)
    layer = {k: v[0] for k, v in params["dense"].items()}
    x = pangu_reference._dense_mlp(attn(x, layer), layer, eps=c.norm_eps)
    for i in range(c.n_routed_layers):
        layer = {k: v[i] for k, v in params["routed"].items()}
        x = attn(x, layer)
        h = pangu_reference._rms_norm(x, layer["mlp_norm"], c.norm_eps)
        _, chosen = jax.lax.top_k(h @ layer["router"], c.experts_per_token)
        for call in (np.asarray(chosen[:20]), np.asarray(chosen[20:])):
            mine = [e for e in call.ravel() if e in held]
            assignments += len(mine)
            touched += len(set(mine))
        x = pangu_reference._expert_mlp(
            x, layer, eps=c.norm_eps, held=tuple(hp["share"]["held_experts"]),
            top_k=4, norm_topk=True, scale=c.routed_scale)
    assert got["moe_assignments"] == assignments > 0
    assert got["moe_experts_touched"] == touched
    assert got["moe_expert_slots"] == 2 * 2 * 4      # calls, layers, held
    assert got["moe_assignments_all"] == 2 * 21 * 4  # layers, live rows, k
    assert got["attn_pairs_prefill"] == 3 * (20 * 21 // 2)
    assert got["attn_rows_prefill"] == 3 * 20
    assert got["attn_rows_decode"] == 3 * 21
    # the loop's path (widths no kernel tiles): both lanes through the
    # one block of 64 rows that holds the live lane's last row
    assert got["attn_blocks_decode"] == 3 * 2 * 64


# -------------------------------------- the shared code's other family
def parents_dropless_rows(params, x, config):
    """``ops/moe.py``'s dropless layer as it stood before it was told
    which experts it holds (the grouped path and the every-expert path,
    a softmax router, every expert held), written out here."""
    T, D = x.shape
    E, k = config.n_experts, config.k
    probs = jax.nn.softmax(
        x.astype(jnp.float32) @ params["router"].astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    if config.norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    experts = experts.astype(jnp.int32)
    if E <= T * k and T <= moe.EVERY_EXPERT_ROWS:
        combine = jnp.zeros((T, E), jnp.float32).at[
            jnp.arange(T)[:, None], experts].set(weights)
        return moe.expert_ffn_every(
            x, params["w_gate"], params["w_up"], params["w_down"],
            combine).astype(x.dtype)
    flat = experts.reshape(T * k)
    order = jnp.argsort(flat, stable=True)
    group_sizes = jnp.zeros(E, jnp.int32).at[flat].add(1)
    ys = moe.expert_ffn(x[order // k], params["w_gate"], params["w_up"],
                        params["w_down"], group_sizes)
    ys = ys[jnp.argsort(order)].reshape(T, k, D)
    return (ys * weights[..., None]).sum(1).astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("norm_topk", [True, False])
def test_every_expert_held_and_a_softmax_router_is_the_parents_layer(
        dtype, norm_topk, path):
    """What ``models/window_moe.py`` asks of the layer is computed as it
    was, to the last bit, with ``held`` left None and with every id
    named."""
    config = moe.MoEConfig(d_model=32, d_ff=16, n_experts=8, k=2,
                           norm_topk_prob=norm_topk)
    params = moe.init_moe_params(jax.random.PRNGKey(0), config, dtype=dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 32), dtype)
    want = np.asarray(parents_dropless_rows(params, x, config), np.float32)
    for held in (None, tuple(range(8))):
        got, counts = moe.moe_ffn_dropless(
            params, x, dataclasses.replace(config, held=held))
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)
        assert [int(n) for n in counts[::2]] == [80, 8]


# ------------------------------------------------------- the engine
def test_the_engine_serves_the_family_as_a_loop_over_its_forward_does():
    """Mixed prompt lengths through ``LlamaEngine`` (chunks of 16, two
    read windows, lanes joining and leaving): every request's greedy
    tokens are those of a loop over the reference on its own tokens."""
    c = F32
    params = lm.init_params(jax.random.PRNGKey(0), c)
    eng = LlamaEngine(c, params, max_batch=3, max_seq=64, prefill_chunk=16,
                      max_slots=3)
    assert eng.windows == [32, 64] and eng.buckets == [16]
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, c.vocab_size, n)) for n in (37, 5, 20, 9)]
    reqs = [GenRequest(f"r{i}", [int(t) for t in p], max_tokens=6)
            for i, p in enumerate(prompts)]
    pending = list(reqs)
    while pending or eng.num_active():
        while pending and eng.add_request(pending[0]):
            pending.pop(0)
        eng.step()
    for req, prompt in zip(reqs, prompts):
        seq = [int(t) for t in prompt]
        for _ in range(6):
            logits = pangu_reference.logits(params, jnp.asarray(seq),
                                            hp_of(c), last=1)[-1]
            seq.append(int(jnp.argmax(logits)))
        assert req.generated == seq[len(prompt):]
    stats = eng.stats.snapshot()
    assert stats["moe_assignments_all"] == (
        (sum(map(len, prompts)) + 4 * 5) * c.n_routed_layers
        * c.experts_per_token)
    assert 0 < stats["moe_assignments"] < stats["moe_assignments_all"]
    assert stats["attn_rows_decode"] > 0 and stats["attn_pairs_prefill"] > 0


def test_the_cells_configuration_is_the_published_one_cut_by_its_share():
    """The configuration file through its family: every width the
    source's, the router at its width, the held ids, the vocabulary's
    slice; and the chunk the engine derives from it on a v5e."""
    from benchmarks import spec
    from ray_tpu.llm._internal.engine import derived_prefill_chunk

    cell = spec.load_cell("openpangu-ultra-moe-718b.serve-longdoc", False)
    hp = cell["hp"]
    cfg = spec.family_of(hp).model_config(hp)
    assert (cfg.dim, cfg.n_heads, cfg.q_rank, cfg.kv_rank, cfg.nope_dim,
            cfg.rope_dim, cfg.v_dim, cfg.ffn_dim, cfg.expert_dim) == (
        7680, 128, 1536, 512, 128, 64, 128, 18432, 2048)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.held_experts) == (
        256, 8, tuple(range(8)))
    shapes = jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    count = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert round(count / 1e9, 2) == 3.41        # 6.83 GB as initialised
    assert shapes["routed"]["router"].shape == (4, 7680, 256)
    assert shapes["routed"]["w_gate"].shape == (4, 8, 7680, 2048)
    assert shapes["dense"]["w_gate"].shape == (1, 7680, 18432)
    lanes = cell["serve"]["max_batch_size"]
    assert lanes == cell["traffic"]["clients"] == 16
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, lanes, 16384))
    assert sum(a.size * a.dtype.itemsize for a in
               jax.tree_util.tree_leaves(cache)) // 10 ** 7 == 150  # 1.51 GB
    # the held experts (1.51 G parameters) are read beside the 1.74 G
    # every row multiplies; a lane of 16 384 holds 8192 rows on average,
    # whose expansion (168 MFLOP a row over 5 layers) is 394 rows' worth
    # of 2 FLOPs a parameter: 240 x 1.87 + 394 = 843 rows on a v5e
    terms = lm.chunk_terms(cfg, 16384)
    held = sum(shapes["routed"][k].size for k in moe.EXPERT_WEIGHTS)
    every_row = (
        sum(shapes[kind][k].size for kind in ("dense", "routed")
            for k in ("wdq", "wuq", "wdkv", "wuk", "wuv", "wo"))
        + sum(shapes["dense"][k].size for k in moe.EXPERT_WEIGHTS)
        + sum(shapes["routed"][k].size for k in lm.SHARED_WEIGHTS)
        + shapes["lm_head"].size)
    assert terms == {
        "read_beside": held / every_row,
        "once_rows": 5 * 2 * 512 * 128 * 256 * 8192 / (2 * every_row)}
    assert (round(terms["read_beside"], 2), round(terms["once_rows"])) == (
        0.87, 394)
    assert derived_prefill_chunk("TPU v5 lite", 2, 16384, **terms) == 1024
    assert derived_prefill_chunk(
        "TPU v5 lite", 2, 16384, read_beside=terms["read_beside"]) == 512


def test_the_chunk_rule_is_told_which_weights_hold_most_of_the_model():
    """By hand at the tiny widths: 124 928 parameters every row
    multiplies (three layers' attention, a dense MLP, two shared
    experts, the head) beside 49 152 of held experts, so the experts'
    read and the expansion are paid once; with every expert held and
    wider the experts hold most and an expert's rows are what counts."""
    every_row = 3 * 18432 + 3 * 64 * 128 + 2 * 3 * 64 * 32 + 64 * 512
    assert lm.chunk_terms(F32, 256) == {
        "read_beside": 2 * 4 * 3 * 64 * 32 / every_row,
        "once_rows": 3 * 2 * 32 * 4 * (16 + 16) * 128 / (2 * every_row)}
    assert (lm.chunk_terms(F32, 512)["once_rows"]
            == 2 * lm.chunk_terms(F32, 256)["once_rows"])
    mostly_experts = dataclasses.replace(
        F32, held_experts=tuple(range(16)), expert_dim=64, n_dense_layers=0)
    assert lm.chunk_terms(mostly_experts, 256) == {"row_share": 4 / 16}


@pytest.mark.parametrize("kind, max_seq, chunk", [
    # 8 of 256 experts held beside latent attention at the published
    # widths, bf16: the ridge x 1.87 + the expansion's rows' worth
    ("TPU v5 lite", 16384, 1024),   # 449 + 394 = 843
    ("TPU v4", 16384, 1024),        # 418 + 394 = 812
    ("TPU v5", 16384, 512),         # 310 + 394 = 704
    ("TPU v5p", 16384, 512),
    ("TPU v6 lite", 16384, 1024),   # 1044 + 394 = 1439
    ("cpu", 16384, 512),            # unknown: the smallest ratio
    # a shorter cache holds fewer rows to expand: 449 + 197 = 646
    ("TPU v5 lite", 8192, 512),
    ("TPU v5 lite", 32768, 1024),   # 449 + 788 = 1237
    ("TPU v5 lite", 512, 512),      # 449 + 12, capped by max_seq
    ("TPU v5 lite", 256, 256),
    ("TPU v5 lite", 15872, 512),    # 831 -> 1024, halved until it divides
])
def test_the_cells_chunk_follows_from_configuration_cache_and_chip(
        kind, max_seq, chunk):
    from benchmarks import spec
    from ray_tpu._private.accelerators.tpu import CHIP_PEAKS
    from ray_tpu.llm._internal.engine import derived_prefill_chunk

    assert kind in CHIP_PEAKS or kind == "cpu"
    hp = spec.load_cell("openpangu-ultra-moe-718b.serve-longdoc", False)["hp"]
    cfg = spec.family_of(hp).model_config(hp)
    got = derived_prefill_chunk(kind, 2, max_seq,
                                **lm.chunk_terms(cfg, max_seq))
    assert got == chunk <= max_seq and max_seq % got == 0


def test_an_engine_of_the_derived_chunk_serves_what_one_of_256_rows_does():
    """Built with no ``prefill_chunk``, the tiny configuration's engine
    takes the rule's answer for its own widths (float32 on a kind the
    table lacks: 332 x 1.39 + 101 rows' worth of expansion at 2048 ->
    512), with the buckets and windows that follow, and a seeded
    prompt's greedy tokens are those of 256-row chunks."""
    from ray_tpu.llm._internal.engine import derived_prefill_chunk

    params = lm.init_params(jax.random.PRNGKey(0), F32)
    kw = dict(max_batch=2, max_seq=2048, max_slots=2)
    eng = LlamaEngine(F32, params, **kw)
    assert eng.prefill_chunk == 512 == derived_prefill_chunk(
        jax.devices()[0].device_kind, 4, 2048, **lm.chunk_terms(F32, 2048))
    assert eng.buckets == [128, 256, 512] and eng.windows == [1024, 2048]
    small = LlamaEngine(F32, params, prefill_chunk=256, **kw)
    rng = np.random.default_rng(49)
    # whole chunks and a tail, a bucket under the chunk, a few rows
    for n in (1300, 200, 9):
        prompt = [int(t) for t in rng.integers(1, F32.vocab_size, n)]
        assert (eng.generate(prompt, max_tokens=6)
                == small.generate(prompt, max_tokens=6))
    assert eng.stats.prefill_chunks == 5 < small.stats.prefill_chunks
