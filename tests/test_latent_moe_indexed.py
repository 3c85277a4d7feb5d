"""``models/latent_moe.py`` with an indexer (every row attends to the
``index_topk`` rows of largest index score), ``ops/index_select.py``, the
score kernel of ``ops/pallas_index_score.py``, the selection mask in
``ops/pallas_latent_attention.py``'s prefill kernel and the groups and
selection bias of ``ops/moe.py`` ``route_top_k``: toy sizes on the CPU,
float32 parameters where a tight limit needs them, against the float32
reference of ``benchmarks/families/deepseek_reference.py`` (which
imports nothing of ``ray_tpu``) and against counts made by hand here."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.families import deepseek_reference as reference  # noqa: E402
from ray_tpu.llm import GenRequest, LlamaEngine  # noqa: E402
from ray_tpu.models import latent_moe as lm  # noqa: E402
from ray_tpu.ops import index_select, moe, pallas_index_score  # noqa: E402
from ray_tpu.ops import pallas_latent_attention as kernel  # noqa: E402

F32 = dataclasses.replace(lm.LATENT_MOE_INDEXED_TINY, dtype=jnp.float32,
                          param_dtype=jnp.float32)     # 1 dense + 2 routed
FIVE = dataclasses.replace(F32, n_layers=5)             # 1 dense + 4 routed


def hp_of(c: lm.LatentMoEConfig) -> dict:
    """The config.json keys the reference reads, from a configuration
    object of the program's."""
    y = c.yarn
    return {"rms_norm_eps": c.norm_eps, "rope_theta": c.rope_theta,
            "rope_scaling": None if y is None else {
                "type": "yarn", "factor": y.factor, "mscale": 1,
                "mscale_all_dim": 1, "beta_fast": y.beta_fast,
                "beta_slow": y.beta_slow,
                "original_max_position_embeddings": y.original_max_position},
            "kv_lora_rank": c.kv_rank, "qk_nope_head_dim": c.nope_dim,
            "index_topk": c.index_topk, "num_hidden_layers": c.n_layers,
            "first_k_dense_replace": c.n_dense_layers,
            "num_experts_per_tok": c.experts_per_token,
            "n_group": c.n_groups, "topk_group": c.groups_kept,
            "norm_topk_prob": c.norm_topk_prob,
            "routed_scaling_factor": c.routed_scale,
            "share": {"router_experts": c.n_experts,
                      "held_experts": list(c.held_experts)}}


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


FORWARD = jax.jit(lm.forward_with_cache, static_argnames=("config", "rows"))


def served(c, params, tokens, *, chunk, prefilled, lanes, lane, max_seq,
           rows=None, shards=1, pad_to=None, said=None):
    """``tokens`` through the cache the way the engine sends them: the
    first ``prefilled`` in chunks of ``chunk`` rows (each padded to
    ``pad_to`` or to the chunk) into lane ``lane`` of the first of
    ``shards`` caches, the others one decode call each, every other lane
    idle -> ({position: logits}, the caches). ``said``: a list that
    takes what every call says it chose at the sequence's rows
    (``read_choices``)."""
    caches = tuple(lm.init_cache(c, lanes, max_seq, pad_to or chunk)
                   for _ in range(shards))
    got = {}
    for start in range(0, prefilled, chunk):
        n = min(chunk, prefilled - start)
        padded = np.zeros((1, pad_to or chunk), np.int32)
        padded[0, :n] = tokens[start:start + n]
        logits, first = FORWARD(
            params, jnp.asarray(padded), caches[0], jnp.asarray([start]), c,
            slot=jnp.int32(lane), logits_at=jnp.asarray([n - 1]), rows=rows)
        caches = (first, *caches[1:])
        got[start + n - 1] = np.asarray(logits[0, 0])
        if said is not None:
            said.append(lm.read_choices(first)[:, :n])
    for p in range(prefilled, len(tokens)):
        lengths = np.full(shards * lanes, max_seq - 1, np.int32)
        lengths[lane] = p
        row = np.zeros((shards * lanes, 1), np.int32)
        row[lane, 0] = tokens[p]
        logits, caches = FORWARD(
            params, jnp.asarray(row), caches if shards > 1 else caches[0],
            jnp.asarray(lengths), c, rows=rows)
        caches = caches if shards > 1 else (caches,)
        got[p] = np.asarray(logits[lane, 0])
        if said is not None:
            said.append(lm.read_choices(caches[0])[:, lane:lane + 1])
    return got, caches


# ------------------------------------------------- against the reference
@pytest.mark.parametrize("case", [
    dict(config=F32, lanes=3, lane=1),
    dict(config=F32, lanes=2, lane=1, shards=2),
    dict(config=F32, lanes=2, lane=0, pad_to=32),
    dict(config=FIVE, lanes=2, lane=1, rows=128),
    dict(config=dataclasses.replace(FIVE, held_experts=(9, 2, 15)), lanes=2,
         lane=0),
    dict(config=dataclasses.replace(FIVE, yarn=None, score_mscale=1.0),
         lanes=2, lane=0),
], ids=["tiny", "two_shards", "padded_chunks", "read_window",
        "scattered_ids", "default_rope"])
def test_chunks_then_decodes_through_the_cache_equal_the_reference(case):
    """Contexts of 70 rows, past the toy ``index_topk`` (16) and the
    YaRN table's original context (64); an idle lane beside the live one in every decode call; chunks whose
    last is padded (52 = 3 x 16 + 4), and all of them where ``pad_to``;
    two shards through ``by_shard``."""
    case = dict(case)
    c = case.pop("config")
    params = lm.init_params(jax.random.PRNGKey(0), c)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (70,), 0, c.vocab_size))
    want = reference.logits(params, tokens, hp_of(c))
    got, _ = served(c, params, tokens, chunk=16, prefilled=52, max_seq=256,
                    **case)
    assert sorted(got) == [15, 31, 47, 51, *range(52, 70)]
    for p, row in got.items():
        assert rel_rms(row, want[p]) < 2e-5, p


def test_a_call_with_start_0_reads_nothing_the_slot_held():
    """A second sequence into a slot a longer one used: its logits are
    those of a fresh cache's (the rows by position behind its own, the
    index keys among them, are the first one's still)."""
    c = FIVE
    params = lm.init_params(jax.random.PRNGKey(0), c)
    first, second = (np.asarray(jax.random.randint(
        jax.random.PRNGKey(s), (n,), 0, c.vocab_size))
        for s, n in ((1, 70), (2, 40)))
    _, caches = served(c, params, first, chunk=16, prefilled=64, lanes=2,
                       lane=1, max_seq=128)
    fresh, _ = served(c, params, second, chunk=16, prefilled=32, lanes=2,
                      lane=1, max_seq=128)
    cache = caches[0]
    for start in (0, 16):
        row = jnp.asarray(second[None, start:start + 16])
        logits, cache = FORWARD(params, row, cache, jnp.asarray([start]), c,
                                slot=jnp.int32(1), logits_at=jnp.asarray([15]))
        np.testing.assert_allclose(logits[0, 0], fresh[start + 15],
                                   rtol=0, atol=1e-5)
    for p in range(32, 40):
        lengths = jnp.asarray([127, p], jnp.int32)
        logits, cache = FORWARD(params, jnp.asarray([[0], [second[p]]]),
                                cache, lengths, c)
        np.testing.assert_allclose(logits[1, 0], fresh[p], rtol=0, atol=1e-5)


def test_within_index_topk_a_layer_is_dense_latent_attention():
    """At contexts of ``index_topk`` rows or fewer the selection takes
    every row the causal mask allows, and the expanded form under that
    mask gives bit for bit what it gives with no mask, in float32."""
    c = F32
    rng = np.random.default_rng(0)
    B, T, S = 2, 8, 32
    layer = {"wuk": jnp.asarray(rng.normal(size=(c.kv_rank, c.n_heads,
                                                 c.nope_dim)), jnp.float32),
             "wuv": jnp.asarray(rng.normal(size=(c.kv_rank, c.n_heads,
                                                 c.v_dim)), jnp.float32)}
    q_nope = jnp.asarray(rng.normal(size=(B, T, c.n_heads, c.nope_dim)),
                         jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(B, T, c.n_heads, c.rope_dim)),
                         jnp.float32)
    stack = (jnp.asarray(rng.normal(size=(1, B, S, c.kv_rank)), jnp.float32),
             jnp.asarray(rng.normal(size=(1, B, c.rope_dim, S)), jnp.float32))
    start = jnp.asarray([3, 0])
    pos = start[:, None] + jnp.arange(T)[None]
    before = jnp.arange(S)[None, None, :] <= pos[:, :, None]
    scores = jnp.asarray(rng.normal(size=(B, T, S)), jnp.float32)
    allowed = index_select.select_mask(scores, before, F32.index_topk)
    np.testing.assert_array_equal(allowed, before)      # 11 rows at the most, of 16
    dense = lm.attend_expanded(c, q_nope, q_rope, stack, 0, 0, S, start, layer)
    chosen = lm.attend_expanded(c, q_nope, q_rope, stack, 0, 0, S, start,
                                layer, allowed)
    np.testing.assert_array_equal(dense, chosen)


# --------------------------------------------------------- the selection
def reference_selection(scores, valid, k):
    """The reference's spelling: a stable sort of the negated scores."""
    s = np.where(valid, scores, -np.inf)
    out = np.zeros(s.shape, bool)
    for i, row in enumerate(s):
        order = np.argsort(-row, kind="stable")[:k]
        out[i, order] = True
    return out & valid


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_the_selected_set_is_the_references_and_the_tie_rule_holds(ties):
    rng = np.random.default_rng(3)
    T, S, k = 24, 96, 16
    scores = rng.normal(size=(T, S)).astype(np.float32)
    if ties:
        # few distinct values, zeros of both signs and whole rows equal
        scores = np.round(scores * 2) / 2
        scores[scores == 0] *= rng.choice([-1.0, 1.0], (scores == 0).sum())
        scores[5] = 0.25
    valid = np.arange(S)[None, :] <= np.arange(40, 40 + T)[:, None]
    valid[:3] = np.arange(S)[None, :] <= np.asarray([3, 15, 16])[:, None]
    want = reference_selection(scores, valid, k)
    got = np.asarray(index_select.select_mask(
        jnp.asarray(scores), jnp.asarray(valid), k))
    np.testing.assert_array_equal(got, want)
    assert got.sum(-1).tolist() == np.minimum(valid.sum(-1), k).tolist()
    rows, chosen = index_select.select_rows(
        jnp.asarray(scores), jnp.asarray(valid), k)
    as_mask = np.zeros((T, S), bool)
    for i in range(T):
        as_mask[i, np.asarray(rows[i])[np.asarray(chosen[i])]] = True
    np.testing.assert_array_equal(as_mask, want)
    if ties:
        # row 5 is all one score: its 16 rows are the first 16 by index
        assert np.flatnonzero(got[5]).tolist() == list(range(16))


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_score_kernel_equals_its_jax_numpy_form(dtype, tol):
    """Interpreted on the CPU at a small shape the kernel tiles; the
    blocks behind a tile's last row are skipped and hold 0, which the
    selection never reads."""
    rng = np.random.default_rng(1)
    B, T, S, Hi, di = 2, 256, 1024, 4, 128
    q = jnp.asarray(rng.normal(size=(B, T, Hi, di)), dtype)
    keys = jnp.asarray(rng.normal(size=(B, S, di)), dtype)
    w = jnp.asarray(rng.normal(size=(B, T, Hi)), jnp.float32)
    start = jnp.asarray([0, 512])
    assert pallas_index_score.untileable(q, keys) is None
    got = np.asarray(pallas_index_score.index_score(q, w, keys, start))
    want = np.asarray(index_select.index_scores_blockwise(q, w, keys))
    before = (np.arange(S)[None, None, :]
              <= np.asarray(start)[:, None, None] + np.arange(T)[None, :, None])
    scale = np.abs(want).max()
    assert np.abs(np.where(before, got - want, 0.0)).max() <= tol * scale
    # sequence 0's tiles see nothing past row 255: those blocks are 0
    assert not got[0, :, 512:].any()
    np.testing.assert_allclose(
        np.where(before, index_select.index_scores(q, w, keys, start), 0),
        np.where(before, got, 0))
    assert "lanes" in pallas_index_score.untileable(q[..., :64], keys[..., :64])


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_prefill_kernel_under_a_selection_equals_the_block_loop(
        dtype, tol):
    """The expanded form's kernel with ``allowed`` against the
    ``jax.numpy`` loop with it: rows whose first marked row lies blocks
    behind row 0 (what they gathered before fades to 0); the scores'
    scale the configuration's (a YaRN factor's square in it)."""
    rng = np.random.default_rng(2)
    nope = 128
    c = dataclasses.replace(
        lm.LATENT_MOE_INDEXED_TINY, dim=64, n_heads=4, q_rank=32,
        kv_rank=128, nope_dim=nope, rope_dim=16, v_dim=128, dtype=dtype)
    B, T, S = 1, 128, 512
    layer = {"wuk": jnp.asarray(rng.normal(size=(c.kv_rank, 4, nope)) / 11,
                                dtype),
             "wuv": jnp.asarray(rng.normal(size=(c.kv_rank, 4, 128)) / 11,
                                dtype)}
    q_nope = jnp.asarray(rng.normal(size=(B, T, 4, nope)), dtype)
    q_rope = jnp.asarray(rng.normal(size=(B, T, 4, 16)), dtype)
    stack = (jnp.asarray(rng.normal(size=(2, 3, S, c.kv_rank)), dtype),
             jnp.asarray(rng.normal(size=(2, 3, 16, S)), dtype))
    start = jnp.asarray([300])
    pos = start[:, None] + jnp.arange(T)[None]
    before = np.arange(S)[None, None, :] <= np.asarray(pos)[:, :, None]
    allowed = before & (rng.random((B, T, S)) < 0.05)
    allowed[:, :, :256] &= np.arange(T)[None, :, None] % 2 == 0
    allowed[0, np.arange(T), np.asarray(pos[0])] = True   # one at least
    allowed = jnp.asarray(allowed)
    args = (c, q_nope, q_rope, stack, 1, 2, S, start, layer, allowed)
    assert kernel.untileable(
        *(jnp.zeros((B, 4, T, w), dtype) for w in (-(-nope // 128) * 128, 16)),
        *stack, jnp.zeros((c.kv_rank, 4, -(-nope // 128) * 128), dtype),
        layer["wuv"], S) is None
    got = np.asarray(lm.attend_expanded(*args), np.float32)
    read = lm._stack_reader(stack, 1, 2, B)
    want = np.asarray(lm.attend_expanded_blockwise(
        c, q_nope, q_rope, read, S, pos, layer, allowed), np.float32)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()
    dense = np.asarray(lm.attend_expanded(*args[:-1]), np.float32)
    assert np.abs(dense - want).max() > 10 * tol * np.abs(want).max()


# ------------------------------------------------------------ the experts
def test_the_held_shares_add_up_to_the_uncut_layer_under_groups_and_bias():
    """Every chip's share of a layer that routes within its best groups
    by score + bias (4 groups of 4, 2 kept), the shared expert once,
    sums to the layer that holds all sixteen; the bias and the groups
    move some rows' choices and no weight; ``route_top_k`` chooses what
    the reference's router does."""
    c = F32
    rng = np.random.default_rng(4)
    D, E, F = c.dim, c.n_experts, c.expert_dim
    x = jnp.asarray(rng.normal(size=(40, D)), jnp.float32)
    whole = {"router": jnp.asarray(rng.normal(size=(D, E)) / 8, jnp.float32),
             "router_bias": jnp.asarray(0.3 * rng.normal(size=(E,)),
                                        jnp.float32),
             **{k: jnp.asarray(rng.normal(size=s) / 8, jnp.float32)
                for k, s in (("w_gate", (E, D, F)), ("w_up", (E, D, F)),
                             ("w_down", (E, F, D)), ("shared_gate", (D, F)),
                             ("shared_up", (D, F)), ("shared_down", (F, D)))}}
    shared = {k: whole[k] for k in lm.SHARED_WEIGHTS}
    routers = {k: whole[k] for k in ("router", "router_bias")}

    def layer(held, with_shared):
        config = dataclasses.replace(c.moe, held=held)
        ids = list(range(E)) if held is None else list(held)
        params = {**routers, **(shared if with_shared else {}),
                  **{k: whole[k][jnp.asarray(ids)] for k in moe.EXPERT_WEIGHTS}}
        return moe.moe_ffn_dropless(params, x, config)[0]

    uncut = layer(None, True)
    parts = [layer(tuple(range(i, i + 4)), i == 0) for i in range(0, E, 4)]
    np.testing.assert_allclose(sum(parts), uncut, rtol=0, atol=2e-5)
    weights, experts = moe.route_top_k(x, whole["router"], c.moe,
                                       whole["router_bias"])
    assert c.moe.n_groups == 4 and c.moe.groups_kept == 2
    groups = np.asarray(experts) // 4
    assert all(len(set(row)) <= 2 for row in groups.tolist())
    _, _, chosen = reference.route(
        x, {"router": whole["router"], "router_bias": whole["router_bias"]},
        top_k=c.experts_per_token, groups=4, kept=2)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    # the groups bite, and so does the bias
    free = dataclasses.replace(c.moe, n_groups=1, groups_kept=1)
    _, ungrouped = moe.route_top_k(x, whole["router"], free,
                                   whole["router_bias"])
    assert (np.sort(experts, -1) != np.sort(ungrouped, -1)).any()
    plain_w, plain = moe.route_top_k(x, whole["router"], c.moe)
    differs = (np.sort(experts, -1) != np.sort(plain, -1)).any(-1)
    assert 0 < differs.sum() < len(differs)
    scores = jax.nn.sigmoid(x @ whole["router"])
    np.testing.assert_allclose(
        weights, np.take_along_axis(np.asarray(scores), np.asarray(experts), -1)
        / np.take_along_axis(np.asarray(scores), np.asarray(experts),
                             -1).sum(-1, keepdims=True) * c.routed_scale,
        rtol=1e-6)
    np.testing.assert_allclose(weights[~differs].sum(-1), c.routed_scale,
                               rtol=1e-6)
    assert plain_w.shape == weights.shape


# ----------------------------------------------------------- the counters
def test_every_counter_of_one_call_equals_a_count_made_by_hand():
    c = F32                 # 3 layers, 2 of them routed; index_topk 16
    params = lm.init_params(jax.random.PRNGKey(0), c)
    cache = lm.init_cache(c, 2, 128, 16)
    assert cache["counts"].shape == (11, 2)
    tokens = np.arange(16)[None] % c.vocab_size
    # a chunk of 16 rows from row 32, 10 of them live
    _, cache = FORWARD(params, jnp.asarray(tokens), cache, jnp.asarray([32]),
                       c, slot=jnp.int32(1), logits_at=jnp.asarray([9]))
    got = lm.read_counters(cache)
    live = range(32, 42)
    assert got["attn_rows_indexed"] == 3 * sum(p + 1 for p in live)
    assert got["attn_rows_selected"] == 3 * 10 * 16
    assert got["attn_pairs_prefill"] == 3 * 10 * 16
    assert got["attn_rows_prefill"] == 3 * 42
    assert got["attn_rows_decode"] == got["attn_blocks_decode"] == 0
    assert got["moe_assignments_all"] == 2 * 10 * c.experts_per_token
    assert got["moe_expert_slots"] == 2 * c.n_held
    assert 0 < got["moe_assignments"] < got["moe_assignments_all"]
    # a decode call: lane 0 idle, lane 1 at row 5 (under index_topk)
    before = got
    _, cache = FORWARD(params, jnp.asarray([[0], [7]]), cache,
                       jnp.asarray([127, 5]), c)
    got = {k: v - before[k] for k, v in lm.read_counters(cache).items()}
    assert got["attn_rows_indexed"] == 3 * 6
    assert got["attn_rows_selected"] == got["attn_rows_decode"] == 3 * 6
    assert got["attn_blocks_decode"] == 3 * 16      # the places gathered
    assert got["attn_pairs_prefill"] == got["attn_rows_prefill"] == 0
    assert got["moe_assignments_all"] == 2 * c.experts_per_token
    assert lm.attn_rows_read(c, cache, 64) == 64
    # a model with no indexer has neither the words nor the leaves
    plain = lm.init_cache(dataclasses.replace(
        c, index_topk=0, index_heads=0, index_dim=0), 2, 128, 16)
    assert set(plain) == {"latent", "rope_key", "counts"}
    assert plain["counts"].shape == (9, 2)


# ------------------------------------------------- what a call chose
@pytest.mark.parametrize("case", [
    dict(config=F32, lanes=3, lane=1),
    dict(config=F32, lanes=2, lane=1, shards=2),
    dict(config=FIVE, lanes=2, lane=0, pad_to=32),
], ids=["tiny", "two_shards", "padded_chunks"])
def test_what_every_call_says_it_chose_is_what_the_reference_chooses(case):
    """``read_choices`` after every call: each layer's set at each
    of the sequence's rows, as bits, is the reference's own (float32 on
    both sides), ``min(t + 1, index_topk)`` rows of it; each routed
    layer's experts are experts; and the reference under those choices
    gives the calls' logits at a margin of 0."""
    case = dict(case)
    c = case.pop("config")
    params = lm.init_params(jax.random.PRNGKey(0), c)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (70,), 0, c.vocab_size))
    said = []
    got, _ = served(c, params, tokens, chunk=16, prefilled=52, max_seq=256,
                    said=said, **case)
    choices = np.concatenate(said, axis=1)
    n_full, n_routed = c.n_layers, c.n_routed_layers
    assert choices.shape == (n_full + 1, 70, 256)
    assert choices.dtype == np.int32
    hp = hp_of(c)
    for f in range(n_full):
        rows = np.asarray(reference.handed_rows(
            jnp.asarray(choices[f]), 70))
        assert (rows.sum(1) == np.minimum(np.arange(70) + 1, 16)).all()
        assert not np.triu(rows, 1).any()         # none behind the query
    experts = choices[-1][:, :n_routed * 4].reshape(70, n_routed, 4)
    assert (choices[-1][:, n_routed * 4:] == -1).all()
    assert (experts >> 16 == np.arange(n_routed)[:, None]).all()
    experts = experts & 0xFFFF
    # no two entries of a row are the same number: rows compare as sets
    assert all(len(set(row)) == 256 for row in choices[0].tolist())
    assert experts.min() >= 0 and experts.max() < c.n_experts
    assert all(len(set(row)) == 4 for row in experts.reshape(-1, 4).tolist())
    under, margin = reference.logits(params, tokens, hp, choices=choices)
    assert margin.shape == (n_full + n_routed, 70)
    # (a set differs where two scores tie to float32's last digits, and
    # the rows behind such a row then read otherwise than the
    # reference's own: under the call's choices they read the call's)
    assert 0.0 <= float(np.asarray(margin).min())
    assert float(np.asarray(margin).max()) < 1e-5
    for p, row in got.items():
        assert rel_rms(row, under[p]) < 2e-5, p


@pytest.mark.parametrize("row", [69, 50], ids=["last_block", "two_blocks"])
def test_a_pass_that_differs_from_a_late_row_on_is_made_from_that_row_on(
        row, monkeypatch):
    """The reference under choices, asked again with one late row's
    choices replaced: with the whole pass kept (``memo``) it computes
    the query blocks from that row's on and gives the whole pass's
    numbers, margins too; other tokens, or other weights, are a whole
    pass again."""
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    jax.clear_caches()
    c = FIVE
    params = lm.init_params(jax.random.PRNGKey(0), c)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (70,), 0, c.vocab_size))
    said = []
    served(c, params, tokens, chunk=16, prefilled=52, max_seq=256, said=said,
           lanes=2, lane=0)
    choices, hp, memo = np.concatenate(said, axis=1), hp_of(c), {}
    reference.logits(params, tokens, hp, choices=choices, memo=memo)
    assert len(memo["inputs"]) == c.n_layers + 1
    other = choices.copy()
    other[:, row] = choices[:, row - 1]        # another row's sets
    assert reference._same_until(memo, params, tokens, other) == \
        row // 16 * 16
    whole, far = reference.logits(params, tokens, hp, choices=other)
    again, margin = reference.logits(params, tokens, hp, choices=other,
                                          memo=memo, last=24)
    assert rel_rms(again, whole[-24:]) < 1e-6
    assert np.allclose(np.asarray(margin), np.asarray(far), rtol=1e-4,
                       atol=1e-5)
    assert float(np.asarray(far)[:, row].max()) > 0.1
    assert np.array_equal(memo["choices"], choices)     # the whole pass's
    assert reference._same_until(memo, params, tokens[::-1], other) == 0
    copied = {**params, "final_norm": jnp.copy(params["final_norm"])}
    assert reference._same_until(memo, copied, tokens, other) == 0
    jax.clear_caches()


def said_words(sets, width=128):
    """Sets of cache rows, one a query row -> the words the program lays
    their bits into."""
    words = np.zeros((len(sets), width), np.uint32)
    for t, rows in enumerate(sets):
        for r in rows:
            words[t, r // 4096 * 128 + r % 128] |= np.uint32(1) << np.uint32(
                r % 4096 // 128)
    return words


def handed(sets, width=128):
    """... -> the tagged halves ``read_choices`` says them in."""
    cache = {"said_rows": said_words(sets, width)[None],
             "said_experts": np.zeros((1, len(sets), 1), np.int32),
             "said_count": len(sets)}
    return lm.read_choices(cache)[0]


@pytest.mark.parametrize("what, far", [
    ("the_edge_rows_exchanged", False), ("the_best_row_left_out", True),
    ("a_row_too_few", None), ("a_row_behind_the_query", None),
], ids=lambda x: x if isinstance(x, str) else "")
def test_a_handed_set_is_judged_by_how_far_it_lies_from_the_references(
        what, far):
    """The reference under a set that is not its own, at one row: the
    12th and 13th best exchanged is as far as their two scores are apart
    in units of three of the row's root mean squares; the best row left
    out for the 13th
    is as far as the best lies over the 12th; a set of another size, or
    with a row behind the query's own, is no selection (``FAR``). Every
    other row's margin stays 0."""
    rng = np.random.default_rng(3)
    S, k, at = 40, 12, 30
    q = jnp.asarray(rng.normal(size=(S, 4, 16)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(S, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(S, 4)), jnp.float32)
    own, zero = reference.selected_rows(q, keys, w, k)
    own = np.asarray(own).reshape(-1, S)[:S]
    assert float(np.abs(np.asarray(zero)).max()) == 0.0
    score = np.asarray((jnp.maximum(jnp.einsum("thd,sd->ths", q, keys), 0.0)
                        * w[..., None]).sum(1))[at, :at + 1]
    order = np.argsort(-score, kind="stable")
    sets = [set(np.flatnonzero(row).tolist()) for row in own]
    unit = reference.SET_UNIT * float(np.sqrt(np.mean(score ** 2)))
    if what == "the_edge_rows_exchanged":
        sets[at] = sets[at] - {order[k - 1]} | {order[k]}
        want = (score[order[k - 1]] - score[order[k]]) / unit
    elif what == "the_best_row_left_out":
        sets[at] = sets[at] - {order[0]} | {order[k]}
        want = (score[order[0]] - score[order[k - 1]]) / unit
    elif what == "a_row_too_few":
        sets[at] = sets[at] - {order[3]}
        want = reference.FAR
    else:
        sets[at] = sets[at] - {order[3]} | {at + 2}
        want = reference.FAR
    taken, margin = reference.selected_rows(
        q, keys, w, k, handed=jnp.asarray(handed(sets)))
    margin = np.asarray(margin)
    assert np.asarray(taken).reshape(-1, S)[at].nonzero()[0].tolist() == \
        sorted(sets[at])
    assert margin[at] == pytest.approx(want, rel=1e-4) and want > 0
    assert (np.delete(margin, at) == 0).all()
    if far is not None:
        assert bool(margin[at] > 0.3) is far


@pytest.mark.parametrize("rows", [40, 4096, 5000], ids=str)
def test_a_set_said_as_a_mask_or_as_a_list_is_the_same_bits(rows):
    """``mask_as_bits`` of a mask, ``rows_as_bits`` of the same set's
    list, the hand-made words and the reference's reading of them back
    agree, within a group of 4096 rows and across two."""
    rng = np.random.default_rng(rows)
    words = index_select.said_words(rows) + 128
    picked = np.sort(rng.choice(rows, size=(3, 9), replace=False), axis=1)
    picked[2, 5:] = 0                       # a lane with five rows only
    chosen = np.ones((3, 9), bool)
    chosen[2, 5:] = False
    mask = np.zeros((3, rows), bool)
    for b in range(3):
        mask[b, picked[b][chosen[b]]] = True
    sets = [np.flatnonzero(m) for m in mask]
    want = said_words(sets, words)
    got = np.asarray(index_select.mask_as_bits(jnp.asarray(mask), words))
    assert (got == want).all()
    got = np.asarray(index_select.rows_as_bits(
        jnp.asarray(picked, jnp.int32), jnp.asarray(chosen), words))
    assert (got == want).all()
    back = reference.handed_rows(jnp.asarray(handed(sets, words)), rows)
    assert (np.asarray(back) == mask).all()


# -------------------------------------------------------------- the engine
def test_the_engine_serves_the_family_as_a_loop_over_its_forward_does():
    """``LlamaEngine`` with nothing but the configuration: greedy tokens
    of two prompts, one of them past ``index_topk``, equal the
    reference's argmax at every step; a model with no indexer says no
    choices."""
    c = dataclasses.replace(FIVE, max_seq_len=128)
    params = lm.init_params(jax.random.PRNGKey(0), c)
    eng = LlamaEngine(c, params, max_batch=2, max_seq=128, prefill_chunk=16)
    assert eng.prefill_chunk == 16 and eng.windows == [64, 128]
    assert eng.read_choices is lm.read_choices      # what its calls chose
    prompts = [[1 + (7 * j + i) % 500 for j in range(n)]
               for i, n in enumerate((50, 9))]
    reqs = [GenRequest(f"r{i}", prompt, max_tokens=6)
            for i, prompt in enumerate(prompts)]
    for req in reqs:
        assert eng.add_request(req)
    while eng.num_active():
        eng.step()
    for req, prompt in zip(reqs, prompts):
        seq = list(prompt)
        assert len(req.generated) == 6
        for tok in req.generated:
            want = reference.logits(params, np.asarray(seq), hp_of(c),
                                         last=1)
            assert int(np.argmax(want[0])) == tok
            seq.append(tok)
    counted = eng.stats.snapshot()
    assert counted["attn_rows_selected"] < counted["attn_rows_indexed"]
    plain = dataclasses.replace(lm.LATENT_MOE_TINY, max_seq_len=128)
    assert LlamaEngine(plain, lm.init_params(jax.random.PRNGKey(0), plain),
                       max_batch=2, max_seq=128,
                       prefill_chunk=16).read_choices is None


def test_the_chunk_rule_of_the_cell_comes_to_1024_rows():
    from benchmarks import spec
    from ray_tpu.llm._internal.engine import derived_prefill_chunk

    cell = spec.load_cell("deepseek-v3.2-exp.serve-longctx", False)
    cfg = spec.family_of(cell["hp"]).model_config(cell["hp"])
    assert (cfg.dim, cfg.n_heads, cfg.index_heads, cfg.index_dim,
            cfg.index_topk, cfg.n_groups, cfg.groups_kept) == (
        7168, 128, 64, 128, 2048, 8, 4)
    assert not cfg.sandwich_norm and cfg.selection_bias
    assert cfg.score_scale == pytest.approx(
        (0.1 * np.log(40.0) + 1.0) ** 2 / np.sqrt(192.0))
    max_seq = cell["serve"]["max_seq_len"]
    terms = lm.chunk_terms(cfg, max_seq)
    assert derived_prefill_chunk("TPU v5 lite", 2, max_seq, **terms) == 1024
    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert 3.21e9 < count < 3.23e9          # the configuration's reduced_why
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 16, max_seq, 1024))
    assert cache["index_key"].shape == (5, 16, max_seq, 128)
    assert cache["said_rows"].shape == (5, 1024, 7 * 128)
    held = sum(a.size * a.dtype.itemsize for a in cache.values())
    assert 2.88e9 < held < 2.92e9
