"""The deepseek family's own pieces of the benchmark: what its
configuration builds, the required work of its indexer and of its two
selected attention forms, and its roofline reader on its recording with
the chip's peaks (``test_doors.py`` hands every reader ``peak: {}``,
under which this one reads nothing and says so)."""

import json

import pytest
from test_doors import serving_ctx, serving_recording  # noqa: F401

from benchmarks import spec
from benchmarks.families import deepseek_flops
from benchmarks.readers import indexed_attention_roofline

CELL = "deepseek-v3.2-exp.serve-longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_builds_the_published_widths_and_the_share():
    import math

    hp = spec.load_cell(CELL, False)["hp"]
    cfg = spec.family_of(hp).model_config(hp)
    assert (cfg.dim, cfg.n_heads, cfg.q_rank, cfg.kv_rank) == (
        7168, 128, 1536, 512)
    assert (cfg.nope_dim, cfg.rope_dim, cfg.v_dim) == (128, 64, 128)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (64, 128, 2048)
    assert (cfg.ffn_dim, cfg.expert_dim, cfg.shared_dim) == (18432, 2048, 2048)
    # the router keeps its width, its groups and its experts per token
    assert (cfg.n_experts, cfg.experts_per_token, cfg.n_held) == (256, 8, 8)
    assert (cfg.n_groups, cfg.groups_kept, cfg.routed_scale) == (8, 4, 2.5)
    assert cfg.held_experts == tuple(range(8)) and cfg.selection_bias
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.vocab_size) == (
        5, 1, 16160)
    assert not cfg.sandwich_norm and cfg.norm_eps == 1e-6
    # YaRN: the table unscaled, the factor squared in the scores' scale
    assert (cfg.yarn.theta, cfg.yarn.factor, cfg.yarn.original_max_position,
            cfg.yarn.beta_fast, cfg.yarn.beta_slow,
            cfg.yarn.attention_factor) == (10000.0, 40.0, 4096, 32.0, 1.0, 1.0)
    m = 0.1 * math.log(40.0) + 1.0
    assert cfg.score_scale == pytest.approx(m * m / math.sqrt(192))
    assert set(hp["reduced"]) == set(hp["published"])
    with pytest.raises(ValueError, match="served only"):
        spec.family_of(hp).model_config(hp, {"remat": True})
    with pytest.raises(ValueError, match="held here"):
        spec.family_of(hp).model_config({**hp, "n_routed_experts": 16})


def test_every_key_of_the_catalog_row_is_the_files_but_the_reduced():
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog beside the guides")
    published = next(r for r in rows if r["name"] == "DeepSeek-V3.2-Exp")
    on_file = spec.load_json("configs", "deepseek-v3.2-exp.json")
    assert on_file["source"] == published["source_url"]
    assert sorted(on_file["reduced"]) == sorted((
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"))
    for key, value in published["config"].items():
        if key in on_file["reduced"]:
            assert on_file["published"][key] == value
        else:
            assert on_file[key] == value, key
    for note in ("indexer_rope", "indexer_layer_norm", "index_keys_bf16",
                 "no_hadamard", "selection_bias", "group_limit", "ties",
                 "norms", "rope", "weights", "mtp_layer"):
        assert note in on_file["assumed"], note
    for key in ("reduced_why", "deployment", "rehearsal_why"):
        assert len(on_file[key]) > 100, key


def test_the_work_counts_what_was_asked():
    hp = spec.load_cell(CELL, False)["hp"]
    assert hp["num_hidden_layers"] == 5
    # one layer of a 256-row chunk that starts at row 768
    pairs = sum(range(769, 1025))
    work = deepseek_flops.index_work(hp, pairs, 1024)
    assert work == {"flops": 2 * 64 * 128 * pairs, "bytes": 256 * 1024}
    work = deepseek_flops.selected_prefill_work(hp, pairs, 1024)
    assert work == {"flops": 2 * 128 * 320 * pairs, "bytes": 1152 * 1024}
    work = deepseek_flops.selected_decode_work(hp, 2048)
    assert work == {"flops": 2 * 128 * 1088 * 2048, "bytes": 1152 * 2048}
    # a chunk of 1024 rows from row 1536: rows 1536 .. 2047 see under
    # 2048 rows, the others 2048 each
    chunk = [{"rows": "1024", "start": "1536"}]
    got = indexed_attention_roofline.selected_prefill(hp, chunk, [])
    pairs = sum(min(t + 1, 2048) for t in range(1536, 2560))
    assert got["flops"] == 5 * 2 * 128 * 320 * pairs
    assert got["bytes"] == 5 * 1152 * 2560
    got = indexed_attention_roofline.index(
        hp, chunk, [{"rows": "2", "attended": "9000"}])
    pairs = sum(t + 1 for t in range(1536, 2560))
    assert got["flops"] == 5 * 2 * 64 * 128 * (pairs + 9000)
    assert got["bytes"] == 5 * 256 * (2560 + 9000)
    got = indexed_attention_roofline.selected_decode(
        hp, [], [{"rows": "2", "attended": "9000"},
                 {"rows": "3", "attended": "5000"}])
    assert got["bytes"] == 5 * 1152 * (4096 + 5000)


@pytest.mark.parametrize("name", ["index_score", "attn_selected_prefill",
                                  "attn_selected_decode"])
def test_the_roofline_reader_reads_its_recording_with_the_chips_peaks(name):
    ctx = serving_ctx(CELL, None)
    args = spec.load_json("metrics", f"{name}_roofline.json")["args"]
    assert isinstance(indexed_attention_roofline.read(ctx, args),
                      spec.NotRead)
    ctx["peak"] = spec.load_json("peaks.json")["TPU v5 lite"]
    share = indexed_attention_roofline.read(ctx, args)
    assert 0.0 < share <= 100.0
    # on a configuration with no indexer it reads nothing and says so
    bare = {**ctx, "cell": {"hp": {k: v for k, v in ctx["cell"]["hp"].items()
                                   if k != "index_topk"}}}
    assert "no indexer" in indexed_attention_roofline.read(bare, args)
    _, spans = indexed_attention_roofline.FORMS[args["form"]]
    carried = indexed_attention_roofline.CARRIED[spans[0]]
    # (the recording is read once for all tests: put back what is taken)
    spans = list(ctx["trace"].host_spans)
    try:
        for i, (span, a, b, stats) in enumerate(spans):
            ctx["trace"].host_spans[i] = (span, a, b, {
                k: v for k, v in stats.items() if k != carried})
        assert carried in indexed_attention_roofline.read(ctx, args)
    finally:
        ctx["trace"].host_spans[:] = spans


# ------------------------------- under the engine's own choices (PR 61's door)
def test_the_cell_asks_for_the_engines_choices_and_the_family_answers():
    """The cell's rows are compared under what the engine chose, rows
    and experts: its file says so and states a margin's limit and no
    share of rows, the family gives ``reference_routed``, the program's
    module ``read_choices``, and the control plants the choice faults."""
    import control_deepseek

    from benchmarks import serve_load
    from ray_tpu.models import latent_moe

    for rehearse in (False, True):
        cell = spec.load_cell(CELL, rehearse)
        check = cell["serve"]["reference_check"]
        assert serve_load.routed(check) and check["route_margin_tol"] > 0
        assert "rel_rms_over_share" not in check
        assert hasattr(spec.family_of(cell["hp"]), "reference_routed")
    assert callable(latent_moe.read_choices)
    hp = spec.load_cell(CELL, True)["hp"]
    assert spec.family_of(hp).model_config(hp).says_choices
    assert callable(control_deepseek.router_fault) and callable(control_deepseek.fp8)


@pytest.mark.parametrize("form", ["select_mask", "select_rows"])
def test_the_planted_selection_fault_leaves_out_the_best_row(form):
    """Under ``router_fault()`` a query row with a multiple of 64 rows
    to choose from (and more than k) takes the k best but the first and
    the best passed over; every other row chooses as the sound program
    does; and the patch goes when the block ends."""
    import control_deepseek
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import index_select

    rng = np.random.default_rng(7)
    S, k = 96, 8
    scores = jnp.asarray(rng.normal(size=(S, S)), jnp.float32)
    valid = jnp.asarray(np.tril(np.ones((S, S), bool)))

    def chosen():
        if form == "select_mask":
            return np.asarray(index_select.select_mask(scores, valid, k))
        rows, kept = index_select.select_rows(scores, valid, k)
        out = np.zeros((S, S), bool)
        for t, (r, on) in enumerate(zip(np.asarray(rows), np.asarray(kept))):
            out[t, r[on]] = True
        return out

    sound = chosen()
    with control_deepseek.router_fault():
        faulted = chosen()
    assert (chosen() == sound).all()
    order = np.argsort(-np.where(np.asarray(valid), np.asarray(scores),
                                 -np.inf), axis=1, kind="stable")
    for t in range(S):
        if (t + 1) % 64 or t + 1 <= k:
            assert (faulted[t] == sound[t]).all(), t
        else:
            assert sorted(np.flatnonzero(faulted[t])) == sorted(
                order[t, 1:k + 1]), t


def test_the_planted_expert_fault_takes_an_expert_from_the_middle():
    import control_deepseek
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import moe

    rng = np.random.default_rng(8)
    held = (1, 6, 7, 10)
    config = moe.MoEConfig(d_model=16, d_ff=8, n_experts=12, k=3,
                           norm_topk_prob=True, scoring="sigmoid",
                           routed_scale=2.0, held=held, n_groups=3,
                           groups_kept=2)
    x = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 12)), jnp.float32)
    bias = jnp.asarray(0.1 * rng.normal(size=12), jnp.float32)
    weights, experts = moe.route_top_k(x, router, config, bias)
    with control_deepseek.router_fault():
        got_w, got_e = moe.route_top_k(x, router, config, bias)
    scores = 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(router)))
    order = np.argsort(-(scores + np.asarray(bias)), axis=1, kind="stable")
    for t in range(40):
        if t % 32 != 16:
            assert (np.asarray(got_e)[t] == np.asarray(experts)[t]).all()
            assert np.allclose(np.asarray(got_w)[t], np.asarray(weights)[t])
        else:
            sound = np.asarray(experts)[t].tolist()
            middle = next(e for e in order[t, 6:] if e not in held)
            want = [*sound[:2], middle]
            assert np.asarray(got_e)[t].tolist() == want
            raw = scores[t, want]
            assert np.allclose(np.asarray(got_w)[t], 2.0 * raw / raw.sum(),
                               rtol=1e-5)
    assert np.allclose(np.asarray(got_w).sum(1), 2.0, rtol=1e-5)


# -------------------------------------------- a pass padded on to a few lengths
def test_a_pass_padded_on_to_a_stated_length_gives_the_sequences_own_rows(
        monkeypatch):
    """``families/deepseek.py`` pads a served request's pass on, behind
    its tokens, to one of ``PASS_LENGTHS`` so that a few sets of programs
    serve every length: the rows it returns are the sequence's own, the
    last ``last`` of them counted from its real end, as the pass at the
    sequence's own length gives them (past the rehearsal's
    ``index_topk``, so the selection bites); a toy's sequence and one
    past the longest run at their own length."""
    import jax
    import numpy as np

    from benchmarks.families import deepseek, deepseek_reference

    hp = spec.load_cell(CELL, True)["hp"]
    assert hp["index_topk"] < 100
    params = deepseek.init_params(jax.random.PRNGKey(5),
                                  deepseek.model_config(hp))
    tokens = np.random.default_rng(5).integers(
        0, hp["vocab_size"], 300).astype(np.int32)
    plain = np.asarray(deepseek_reference.logits(params, tokens, hp, last=40))
    lengths = []
    inner = deepseek_reference.logits
    monkeypatch.setattr(deepseek_reference, "logits", lambda p, t, *a, **k: (
        lengths.append(len(t)), inner(p, t, *a, **k))[1])
    monkeypatch.setattr(deepseek, "PASS_LENGTHS", (384, 640))
    padded = np.asarray(deepseek.reference_logits(params, tokens, hp,
                                                  last=40))
    assert lengths == [384] and padded.shape == plain.shape
    np.testing.assert_allclose(padded, plain, rtol=0, atol=2e-5)
    deepseek.reference_logits(params, tokens[:96], hp, last=8)      # a toy's
    deepseek.reference_logits(params, np.tile(tokens, 3), hp, last=8)
    assert lengths[1:] == [96, 900]


def test_the_reference_router_judges_a_handed_group_and_a_handed_expert():
    """``handed_margin`` with groups: the reference's own experts read
    0; the k-th exchanged for the k+1-th within the kept groups reads
    the gap of the two over their slopes; an expert of a group the
    reference dropped reads the groups' gap at the least."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.families import deepseek_reference as ref

    rng = np.random.default_rng(11)
    S, E, groups, kept, k = 24, 16, 4, 2, 4
    h = jnp.asarray(rng.normal(size=(S, 8)), jnp.float32)
    layer = {"router": jnp.asarray(rng.normal(size=(8, E)), jnp.float32),
             "router_bias": jnp.asarray(0.1 * rng.normal(size=E), jnp.float32)}
    scores, select, chosen = ref.route(h, layer, top_k=k, groups=groups,
                                       kept=kept)
    slope = scores * (1 - scores)
    own = ref.handed_margin(select, slope, chosen, groups, kept)
    assert float(np.abs(np.asarray(own)).max()) == 0.0
    assert all(len({e // 4 for e in row}) <= kept
               for row in np.asarray(chosen).tolist())
    masked = np.asarray(ref.kept_groups(select, groups, kept)[0])
    order = np.argsort(-masked, axis=1, kind="stable")
    swapped = np.asarray(chosen).copy()
    swapped[:, -1] = order[:, k]            # the best passed over, kept groups
    got = np.asarray(ref.handed_margin(select, slope, jnp.asarray(swapped),
                                       groups, kept))
    sel, slo = np.asarray(select), np.asarray(slope)
    rows = np.arange(S)
    want = (sel[rows, order[:, k - 1]] - sel[rows, order[:, k]]) / np.sqrt(
        slo[rows, order[:, k - 1]] ** 2 + slo[rows, order[:, k]] ** 2)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # an expert of a dropped group: far by the groups' own gap or more
    dropped = np.isinf(masked) & (masked < 0)
    outside = np.asarray(chosen).copy()
    outside[:, -1] = np.argmax(np.where(dropped, sel, -np.inf), axis=1)
    far = np.asarray(ref.handed_margin(select, slope, jnp.asarray(outside),
                                       groups, kept))
    assert (far > 0).all() and far.mean() > want.mean()
