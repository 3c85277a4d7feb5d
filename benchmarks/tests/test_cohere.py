"""The cohere family's own pieces of the benchmark: what its
configuration builds (the catalog row's widths, the share), the required
work of its chunk-form attention, its roofline reader on its recording
with the chip's peaks (``test_doors.py`` hands every reader ``peak: {}``,
under which this one reads nothing and says so), and its control."""

import json

import pytest
from test_doors import serving_ctx, serving_recording  # noqa: F401

from benchmarks import spec
from benchmarks.families import cohere_flops
from benchmarks.readers import attn_chunk_roofline

CELL = "command-a-plus-05-2026.serve-rag"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_builds_the_published_widths_and_the_share():
    hp = spec.load_cell(CELL, False)["hp"]
    cfg = spec.family_of(hp).model_config(hp)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        4096, 128, 8, 128)
    assert (cfg.expert_dim, cfg.shared_dim, cfg.n_shared_experts) == (
        4096, 16384, 4)
    # the router keeps its width and its experts per token; 16 are held,
    # a block that is not the first
    assert (cfg.n_experts, cfg.experts_per_token, cfg.n_held) == (128, 8, 16)
    assert cfg.held_experts == tuple(range(16, 32))
    assert cfg.layer_types == ("sliding", "sliding", "sliding", "full")
    assert (cfg.sliding_window, cfg.vocab_size, cfg.n_layers) == (
        4096, 32768, 4)
    assert cfg.rope_theta == 50000 and cfg.norm_eps == 1e-5
    assert cfg.moe.shared_scale == 0.25 and cfg.moe.scoring == "sigmoid"
    assert set(hp["reduced"]) == set(hp["published"]) == {
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"}
    with pytest.raises(ValueError, match="served only"):
        spec.family_of(hp).model_config(hp, {"remat": True})
    with pytest.raises(ValueError, match="held here"):
        spec.family_of(hp).model_config({**hp, "num_experts": 128})
    with pytest.raises(ValueError, match="use_parallel_block"):
        spec.family_of(hp).model_config({**hp, "use_parallel_block": False})


def test_every_key_of_the_catalogs_row_stands_letter_for_letter():
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog on this machine")
    row = next(r for r in rows if r["name"] == "command-a-plus-05-2026")
    hp = spec.load_json("configs", "command-a-plus-05-2026.json")
    assert hp["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in hp["reduced"]:
            assert hp["published"][key] == value
        else:
            assert hp[key] == value, key
    assert hp["layer_types"] == row["config"]["layer_types"][:4]


def test_attention_work_counts_what_was_asked():
    hp = spec.load_cell(CELL, False)["hp"]
    # a full layer: 256 rows from row 768 see rows 0..t
    assert cohere_flops.chunk_pairs_and_rows(256, 768) == (
        sum(range(769, 1025)), 1024)
    # a sliding layer before its window fills, and well past it
    assert cohere_flops.chunk_pairs_and_rows(256, 768, 4096) == (
        sum(range(769, 1025)), 1024)
    assert cohere_flops.chunk_pairs_and_rows(1024, 8192, 4096) == (
        1024 * 4096, 4096 + 1023)
    work = cohere_flops.chunk_attention_work(hp, [(1024, 8192)])
    pairs = 3 * 1024 * 4096 + 1024 * 8192 + 1024 * 1025 // 2
    assert work["flops"] == 4 * 128 * 128 * pairs
    assert work["bytes"] == 2 * 128 * (
        2 * 8 * (3 * 5119 + 9216) + 2 * 128 * 4 * 1024)


def test_the_roofline_reader_reads_its_recording_with_the_chips_peaks():
    ctx = serving_ctx(CELL, None)
    args = spec.load_json("metrics", "attn_chunk_roofline.rag.json")["args"]
    assert isinstance(attn_chunk_roofline.read(ctx, args), spec.NotRead)
    ctx["peak"] = spec.load_json("peaks.json")["TPU v5 lite"]
    share = attn_chunk_roofline.read(ctx, args)
    assert 0.0 < share <= 100.0
    # over fewer of the form's ops the same work reads a larger share
    assert attn_chunk_roofline.read(
        ctx, {**args, "scopes": ["attn_cached"]}) > share
    # another family's configuration: nothing, and the reason
    other = spec.load_cell("internlm2-1.8b.serve-chat", True)
    assert "window" in attn_chunk_roofline.read({**ctx, "cell": other}, args)
    for i, (name, a, b, stats) in enumerate(ctx["trace"].host_spans):
        ctx["trace"].host_spans[i] = (name, a, b, {
            k: v for k, v in stats.items() if k != "start"})
    assert "start" in attn_chunk_roofline.read(ctx, args)


def test_the_control_rounds_the_shared_experts_and_nothing_else():
    import controls
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import parallel_moe as pm

    hp = spec.load_cell(CELL, True)["hp"]
    cfg = spec.family_of(hp).model_config(hp)
    params = spec.family_of(hp).init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.arange(48)[None] % 500)
    sound = pm.forward(params, tokens, cfg)
    with controls.of(hp).fp8():
        rounded = pm.forward(params, tokens, cfg)
    again = pm.forward(params, tokens, cfg)         # the patch is gone
    np.testing.assert_array_equal(np.asarray(sound), np.asarray(again))
    err = float(jnp.sqrt(jnp.mean((rounded - sound) ** 2)
                         / jnp.mean(sound ** 2)))
    assert 0.03 < err < 0.5
