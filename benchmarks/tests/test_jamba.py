"""The jamba family's own pieces of the benchmark: what its configuration
builds, the required work of the scan's two forms, its roofline reader
on its recording with the chip's peaks (``test_doors.py`` hands every
reader ``peak: {}``, under which this one reads nothing and says so),
and its control."""

import copy
import json
import os

import pytest
from test_doors import serving_ctx, serving_recording  # noqa: F401

from benchmarks import spec
from benchmarks.families import jamba_flops
from benchmarks.readers import ssm_roofline

CELL = "ai21-jamba2-3b.serve-reason"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_builds_the_published_widths_whole():
    hp = spec.load_cell(CELL, False)["hp"]
    cfg = spec.family_of(hp).model_config(hp)
    assert (cfg.dim, cfg.n_layers, cfg.vocab_size) == (2560, 28, 65536)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (20, 1, 128)
    assert (cfg.attn_period, cfg.attn_offset) == (14, 7)
    assert (cfg.n_state_layers, cfg.n_attn_layers) == (26, 2)
    assert (cfg.inner, cfg.d_state, cfg.d_conv, cfg.dt_rank) == (
        5120, 16, 4, 160)
    assert cfg.ffn_dim == 8192 and cfg.norm_eps == 1e-6
    assert hp["reduced"] == [] and "published" not in hp
    with pytest.raises(ValueError, match="served only"):
        spec.family_of(hp).model_config(hp, {"remat": True})
    with pytest.raises(ValueError, match="num_experts 1"):
        spec.family_of(hp).model_config({**hp, "num_experts": 16})
    with pytest.raises(ValueError, match="bias"):
        spec.family_of(hp).model_config({**hp, "mamba_proj_bias": True})


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_the_catalog_rows_config_letter_for_letter():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    hp = spec.load_json("configs", "ai21-jamba2-3b.json")
    assert hp["source"] == row["source_url"]
    assert {k: hp[k] for k in row["config"]} == row["config"]


def test_the_weights_add_up_to_the_models_three_billion():
    """The sum ``reduced_why`` makes, from the tree the program builds."""
    import jax

    hp = spec.load_cell(CELL, False)["hp"]
    family = spec.family_of(hp)
    cfg = family.model_config(hp)
    shapes = jax.eval_shape(
        lambda: family.init_params(jax.random.PRNGKey(0), cfg))
    sizes = jax.tree_util.tree_map(lambda a: a.size, shapes)
    per_layer = lambda tree, layers: sum(
        jax.tree_util.tree_leaves(tree)) / layers
    assert per_layer(sizes["mamba"], 26) == pytest.approx(41.24e6, rel=2e-3)
    assert per_layer(sizes["attn"], 2) == pytest.approx(13.76e6, rel=2e-3)
    assert per_layer(sizes["mlp"], 28) == pytest.approx(62.91e6, rel=2e-3)
    total = sum(jax.tree_util.tree_leaves(sizes))
    assert total == pytest.approx(3.03e9, rel=3e-3)
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(shapes))
    assert nbytes == pytest.approx(6.06e9, rel=3e-3)
    # a lane of the cell's cache: 26 states and tails, 4096 rows of keys
    # and values in 2 layers
    from ray_tpu.models import hybrid_ssm

    cache = jax.eval_shape(lambda: hybrid_ssm.init_cache(cfg, 128, 4096))
    lane = {k: a.size * a.dtype.itemsize / 128 for k, a in cache.items()}
    assert lane["state"] + lane["tail"] == 26 * (327_680 + 30_720)
    assert lane["k"] + lane["v"] == 4096 * 1024


def test_scan_work_counts_what_was_asked():
    hp = spec.load_cell(CELL, False)["hp"]
    assert jamba_flops.state_layers(hp) == 26
    assert jamba_flops.row_flops(hp) == 7 * 5120 * 16 + 3 * 5120
    work = jamba_flops.chunk_call(hp, 512)
    assert work["flops"] == 512 * (7 * 5120 * 16 + 3 * 5120)
    assert work["bytes"] == (512 * 5120 * 12 + 512 * 129    # c, y, dt; B, C
                             + 2 * 327_680 + 17 * 5120 * 4)  # state; A, D
    work = jamba_flops.step_call(hp, 100)
    assert work["bytes"] == (100 * 5120 * 12 + 100 * 129
                             + 100 * 2 * 327_680 + 17 * 5120 * 4)
    # memory bounds either form on a v5e, by far
    peak = spec.load_json("peaks.json")["TPU v5 lite"]
    for work in (jamba_flops.chunk_call(hp, 512),
                 jamba_flops.step_call(hp, 128)):
        assert (work["bytes"] / peak["hbm_bytes_per_s"]
                > 5 * work["flops"] / peak["bf16_flops_per_s"])


@pytest.mark.parametrize("form", ["scan", "step"])
def test_the_roofline_reader_reads_its_recording_with_the_chips_peaks(form):
    ctx = serving_ctx(CELL, None)
    args = spec.load_json(
        "metrics", f"ssm_{form}_roofline.reason.json")["args"]
    assert isinstance(ssm_roofline.read(ctx, args), spec.NotRead)
    ctx["peak"] = spec.load_json("peaks.json")["TPU v5 lite"]
    share = ssm_roofline.read(ctx, args)
    assert 0.0 < share < 100.0
    # over more ops the same work reads a smaller share
    assert ssm_roofline.read(ctx, {**args, "scopes": ["ssm"]}) < share
    # on a copy: the recording is read once for every test of the file
    bare = copy.copy(ctx["trace"])
    bare.host_spans = [
        (name, a, b, {k: v for k, v in stats.items() if k != "rows"})
        for name, a, b, stats in ctx["trace"].host_spans]
    assert "rows" in ssm_roofline.read({**ctx, "trace": bare}, args)


def test_the_control_rounds_the_two_projections_and_nothing_else():
    """Under ``fp8()`` the mixer's W_in and W_out matmuls see rounded
    operands; the convolution, W_x, W_dt, the scan and the MLPs the
    sound ones: a whole forward moves at every row, and outside it the
    module is as it was."""
    import dataclasses

    import control_jamba
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import hybrid_ssm as hs

    c = dataclasses.replace(hs.HYBRID_SSM_TINY, dtype=jnp.float32,
                            param_dtype=jnp.float32)
    params = hs.init_params(jax.random.PRNGKey(0), c)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, 512)
    sound = np.asarray(hs.forward(params, tokens, c))
    with control_jamba.fp8():
        rounded = np.asarray(hs.forward(params, tokens, c))
    again = np.asarray(hs.forward(params, tokens, c))
    np.testing.assert_array_equal(sound, again)
    rows = (np.sqrt(((rounded - sound) ** 2).mean(-1))
            / np.sqrt((sound ** 2).mean(-1)))[0]
    assert rows.min() > 0.03 and rows.max() < 1.0
