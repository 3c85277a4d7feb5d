"""The mellum family's own pieces of the benchmark: what its
configuration builds, the required work of its expert matmuls, and its
roofline reader on its recording with the chip's peaks (``test_doors.py``
hands every reader ``peak: {}``, under which this one reads nothing and
says so)."""

import pytest
from test_doors import serving_ctx, serving_recording  # noqa: F401

from benchmarks import spec
from benchmarks.families import mellum_flops
from benchmarks.readers import moe_experts_roofline

CELL = "mellum2-12b-a2.5b.serve-ide-mix"


def test_the_configuration_builds_the_published_sizes():
    hp = spec.load_cell(CELL, False)["hp"]
    cfg = spec.family_of(hp).model_config(hp)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2304, 32, 4, 128)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.expert_dim) == (
        64, 8, 896)
    assert cfg.layer_types == ("sliding", "sliding", "sliding", "full") * 2
    assert cfg.sliding_window == 1024 and cfg.vocab_size == 98304
    assert cfg.full_rope.factor == 16 and cfg.full_rope.theta == 500000
    assert cfg.full_rope.attention_factor == 1.2772588722239782
    with pytest.raises(ValueError, match="served only"):
        spec.family_of(hp).model_config(hp, {"remat": True})


def test_expert_work_counts_what_was_asked():
    hp = {"hidden_size": 2304, "moe_intermediate_size": 896}
    # a 16-lane decode call of one layer: 128 assignments at 48 experts
    work = mellum_flops.expert_work(hp, 128, 48)
    assert work["flops"] == 6 * 2304 * 896 * 128
    assert work["bytes"] == 2 * (3 * 2304 * 896 * 48 + 2 * 2304 * 128)


def test_the_roofline_reader_reads_its_recording_with_the_chips_peaks():
    ctx = serving_ctx(CELL, None)
    args = spec.load_json("metrics", "moe_experts_roofline.json")["args"]
    assert isinstance(moe_experts_roofline.read(ctx, args), spec.NotRead)
    ctx["peak"] = spec.load_json("peaks.json")["TPU v5 lite"]
    share = moe_experts_roofline.read(ctx, args)
    assert 0.0 < share <= 100.0
    # without the compiler's own ragged-dot ops the traced time is the
    # activation's alone, and the share is no share
    assert moe_experts_roofline.read(ctx, {**args, "ops": "^no such op"}) \
        > share
    ctx["samples"].pop("stats.moe_assignments")
    assert "moe_assignments" in moe_experts_roofline.read(ctx, args)
