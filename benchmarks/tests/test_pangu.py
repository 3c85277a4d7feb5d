"""The pangu family's own pieces of the benchmark: what its configuration
builds, the required work of its two attention forms, and its roofline
reader on its recording with the chip's peaks (``test_doors.py`` hands
every reader ``peak: {}``, under which this one reads nothing and says
so)."""

import pytest
from test_doors import serving_ctx, serving_recording  # noqa: F401

from benchmarks import spec
from benchmarks.families import pangu_flops
from benchmarks.readers import latent_attention_roofline

CELL = "openpangu-ultra-moe-718b.serve-longdoc"


def test_the_configuration_builds_the_published_widths_and_the_share():
    hp = spec.load_cell(CELL, False)["hp"]
    cfg = spec.family_of(hp).model_config(hp)
    assert (cfg.dim, cfg.n_heads, cfg.q_rank, cfg.kv_rank) == (
        7680, 128, 1536, 512)
    assert (cfg.nope_dim, cfg.rope_dim, cfg.v_dim) == (128, 64, 128)
    assert (cfg.ffn_dim, cfg.expert_dim, cfg.shared_dim) == (
        18432, 2048, 2048)
    # the router keeps its width and its experts per token; 8 are held
    assert (cfg.n_experts, cfg.experts_per_token, cfg.n_held) == (256, 8, 8)
    assert cfg.held_experts == tuple(range(8))
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.vocab_size) == (
        5, 1, 19200)
    assert cfg.routed_scale == 2.5 and cfg.rope_theta == 25.6e6
    assert set(hp["reduced"]) == set(hp["published"])
    with pytest.raises(ValueError, match="served only"):
        spec.family_of(hp).model_config(hp, {"remat": True})
    with pytest.raises(ValueError, match="held here"):
        spec.family_of(hp).model_config({**hp, "n_routed_experts": 16})


def test_attention_work_counts_what_was_asked():
    hp = spec.load_cell(CELL, False)["hp"]
    # one layer of a 256-row chunk that starts at row 768
    pairs = sum(range(769, 1025))
    work = pangu_flops.prefill_work(hp, pairs, 1024)
    assert work["flops"] == (2 * 128 * (128 + 64 + 128) * pairs
                             + 2 * 512 * 128 * (128 + 128) * 1024)
    assert work["bytes"] == 2 * 576 * 1024
    # one layer of a decode lane at 1000 rows
    work = pangu_flops.decode_work(hp, 1000)
    assert work["flops"] == 2 * 128 * (576 + 512) * 1000
    assert work["bytes"] == 1152 * 1000


@pytest.mark.parametrize("form", ["prefill", "decode"])
def test_the_roofline_reader_reads_its_recording_with_the_chips_peaks(form):
    ctx = serving_ctx(CELL, None)
    args = spec.load_json(
        "metrics", f"attn_latent_{form}_roofline.longdoc.json")["args"]
    assert isinstance(latent_attention_roofline.read(ctx, args), spec.NotRead)
    ctx["peak"] = spec.load_json("peaks.json")["TPU v5 lite"]
    share = latent_attention_roofline.read(ctx, args)
    assert 0.0 < share <= 100.0
    # over fewer of the form's ops the same work reads a larger share
    if form == "prefill":
        assert latent_attention_roofline.read(
            ctx, {**args, "scopes": ["latent_expand"]}) > share
    carried = latent_attention_roofline.FORMS[form][0]
    for i, (name, a, b, stats) in enumerate(ctx["trace"].host_spans):
        ctx["trace"].host_spans[i] = (name, a, b, {
            k: v for k, v in stats.items() if k != carried})
    assert carried in latent_attention_roofline.read(ctx, args)
