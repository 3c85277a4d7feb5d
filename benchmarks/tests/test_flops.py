"""flops.py against counts made by hand."""

import pytest

from benchmarks import flops, spec

INTERNLM = spec.load_cell("internlm2-1.8b.train-2k", False)["hp"]   # 16 layers
INTERNLM_FULL = spec.load_cell("internlm2-1.8b.serve-chat", False)["hp"]
MISTRAL = spec.load_cell("mistral-7b-v0.3.train-fsdp4", False)["hp"]  # 16 layers
PEAK = spec.load_json("peaks.json")["TPU v5 lite"]


def test_matmul_params_by_hand():
    # internlm2: q 2048x2048, k,v 2048x1024 each, o 2048x2048, mlp 3x2048x8192
    layer = 2048 * 2048 * 2 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert layer == 62_914_560
    assert flops.matmul_params(INTERNLM) == 16 * layer + 2048 * 92544
    assert flops.matmul_params(INTERNLM_FULL) == 24 * layer + 2048 * 92544
    # the published model has 1.89 B parameters
    assert flops.total_params(INTERNLM_FULL) == pytest.approx(1.889e9, rel=1e-3)
    # mistral: q,o 4096x4096, k,v 4096x1024, mlp 3x4096x14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert flops.matmul_params(MISTRAL) == 16 * layer + 4096 * 32768
    full = {**MISTRAL, "num_hidden_layers": 32}
    assert flops.total_params(full) == pytest.approx(7.248e9, rel=1e-3)


def test_train_flops_per_token_by_hand():
    # 6 per matmul weight; attention: 3 x (QK^T + PV) x 2 flops x hd x heads
    # x (S+1)/2 visible keys x layers
    want = 6 * 1_196_163_072 + 3 * 2 * 2 * 128 * 16 * (2049 / 2) * 16
    assert flops.matmul_params(INTERNLM) == 1_196_163_072
    assert flops.train_flops_per_token(INTERNLM, 2048) == pytest.approx(want)
    assert want == pytest.approx(7.58e9, rel=2e-3)   # ISSUE: about 7.6 GFLOP
    want = 6 * flops.matmul_params(MISTRAL) + 3 * 2 * 2 * 128 * 32 * (4097 / 2) * 16
    assert flops.train_flops_per_token(MISTRAL, 4096) == pytest.approx(want)
    assert want == pytest.approx(23.3e9, rel=1e-2)   # ISSUE: about 23.3 GFLOP


def test_flash_calls_by_hand():
    pairs = 2048 * 2049 / 2
    fwd = flops.flash_call("flash_attention_fwd", 4, 2048, INTERNLM)
    assert fwd["flops"] == pytest.approx(2 * 2 * 4 * 16 * pairs * 128)
    # q, o: 4x2048x16x128 bf16; k, v: 4x2048x8x128 bf16; lse 4x16x2048 f32
    assert fwd["bytes"] == 2 * 33_554_432 + 2 * 16_777_216 + 524_288
    dkv = flops.flash_call("flash_attention_bwd_dkv", 4, 2048, INTERNLM)
    dq = flops.flash_call("flash_attention_bwd_dq", 4, 2048, INTERNLM)
    assert dkv["flops"] == 2 * fwd["flops"] and dq["flops"] == 1.5 * fwd["flops"]
    # the three kernels of a step together: three times the forward's
    # model FLOPs, plus two recomputed QK^T and one recomputed dO V^T
    assert fwd["flops"] + dkv["flops"] + dq["flops"] == pytest.approx(
        4.5 * fwd["flops"])


def test_least_seconds_names_its_bound():
    fwd = flops.flash_call("flash_attention_fwd", 4, 2048, INTERNLM)
    t, bound = flops.least_seconds(fwd, PEAK)
    assert bound == "compute" and t == pytest.approx(fwd["flops"] / 197e12)
    t, bound = flops.least_seconds({"flops": 1e9, "bytes": 7.5e9}, PEAK)
    assert bound == "memory" and t == pytest.approx(7.5e9 / 819e9)
