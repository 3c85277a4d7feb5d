"""Operations and bytes the algorithms need, from shapes alone.

The yardstick for ``train_mfu_pct`` and ``flash_attention_roofline_pct``.
Counted once, as the mathematics requires: causal attention is half a
square, and recomputation (remat) is not work the algorithm asked for.
``hp`` is a configuration file's dict (Hugging Face key names).
"""

from __future__ import annotations


def matmul_params(hp: dict) -> int:
    """Weights that take part in a matrix multiplication per token:
    every layer's q, k, v, o, gate, up, down and the output head (the
    embedding is a lookup)."""
    d, hd = hp["hidden_size"], hp["head_dim"]
    h, kv = hp["num_attention_heads"], hp["num_key_value_heads"]
    f = hp["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return hp["num_hidden_layers"] * per_layer + d * hp["vocab_size"]


def total_params(hp: dict) -> int:
    d = hp["hidden_size"]
    norms = hp["num_hidden_layers"] * 2 * d + d
    return matmul_params(hp) + hp["vocab_size"] * d + norms


def train_flops_per_token(hp: dict, seq: int) -> float:
    """Forward + backward: 6 per matmul weight, plus causal attention.
    Per token and layer the forward does QK^T and PV over (seq+1)/2
    visible keys on average, 2*hd multiply-adds each per head; the
    backward costs twice the forward."""
    visible = (seq + 1) / 2
    attn_fwd = 2 * 2 * visible * hp["num_attention_heads"] * hp["head_dim"]
    return 6.0 * matmul_params(hp) + 3.0 * attn_fwd * hp["num_hidden_layers"]


# matrix multiplications over the (S, S) causal triangle in each flash
# kernel, as the two-kernel backward needs them: fwd QK^T, PV; bwd_dkv
# QK^T, P^T dO, dO V^T, dS^T Q; bwd_dq QK^T, dO V^T, dS K
FLASH_MATMULS = {
    "flash_attention_fwd": 2,
    "flash_attention_bwd_dkv": 4,
    "flash_attention_bwd_dq": 3,
}


def flash_call(kernel: str, batch: int, seq: int, hp: dict) -> dict:
    """FLOPs and HBM bytes of ONE call of a flash kernel on (batch, seq)
    with this configuration's heads: bf16 operands, float32 row
    statistics (lse, delta)."""
    h, kv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                 hp["head_dim"])
    pairs = seq * (seq + 1) / 2
    flops = FLASH_MATMULS[kernel] * 2.0 * batch * h * pairs * hd
    q_like = batch * seq * h * hd * 2       # q, o, do, dq
    kv_like = batch * seq * kv * hd * 2     # k, v, dk, dv
    rows = batch * h * seq * 4              # lse, delta
    if kernel == "flash_attention_fwd":
        nbytes = 2 * q_like + 2 * kv_like + rows
    elif kernel == "flash_attention_bwd_dkv":
        nbytes = 2 * q_like + 4 * kv_like + 2 * rows
    else:
        nbytes = 3 * q_like + 2 * kv_like + 2 * rows
    return {"flops": flops, "bytes": float(nbytes)}


def least_seconds(work: dict, peak: dict) -> tuple:
    """(least time on this chip, which peak bounds it)."""
    t_compute = work["flops"] / peak["bf16_flops_per_s"]
    t_memory = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
