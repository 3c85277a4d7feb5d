"""Required work of the ``mellum`` family's expert matmuls, from what
was asked of them and not from what implements them: a routed
assignment (one row at one of its chosen experts) is three matmuls,
``2 * hidden * expert_dim`` FLOPs each; an expert that has a row or more
has its three matrices read once, ``3 * hidden * expert_dim`` parameters
of ``bytes_per_param``; and every assignment's row goes in and comes
out, ``2 * hidden`` activations of ``bytes_per_param``. The counts are
the engine's (``EngineStats.moe_assignments``, ``moe_experts_touched``),
taken between two snapshots."""

from __future__ import annotations


def expert_work(hp: dict, assignments: float, experts_touched: float,
                bytes_per_param: int = 2) -> dict:
    d, f = hp["hidden_size"], hp["moe_intermediate_size"]
    return {
        "flops": 6.0 * d * f * assignments,
        "bytes": bytes_per_param * (3.0 * d * f * experts_touched
                                    + 2.0 * d * assignments),
    }

