"""Required work of the ``jamba`` family's selective scan in its two
forms, from what was asked of them and not from what implements them: a
floor under any implementation of the scope, so that no later kernel can
read over 100 %.

**Where the scope's edge lies** (``models/hybrid_ssm.py``,
``ssm_sublayer``): the scopes ``ssm_scan`` (a chunk call) and
``ssm_step`` (a decode call) begin with the convolved rows ``c``, ``dt``
(behind its softplus), ``Bm`` and ``Cm`` (behind their norms), all
float32, ``live``, and the lanes' state where it lies in the cache's
stack (float32), and end with ``y`` (float32; the ``D c`` term is
inside, the gate ``silu(z)`` and ``W_out`` are not) and the new state
back in the stack. ``A = -exp(A_log)`` and ``D`` are a layer's, read
once a call. The state's slice out of the stack and its write back
(``state_slice``, ``state_write``) lie INSIDE the form's scope and carry
its name beside their own: the bytes below are the state read once and
written once a call, and the traced time holds both. (The tail's write
rides there too and is not counted: a floor.)

A row of a layer, for each of ``E x n`` (channel, state) pairs: ``dt A``
(1), ``exp`` (1), ``. h`` (1), ``dt c`` shared by a channel's states,
``. Bm`` (1), ``+`` (1), ``h Cm`` and its sum (2): 7 FLOPs, and ``D c``
2 a channel. Nothing an implementation may keep on the chip is counted
(no ``(T, E, n)`` intermediate, no spread of ``Bm`` over lanes).
"""

from __future__ import annotations


def _sizes(hp: dict):
    return (hp["mamba_expand"] * hp["hidden_size"], hp["mamba_d_state"])


def row_flops(hp: dict) -> float:
    inner, states = _sizes(hp)
    return 7.0 * inner * states + 3.0 * inner


def chunk_call(hp: dict, rows: int, bytes_per_value: int = 4) -> dict:
    """One chunk-form call of one state layer over ``rows`` rows of one
    sequence."""
    inner, states = _sizes(hp)
    return {
        "flops": row_flops(hp) * rows,
        "bytes": float(
            rows * inner * (2 * bytes_per_value + 4)      # c, y; dt
            + rows * (2 * states * 4 + 1)                 # Bm, Cm; live
            + 2 * states * inner * 4                      # the state, in and out
            + (states + 1) * inner * 4),                  # A, D
    }


def step_call(hp: dict, lanes: int, bytes_per_value: int = 4) -> dict:
    """One step-form call of one state layer over ``lanes`` lanes."""
    inner, states = _sizes(hp)
    return {
        "flops": row_flops(hp) * lanes,
        "bytes": float(
            lanes * inner * (2 * bytes_per_value + 4)
            + lanes * (2 * states * 4 + 1)
            + 2 * lanes * states * inner * 4
            + (states + 1) * inner * 4),
    }


def state_layers(hp: dict) -> int:
    """The layers that are not attention: one a period is."""
    layers = hp["num_hidden_layers"]
    return layers - layers // hp["attn_layer_period"]
