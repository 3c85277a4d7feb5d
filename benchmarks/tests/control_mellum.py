"""The mellum family's control (``controls.py`` says what a family's
control file gives): ``ops/moe.py``'s ``expert_ffn`` and
``expert_ffn_every``, the SwiGLU of the dropless expert layer as the
grouped matmul over each expert's rows and as the batched matmul of a
few rows over every expert, with every operand of the three matmuls in
fp8 and nothing else changed (the router, the sort, the combine and the
attention stay as they are), patched over the program in the test's (or
``serving_control.py``'s) own process for as long as ``fp8()`` is open,
never in the program. The expert matmuls are the sublayer that does most
of the work of this family's programs (PERF.md section 5)."""

import contextlib

import jax
from control_llama import to_fp8


def expert_ffn_in_fp8(xs, w_gate, w_up, w_down, group_sizes, layer=None):
    """``moe.expert_ffn`` with every operand of its three matmuls in fp8
    and nothing else changed. Of stacked weights the layer's own are
    taken out first (a copy the program avoids, which a control may
    make), so that each is rounded under its own scale."""
    import jax.numpy as jnp

    if layer is not None:
        w_gate, w_up, w_down = (
            jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
            for w in (w_gate, w_up, w_down))
    xs = to_fp8(xs)
    gate = jax.lax.ragged_dot(xs, to_fp8(w_gate), group_sizes)
    up = jax.lax.ragged_dot(xs, to_fp8(w_up), group_sizes)
    return jax.lax.ragged_dot(
        to_fp8(jax.nn.silu(gate) * up), to_fp8(w_down), group_sizes,
        preferred_element_type=jnp.float32)


def expert_ffn_every_in_fp8(x, w_gate, w_up, w_down, combine, layer=None):
    """``moe.expert_ffn_every`` the same way: the three batched matmuls'
    operands in fp8, the weighing and the sum as they are."""
    import jax.numpy as jnp

    if layer is not None:
        w_gate, w_up, w_down = (
            jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
            for w in (w_gate, w_up, w_down))
    x = to_fp8(x)
    gate = jnp.einsum("td,edf->etf", x, to_fp8(w_gate))
    up = jnp.einsum("td,edf->etf", x, to_fp8(w_up))
    ys = jnp.einsum("etf,efd->etd", to_fp8(jax.nn.silu(gate) * up),
                    to_fp8(w_down), preferred_element_type=jnp.float32)
    return (ys * combine.T[:, :, None]).sum(0)


@contextlib.contextmanager
def fp8():
    """The two in ``expert_ffn``'s and ``expert_ffn_every``'s place: what
    is traced while this is open runs the expert matmuls' operands in
    fp8, whichever way a call multiplies."""
    from ray_tpu.ops import moe

    sound = moe.expert_ffn, moe.expert_ffn_every
    moe.expert_ffn, moe.expert_ffn_every = (
        expert_ffn_in_fp8, expert_ffn_every_in_fp8)
    try:
        yield
    finally:
        moe.expert_ffn, moe.expert_ffn_every = sound
