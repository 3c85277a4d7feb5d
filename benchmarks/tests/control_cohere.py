"""The cohere family's control (``controls.py`` says what a family's
control file gives): the shared experts' three matmuls of
``models/parallel_moe.py``'s block (``ops/moe.py``'s ``moe_shared``: the
four shared experts side by side along the width, gate, up and down)
with every operand in fp8 and nothing else changed (the norm, the
router, the held experts, the attention and the scale to the mean stay
as they are), patched over the program in the test's (or
``serving_control.py``'s) own process for as long as ``fp8()`` is open,
never in the program. The shared experts are half of a row's matmul
work (0.403 of 0.79 GFLOPs a row a layer) and every row meets them."""

import contextlib

import jax
from control_llama import to_fp8


@contextlib.contextmanager
def fp8():
    """What is traced while this is open computes the shared experts in
    fp8: ``window_moe.moe_mix`` is given the layer without them (the
    routed part and its counts as they are), and their SwiGLU is added
    here, as ``ops/moe.py`` adds it, with the operands rounded."""
    from ray_tpu.models import window_moe as wm

    sound = wm.moe_mix

    def moe_mix(c, h, layer, experts, index, live=None):
        routed, counts = sound(
            c, h, {k: v for k, v in layer.items()
                   if k not in wm.SHARED_WEIGHTS}, experts, index, live)
        gate_w, up_w, down_w = (to_fp8(layer[k].astype(c.dtype))
                                for k in wm.SHARED_WEIGHTS)
        x = to_fp8(h)
        shared = to_fp8(jax.nn.silu(x @ gate_w) * (x @ up_w)) @ down_w
        scale = c.moe.shared_scale
        return routed + (shared if scale == 1.0 else shared * scale), counts

    wm.moe_mix = moe_mix
    try:
        yield
    finally:
        wm.moe_mix = sound
