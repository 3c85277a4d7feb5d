"""The jamba family's control (``controls.py`` says what a family's
control file gives): the Mamba mixer's two large projections of
``models/hybrid_ssm.py``, ``W_in`` (the hidden stream into ``[u | z]``)
and ``W_out`` (the gated scan's rows back out), with both operands of
their matmuls in fp8 and nothing else changed (the convolution, ``W_x``,
``W_dt``, the norms, the scan in either form, the state, the tail,
attention and every MLP stay as they are), patched over the program in
the test's (or ``serving_control.py``'s) own process for as long as
``fp8()`` is open, never in the program. The two hold 39.3 M of a state
layer's 104.2 M parameters, in 26 of 28 layers, and are met by every
row.

Two more contexts beside the control, for the cell's limits
(``tolerance_why`` has their readings), neither found by
``controls.of``: ``bf16_state()`` puts what the configuration states in
float32 (the recurrence of both forms and the state a lane carries from
call to call) in bf16, the nearest precision under it, and has to read
as not correct; ``f32_mixer()`` is a witness and no control, the whole
state-space mixer in float32 with only the hidden stream, attention and
the MLPs left in bf16, which says how much of a sound row's distance is
the mixer's own rounding."""

import contextlib

from control_llama import to_fp8


@contextlib.contextmanager
def fp8():
    """What is traced while this is open runs ``ssm_in`` and ``ssm_out``
    with their operands rounded to fp8."""
    from ray_tpu.models import hybrid_ssm as hs

    sound_in, sound_out = hs.ssm_in, hs.ssm_out

    def ssm_in(c, h, layer):
        return sound_in(c, to_fp8(h), {**layer, "w_in": to_fp8(layer["w_in"])})

    def ssm_out(c, gated, layer):
        return sound_out(c, to_fp8(gated),
                         {**layer, "w_out": to_fp8(layer["w_out"])})

    hs.ssm_in, hs.ssm_out = ssm_in, ssm_out
    try:
        yield
    finally:
        hs.ssm_in, hs.ssm_out = sound_in, sound_out


@contextlib.contextmanager
def bf16_state():
    """What is traced while this is open computes the recurrence in
    bf16, every operand and every row's state, in both forms (the chunk
    form as the loop over rows: the kernel is float32 by its layout),
    and hands a state rounded to bf16 on to the next call."""
    import jax.numpy as jnp

    from ray_tpu.models import hybrid_ssm as hs
    from ray_tpu.ops import selective_scan as ss

    def in_bf16(form):
        def rounded(*operands):
            *floats, live = operands
            y, h = form(*(a.astype(jnp.bfloat16) for a in floats), live)
            return y.astype(jnp.float32), h.astype(jnp.float32)
        return rounded

    sound_sublayer, sound_step = hs.ssm_sublayer, ss.scan_step
    chunk = in_bf16(ss.scan_chunk_rows)

    def ssm_sublayer(*args):
        return sound_sublayer(*args, scan_chunk=chunk)

    hs.ssm_sublayer, ss.scan_step = ssm_sublayer, in_bf16(sound_step)
    try:
        yield
    finally:
        hs.ssm_sublayer, ss.scan_step = sound_sublayer, sound_step


@contextlib.contextmanager
def f32_mixer():
    """What is traced while this is open computes every matmul of the
    state-space mixer on float32 operands at the highest precision; the
    sublayer takes and returns the stream in the type it came in, and
    the tail is kept in the cache's type."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import hybrid_ssm as hs

    sound = hs.ssm_sublayer

    def ssm_sublayer(c, x, *rest):
        with jax.default_matmul_precision("highest"):
            out, state, tail = sound(
                dataclasses.replace(c, dtype=jnp.float32), x, *rest)
        return out.astype(x.dtype), state, tail

    hs.ssm_sublayer = ssm_sublayer
    try:
        yield
    finally:
        hs.ssm_sublayer = sound
