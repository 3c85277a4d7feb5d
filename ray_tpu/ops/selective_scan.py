"""The selective scan of a Mamba-1 mixer, in the two forms a served
state-space layer needs (``models/hybrid_ssm.py``).

The recurrence, per channel ``c`` of the inner width ``E`` and state
``s`` of ``n``, all in float32::

    h_t[s, c] = exp(dt_t[c] * A[s, c]) * h_{t-1}[s, c]
                + (dt_t[c] * x_t[c]) * Bm_t[s]
    y_t[c]    = sum_s h_t[s, c] * Cm_t[s] + D[c] * x_t[c]

The decay is its own for every (channel, state): ``E x n`` scalar
recurrences a row, elementwise work with an ``exp`` in the middle and
no matmul form (there is one only where the decay is a scalar a head).

**Layout.** The state is ``(n, E)``, the channels last: 5120 channels
are forty whole registers of 128 lanes and 16 states two of 8
sublanes, where ``(E, n)`` would pad 16 to 128 lanes and hold eight
times its bytes on the chip. ``A`` lies the same way.

**Two forms over the same arguments.** ``scan_chunk``: ``T`` rows of
one sequence (a prefill chunk), the state read once and written once a
call. ``scan_step``: one row of each of ``B`` lanes (a decode). Rows
that are nobody's (``live`` false: the rows of a padded chunk behind
its last token, an idle decode lane) leave the state exactly as it
was: with ``dt`` and ``x`` zeroed the recurrence is the identity
(``exp(0) * h + 0``), and the step form selects the old state besides.

**How the chunk form is computed.** ``scan_chunk_rows`` is the plain
``lax.scan`` over rows: what a model's plain ``forward`` calls, and
the kernel's numerical reference in the tests. ``scan_chunk`` is a
Pallas kernel and refuses shapes it cannot tile (``untileable``: every
served width and every bucket of rows tiles, so a second served path
would be one no cell runs): grid (a tile of ``_CHANNELS``
channels, a block of ``_ROWS`` rows), the tile's state resident in
VMEM across the row blocks (the output block, whose index does not
move along that axis), the rows walked eight at a time, each 128-lane
group of channels a chain of its own so that the chains' latencies
hide one another. ``Bm`` and ``Cm`` reach the kernel spread over 128
lanes, ``(T, n, 128)``: a row's ``Bm_t[s]`` is one number a sublane
that multiplies every lane, and a register is made so from a compact
``(T, n)`` only by a lane broadcast a row, which the spread array pays
once in XLA (16 KB a row, re-read for every channel tile: PERF.md
section 6, PR 52, has what that reads). Every operand of either form
is float32 (the rows in groups of 8, a float32 register's sublanes):
the mixer rounds the scan's rows once, behind their gate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LANES = 128
_GROUP = 8          # rows walked at a time: a float32 register's sublanes
# channels a grid step and rows a block (PERF.md section 6, PR 52)
_CHANNELS = 1024
_ROWS = 128
_VMEM_LIMIT_BYTES = 32 * 2**20

F32 = jnp.float32


def _masked(x, dt, live):
    """``dt`` and ``x`` with the rows that are nobody's zeroed: there
    the recurrence is the identity."""
    live = live[..., None]
    return jnp.where(live, x, 0.0), jnp.where(live, dt, 0.0)


def scan_step(x, dt, Bm, Cm, A, D, h, live):
    """One row of each of B lanes. x (B, E), dt (B, E), Bm and Cm
    (B, n), A (n, E), D (E,), h (B, n, E), all float32; live (B,) bool
    -> (y (B, E), h (B, n, E)), float32."""
    x, dt = _masked(x, dt, live)
    new = (jnp.exp(dt[:, None, :] * A[None]) * h
           + (dt * x)[:, None, :] * Bm[:, :, None])
    y = (new * Cm[:, :, None]).sum(axis=1) + D[None] * x
    return y, jnp.where(live[:, None, None], new, h)


def scan_chunk_rows(x, dt, Bm, Cm, A, D, h_in, live):
    """``scan_chunk`` as a ``lax.scan`` over the rows, in ``jax.numpy``."""
    x, dt = _masked(x, dt, live)

    def row(h, r):
        x_t, dt_t, b_t, c_t = r
        h = jnp.exp(dt_t[None, :] * A) * h + (dt_t * x_t)[None, :] * b_t[:, None]
        return h, (h * c_t[:, None]).sum(axis=0) + D * x_t

    h, y = jax.lax.scan(row, h_in, (x, dt, Bm, Cm))
    return y, h


def untileable(T: int, E: int, n: int):
    """Why the kernel cannot take these shapes, or None when it can."""
    if E % _LANES:
        return f"inner width {E} not a multiple of {_LANES} lanes"
    if n % _GROUP:
        return f"state size {n} not a multiple of {_GROUP} sublanes"
    if T % _GROUP:
        return f"chunk rows {T} not a multiple of {_GROUP}"
    return None


def scan_chunk(x, dt, Bm, Cm, A, D, h_in, live):
    """T rows of one sequence. x (T, E) the convolved rows, dt (T, E),
    Bm and Cm (T, n), A (n, E), D (E,), h_in (n, E), all float32; live
    (T,) bool -> (y (T, E), h_out (n, E)), float32. Raises where the
    kernel cannot tile the shapes."""
    T, E = x.shape
    n = A.shape[0]
    if (why := untileable(T, E, n)) is not None:
        raise ValueError(f"selective_scan.scan_chunk: {why}")
    x, dt = _masked(x, dt, live)
    return _kernel_call()(x, dt, Bm, Cm, A, D, h_in,
                          channels=_divisor(E, _CHANNELS, _LANES),
                          rows=_divisor(T, _ROWS, _GROUP),
                          interpret=_interpret())


def _interpret() -> bool:
    # on the CPU the kernel's own code runs interpreted
    # (``pallas_attention._interpret`` says why that is safe to ask)
    return jax.default_backend() == "cpu"


def _divisor(size: int, preferred: int, unit: int) -> int:
    """The largest multiple of ``unit`` at most ``preferred`` that
    divides ``size`` (itself a multiple of ``unit``)."""
    best = min(preferred, size) // unit * unit
    while size % best:
        best -= unit
    return best


@functools.cache
def _kernel_call():
    """The kernel's jitted call. Pallas is imported here, by the first
    chunk program that is traced, and not with the module: it takes a
    second to import, ``ray_tpu.models`` imports this module for every
    family, and a replica's start is a metric of every cell."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h_in_ref,
                y_ref, h_ref, *, rows: int, channels: int):
        """One tile of channels over one block of rows. x, dt, y (rows,
        channels); b, c (rows, n, 128); a, h_in, h (n, channels); d (1,
        channels). ``h_ref`` is the tile's state: the output block stays
        where it is along the row axis, so it carries the state from block
        to block and leaves once, behind the last."""
        @pl.when(pl.program_id(1) == 0)
        def _first_block():
            h_ref[...] = h_in_ref[...]

        lanes = [slice(k, k + _LANES) for k in range(0, channels, _LANES)]
        a = [a_ref[:, at] for at in lanes]
        d = [d_ref[:, at] for at in lanes]
        row_of = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, _LANES), 0)

        def group(g, h):
            at_rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
            x8, dt8 = x_ref[at_rows, :], dt_ref[at_rows, :]
            b8, c8 = b_ref[at_rows], c_ref[at_rows]          # (8, n, 128)
            out = []
            for k, at in enumerate(lanes):
                h_k, y8 = h[k], jnp.zeros((_GROUP, _LANES), F32)
                for r in range(_GROUP):
                    x_r, dt_r = x8[r:r + 1, at], dt8[r:r + 1, at]    # (1, 128)
                    h_k = jnp.exp(dt_r * a[k]) * h_k + (dt_r * x_r) * b8[r]
                    y_r = (jnp.sum(h_k * c8[r], axis=0, keepdims=True)
                           + d[k] * x_r)
                    y8 = jnp.where(row_of == r, y_r, y8)
                y_ref[at_rows, at] = y8
                out.append(h_k)
            return tuple(out)

        h = jax.lax.fori_loop(0, rows // _GROUP, group,
                              tuple(h_ref[:, at] for at in lanes))
        for k, at in enumerate(lanes):
            h_ref[:, at] = h[k]


    # jitted, so that a program whose layer scans call it with the same
    # shapes traces and lowers the kernel once
    @functools.partial(jax.jit, static_argnames=("channels", "rows", "interpret"))
    def call(x, dt, Bm, Cm, A, D, h_in, *, channels, rows, interpret):
        T, E = x.shape
        n = A.shape[0]
        spread = lambda m: jnp.broadcast_to(m[:, :, None], (T, n, _LANES))
        by_rows = pl.BlockSpec((rows, channels), lambda i, j: (j, i))
        by_state = pl.BlockSpec((rows, n, _LANES), lambda i, j: (j, 0, 0))
        by_tile = lambda height: pl.BlockSpec((height, channels),
                                              lambda i, j: (0, i))
        return pl.pallas_call(
            functools.partial(_kernel, rows=rows, channels=channels),
            grid=(E // channels, T // rows),
            in_specs=[by_rows, by_rows, by_state, by_state, by_tile(n),
                      by_tile(1), by_tile(n)],
            out_specs=[by_rows, by_tile(n)],
            out_shape=[jax.ShapeDtypeStruct((T, E), F32),
                       jax.ShapeDtypeStruct((n, E), F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            cost_estimate=pl.CostEstimate(
                flops=9 * T * E * n, transcendentals=T * E * n,
                bytes_accessed=4 * (3 * T * E + 3 * n * E
                                    + 2 * (E // channels) * T * n * _LANES)),
            interpret=interpret,
            name="selective_scan_chunk",
        )(x, dt, spread(Bm), spread(Cm), A, D[None, :], h_in)

    return call
