"""What every served family's cached forward has in common. A family's
module (``models/llama.py``, ``window_moe.py``, ``latent_moe.py``) states
its cache, its mixer (what turns a layer's input into the attended rows,
writing the cache on the way) and its feed-forward; the call around them
is here, once: **the call's rows** (``Call``; the idle position is
defined here and the engine dispatches what it says), **embedding and
head**, **the layer scan** (``scan_layers``) and **the device counters**
(two int32 words each in the cache, ``fold_counts``, ``read_counters``).

**A call over several shards.** A family's ``forward_with_cache`` takes
one shard's cache or a tuple of several (``caches_of``), the tokens and
offsets of all their lanes in one batch, shard after shard. Everything
that multiplies a weight (embedding, projections, feed-forward, final
norm, head) runs once over all the rows, so a weight is read once a
call; what touches a cache runs once a shard on that shard's rows and
that shard's cache alone (``Call.by_shard``), every cache carried
through the layer scan and updated in place. A lane of any shard rides
such a call as it rides a call of its own shard: an idle or mid-prefill
one at the idle position, writing its scratch row. What the call counts
on the device is folded into the first cache's words. The rows are
those of a call a shard up to what a matmul over more rows rounds
otherwise. A call with one cache traces to the program it always was.

It imports no family's module and takes no argument that says which one
calls it. The scopes it opens (``embed``, ``layers``, ``head``) are
names ``benchmarks/`` reads out of a trace.
"""

from __future__ import annotations

import copy
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * weight.astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """A LayerNorm with a gain and no bias: the mean is subtracted,
    which ``rms_norm`` does not do; mean and variance in float32."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * weight.astype(dt)


def idle_position(max_seq: int) -> int:
    """The position of a decode lane that is nobody's (free, mid-prefill
    or past its last token): the cache's last row, which no live
    sequence reaches, so what the lane writes is never attended to."""
    return max_seq - 1


def caches_of(cache) -> Tuple[tuple, Any]:
    """A cached forward's ``cache`` argument, one shard's pytree or a
    tuple of several shards' -> (the caches as a tuple, ``back``: the
    tuple of caches the call leaves, as the caller gave them)."""
    if isinstance(cache, tuple):
        return cache, tuple
    return (cache,), lambda caches: caches[0]


class Call:
    """The rows of one cached call: ``tokens`` (B, T) appended at the
    per-sequence offsets ``start_pos`` (B,) of a cache ``max_seq`` rows
    long. T is static (bucketed by the engine); ``start_pos`` is traced.

    Row ``b`` of ``tokens`` belongs to row ``b`` of the cache, or to row
    ``slot + b`` where ``slot`` (a traced scalar) is given: the engine
    prefills one sequence, tokens (1, T), into its slot of a shard.

    With ``logits_at`` (B,), the row of each sequence's T whose logits
    the caller keeps, the final norm and the head run on those rows
    alone and the logits are (B, 1, V): a prefill chunk samples from
    its last real token only.

    ``rows`` (static; default all ``max_seq``) is the read window:
    attention reads cache rows ``[0, rows)`` of each sequence and no
    more. The caller vouches that every row a live query may attend to
    (``start_pos + T`` of them) lies inside; a masked row weighs
    exp(-1e30 - max) = 0 exactly, so any such window gives the full
    read's result. Writes go to the full cache wherever ``start_pos``
    says, inside the window or not (an idle lane's to its scratch row).

    ``shards``: how many caches the B sequences lie in, B / shards in
    each, shard after shard (``by_shard``). Only whole shards ride
    together: several of them leave no place for a ``slot``.
    """

    def __init__(self, tokens, start_pos, max_seq: int, *, slot=None,
                 logits_at=None, rows: Optional[int] = None, shards: int = 1):
        self.B, self.T = tokens.shape
        if shards > 1 and (slot is not None or self.B % shards):
            raise ValueError(
                f"{self.B} sequences, slot {slot}, over {shards} caches")
        self.start_pos = start_pos
        self.pos = start_pos[:, None] + jnp.arange(self.T)[None, :]  # (B, T)
        self.first = 0 if slot is None else slot
        self.window = max_seq if rows is None else rows
        self.logits_at = logits_at
        self.max_seq = max_seq

    def live(self) -> jax.Array:
        """(B, T) bool: the rows that are somebody's tokens. Not a
        decode lane at the idle position; not the rows of a padded chunk
        behind the one its logits are taken at. They are computed like
        the others and left out of every count."""
        if self.T == 1:
            return self.start_pos[:, None] != idle_position(self.max_seq)
        if self.logits_at is not None:
            return jnp.arange(self.T)[None, :] <= self.logits_at[:, None]
        return jnp.ones((self.B, self.T), bool)

    def by_shard(self, mix, states, *rows):
        """``mix(part, state, *rows) -> (out, state)`` once a shard:
        ``part`` is the call of that shard's sequences alone, ``state``
        what the scan carries of its cache, ``rows`` arrays (or None)
        with a leading axis of the call's sequences, cut alike ->
        (the outs joined along that axis, the states as a tuple). With
        one shard ``part`` is the call and nothing is cut or joined;
        with several, ``mix`` is traced once and called a shard (it may
        close over what the caller's trace holds, not write to it)."""
        if len(states) == 1:
            out, state = mix(self, states[0], *rows)
            return out, (state,)
        n = self.B // len(states)

        # jitted, so that the shards share one trace and one lowering of
        # the mixer (a lane's cache write is a few dozen operations, and
        # tracing and lowering them is what a variant costs a replica's
        # start); the compiler inlines the calls
        @jax.jit
        def shard(start_pos, pos, state, *rows):
            part = copy.copy(self)
            part.B, part.start_pos, part.pos = n, start_pos, pos
            return mix(part, state, *rows)

        outs, new = [], []
        for s, state in enumerate(states):
            at = slice(s * n, (s + 1) * n)
            out, state = shard(self.start_pos[at], self.pos[at], state,
                               *(r if r is None else r[at] for r in rows))
            outs.append(out)
            new.append(state)
        return (jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *outs),
                tuple(new))


def embed(params: Dict[str, Any], tokens: jax.Array, config) -> jax.Array:
    with jax.named_scope("embed"):
        return params["embed"].astype(config.dtype)[tokens]


def final_rows(params: Dict[str, Any], x: jax.Array, config,
               logits_at=None, norm=rms_norm) -> jax.Array:
    """The rows the head runs on, through the final norm (``norm``: the
    family's): row ``logits_at[b]`` of each sequence (B, 1, D), or all
    of them."""
    if logits_at is not None:
        x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
    return norm(x, params["final_norm"], config.norm_eps)


def head(params: Dict[str, Any], x: jax.Array, config,
         logits_at=None) -> jax.Array:
    """-> float32 logits of ``final_rows``: the product in the compute
    type, widened."""
    with jax.named_scope("head"):
        x = final_rows(params, x, config, logits_at)
        logits = jnp.einsum(
            "bsd,dv->bsv", x, params["lm_head"].astype(config.dtype))
        return logits.astype(jnp.float32)


def scan_layers(step, x: jax.Array, state, layers, n_counted: int = 0):
    """``step(x, state, layer, i) -> (x, state, counted)`` over the
    stacked ``layers``, ``i`` the traced index of the layer among them;
    ``state`` is whatever of the cache the step writes and reads,
    carried so that a jit that donates it updates it in place.
    ``counted``: int32 (n_counted,), summed over the layers; a step that
    counts nothing returns None. -> (x, state, the sum or None).

    Operations scoped ``layers`` and nothing deeper are the scan's own:
    a layer's weights sliced out of the stack."""
    def body(carry, layer):
        x, state, counts, i = carry
        x, state, counted = step(x, state, layer, i)
        if counts is not None:
            counts = counts + counted
        return (x, state, counts, i + 1), None

    with jax.named_scope("layers"):
        counts = jnp.zeros(n_counted, jnp.int32) if n_counted else None
        (x, state, counts, _), _ = jax.lax.scan(
            body, (x, state, counts, jnp.int32(0)), layers)
    return x, state, counts


# A counter is two int32 words, high and low; the low word carries into
# the high one from here, and no call may count this much at once.
_CARRY_BITS = 30


def counter_words(n: int) -> jax.Array:
    """``n`` counters at zero, as a cache holds them: int32 (n, 2)."""
    return jnp.zeros((n, 2), jnp.int32)


def fold_counts(words: jax.Array, counted: jax.Array) -> jax.Array:
    """``words`` (n, 2) with one call's ``counted`` int32 (n,) added."""
    with jax.named_scope("layers"):     # where a trace has always had them
        low = words[:, 1] + counted
        return jnp.stack([words[:, 0] + (low >> _CARRY_BITS),
                          low & ((1 << _CARRY_BITS) - 1)], axis=1)


def folded(caches: tuple, counted: jax.Array) -> list:
    """Every cache's counter words behind a call that counted
    ``counted``: folded into the first's, the others' as they were (a
    reader sums the shards')."""
    return [fold_counts(caches[0]["counts"], counted),
            *(each["counts"] for each in caches[1:])]


def read_counters(cache, names: Sequence[str]) -> Dict[str, int]:
    """What the programs that wrote one cache shard have counted under
    each of ``names``, the order of its ``counts`` (waits for the
    program that last wrote it)."""
    hi_lo = np.asarray(cache["counts"]).astype(np.int64)
    totals = (hi_lo[:, 0] << _CARRY_BITS) + hi_lo[:, 1]
    return dict(zip(names, (int(t) for t in totals)))
