"""Decoders whose layers are state-space (Mamba-1) mixers with an
attention layer every ``attn_period`` (AI21's Jamba convention: layer
``i`` is attention where ``i % attn_period == attn_offset``), a dense
SwiGLU behind every mixer, no position term of any kind and a tied
head. Served through ``llm/_internal/engine.py`` as the other families
are; not trained (``ops/selective_scan.py`` has no backward pass).

**A layer** (``N`` an RMS norm with its own gain): ``x = x + mixer(
N1(x))``, then ``x = x + mlp(N2(x))``. Attention is the llama family's
sublayer (``llama.attention_sublayer``, its cached form
``_attention_cached`` over ``write_and_read``) with the rotation
switched off: cos 1 and sin 0 at every position. The feed-forward is
``llama.mlp_sublayer``.

**The Mamba mixer** (inner width ``E`` = expand x dim, state ``n``,
rank ``R``, kernel ``K``): ``[u | z] = h W_in``; ``c_t = silu(b_conv +
sum_j w_conv[j] * u_{t-K+1+j})`` a channel, the rows before a sequence's
first being zero; ``[d | Bm | Cm] = c W_x``, each through an RMS norm
of its own; ``dt = softplus(d W_dt + b_dt)``; ``A = -exp(A_log)``; the
selective scan of ``ops/selective_scan.py`` over (c, dt, Bm, Cm, A, D)
from the state the sequence has; the output ``(y * silu(z)) W_out``.
The scan, ``A``, ``dt``, the convolution's sum and the convolved rows
the scan takes are float32, as are its rows up to their gate; ``u``,
``z``, the tail and every matmul's operands are the compute type.

**The cache has three kinds of leaf.** ``state`` (state layers, B, n,
E) float32: a lane's recurrent state a layer, the channels last
(``ops/selective_scan.py`` says why). ``tail`` (state layers, B, (K - 1)
x E): the last K - 1 rows of ``u`` a lane a layer, side by side, in the
compute type. (As (B, K - 1, E) or (K - 1, B, E) a chunk program, which
reads one lane's, laid the whole stack out the other way round than a
decode program, and was bracketed by two transposing copies of it,
whichever was declared: tests/aot_compile_check.py, ``state``. Side by
side there is one way to lay it out, a decode call's K - 1 rows are
whole (B, E) tiles, and a chunk call turns one lane's 30 KB.)
``k`` / ``v`` (attention layers, B, KVH, max_seq, hd): rows by
position, as the llama family keeps them. ``counts``: the device words
of ``COUNTERS``. Of a sequence of any length the state layers hold
``n x E`` float32 and ``(K - 1) x E`` values a layer, and no more.

**What a state owes the engine.** Rows addressed by position get these
for free; a state does not, so the mixer states them (``ssm_sublayer``,
and tests/test_hybrid_ssm.py keeps each):

- a call whose ``start_pos`` is 0 begins a sequence: it starts from a
  zero state and a zero tail whatever the slot held;
- rows that are nobody's (``decoder.Call.live()`` false: the rows of a
  padded chunk behind its last token; a decode lane at the idle
  position, be it free, mid-prefill or past its last token) leave state
  and tail exactly as they were. A lane that is mid-prefill rides every
  decode call between its chunks, so this is the engine's own
  correctness, not only a probe's;
- a chunk that starts at row ``s > 0`` begins from the state and the
  tail the call before left.

**The layers are stacked by kind** (``mamba``: the state layers',
``attn``: the attention layers', ``mlp``: every layer's feed-forward)
and scanned by period: ``decoder.scan_layers`` over the periods, and
inside a period a scan over the state layers before its attention
layer, that layer, a scan over the state layers behind it. Three layer
bodies are traced whatever ``attn_period`` is; a layer's weights are
indexed out of the stacks where they are used.

**Counters** (``COUNTERS``): ``ssm_rows``, the rows the state layers
computed (a call's B x T in each), and ``ssm_rows_live``, those of them
that were somebody's, each summed over layers and calls.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import selective_scan

from . import decoder
from .decoder import rms_norm
from .llama import (
    LlamaConfig,
    _attention_cached,
    attention_sublayer,
    make_dense_init,
    mlp_sublayer,
    write_and_read,
)

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig(LlamaConfig):
    # ``rope_theta`` means nothing here: no layer turns anything
    attn_period: int = 14
    attn_offset: int = 7
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    # the step a state-space layer's ``b_dt`` is seeded for, drawn
    # log-uniformly between the two (the Mamba paper's initialisation)
    dt_init: tuple = (0.001, 0.1)

    model_module = "ray_tpu.models.hybrid_ssm"

    def __post_init__(self):
        if self.n_layers % self.attn_period:
            raise ValueError(
                f"{self.n_layers} layers are not whole periods of "
                f"{self.attn_period}")
        if not 0 <= self.attn_offset < self.attn_period:
            raise ValueError(
                f"attn_offset {self.attn_offset} outside a period of "
                f"{self.attn_period}")

    @property
    def inner(self) -> int:
        return self.expand * self.dim

    @property
    def n_attn_layers(self) -> int:
        return self.n_layers // self.attn_period

    @property
    def n_state_layers(self) -> int:
        return self.n_layers - self.n_attn_layers


HYBRID_SSM_TINY = HybridSSMConfig(
    vocab_size=512, dim=64, n_layers=8, n_heads=4, n_kv_heads=1,
    head_size=16, ffn_dim=128, max_seq_len=256, remat=False,
    attn_period=4, attn_offset=2, d_state=16, d_conv=4, expand=2, dt_rank=8,
)

COUNTERS = ("ssm_rows", "ssm_rows_live")


# -- parameters --------------------------------------------------------
def param_specs(config: HybridSSMConfig) -> Dict[str, Any]:
    """Everything whole on every device: the family is served on one
    chip."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), config))
    return jax.tree_util.tree_map(lambda a: P(*[None] * a.ndim), shapes)


def init_params(rng: jax.Array, config: HybridSSMConfig) -> Dict[str, Any]:
    """``mamba``, ``attn`` and ``mlp``: each kind's layers stacked, in
    ``param_dtype``; ``a_log``, ``d`` and ``dt_bias`` float32. Fan-in
    scaled normal but the state-space parameters, which are seeded as
    the Mamba paper initialises them: ``A[s, c] = -(s + 1)``, ``b_dt``
    the inverse softplus of a step drawn log-uniformly over
    ``dt_init``, ``D`` 1. (A normal draw there gives decays that forget
    within a row or never.) The head is the embedding's transpose."""
    c = config
    dense = make_dense_init(c)
    keys = iter(jax.random.split(rng, 16))
    L, Lm, La = c.n_layers, c.n_state_layers, c.n_attn_layers
    D, E, n, R, K, hd = c.dim, c.inner, c.d_state, c.dt_rank, c.d_conv, c.head_dim
    ones = lambda *shape: jnp.ones(shape, c.param_dtype)
    low, high = (math.log(v) for v in c.dt_init)
    step = jnp.exp(jax.random.uniform(next(keys), (Lm, E), F32, low, high))
    return {
        "embed": dense(next(keys), (c.vocab_size, D), D),
        "mamba": {
            "norm": ones(Lm, D),
            "w_in": dense(next(keys), (Lm, D, 2 * E), D),
            "conv_w": dense(next(keys), (Lm, K, E), K),
            "conv_b": dense(next(keys), (Lm, E), K),
            "w_x": dense(next(keys), (Lm, E, R + 2 * n), E),
            "dt_norm": ones(Lm, R), "b_norm": ones(Lm, n),
            "c_norm": ones(Lm, n),
            "w_dt": dense(next(keys), (Lm, R, E), R),
            # softplus(dt_bias) = step
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=F32))[None, :, None],
                (Lm, n, E)),
            "d": jnp.ones((Lm, E), F32),
            "w_out": dense(next(keys), (Lm, E, D), E),
        },
        "attn": {
            "attn_norm": ones(La, D),
            "wq": dense(next(keys), (La, D, c.n_heads, hd), D),
            "wk": dense(next(keys), (La, D, c.n_kv_heads, hd), D),
            "wv": dense(next(keys), (La, D, c.n_kv_heads, hd), D),
            "wo": dense(next(keys), (La, c.n_heads, hd, D), c.n_heads * hd),
        },
        "mlp": {
            "mlp_norm": ones(L, D),
            "w_gate": dense(next(keys), (L, D, c.ffn_dim), D),
            "w_up": dense(next(keys), (L, D, c.ffn_dim), D),
            "w_down": dense(next(keys), (L, c.ffn_dim, D), c.ffn_dim),
        },
        "final_norm": ones(D),
    }


# -- the sublayers -----------------------------------------------------
def ssm_in(c: HybridSSMConfig, h, layer):
    """h (B, T, D) -> (u, z), each (B, T, E)."""
    with jax.named_scope("ssm_in"):
        uz = jnp.einsum("btd,de->bte", h, layer["w_in"].astype(c.dtype))
        return uz[..., :c.inner], uz[..., c.inner:]


def ssm_conv(c: HybridSSMConfig, u, tail, layer, rows_live):
    """u (B, T, E) behind each sequence's ``tail`` (B, (K - 1) x E), its
    last K - 1 rows side by side -> (the convolved rows through silu
    (B, T, E) float32, the tail behind each sequence's ``rows_live``
    (B,) leading rows: all of the old one where that is 0)."""
    K, E, (B, T, _) = c.d_conv, c.inner, u.shape
    with jax.named_scope("ssm_conv"):
        w = layer["conv_w"].astype(F32)
        if T == 1:
            # a lane's row is live or not: whole (B, E) tiles and a
            # select, no row of three and no gather
            rows = [tail[:, j * E:(j + 1) * E] for j in range(K - 1)]
            rows.append(u[:, 0])
            conv = sum(w[j] * rows[j].astype(F32) for j in range(K))[:, None]
            new = jnp.where((rows_live > 0)[:, None],
                            jnp.concatenate(rows[1:], axis=-1), tail)
        else:
            behind = jnp.concatenate([tail.reshape(B, K - 1, E), u], axis=1)
            conv = sum(w[j] * behind[:, j:j + T].astype(F32)
                       for j in range(K))
            new = jax.vmap(lambda rows, at: jax.lax.dynamic_slice_in_dim(
                rows, at, K - 1))(behind, rows_live).reshape(B, (K - 1) * E)
        conv = conv + layer["conv_b"].astype(F32)
        return jax.nn.silu(conv), new


def ssm_x(c: HybridSSMConfig, xc, layer):
    """The convolved rows (B, T, E) -> (dt (B, T, E), Bm (B, T, n), Cm),
    all float32."""
    R, n = c.dt_rank, c.d_state
    with jax.named_scope("ssm_x"):
        dbc = jnp.einsum("bte,er->btr", xc.astype(c.dtype),
                         layer["w_x"].astype(c.dtype),
                         preferred_element_type=F32)
        d = rms_norm(dbc[..., :R], layer["dt_norm"], c.norm_eps)
        Bm = rms_norm(dbc[..., R:R + n], layer["b_norm"], c.norm_eps)
        Cm = rms_norm(dbc[..., R + n:], layer["c_norm"], c.norm_eps)
    with jax.named_scope("ssm_dt"):
        dt = jnp.einsum("btr,re->bte", d.astype(c.dtype),
                        layer["w_dt"].astype(c.dtype),
                        preferred_element_type=F32)
        return jax.nn.softplus(dt + layer["dt_bias"]), Bm, Cm


def ssm_out(c: HybridSSMConfig, gated, layer):
    """The scan's rows through their gate, ``y * silu(z)`` (B, T, E) in
    the compute type -> (B, T, D)."""
    with jax.named_scope("ssm_out"):
        return jnp.einsum("bte,ed->btd", gated, layer["w_out"].astype(c.dtype))


def ssm_sublayer(c: HybridSSMConfig, x, layer, state, tail, start_pos, live,
                 scan_chunk=selective_scan.scan_chunk):
    """Pre-norm Mamba mixer + residual. x (B, T, D); ``state`` (B, n,
    E) float32 and ``tail`` (B, (K - 1) x E) as the call's lanes hold
    them; ``start_pos`` (B,); ``live`` (B, T), each sequence's a
    leading run -> (x, state, tail). T of 1 takes the step form of the
    scan, T over 1 the chunk form (``scan_chunk``), a sequence at a
    time."""
    B, T, _ = x.shape
    with jax.named_scope("ssm"):
        fresh = start_pos == 0
        state = jnp.where(fresh[:, None, None], 0.0, state)
        tail = jnp.where(fresh[:, None], 0, tail)
        u, z = ssm_in(c, rms_norm(x, layer["norm"], c.norm_eps), layer)
        xc, tail = ssm_conv(c, u, tail, layer, live.sum(axis=1))
        dt, Bm, Cm = ssm_x(c, xc, layer)
        A = -jnp.exp(layer["a_log"])
        if T == 1:
            with jax.named_scope("ssm_step"):
                y, state = selective_scan.scan_step(
                    xc[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0], A, layer["d"],
                    state, live[:, 0])
                y = y[:, None]
        else:
            with jax.named_scope("ssm_scan"):
                done = [scan_chunk(
                    xc[b], dt[b], Bm[b], Cm[b], A, layer["d"], state[b],
                    live[b]) for b in range(B)]
                y = jnp.stack([y_b for y_b, _ in done])
                state = jnp.stack([h_b for _, h_b in done])
        # the scan's rows stay float32 up to their gate: one rounding
        # to the compute type, where ``W_out`` takes them
        gated = (y * jax.nn.silu(z.astype(F32))).astype(c.dtype)
        return x + ssm_out(c, gated, layer), state, tail


def _unturned(c: HybridSSMConfig, B: int, T: int):
    """cos and sin that turn nothing: the model has no position term."""
    return (jnp.ones((B, T, c.head_dim // 2), F32),
            jnp.zeros((B, T, c.head_dim // 2), F32))


def scan_periods(c: HybridSSMConfig, params, x, state, mix_state, mix_attn):
    """The layers over ``x`` (B, T, D). ``mix_state(x, state, layer, i)
    -> (x, state)`` is state layer ``i``'s mixer with its residual (of
    the state layers, in order), ``mix_attn`` attention layer ``i``'s;
    the feed-forward behind each is added here. ``state`` is whatever
    of the cache the mixers carry -> (x, state)."""
    period, before = c.attn_period, c.attn_offset
    behind = period - before - 1
    at = lambda stack, i: jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)
    mlp = lambda x, i: mlp_sublayer(c, x, at(params["mlp"], i))

    def state_layers(x, state, count, first_state, first_layer):
        if not count:
            return x, state

        def step(x, state, j, _):
            x, state = mix_state(x, state, at(params["mamba"], first_state + j),
                                 first_state + j)
            return mlp(x, first_layer + j), state, None

        x, state, _ = decoder.scan_layers(step, x, state, jnp.arange(count))
        return x, state

    def one_period(x, state, p, _):
        x, state = state_layers(x, state, before, p * (period - 1), p * period)
        x, state = mix_attn(x, state, at(params["attn"], p), p)
        x = mlp(x, p * period + before)
        x, state = state_layers(x, state, behind, p * (period - 1) + before,
                                p * period + before + 1)
        return x, state, None

    x, state, _ = decoder.scan_layers(one_period, x, state,
                                      jnp.arange(c.n_attn_layers))
    return x, state


def _tied(params):
    """``params`` as ``decoder.head`` takes them: the head is the
    embedding's transpose."""
    return {**params, "lm_head": params["embed"].T}


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: HybridSSMConfig) -> jax.Array:
    """tokens (B, S) int32 -> logits (B, S, V) float32: whole sequences
    from a zero state, the scan row by row in ``jax.numpy``, no cache."""
    c = config
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    cos, sin = _unturned(c, B, S)
    live = jnp.ones((B, S), bool)
    zeros = (jnp.zeros((B, c.d_state, c.inner), F32),
             jnp.zeros((B, (c.d_conv - 1) * c.inner), c.dtype))

    def mix_state(x, state, layer, i):
        x, _, _ = ssm_sublayer(c, x, layer, *zeros, pos[:, 0], live,
                               selective_scan.scan_chunk_rows)
        return x, state

    def mix_attn(x, state, layer, i):
        def mixer(q, k, v, _):
            return _attention_cached(q, k.transpose(0, 2, 1, 3),
                                     v.transpose(0, 2, 1, 3), pos, c)
        return attention_sublayer(c, x, layer, cos, sin, mixer), state

    x, _ = scan_periods(c, params, decoder.embed(params, tokens, c), None,
                        mix_state, mix_attn)
    return decoder.head(_tied(params), x, c)


# -- the cache ---------------------------------------------------------
def init_cache(config: HybridSSMConfig, batch: int, max_seq: int,
               chunk: Optional[int] = None):
    """``state`` (state layers, B, n, E) float32; ``tail`` (state
    layers, B, (K - 1) x E) and ``k`` / ``v`` (attention layers, B, KVH,
    max_seq, hd) in the compute type; ``counts``: the device words of
    ``COUNTERS``."""
    del chunk
    c = config
    Lm, La = c.n_state_layers, c.n_attn_layers
    rows = (La, batch, c.n_kv_heads, max_seq, c.head_dim)
    return {"state": jnp.zeros((Lm, batch, c.d_state, c.inner), F32),
            "tail": jnp.zeros((Lm, batch, (c.d_conv - 1) * c.inner),
                              c.dtype),
            "k": jnp.zeros(rows, c.dtype), "v": jnp.zeros(rows, c.dtype),
            "counts": decoder.counter_words(len(COUNTERS))}


def attn_rows_read(config: HybridSSMConfig, cache, rows: int) -> float:
    """Cache rows a sequence one call reads for attention at the read
    window ``rows``, the layers' mean: an attention layer's the window,
    a state layer's none (its state is no row)."""
    del cache
    return config.n_attn_layers * rows / config.n_layers


# what one cache shard's programs have counted
read_counters = partial(decoder.read_counters, names=COUNTERS)


def _lanes_read(cache, layer, first, B: int):
    """State layer ``layer``'s state (B, n, E) and tail (B, (K - 1) x E) of
    the lanes ``first .. first + B``."""
    state, tail = cache
    _, _, n, E = state.shape
    with jax.named_scope("state_slice"):
        return (jax.lax.dynamic_slice(
                    state, (layer, first, 0, 0), (1, B, n, E))[0],
                jax.lax.dynamic_slice(
                    tail, (layer, first, 0), (1, B, tail.shape[2]))[0])


def _lanes_write(cache, layer, first, new_state, new_tail):
    state, tail = cache
    with jax.named_scope("state_write"):
        return (jax.lax.dynamic_update_slice(
                    state, new_state[None], (layer, first, 0, 0)),
                jax.lax.dynamic_update_slice(
                    tail, new_tail[None].astype(tail.dtype),
                    (layer, first, 0)))


def forward_with_cache(
    params: Dict[str, Any],
    tokens: jax.Array,
    cache: Dict[str, Any],
    start_pos: jax.Array,
    config: HybridSSMConfig,
    *,
    slot: Optional[jax.Array] = None,
    logits_at: Optional[jax.Array] = None,
    rows: Optional[int] = None,
):
    """``llama.forward_with_cache``'s signature and meaning (tokens
    (B, T) appended at ``start_pos`` (B,), ``slot``, ``logits_at``,
    ``rows``) over this family's cache (``init_cache``). T of 1 is a
    decode and takes the scan's step form, T over 1 a chunk and takes
    the chunk form; ``rows`` bounds the attention layers' read and
    means nothing to a state layer. States, tails, keys and values ride
    in the layer scans' carry and are updated in place under a jit that
    donates the cache. ``cache`` may be a tuple of several shards'
    caches (``models/decoder.py``): a state layer then reads each
    shard's lanes, steps them together and writes each shard's back."""
    c = config
    caches, back = decoder.caches_of(cache)
    call = decoder.Call(tokens, start_pos, caches[0]["k"].shape[3],
                        slot=slot, logits_at=logits_at, rows=rows,
                        shards=len(caches))
    B, T, first = call.B, call.T, call.first
    cos, sin = _unturned(c, B, T)
    live = call.live()

    # the lanes' state out of the stack and back is the scan's own work
    # (a decode call's 2.4 GB at 128 lanes), so it carries the form's
    # scope beside its own: ``families/jamba_flops.py`` counts the state
    # read and written once a call, and the scope holds both
    form = "ssm_step" if T == 1 else "ssm_scan"

    # a shard's carry: ((states, tails), {"k", "v"})
    def mix_state(x, shards, layer, i):
        def read(part, carried):
            return _lanes_read(carried[0], i, first, part.B), carried

        def write(part, carried, state, tail):
            return None, (_lanes_write(carried[0], i, first, state, tail),
                          carried[1])

        with jax.named_scope("ssm"), jax.named_scope(form):
            (state, tail), shards = call.by_shard(read, shards)
        x, state, tail = ssm_sublayer(c, x, layer, state, tail, start_pos,
                                      live)
        with jax.named_scope("ssm"), jax.named_scope(form):
            _, shards = call.by_shard(write, shards, state, tail)
        return x, shards

    def mix_attn(x, shards, layer, i):
        def attend(part, carried, q, k, v):
            k_c, v_c, rows_kv = write_and_read(carried[1], k, v, i, part,
                                               part.window)
            with jax.named_scope("attn_cached"):
                return (_attention_cached(q, k_c, v_c, part.pos, c),
                        (carried[0], rows_kv))

        def mixer(q, k, v, _):
            nonlocal shards
            attn, shards = call.by_shard(attend, shards, q, k, v)
            return attn

        x = attention_sublayer(c, x, layer, cos, sin, mixer)
        return x, shards

    x, shards = scan_periods(
        c, params, decoder.embed(params, tokens, c),
        tuple(((each["state"], each["tail"]), {"k": each["k"], "v": each["v"]})
              for each in caches),
        mix_state, mix_attn)
    with jax.named_scope("layers"):     # counted beside the scans
        counted = c.n_state_layers * jnp.stack(
            [jnp.int32(B * T), live.sum().astype(jnp.int32)])
    new_caches = tuple(
        {"state": lanes[0], "tail": lanes[1], **rows_kv, "counts": words}
        for (lanes, rows_kv), words in zip(
            shards, decoder.folded(caches, counted)))
    return decoder.head(_tied(params), x, c, logits_at), back(new_caches)
