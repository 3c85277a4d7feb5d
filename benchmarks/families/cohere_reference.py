"""The ``cohere`` family's plain reference: one sequence through the
decoder in float32 ``jax.numpy`` at ``default_matmul_precision("highest")``,
with no kernel, no cache, no ring, no sorting and no running softmax. It
imports nothing of ``ray_tpu``: it shares with the system only the
layout of the parameter tree (``embed`` (V, D), the head too; ``blocks``,
the layers stacked on a leading axis: ``norm`` (D), ``wq`` (D, H, hd),
``wk`` / ``wv`` (D, KVH, hd), ``wo`` (H, hd, D), ``router`` (D, all
experts), ``w_gate`` / ``w_up`` (held, D, F), ``w_down`` (held, F, D),
and the shared experts side by side along the width: ``shared_gate`` /
``shared_up`` (D, n x F), ``shared_down`` (n x F, D), expert j the
columns / rows ``[j F, (j + 1) F)``; ``final_norm`` (D)).

Equations (config.json of command-a-plus-05-2026, ``model_type``
``cohere2_moe``; what the config leaves open stands under ``assumed`` in
``configs/command-a-plus-05-2026.json``), ``x`` the hidden stream (S, D):

- Norm: ``LN(x) = (x - mean(x)) / sqrt(var(x) + eps) * g``, mean and
  variance over D, a gain and no bias.
- Block, every layer alike (``use_parallel_block``): ``h = LN_l(x)``;
  ``x = x + Attn_l(h) + Moe_l(h)``. After the last layer ``LN_f``, then
  ``logits = logit_scale * LN_f(x) E^T`` with ``E`` the embedding.
- Attention: ``q = Wq h`` as H heads of hd, ``k = Wk h``, ``v = Wv h``
  as KVH heads; query head j reads key/value head ``j // (H / KVH)``. In
  a ``sliding_attention`` layer q and k are turned by position over
  ADJACENT pairs: ``(a[2i], a[2i+1]) -> (a[2i] cos(p w_i) - a[2i+1]
  sin(p w_i), a[2i+1] cos(p w_i) + a[2i] sin(p w_i))``, ``w_i =
  theta^(-2i / hd)``, and row t attends to rows s with ``0 <= t - s <
  sliding_window``. In a ``full_attention`` layer nothing is turned and
  row t attends to every ``s <= t``. Score ``q . k / sqrt(hd)``,
  softmax, ``Wo`` over the heads' results.
- Experts: ``s = sigmoid(h W_r)`` over all experts; the
  ``num_experts_per_tok`` largest; weights ``s_e / sum(chosen s)``;
  routed part the sum over the chosen experts THAT ARE HELD
  (``share.held_experts``) of ``w_e E_e(h)``, ``E(h) = (silu(h Wg) * (h
  Wu)) Wd``; shared part ``(1 / n) sum_j S_j(h)``, the n shared experts
  each computed and their outputs averaged; ``Moe(h)`` = routed part +
  shared part. What the absent experts would add is left out, as the
  program leaves it out.

``logits(..., flip=(layer, row))`` makes one routing flip on purpose: at
that layer and row the last chosen expert and the best one passed over
change places (the weights follow the new choice). It is for reading how
far a flip reaches (the rows behind it whose own choices hold); no
comparison that decides ``correct`` passes it.

Departures from the naive form, each so that a pass of some 13 000 rows
fits beside the served weights and cache on one chip; none changes a
number that is computed: one matrix group is widened to float32 at a
time (an expert's three, one shared expert's three, a key/value head's
share of the attention's four), never a layer; attention runs one
key/value head's ``H / KVH`` query heads at a time and ``QUERY_BLOCK``
query rows at a time against all keys; a SwiGLU runs ``WIDTH_BLOCK`` of
its hidden width at a time; the head ``VOCAB_BLOCK`` rows of the
embedding at a time; and each layer's result is waited for before the
next layer's programs are dispatched.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256
WIDTH_BLOCK = 2048
VOCAB_BLOCK = 4096


def layer_norm(x, g, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g.astype(F32)


def rope_adjacent(a, theta):
    """a (S, heads, hd) at positions 0..S-1, dimensions (2i, 2i + 1)
    turned together by ``p theta^(-2i / hd)``."""
    s, _, hd = a.shape
    w = theta ** -(jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None, None] * w         # (S, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = a[..., 0::2], a[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(a.shape)


def attend(q, k, v, window):
    """q (S, G, hd), k and v (S, hd) -> (S, G, hd): softmax(q k^T /
    sqrt(hd)) v, row t on rows s with ``0 <= t - s`` (``< window`` where
    it is given), QUERY_BLOCK rows at a time."""
    s, g, hd = q.shape
    blocks = -(-s // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - s
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, g, hd)
    rows = jnp.arange(blocks * QUERY_BLOCK).reshape(blocks, QUERY_BLOCK)
    cols = jnp.arange(s)

    def block(args):
        qi, i = args
        behind = i[:, None] - cols[None, :]
        seen = behind >= 0
        if window is not None:
            seen &= behind < window
        scores = jnp.einsum("tgh,sh->gts", qi, k) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("gts,sh->tgh", probs, v)

    return jax.lax.map(block, (qb, rows)).reshape(-1, g, hd)[:s]


@partial(jax.jit, static_argnames=("theta", "window", "turned"))
def attention(h, layer, *, theta, window, turned):
    """h (S, D) float32, normed -> Attn(h) (S, D). One key/value head
    and its query heads at a time."""
    with jax.default_matmul_precision("highest"):
        heads, kv_heads = layer["wq"].shape[1], layer["wk"].shape[1]
        g = heads // kv_heads

        def group(out, w):
            wq, wk, wv, wo = (a.astype(F32) for a in w)
            q = jnp.einsum("sd,dgh->sgh", h, wq)
            k = jnp.einsum("sd,dh->sh", h, wk)
            v = jnp.einsum("sd,dh->sh", h, wv)
            if turned:
                q = rope_adjacent(q, theta)
                k = rope_adjacent(k[:, None], theta)[:, 0]
            return out + jnp.einsum("sgh,ghd->sd",
                                    attend(q, k, v, window), wo), None

        d, hd = layer["wq"].shape[0], layer["wq"].shape[2]
        out, _ = jax.lax.scan(
            group, jnp.zeros_like(h),
            (jnp.moveaxis(layer["wq"].reshape(d, kv_heads, g, hd), 1, 0),
             jnp.moveaxis(layer["wk"], 1, 0), jnp.moveaxis(layer["wv"], 1, 0),
             layer["wo"].reshape(kv_heads, g, hd, d)))
        return out


def swiglu(h, w_gate, w_up, w_down):
    """h (S, D); (D, F), (D, F), (F, D) in any dtype -> (S, D), the
    hidden width WIDTH_BLOCK at a time."""
    width = w_gate.shape[1]
    b = min(WIDTH_BLOCK, width)
    if width % b:
        b = width

    def block(out, w):
        gate, up, down = (a.astype(F32) for a in w)
        return out + (jax.nn.silu(h @ gate) * (h @ up)) @ down, None

    out, _ = jax.lax.scan(
        block, jnp.zeros_like(h),
        (jnp.moveaxis(w_gate.reshape(-1, width // b, b), 1, 0),
         jnp.moveaxis(w_up.reshape(-1, width // b, b), 1, 0),
         w_down.reshape(width // b, b, -1)))
    return out


def route(h, router, top_k, norm_topk, flip_row=None):
    """h (S, D) -> each row's weight for every expert (S, all), 0 where
    it did not choose it. ``flip_row``: at that row the last chosen
    expert and the best one passed over change places."""
    scores = jax.nn.sigmoid(h @ router.astype(F32))             # (S, all)
    gates, chosen = jax.lax.top_k(scores, top_k + 1)
    if flip_row is not None:
        flipped = jnp.arange(h.shape[0]) == flip_row
        last, passed = top_k - 1, top_k
        swap = lambda a: a.at[:, last].set(
            jnp.where(flipped, a[:, passed], a[:, last]))
        gates, chosen = swap(gates), swap(chosen)
    gates, chosen = gates[:, :top_k], chosen[:, :top_k]
    if norm_topk:
        gates = gates / gates.sum(-1, keepdims=True)
    return (jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32)
            * gates[..., None]).sum(1)


@partial(jax.jit, static_argnames=("held", "top_k", "norm_topk", "n_shared"))
def experts(h, layer, held_weights, index, flip_row, *, held, top_k,
            norm_topk, n_shared):
    """h (S, D) float32, normed -> Moe(h) (S, D): the held experts'
    part of the routed sum plus the mean of the shared experts.
    ``layer``: this layer's router and shared experts; ``held_weights``:
    ``w_gate`` / ``w_up`` / ``w_down`` of the held experts, a layer's
    (held, ...) or, with ``index``, every layer's stacked (L, held, ...)
    of which this is layer ``index``: one expert's matrices are taken
    out at a time, never a layer's (1.6 GB at the published widths)."""
    with jax.default_matmul_precision("highest"):
        weight = route(h, layer["router"], top_k, norm_topk, flip_row)
        gates, ups, downs = (held_weights[k]
                             for k in ("w_gate", "w_up", "w_down"))

        def expert(out, w):
            e, weight_e = w
            at = (e,) if index is None else (index, e)
            return out + weight_e[:, None] * swiglu(
                h, gates[at], ups[at], downs[at]), None

        routed, _ = jax.lax.scan(
            expert, jnp.zeros_like(h),
            (jnp.arange(len(held)), weight[:, jnp.asarray(held)].T))

        def shared(out, w):
            return out + swiglu(h, *w), None

        d, width = layer["shared_gate"].shape
        each = width // n_shared
        total, _ = jax.lax.scan(
            shared, jnp.zeros_like(h),
            (jnp.moveaxis(layer["shared_gate"].reshape(d, n_shared, each),
                          1, 0),
             jnp.moveaxis(layer["shared_up"].reshape(d, n_shared, each),
                          1, 0),
             layer["shared_down"].reshape(n_shared, each, d)))
        return routed + total / n_shared


@partial(jax.jit, static_argnames=("eps",))
def _norm(x, g, *, eps):
    return layer_norm(x, g, eps)


@partial(jax.jit, static_argnames=("eps", "scale"))
def _head(x, final_norm, embed, *, eps, scale):
    """The final norm and the tied head, VOCAB_BLOCK rows of the
    embedding widened at a time."""
    with jax.default_matmul_precision("highest"):
        h = layer_norm(x, final_norm, eps)
        vocab = embed.shape[0]
        b = VOCAB_BLOCK if vocab % VOCAB_BLOCK == 0 else vocab
        out = jax.lax.map(lambda rows: h @ rows.astype(F32).T,
                          embed.reshape(vocab // b, b, -1))
        return scale * jnp.moveaxis(out, 0, 1).reshape(x.shape[0], vocab)


def logits(params, tokens, hp: dict, last: int = 0, flip=None):
    """(S, V) float32 logits of one sequence under the configuration
    ``hp`` (the config.json keys and ``share``: the router's width and
    the experts held); ``last`` > 0 keeps only the last ``last``
    positions (the head is the widest matmul); ``flip`` (layer, row): a
    routing flip made on purpose there (the module docstring)."""
    eps = float(hp["layer_norm_eps"])
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    held_weights = {k: params["blocks"][k]
                    for k in ("w_gate", "w_up", "w_down")}
    for i, kind in enumerate(hp["layer_types"][:hp["num_hidden_layers"]]):
        layer = {k: v[i] for k, v in params["blocks"].items()
                 if k not in held_weights}
        sliding = kind == "sliding_attention"
        h = _norm(x, layer["norm"], eps=eps)
        # one layer's programs on the chip at a time: a program's
        # temporaries are taken when it is dispatched, and several
        # layers' worth in flight together is gigabytes at 13 000 rows
        x = jax.block_until_ready(
            x
            + attention(h, layer, theta=float(hp["rope_theta"]),
                        window=int(hp["sliding_window"]) if sliding else None,
                        turned=sliding)
            + experts(h, layer, held_weights, i,
                      flip[1] if flip is not None and flip[0] == i else None,
                      held=tuple(hp["share"]["held_experts"]),
                      top_k=int(hp["num_experts_per_tok"]),
                      norm_topk=bool(hp["norm_topk_prob"]),
                      n_shared=int(hp["num_shared_experts"])))
        del layer, h
    if last:
        x = x[-last:]
    return _head(x, params["final_norm"], params["embed"], eps=eps,
                 scale=float(hp["logit_scale"]))
