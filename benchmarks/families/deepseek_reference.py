"""The ``deepseek`` family's plain reference: one sequence through the
decoder in float32 ``jax.numpy`` at ``default_matmul_precision("highest")``,
with no kernel, no cache, no absorbed form, no threshold search and no
running softmax. It imports nothing of ``ray_tpu``: it shares with the
system only the layout of the parameter tree (``embed``; ``dense`` and
``routed``, each kind's layers stacked on a leading axis: the gains
``attn_norm`` / ``mlp_norm`` (D), ``q_norm`` (q rank), ``kv_norm`` (kv
rank), the index key's LayerNorm ``k_index_norm`` / ``k_index_bias``
(index dim); ``wdq`` (D, q rank), ``wuq`` (q rank, H, nope + rope),
``wdkv`` (D, kv rank + rope), ``wuk`` (kv rank, H, nope), ``wuv`` (kv
rank, H, v), ``wo`` (H, v, D); the indexer's ``wq_index`` (q rank, Hi,
di), ``wk_index`` (D, di), ``w_index`` (D, Hi); a dense layer's
``w_gate`` / ``w_up`` (D, F), ``w_down`` (F, D); a routed layer's
``router`` (D, all experts), ``router_bias`` (all experts), ``w_gate`` /
``w_up`` (held, D, Fe), ``w_down`` (held, Fe, D) and ``shared_gate`` /
``shared_up`` / ``shared_down``; ``final_norm``; ``lm_head``).

Equations (config.json of DeepSeek-V3.2-Exp, ``model_type``
``deepseek_v32``, and its published inference code; what the config
leaves open stands under ``assumed`` in ``configs/deepseek-v3.2-exp.json``).
``N`` is an RMS norm with its own gain.

- Pre-norm: ``x = x + attn(N1 x)``, ``x = x + mlp(N2 x)``; the final
  norm, then the untied head.
- Latent attention: ``cq = Nq(h Wdq)``; a head's ``[q_nope | q_rope] = cq
  Wuq``; ``[ckv | k_rope] = h Wdkv``; ``c = Nkv(ckv)``; ``k_rope`` (one
  vector for all heads) and each head's ``q_rope`` turned by the YaRN
  table (``rope_scaling``: the blended inverse frequencies; halves
  paired; cos and sin not scaled, ``mscale`` = ``mscale_all_dim``);
  ``k_nope = c Wuk``, ``v = c Wuv``; the score of row t on row s is
  ``(q_nope_t . k_nope_s + q_rope_t . k_rope_s) x m^2 / sqrt(nope +
  rope)``, ``m = 0.1 mscale_all_dim ln(factor) + 1``, softmax over the
  rows t selected; then ``Wo``.
- The indexer, every layer: ``qI = cq WqI``, ``kI = LN(h WkI)`` (a
  LayerNorm with gain and bias), the first ``qk_rope_head_dim``
  dimensions of both turned by the same table, ``w = h Ww / sqrt(Hi
  di)``; ``I(t, s) = sum_j w(t, j) relu(qI(t, j) . kI(s))`` for ``s <=
  t``; row t attends to the ``index_topk`` rows of largest ``I(t, .)``,
  all of them where there are no more, of equal scores the row of the
  lower index first: the rows are put in order by a stable sort of the
  negated scores, the ``index_topk``-th of them gives the threshold, and
  of the rows at the threshold the first by index make up the count.
- ``mlp`` of the ``first_k_dense_replace`` leading layers: ``(silu(h
  W_gate) * (h W_up)) W_down``.
- ``mlp`` of the others: ``s = sigmoid(h W_r)`` over all experts; the
  choice is made on ``s + b``: the ``n_group`` groups of equal size are
  each scored by the sum of their two largest ``s + b``, the
  ``topk_group`` best groups are kept, and the ``num_experts_per_tok``
  largest ``s + b`` among them are the token's experts; weights ``s_i /
  sum(chosen s) x routed_scaling_factor``; the routed part is the sum
  over the chosen experts THAT ARE HELD (``share.held_experts``) of ``w_i
  SwiGLU_i(h)``; the shared expert's SwiGLU is added once. What the
  absent experts would add is left out, as the program leaves it out.

Departures from the published description, each noted in the
configuration's ``assumed``: the index keys' fp8 and the Hadamard
rotation of ``qI`` and ``kI`` are left out (the rotation is orthogonal
and leaves every product as it was; fp8 is a storage format), and
multi-token prediction is not built.

Departures from the naive form, each so that a pass of some 25 000 rows
fits beside the served weights and caches on one chip; none changes a
number that is computed: one sublayer is one jitted call; attention
runs ``HEAD_BLOCK`` heads at a time and ``QUERY_BLOCK`` query rows at a
time against all keys; the selection is made ``QUERY_BLOCK`` query rows
at a time, the index heads summed one after the other, and kept as one
boolean matrix for the layer's head blocks; a SwiGLU runs
``WIDTH_BLOCK`` of its hidden width at a time; the held experts are
looped over.

**Under choices handed in** (``logits(..., choices=)``; README.md,
"Under the engine's own routing choices"): the same pass in which a
layer attends to the set it is handed in place of its own ``index_topk``
best and a routed layer takes the experts it is handed in place of its
own, weighted by its OWN scores of them; everything computed over a
choice stays this module's own float32. ``choices`` is int32 (layers +
1, S, W) as the program says them: entry l is layer l's set at every
row, as bits under tags (``handed_rows`` has the order), the last entry
every routed layer's experts side by side, each under its layer's tag.
Beside the logits it returns ``margin`` (layers + routed layers, S): how
far what was handed lies from this module's own choice, 0 where the two
sets are equal.

- *Of a selection*: the larger of how far the worst row handed lies
  under the reference's own ``index_topk``-th best index score, and how
  far the best row NOT handed lies over it (a set of the right size
  that drops a high row for a row at the edge is far by the second),
  in units of ``SET_UNIT`` root mean squares of the row's index scores
  (three: a sound bf16 engine's sets then read about what its experts
  read in the router's logits, and the cell's one ``route_margin_tol``
  holds both kinds); ``FAR`` where the set holds a row behind the
  query's own, or not ``min(t + 1, index_topk)`` rows.
- *Of a routed layer's experts* (``handed_margin``): how far the worst
  expert handed lies under the reference's own k-th best ``score +
  bias``, over the root of the two scores' squared slopes (units of the
  router's logits). A handed expert shows that its group was kept: such
  a group the reference would have dropped is judged the same way, its
  score (the sum of its two best) under the last group the reference
  kept, over the slopes of the four experts that make the two sums; the
  experts are then ranked among the groups the reference keeps once the
  handed ones are; the margin is the larger of the two.

A comparison asks for such a pass again and again over the same
sequence with one late row's choices replaced (the row behind each
decode). Rows before the first row that differs are what they were, so
``logits(..., memo=)`` keeps the last whole pass's rows at every
layer's input in the dict it is handed and computes, of a pass that
differs from it only from some row on, the query blocks from that row's
block on, against keys and values of all rows; same numbers, a fraction
of the work at the cell's probe.
"""

from __future__ import annotations

import math
import weakref
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FAR = 1e6       # the margin of a handed set that is no selection at all
SET_UNIT = 3.0  # root mean squares of a row's index scores a unit of it
# the bits of a handed set: 32 planes of PLANE words make a group of
# 32 x PLANE rows, row r of a group the bit r // PLANE of word r % PLANE
PLANE = 128
HEAD_BLOCK = 16
QUERY_BLOCK = 256
WIDTH_BLOCK = 2048


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def rope_of(hp: dict) -> tuple:
    """The rotary table's numbers as a hashable tuple: (theta, then
    YaRN's factor, original context, beta_fast, beta_slow; or Nones
    where ``rope_scaling`` is null), and the factor ``m`` whose square
    multiplies the scores' scale."""
    scaling = hp.get("rope_scaling")
    if not scaling:
        return (float(hp["rope_theta"]), None, None, None, None), 1.0
    if scaling["type"] != "yarn" or scaling["mscale"] != scaling[
            "mscale_all_dim"]:
        raise ValueError(f"rope_scaling {scaling!r}")
    m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0
    return (float(hp["rope_theta"]), float(scaling["factor"]),
            float(scaling["original_max_position_embeddings"]),
            float(scaling["beta_fast"]), float(scaling["beta_slow"])), m


def inv_freq(rope: tuple, dim: int):
    """The inverse frequencies (dim / 2,) of ``rope_of``'s tuple:
    ``theta^(-2i/dim)``, under YaRN blended with the same over ``factor``
    by a linear ramp between the two correction dimensions."""
    theta, factor, original, beta_fast, beta_slow = rope
    plain = theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor is None:
        return plain

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / factor * ramp


def _rope(x, rope):
    """x (S, ..., rope) at positions 0..S-1, halves paired."""
    s, dim = x.shape[0], x.shape[-1]
    inv = jnp.asarray(inv_freq(rope, dim), F32)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]      # (S, dim/2)
    ang = ang.reshape(s, *[1] * (x.ndim - 2), dim // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _query_blocks(s):
    """(blocks, the positions of every block's rows (blocks, QUERY_BLOCK)
    and the padding behind the last)."""
    blocks = -(-s // QUERY_BLOCK)
    rows = jnp.arange(blocks * QUERY_BLOCK).reshape(blocks, QUERY_BLOCK)
    return blocks, rows, blocks * QUERY_BLOCK - s


def handed_rows(entries, s):
    """entries (T, 2 W) int32, a set a row as the program says it ->
    (T, s) bool. Entry 2 w + j holds, under a tag in its upper half,
    sixteen bits: half j of word w; cache row r is bit ``(r // PLANE) %
    32`` of word ``r // (32 x PLANE) x PLANE + r % PLANE``."""
    groups = -(-s // (32 * PLANE))
    halves = (entries[:, :groups * PLANE * 2] & 0xFFFF).reshape(
        -1, groups, PLANE, 2, 1)
    bits = (halves >> jnp.arange(16, dtype=halves.dtype)) & 1
    # (T, groups, lane, half, bit) -> rows in order: group, half, bit, lane
    return bits.transpose(0, 1, 3, 4, 2).reshape(
        -1, groups * 32 * PLANE)[:, :s] == 1


def index_scores(q_index, k_index, w):
    """q_index (T, Hi, di), k_index (S, di), w (T, Hi) -> (T, S):
    ``sum_j w[t, j] relu(q_index[t, j] . k_index[s])``, the heads one
    after the other."""
    def head(score, qw):
        q_h, w_h = qw                               # (T, di), (T,)
        return score + w_h[:, None] * jax.nn.relu(q_h @ k_index.T), None

    score, _ = jax.lax.scan(
        head, jnp.zeros((q_index.shape[0], k_index.shape[0]), F32),
        (jnp.moveaxis(q_index, 1, 0), w.T))
    return score


def own_rows(score, causal, top_k):
    """score (T, S) with minus infinity where ``causal`` (T, S) is
    False -> (the rows each row attends to by its own scores (T, S)
    bool: the ``top_k`` largest among those it may, all of them where
    there are no more, of equal scores the lower index first; its
    ``top_k``-th best score (T, 1), minus infinity where it attends to
    every row it may)."""
    if score.shape[1] <= top_k:
        return causal, jnp.full((score.shape[0], 1), -jnp.inf)
    order = jnp.argsort(-score, axis=-1, stable=True)
    kth = jnp.take_along_axis(score, order[:, top_k - 1:top_k], axis=-1)
    over, at = score > kth, score == kth
    need = top_k - over.sum(-1, keepdims=True)
    return (over | (at & (jnp.cumsum(at, axis=-1) <= need))) & causal, kth


def selected_rows(q_index, k_index, w, top_k, handed=None, first_block=0):
    """q_index (S, Hi, di), k_index (S, di), w (S, Hi) -> ((query blocks,
    QUERY_BLOCK, S) bool: the rows each row attends to (``own_rows``);
    margin (S,)). ``handed`` (S, W) int32: every row attends to the set
    handed for it (``handed_rows``) in place of its own, and ``margin``
    says how far that set lies from its own (the module docstring); 0
    without. ``first_block``: the query blocks before it are left out
    (of the first result) and their margin is 0."""
    s = q_index.shape[0]
    blocks, rows, pad = _query_blocks(s)
    cols = jnp.arange(s)
    qb = jnp.pad(q_index, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, *q_index.shape[1:])
    wb = jnp.pad(w, ((0, pad), (0, 0))).reshape(blocks, QUERY_BLOCK, -1)
    if handed is None:
        given = jnp.zeros((blocks, QUERY_BLOCK, 0), jnp.int32)
    else:
        given = jnp.pad(handed, ((0, pad), (0, 0))).reshape(
            blocks, QUERY_BLOCK, -1)

    def block(args):
        qi, wi, i, words = args
        score = index_scores(qi, k_index, wi)
        causal = cols[None, :] <= i[:, None]
        unit = jnp.sqrt(jnp.where(causal, score * score, 0.0).sum(-1)
                        / causal.sum(-1))
        score = jnp.where(causal, score, -jnp.inf)
        chosen, kth = own_rows(score, causal, top_k)
        if handed is None:
            return chosen, jnp.zeros(QUERY_BLOCK, F32)
        taken = handed_rows(words, s)
        worst = jnp.where(taken, score, jnp.inf).min(-1)
        left = jnp.where(causal & ~taken, score, -jnp.inf).max(-1)
        kth = jnp.where(i < top_k, worst, kth[:, 0])    # every row: no edge
        apart = jnp.maximum(jnp.maximum(kth - worst, left - kth), 0.0) / (
            SET_UNIT * jnp.maximum(unit, 1e-30))
        sound = ~(taken & ~causal).any(-1) & (
            taken.sum(-1) == jnp.minimum(i + 1, top_k))
        return taken, jnp.where(sound, apart, FAR)

    allowed, margin = jax.lax.map(block, tuple(
        a[first_block:] for a in (qb, wb, rows, given)))
    return allowed, jnp.pad(margin.reshape(-1),
                            (first_block * QUERY_BLOCK, 0))[:s]


def _attend(q, k, v, allowed, scale, first_block=0):
    """q, k (S, G, qk), v (S, G, vd) -> (S, G, vd): softmax(q k^T x
    scale) v, QUERY_BLOCK rows at a time, each row over the rows
    ``allowed`` (query blocks, QUERY_BLOCK, S) bool marks for it. (A row
    of padding behind the last attends to row 0 .. its own, and is cut
    off again.) ``first_block``: the query blocks before it are left out
    (``allowed`` holds none of them) and their rows are 0."""
    s, g, qk = q.shape
    blocks, rows, pad = _query_blocks(s)
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, g, qk)

    def block(args):
        qi, seen = args
        scores = jnp.einsum("tgk,sgk->gts", qi, k) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("gts,sgv->tgv", probs, v)

    out = jax.lax.map(block, (qb[first_block:], allowed))
    return jnp.pad(out.reshape(-1, g, v.shape[-1]),
                   ((first_block * QUERY_BLOCK, 0), (0, 0), (0, 0)))[:s]


@partial(jax.jit, static_argnames=("eps", "rope", "mscale", "kv_rank", "nope",
                                   "top_k", "first_block"))
def _attention(x, layer, handed=None, *, eps, rope, mscale, kv_rank, nope,
               top_k, first_block=0):
    """x (S, D) float32 -> (x + attn(N1(x)), margin (S,)) (``handed`` and
    the margin: ``selected_rows``'). ``first_block``: the rows of the
    query blocks before it get no attention added (``_attend``)."""
    with jax.default_matmul_precision("highest"):
        s, d = x.shape
        h = _rms_norm(x, layer["attn_norm"], eps)
        cq = _rms_norm(h @ layer["wdq"].astype(F32), layer["q_norm"], eps)
        ckv = h @ layer["wdkv"].astype(F32)
        c = _rms_norm(ckv[:, :kv_rank], layer["kv_norm"], eps)
        k_rope = _rope(ckv[:, kv_rank:], rope)                   # (S, rope)
        width = k_rope.shape[-1]
        q_index = jnp.einsum("sr,rhd->shd", cq, layer["wq_index"].astype(F32))
        k_index = _layer_norm(h @ layer["wk_index"].astype(F32),
                              layer["k_index_norm"], layer["k_index_bias"],
                              eps)
        hi, di = q_index.shape[1:]
        turn = lambda a: jnp.concatenate(
            [_rope(a[..., :width], rope), a[..., width:]], -1)
        allowed, margin = selected_rows(
            turn(q_index), turn(k_index),
            (h @ layer["w_index"].astype(F32)) / math.sqrt(hi * di),
            top_k, handed, first_block)
        heads = layer["wuq"].shape[1]
        g = min(HEAD_BLOCK, heads)
        scale = mscale * mscale / math.sqrt(nope + width)

        def by_block(w, axis):
            # the heads' axis split into blocks, the blocks leading
            shape = w.shape[:axis] + (heads // g, g) + w.shape[axis + 1:]
            return jnp.moveaxis(w.reshape(shape), axis, 0)

        def heads_block(out, w):
            wuq, wuk, wuv, wo = (a.astype(F32) for a in w)
            q = jnp.einsum("sr,rgk->sgk", cq, wuq)
            q = jnp.concatenate(
                [q[..., :nope], _rope(q[..., nope:], rope)], -1)
            k = jnp.concatenate(
                [jnp.einsum("sc,cgk->sgk", c, wuk),
                 jnp.broadcast_to(k_rope[:, None], (s, g, width))], -1)
            v = jnp.einsum("sc,cgv->sgv", c, wuv)
            attended = _attend(q, k, v, allowed, scale, first_block)
            return out + jnp.einsum("sgv,gvd->sd", attended, wo), None

        out, _ = jax.lax.scan(
            heads_block, jnp.zeros_like(x),
            (by_block(layer["wuq"], 1), by_block(layer["wuk"], 1),
             by_block(layer["wuv"], 1), by_block(layer["wo"], 0)))
        return x + out, margin


def _swiglu(h, w_gate, w_up, w_down):
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


@partial(jax.jit, static_argnames=("eps",))
def _dense_mlp(x, layer, *, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, layer["mlp_norm"], eps)
        return x + _swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"])


def kept_groups(select, groups: int, kept: int, handed=None):
    """select (S, E) the selection scores -> (``select`` with minus
    infinity in the groups a row drops; the groups' scores (S, groups);
    each group's two best experts' places in it (S, groups, 2)): a group
    is scored by the sum of its two largest selection scores and the
    ``kept`` best are kept, of equal groups the lower first. ``handed``
    (S, groups) bool: groups kept whatever their score, the others then
    ranked for the places left (all of the handed ones, should they be
    more than ``kept``)."""
    by_group = select.reshape(select.shape[0], groups, -1)
    two, at = jax.lax.top_k(by_group, 2)
    best = two.sum(-1)
    ranked = best if handed is None else jnp.where(handed, jnp.inf, best)
    _, keep = jax.lax.top_k(ranked, kept)
    is_kept = (keep[:, :, None] == jnp.arange(groups)).any(1)
    if handed is not None:
        is_kept |= handed
    return (jnp.where(is_kept[:, :, None], by_group, -jnp.inf).reshape(
        select.shape), best, at)


def handed_margin(select, slope, choices, groups: int, kept: int):
    """(S,): how far the worst of the experts ``choices`` (S, k) lies
    under this side's own k-th best selection score ``select`` (S, E,
    before any group is masked), in units of the router's logits (the
    gap of the two scores over the root of their squared slopes); 0
    where the two sets are equal. With groups: the module docstring."""
    k = choices.shape[1]
    of_groups = 0.0
    if groups > 1:
        n, size = select.shape[0], select.shape[1] // groups
        masked, best, at = kept_groups(select, groups, kept)
        steep = (jnp.take_along_axis(slope.reshape(n, groups, size), at, -1)
                 ** 2).sum(-1)
        last, last_at = jax.lax.top_k(best, kept)
        last, last_steep = last[:, -1:], jnp.take_along_axis(
            steep, last_at[:, -1:], -1)
        handed = (choices[:, :, None] // size == jnp.arange(groups)).any(1)
        of_groups = jnp.where(handed, (last - best) / jnp.sqrt(
            steep + last_steep), 0.0).max(-1)
        select, _, _ = kept_groups(select, groups, kept, handed)
    ranked, order = jax.lax.top_k(select, k)
    across = jnp.take_along_axis(slope, order[:, k - 1:], -1)
    gap = ranked[:, k - 1:] - jnp.take_along_axis(select, choices, -1)
    mine = jnp.take_along_axis(slope, choices, -1)
    of_experts = (gap / jnp.sqrt(mine ** 2 + across ** 2)).max(-1)
    return jnp.maximum(jnp.maximum(of_experts, of_groups), 0.0)


def route(h, layer, *, top_k, groups, kept):
    """h (S, D) -> (the scores of all experts (S, E) float32, the
    selection scores ``score + bias`` (S, E), the experts each row
    chooses by them under the group limit (S, top_k))."""
    scores = jax.nn.sigmoid(h @ layer["router"].astype(F32))     # (S, all)
    select = scores + layer["router_bias"].astype(F32)
    within = select if groups == 1 else kept_groups(select, groups, kept)[0]
    return scores, select, jax.lax.top_k(within, top_k)[1]


def routed_part(h, layer, handed=None, *, held, top_k, groups, kept,
                norm_topk, scale):
    """h (S, D) -> ((S, D): the sum, over each row's chosen experts
    (``route``) that are among ``held`` (their ids, in the order the
    layer's expert weights are stacked), of its weight for the expert
    times the expert's SwiGLU; margin (S,)). ``handed`` (S, top_k)
    int32: every row takes these experts in place of its own, weighted
    by its own scores of them, and ``margin`` is ``handed_margin``'s; 0
    without."""
    scores, select, chosen = route(h, layer, top_k=top_k, groups=groups,
                                   kept=kept)
    margin = jnp.zeros(h.shape[0], F32)
    if handed is not None:
        margin = handed_margin(select, scores * (1.0 - scores), handed,
                               groups, kept)
        chosen = handed
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk:
        gates = gates / gates.sum(-1, keepdims=True)
    gates = gates * scale
    # each row's weight for every expert, 0 where it did not choose it
    weight = (jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32)
              * gates[..., None]).sum(1)                         # (S, all)

    def expert(out, w):
        gate, up, down, weight_e = w
        return out + weight_e[:, None] * _swiglu(h, gate, up, down), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (layer["w_gate"], layer["w_up"], layer["w_down"],
         weight[:, jnp.asarray(held)].T))
    return out, margin


@partial(jax.jit, static_argnames=("eps", "held", "top_k", "groups", "kept",
                                   "norm_topk", "scale"))
def _expert_mlp(x, layer, handed=None, *, eps, held, top_k, groups, kept,
                norm_topk, scale):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, layer["mlp_norm"], eps)
        out, margin = routed_part(h, layer, handed, held=held, top_k=top_k,
                                  groups=groups, kept=kept,
                                  norm_topk=norm_topk, scale=scale)
        return x + out + _swiglu(h, layer["shared_gate"], layer["shared_up"],
                                 layer["shared_down"]), margin


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ lm_head.astype(F32)


def _same_until(memo: dict, params, tokens, choices) -> int:
    """The first row of the query block from which a pass under
    ``choices`` differs from the whole pass ``memo`` keeps; 0 where it
    keeps none of these weights (known by one leaf of theirs, weakly
    held) and tokens (a whole pass is due)."""
    if (memo.get("weights", lambda: None)() is not params["final_norm"]
            or not np.array_equal(memo["tokens"], tokens)
            or memo["choices"].shape != choices.shape):
        return 0
    differ = np.flatnonzero((memo["choices"] != choices).any((0, 2)))
    row = differ[0] if len(differ) else len(tokens) - 1
    return int(row) // QUERY_BLOCK * QUERY_BLOCK


def logits(params, tokens, hp: dict, last: int = 0, choices=None, memo=None,
           real: int = 0):
    """(S, V) float32 logits of one sequence under the configuration
    ``hp`` (the config.json keys and ``share``: the router's width and
    the experts held); ``last`` > 0 keeps only the last ``last``
    positions (the head is the widest matmul); ``real`` > 0: only the
    first ``real`` of ``tokens`` are the sequence (what stands behind
    them is padding, which no row before it sees) and ``last`` counts
    back from there. ``choices`` (layers + 1, S, W) int32: the pass
    under choices handed in, which returns (logits, margin (layers +
    routed layers, S)): the module docstring, which says of ``memo`` (a
    dict of the caller's, for passes under ``choices``) too."""
    eps = float(hp["rms_norm_eps"])
    rope, mscale = rope_of(hp)
    attention = partial(
        _attention, eps=eps, rope=rope, mscale=mscale,
        kv_rank=int(hp["kv_lora_rank"]), nope=int(hp["qk_nope_head_dim"]),
        top_k=int(hp["index_topk"]))
    n_layers, n_dense = int(hp["num_hidden_layers"]), int(
        hp["first_k_dense_replace"])
    top_k = int(hp["num_experts_per_tok"])
    expert_mlp = partial(
        _expert_mlp, eps=eps, held=tuple(hp["share"]["held_experts"]),
        top_k=top_k, groups=int(hp["n_group"]), kept=int(hp["topk_group"]),
        norm_topk=bool(hp["norm_topk_prob"]),
        scale=float(hp["routed_scaling_factor"]))
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    of_rows, of_experts = [], []
    start = _same_until(memo, params, tokens, choices) if memo else 0
    inputs = []         # the rows at every layer's input, and the last's output

    def from_start(x):
        return x[start:] if start else x

    def behind(x, mine):
        """``mine``, the rows from ``start`` on, behind ``x``'s before."""
        return jnp.concatenate([x[:start], mine]) if start else mine

    def kept(x, n):
        """``x`` with the rows before ``start`` as the whole pass had
        them at layer ``n``'s input; of a whole pass ``x``, noted where
        there is a ``memo`` to keep it."""
        if start:
            return behind(memo["inputs"][n], x[start:])
        if memo is not None:
            inputs.append(x)
        return x

    for i in range(n_layers):
        x = kept(x, i)
        layer = layer_of(params, hp, i)
        handed = None if choices is None else jnp.asarray(choices[i])
        x, margin = attention(x, layer, handed,
                              first_block=start // QUERY_BLOCK)
        of_rows.append(margin)
        # an MLP is a row's own: of a pass from ``start`` on, those rows'
        if i < n_dense:
            x = behind(x, _dense_mlp(from_start(x), layer, eps=eps))
            continue
        at = (i - n_dense) * top_k
        mine, margin = expert_mlp(
            from_start(x), layer,
            None if choices is None else jnp.asarray(
                from_start(choices[-1])[:, at:at + top_k]) & 0xFFFF)
        x = behind(x, mine)
        of_experts.append(jnp.pad(margin, (start, 0)))
    x = kept(x, n_layers)
    if real:
        x = x[:real]
    if last:
        x = x[-last:]
    out = _head(x, params["final_norm"], params["lm_head"], eps=eps)
    if choices is None:
        return out
    margin = jnp.stack(of_rows + of_experts)
    if start:
        margin = jnp.concatenate([memo["margin"][:, :start],
                                  margin[:, start:]], axis=1)
    elif memo is not None:
        # (the weights by a weak reference to one leaf: a memo that held
        # them would keep a seed's 6 GB alive beside the next seed's)
        memo.update(weights=weakref.ref(params["final_norm"]),
                    tokens=np.array(tokens),
                    choices=np.array(choices), inputs=inputs, margin=margin)
    return out, margin


def layer_of(params, hp: dict, i: int):
    """Layer ``i``'s weights: a dense leading layer's or a routed one's."""
    n_dense = int(hp["first_k_dense_replace"])
    kind, at = ("dense", i) if i < n_dense else ("routed", i - n_dense)
    return {k: v[at] for k, v in params[kind].items()}
