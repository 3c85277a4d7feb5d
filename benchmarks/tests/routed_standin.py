"""A routed family's arithmetic without an engine: a short stack of
pre-norm layers, each a causal latent attention (queries and keys /
values through a normed low-rank bottleneck, so that its logits are as
flat at these seeded weights as such a model's are) and then a top-k
mixture of SwiGLU experts of which this chip holds a few (sigmoid or
softmax scores, a correction bias in the selection, the chosen weights
renormalised and scaled, a shared expert), and a head. ``forward`` is
one function of seeded bf16 weights, evaluated in float32 at ``highest``
(what a family's reference does), in bf16 with float32 accumulation
(what its engine would do), and in bf16 with the shared expert's matmul
operands in fp8 (the control). It returns the logits, each layer's
choices, and the margin by which an expert held here was chosen or
passed over, least over the held experts and the layers, in units of
the router's logits: what a rule that spares near-tied positions would
have to mark them by (README.md, "A served family"; PERF.md section 6,
PR 34, has what the chip read and why no such rule is in the harness).
``readings`` lays the sides beside each other position by position.

Imports nothing of ``ray_tpu``, of ``benchmarks`` nor of any family.
``serving_control.py standin`` reads it on the chip at ``CHIP``'s widths;
``test_serving_reference.py`` keeps it at ``TOY``'s.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32, BF16 = jnp.float32, jnp.bfloat16

# a routed decoder's widths as one of 32 chips that share each layer
# holds them: 12 of 384 experts, an eighth of the vocabulary's rows
CHIP = dict(width=7168, experts=384, expert_width=2048, top_k=8, held=12,
            layers=6, vocab=20480, heads=64, head_dim=128, q_rank=1536,
            kv_rank=512, seq=2048, seqs=8, score="sigmoid", scale=2.827,
            std=0.02)
# a test's size: the weights wider, so that a sublayer still adds about
# what the residual stream carries
TOY = dict(width=128, experts=32, expert_width=64, top_k=4, held=4, layers=3,
           vocab=128, heads=2, head_dim=16, q_rank=64, kv_rank=32, seq=64,
           seqs=8, score="sigmoid", scale=2.0, std=0.09)


def fp8(a):
    """Rounded to 4 exponent and 3 mantissa bits under a per-tensor
    scale."""
    f = a.astype(F32)
    scale = jnp.max(jnp.abs(f)) / 240.0
    return (jax.lax.reduce_precision(f / scale, exponent_bits=4,
                                     mantissa_bits=3) * scale).astype(a.dtype)


def _mm(a, b, side):
    if side == "f32":
        return jnp.matmul(a, b.astype(F32), precision="highest")
    return jnp.matmul(a.astype(BF16), b,
                      preferred_element_type=F32).astype(BF16)


def _norm(x):
    f = x.astype(F32)
    return (f * jax.lax.rsqrt(jnp.mean(f * f, -1, keepdims=True) + 1e-6)
            ).astype(x.dtype)


def _swiglu(h, w, side, lower=lambda a: a):
    """``w`` (3, d, f): gate, up and the down projection transposed;
    ``lower`` rounds every matmul operand."""
    gate, up = (_mm(lower(h), lower(w[i]), side) for i in (0, 1))
    return _mm(lower(jax.nn.silu(gate) * up), lower(w[2].T), side)


@partial(jax.jit, static_argnames=("sizes",))
def layer_weights(key, *, sizes):
    """One layer's weights, bf16 N(0, std); the router's correction
    bias float32 N(0, 0.01)."""
    s = dict(sizes)
    d, f, hd = s["width"], s["expert_width"], s["heads"] * s["head_dim"]
    shapes = {"wq_a": (d, s["q_rank"]), "wq_b": (s["q_rank"], hd),
              "wkv_a": (d, s["kv_rank"]), "wkv_b": (s["kv_rank"], 2 * hd),
              "wo": (hd, d),
              "router": (d, s["experts"]), "shared": (3, d, f),
              "held": (s["held"], 3, d, f)}
    keys = jax.random.split(key, len(shapes) + 1)
    w = {name: s["std"] * jax.random.normal(k, shape, BF16)
         for k, (name, shape) in zip(keys, shapes.items())}
    w["bias"] = 0.01 * jax.random.normal(keys[-1], (s["experts"],), F32)
    return w


def _route(h, w, s, force):
    """The router in float32 on every side, as such models run it:
    (chosen (S, k), the held experts' weights (S, held), margin (S,)).
    ``force`` (S,) moves the best held expert that was passed over into
    the choice: a flip made on purpose."""
    k, held = s["top_k"], s["held"]
    z = jnp.matmul(h.astype(F32), w["router"].astype(F32), precision="highest")
    scores = jax.nn.sigmoid(z) if s["score"] == "sigmoid" \
        else jax.nn.softmax(z, -1)
    select = scores + w["bias"]
    if force is not None:
        last_in = jax.lax.top_k(select, k)[0][:, -1:]
        passed = jnp.where(select[:, :held] >= last_in, -jnp.inf,
                           select[:, :held])
        select = select + force[:, None] * 10.0 * jax.nn.one_hot(
            passed.argmax(-1), s["experts"])
    ranked, order = jax.lax.top_k(select, k + 1)
    chosen = order[:, :k]
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = s["scale"] * picked / picked.sum(-1, keepdims=True)
    of_held = jnp.where(chosen[:, :, None] == jnp.arange(held), weights[
        :, :, None], 0.0).sum(1)
    # a held expert in the choice: over the best passed over; one
    # passed over: under the last chosen. In units of the router's
    # logits: the gap of the two selection scores over their slopes (a
    # saturated score moves little for the same noise in its logit)
    slope = scores * (1.0 - scores)
    edge = jnp.take_along_axis(slope, order[:, k - 1:], -1)   # last in, out
    mine, inside = select[:, :held], select[:, :held] >= ranked[:, k - 1:k]
    gap = jnp.where(inside, mine - ranked[:, k:], ranked[:, k - 1:k] - mine)
    across = jnp.where(inside, edge[:, 1:], edge[:, :1])
    margin = (gap / jnp.sqrt(slope[:, :held] ** 2 + across ** 2)).min(-1)
    return chosen, of_held, margin


def _layer(x, w, s, side, force):
    """One sequence x (S, D) through attention and the mixture."""
    n, heads, hd = x.shape[0], s["heads"], s["head_dim"]
    h = _norm(x)
    q = _mm(_norm(_mm(h, w["wq_a"], side)), w["wq_b"], side).reshape(
        n, heads, hd)
    k, v = jnp.split(_mm(_norm(_mm(h, w["wkv_a"], side)), w["wkv_b"], side
                         ).reshape(n, heads, 2 * hd), 2, -1)
    if side == "f32":
        att = jnp.einsum("shk,thk->hst", q, k, precision="highest")
    else:
        att = jnp.einsum("shk,thk->hst", q, k, preferred_element_type=F32)
    att = jnp.where(jnp.tril(jnp.ones((n, n), bool)), att / math.sqrt(hd),
                    -jnp.inf)
    probs = jax.nn.softmax(att, -1).astype(q.dtype)
    mixed = jnp.einsum("hst,thk->shk", probs, v, precision=(
        "highest" if side == "f32" else None),
        preferred_element_type=F32).astype(q.dtype)
    x = x + _mm(mixed.reshape(n, heads * hd), w["wo"], side)
    h = _norm(x)
    chosen, of_held, margin = _route(h, w, s, force)
    out = _swiglu(h, w["shared"], side, fp8 if side == "fp8" else lambda a: a)

    def add(total, expert):
        weights, gate_of = expert
        return total + gate_of[:, None].astype(total.dtype) * _swiglu(
            h, weights, side), None

    out = jax.lax.scan(add, out, (w["held"], of_held.T))[0]
    return x + out, chosen, margin


@partial(jax.jit, static_argnames=("sizes", "side", "forced"))
def _layer_of_all(x, w, force, *, sizes, side, forced):
    s = dict(sizes)
    return jax.lax.map(
        lambda row: _layer(row[0], w, s, side, row[1] if forced else None),
        (x, force))


@partial(jax.jit, static_argnames=("side",))
def _head(x, head, *, side):
    out = _mm(_norm(x), head, side)
    return out.astype(F32)


def forward(seed: int, sizes: dict, side: str, force=None) -> dict:
    """The stack on ``sizes['seqs']`` seeded sequences: logits (B, S, V)
    float32, ``chosen`` (L, B, S, k), and ``margin`` (B, S), the least
    over the layers. ``force`` (B, S)
    flips a choice in the first layer at the positions it marks. The
    weights are made from the seed layer by layer, the same on every
    side, one layer's alive at a time."""
    s = sizes
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    x = jax.random.normal(jax.random.fold_in(key, 10**6),
                          (s["seqs"], s["seq"], s["width"]), BF16)
    x = x.astype(F32 if side == "f32" else BF16)
    none = jnp.zeros((s["seqs"], s["seq"]), F32)
    chosen, margin = [], jnp.full((s["seqs"], s["seq"]), jnp.inf, F32)
    frozen = tuple(sorted(s.items()))
    for i in range(s["layers"]):
        w = layer_weights(jax.random.fold_in(key, i), sizes=frozen)
        forced = force is not None and i == 0
        x, picked, m = _layer_of_all(
            x, w, jnp.asarray(force, F32) if forced else none, sizes=frozen,
            side=side, forced=forced)
        chosen.append(picked)
        margin = jnp.minimum(margin, m)
    head = s["std"] * jax.random.normal(jax.random.fold_in(key, 10**6 + 1),
                                    (s["width"], s["vocab"]), BF16)
    return {"logits": _head(x, head, side=side), "chosen": jnp.stack(chosen),
            "margin": margin}


@jax.jit
def rel_rms(got, ref):
    """Of each row, as ``server.reference_readings`` takes it."""
    return jnp.sqrt(jnp.mean((got - ref) ** 2, -1)
                    / jnp.mean(ref ** 2, -1))


@jax.jit
def choice_gap(got, ref):
    """Of each row, as ``server.served_readings`` takes it: the
    reference's best logit minus its logit of the token ``got`` puts
    first."""
    own = jnp.take_along_axis(ref, got.argmax(-1)[..., None], -1)[..., 0]
    return ref.max(-1) - own


def flipped(a, b, held: int):
    """(B, S): in some layer a held expert is in one side's choice and
    not in the other's."""
    def has(chosen):
        return (chosen[..., None] == jnp.arange(held)).any(-2)
    return np.asarray((has(a) != has(b)).any((0, -1)))


def readings(seed: int, sizes: dict) -> dict:
    """Position by position (B, S): the bf16 side's relative RMS and
    choice gap against the float32 side, whether a choice that involves
    a held expert flipped between them, the float32 side's margin, and
    the fp8 control's relative RMS and choice gap; ``logit_rms``, the
    float32 logits' own size, for a gap to be read against."""
    ref = forward(seed, sizes, "f32")
    out = {"margin": np.asarray(ref["margin"]),
           "logit_rms": float(jnp.sqrt(jnp.mean(ref["logits"] ** 2)))}
    for side in ("bf16", "fp8"):
        got = forward(seed, sizes, side)
        out[f"{side}_rel_rms"] = np.asarray(rel_rms(got["logits"],
                                                    ref["logits"]))
        out[f"{side}_choice_gap"] = np.asarray(choice_gap(got["logits"],
                                                          ref["logits"]))
        if side == "bf16":
            out["flipped"] = flipped(ref["chosen"], got["chosen"],
                                     sizes["held"])
    return out


def reach(seed: int, sizes: dict, at: int) -> dict:
    """A flip made on purpose at position ``at`` of every sequence, in
    the first layer of the float32 side: the relative RMS it moves that
    row by, and the rows before and behind it (through attention); of
    the rows behind, the sound side's margin and whether a choice of
    their own flipped with their moved input."""
    force = np.zeros((sizes["seqs"], sizes["seq"]), np.float32)
    force[:, at] = 1.0
    sound = forward(seed, sizes, "f32")
    moved = forward(seed, sizes, "f32", force=force)
    by = np.asarray(rel_rms(moved["logits"], sound["logits"]))
    return {"at": by[:, at], "before": by[:, :at], "behind": by[:, at + 1:],
            "behind_margin": np.asarray(sound["margin"])[:, at + 1:],
            "behind_flipped": flipped(sound["chosen"], moved["chosen"],
                                      sizes["held"])[:, at + 1:]}
