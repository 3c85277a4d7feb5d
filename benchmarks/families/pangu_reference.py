"""The ``pangu`` family's plain reference: one sequence through the
decoder in float32 ``jax.numpy`` at ``default_matmul_precision("highest")``,
with no kernel, no cache, no absorbed form, no sorting and no running
softmax. It imports nothing of ``ray_tpu``: it shares with the system
only the layout of the parameter tree (``embed``; ``dense`` and
``routed``, each kind's layers stacked on a leading axis: the six gains
``attn_norm`` / ``attn_post_norm`` / ``mlp_norm`` / ``mlp_post_norm``
(D), ``q_norm`` (q_lora_rank), ``kv_norm`` (kv_lora_rank); ``wdq`` (D,
q_lora_rank), ``wuq`` (q_lora_rank, H, nope + rope), ``wdkv`` (D,
kv_lora_rank + rope), ``wuk`` (kv_lora_rank, H, nope), ``wuv``
(kv_lora_rank, H, v), ``wo`` (H, v, D); a dense layer's ``w_gate`` /
``w_up`` (D, F), ``w_down`` (F, D); a routed layer's ``router`` (D, all
experts), ``w_gate`` / ``w_up`` (held, D, Fe), ``w_down`` (held, Fe, D)
and ``shared_gate`` / ``shared_up`` / ``shared_down``; ``final_norm``;
``lm_head``).

Equations (config.json of openPangu-Ultra-MoE-718B, ``model_type``
``pangu_ultra_moe``; what the config leaves open stands under
``assumed`` in ``configs/openpangu-ultra-moe-718b.json``). ``N`` is an
RMS norm with its own gain.

- Sandwich norm: ``x = x + N2(attn(N1(x)))``, then ``x = x + N4(mlp(
  N3(x)))``; the final norm, then the untied head.
- Latent attention: ``cq = Nq(h Wdq)``; a head's ``[q_nope | q_rope] =
  cq Wuq``; ``[ckv | k_rope] = h Wdkv``; ``c = Nkv(ckv)``; ``k_rope``
  (one vector for all heads) and each head's ``q_rope`` are turned by
  the rotary table at the row's position (halves paired, ``theta^(-2i /
  rope)``); a head's ``k_nope = c Wuk``, ``v = c Wuv``; the score of
  row t on row s is ``(q_nope_t . k_nope_s + q_rope_t . k_rope_s) /
  sqrt(nope + rope)``, causal, softmax; the heads' results go through
  ``Wo``. Only this, the expanded form, is written here.
- ``mlp`` of the ``first_k_dense_replace`` leading layers: ``(silu(h
  W_gate) * (h W_up)) W_down``.
- ``mlp`` of the others: ``s = sigmoid(h W_r)`` over all experts, the
  ``num_experts_per_tok`` largest, weights ``s_i / sum(chosen s) x
  routed_scaling_factor``; the routed part is the sum over the chosen
  experts THAT ARE HELD (``share.held_experts``: the chip's share of its
  deployment) of ``w_i SwiGLU_i(h)``; the shared expert's SwiGLU is
  added once. What the absent experts would add is left out, as the
  program leaves it out.

Departures from the naive form, each so that a pass of some 13 000 rows
fits beside the served weights and cache on one chip; none changes a
number that is computed:

- one sublayer is one jitted call, so the float32 copies of one
  sublayer's weights are alive at a time;
- attention runs ``HEAD_BLOCK`` heads at a time (a block's queries,
  keys and values are made, attended and put through their rows of
  ``Wo``, and the blocks' results summed), and within a block
  ``QUERY_BLOCK`` query rows at a time against all keys (the scores of
  256 rows x 16 heads x 13 056 keys are 0.2 GB);
- a SwiGLU runs ``WIDTH_BLOCK`` of its hidden width at a time (the sum
  over the width of the down-projection, taken block by block);
- the held experts are looped over, each applied to every row and
  weighted by the row's weight for it, which is 0 where the row did not
  choose it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 16
QUERY_BLOCK = 256
WIDTH_BLOCK = 2048


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, theta):
    """x (S, ..., rope) at positions 0..S-1, halves paired."""
    s, rope = x.shape[0], x.shape[-1]
    inv = theta ** -(jnp.arange(0, rope, 2, dtype=F32) / rope)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]      # (S, rope/2)
    ang = ang.reshape(s, *[1] * (x.ndim - 2), rope // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : rope // 2], x[..., rope // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _causal(q, k, v):
    """q, k (S, G, qk), v (S, G, vd) -> (S, G, vd): softmax(q k^T /
    sqrt(qk)) v under the causal mask, QUERY_BLOCK rows at a time."""
    s, g, qk = q.shape
    blocks = -(-s // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - s
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, g, qk)
    rows = jnp.arange(blocks * QUERY_BLOCK).reshape(blocks, QUERY_BLOCK)
    cols = jnp.arange(s)

    def block(args):
        qi, i = args
        scores = jnp.einsum("tgk,sgk->gts", qi, k) / math.sqrt(qk)
        seen = cols[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("gts,sgv->tgv", probs, v)

    out = jax.lax.map(block, (qb, rows))
    return out.reshape(blocks * QUERY_BLOCK, g, v.shape[-1])[:s]


@partial(jax.jit, static_argnames=("eps", "theta", "kv_rank", "nope"))
def _attention(x, layer, *, eps, theta, kv_rank, nope):
    """x (S, D) float32 -> x + N2(attn(N1(x)))."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, layer["attn_norm"], eps)
        cq = _rms_norm(h @ layer["wdq"].astype(F32), layer["q_norm"], eps)
        ckv = h @ layer["wdkv"].astype(F32)
        c = _rms_norm(ckv[:, :kv_rank], layer["kv_norm"], eps)
        k_rope = _rope(ckv[:, kv_rank:], theta)                  # (S, rope)
        heads = layer["wuq"].shape[1]
        g = min(HEAD_BLOCK, heads)

        def by_block(w, axis):
            # the heads' axis split into blocks, the blocks leading
            shape = w.shape[:axis] + (heads // g, g) + w.shape[axis + 1:]
            return jnp.moveaxis(w.reshape(shape), axis, 0)

        def heads_block(out, w):
            wuq, wuk, wuv, wo = (a.astype(F32) for a in w)
            q = jnp.einsum("sr,rgk->sgk", cq, wuq)
            q = jnp.concatenate(
                [q[..., :nope], _rope(q[..., nope:], theta)], -1)
            k = jnp.concatenate(
                [jnp.einsum("sc,cgk->sgk", c, wuk),
                 jnp.broadcast_to(k_rope[:, None], (x.shape[0], g,
                                                    k_rope.shape[-1]))], -1)
            v = jnp.einsum("sc,cgv->sgv", c, wuv)
            return out + jnp.einsum("sgv,gvd->sd", _causal(q, k, v), wo), None

        out, _ = jax.lax.scan(
            heads_block, jnp.zeros_like(x),
            (by_block(layer["wuq"], 1), by_block(layer["wuk"], 1),
             by_block(layer["wuv"], 1), by_block(layer["wo"], 0)))
        return x + _rms_norm(out, layer["attn_post_norm"], eps)


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
        out = _swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"])
        return x + _rms_norm(out, layer["mlp_post_norm"], eps)


def routed_part(h, layer, *, held, top_k, norm_topk, scale):
    """h (S, D) -> (S, D): the sum, over each row's chosen experts that
    are among ``held`` (their ids, in the order the weights are
    stacked), of its weight for the expert times the expert's SwiGLU."""
    scores = jax.nn.sigmoid(h @ layer["router"].astype(F32))     # (S, all)
    gates, chosen = jax.lax.top_k(scores, top_k)
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
    return out


@partial(jax.jit, static_argnames=("eps", "held", "top_k", "norm_topk",
                                   "scale"))
def _expert_mlp(x, layer, *, eps, held, top_k, norm_topk, scale):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, layer["mlp_norm"], eps)
        out = routed_part(h, layer, held=held, top_k=top_k,
                          norm_topk=norm_topk, scale=scale)
        if "shared_gate" in layer:
            out = out + _swiglu(h, layer["shared_gate"], layer["shared_up"],
                                layer["shared_down"])
        return x + _rms_norm(out, layer["mlp_post_norm"], eps)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ lm_head.astype(F32)


def logits(params, tokens, hp: dict, last: int = 0):
    """(S, V) float32 logits of one sequence under the configuration
    ``hp`` (the config.json keys and ``share``: the router's width and
    the experts held); ``last`` > 0 keeps only the last ``last``
    positions (the head is the widest matmul)."""
    eps = float(hp["rms_norm_eps"])
    attention = partial(
        _attention, eps=eps, theta=float(hp["rope_theta"]),
        kv_rank=int(hp["kv_lora_rank"]), nope=int(hp["qk_nope_head_dim"]))
    n_dense = int(hp["first_k_dense_replace"])
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    for i in range(n_dense):
        layer = {k: v[i] for k, v in params["dense"].items()}
        x = _dense_mlp(attention(x, layer), layer, eps=eps)
    for i in range(int(hp["num_hidden_layers"]) - n_dense):
        layer = {k: v[i] for k, v in params["routed"].items()}
        x = _expert_mlp(
            attention(x, layer), layer, eps=eps,
            held=tuple(hp["share"]["held_experts"]),
            top_k=int(hp["num_experts_per_tok"]),
            norm_topk=bool(hp["norm_topk_prob"]),
            scale=float(hp["routed_scaling_factor"]))
    if last:
        x = x[-last:]
    return _head(x, params["final_norm"], params["lm_head"], eps=eps)
