"""The plain reference: this decoder's forward pass and loss in float32.

Straightforward ``jax.numpy`` with no kernel, no cache, no scan and no
batching tricks, at ``default_matmul_precision("highest")`` (on a TPU a
float32 matmul otherwise runs in bf16 passes). It imports nothing from
``ray_tpu``: it shares with the system only the *layout* of the
parameter tree (``embed``, ``blocks`` with a leading layer axis,
``final_norm``, ``lm_head``), which is the interface it is handed.

Equations (InternLM2 / Mistral / Llama decoder): pre-norm RMSNorm,
grouped-query causal attention with rotary embeddings on halves
(x1, x2 = split(x); rotate-half convention), SwiGLU feed-forward,
untied output head.

One layer is one jitted call, so one compile serves every depth and the
float32 copy of only one layer's weights is alive at a time.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, theta):
    # x (S, H, hd); positions 0..S-1
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("theta", "eps"))
def _layer(x, layer, *, theta, eps):
    """x (S, D) float32; layer: this layer's weights in any dtype."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in layer.items()}
        s = x.shape[0]
        n_heads, hd = w["wq"].shape[1], w["wq"].shape[2]
        n_kv = w["wk"].shape[1]
        h = _rms_norm(x, w["attn_norm"], eps)
        q = _rope(jnp.einsum("sd,dhk->shk", h, w["wq"]), theta)
        k = _rope(jnp.einsum("sd,dhk->shk", h, w["wk"]), theta)
        v = jnp.einsum("sd,dhk->shk", h, w["wv"])
        causal = jnp.tril(jnp.ones((s, s), bool))
        group = n_heads // n_kv
        outs = []
        for g in range(n_kv):  # one KV head at a time: (group, S, S) logits
            qg = q[:, g * group:(g + 1) * group]  # (S, group, hd)
            logits = jnp.einsum("sgk,tk->gst", qg, k[:, g]) / math.sqrt(hd)
            logits = jnp.where(causal[None], logits, -jnp.inf)
            probs = jax.nn.softmax(logits, axis=-1)
            outs.append(jnp.einsum("gst,tk->sgk", probs, v[:, g]))
        attn = jnp.concatenate(outs, axis=1)  # (S, H, hd)
        x = x + jnp.einsum("shk,hkd->sd", attn, w["wo"])
        h = _rms_norm(x, w["mlp_norm"], eps)
        gate = h @ w["w_gate"]
        up = h @ w["w_up"]
        return x + (jax.nn.silu(gate) * up) @ w["w_down"]


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ lm_head.astype(F32)


def hidden_states(params, tokens, *, theta: float, eps: float):
    """tokens (S,) int -> (S, D) float32 before the final norm."""
    x = params["embed"][tokens].astype(F32)
    n_layers = params["blocks"]["wq"].shape[0]
    for i in range(n_layers):
        layer = {k: v[i] for k, v in params["blocks"].items()}
        x = _layer(x, layer, theta=float(theta), eps=float(eps))
    return x


def logits(params, tokens, *, theta: float, eps: float, last: int = 0):
    """(S, V) float32 logits of one sequence; ``last`` > 0 keeps only
    the last ``last`` positions (the head is the widest matmul)."""
    x = hidden_states(params, tokens, theta=theta, eps=eps)
    if last:
        x = x[-last:]
    return _head(x, params["final_norm"], params["lm_head"], eps=float(eps))


def loss(params, tokens, *, theta: float, eps: float, rows: int = 1024):
    """Mean next-token cross entropy of one sequence tokens (S+1,),
    the head taken ``rows`` positions at a time."""
    x = hidden_states(params, tokens[:-1], theta=theta, eps=eps)
    targets = tokens[1:]
    total = jnp.zeros((), F32)
    for i in range(0, x.shape[0], rows):
        lg = _head(x[i:i + rows], params["final_norm"], params["lm_head"],
                   eps=float(eps))
        logp = jax.nn.log_softmax(lg, axis=-1)
        total += -jnp.take_along_axis(
            logp, targets[i:i + rows, None], axis=-1
        ).sum()
    return total / x.shape[0]
