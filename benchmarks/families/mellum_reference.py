"""The ``mellum`` family's plain reference: one sequence through the
decoder in float32 ``jax.numpy`` at ``default_matmul_precision("highest")``,
with no kernel, no cache, no ring and no sorting. It imports nothing of
``ray_tpu``: it shares with the system only the layout of the parameter
tree (``embed``; ``blocks`` with a leading layer axis: ``attn_norm``,
``wq``/``wk``/``wv`` (D, heads, hd), ``wo`` (H, hd, D), ``mlp_norm``,
``router`` (D, E), ``w_gate``/``w_up`` (E, D, F), ``w_down`` (E, F, D);
``final_norm``; ``lm_head``).

Equations (config.json of Mellum2-12B-A2.5B-Instruct, ``model_type``
``mellum``). Every layer is pre-norm (RMSNorm) attention and a routed
feed-forward, each with a residual.

- Attention: grouped queries, rotary on halves, softmax(QK^T / sqrt(hd)),
  causal. A ``sliding_attention`` layer lets row i attend to rows j with
  0 <= i - j < ``sliding_window`` and turns with the default table,
  ``theta^(-2i/d)``. A ``full_attention`` layer attends to every earlier
  row and turns with the YaRN table: with d the head size, the dimension
  at which a rotation completes r times over the original context is
  c(r) = d ln(original_max / (2 pi r)) / (2 ln theta); low = floor(
  c(beta_fast)), high = ceil(c(beta_slow)); ramp_i = clip((i - low) /
  (high - low), 0, 1) over the d/2 frequencies; the inverse frequency is
  theta^(-2i/d) (1 - ramp_i) + theta^(-2i/d) / factor * ramp_i; cos and
  sin are multiplied by ``attention_factor`` (0.1 ln factor + 1 where the
  config gives none).
- Feed-forward: scores = softmax(h W_r) over all experts, the
  ``num_experts_per_tok`` largest, renormalised to sum to 1 where
  ``norm_topk_prob``; out = sum over the chosen e of
  w_e (silu(h W_gate_e) * (h W_up_e)) W_down_e. Nothing is dropped.

Departures from the naive form, each so that a pass of some 15 000 rows
fits beside the served weights and caches on one chip; none changes a
number that is computed:

- one layer is one jitted call, so the float32 copy of one layer's
  attention weights is alive at a time;
- attention runs ``QUERY_BLOCK`` query rows at a time against all keys
  (the logits of 256 rows x 32 heads x 15 360 keys are 0.5 GB);
- the experts are looped over, each cast to float32 in its turn and
  applied to every row, weighted by the row's weight for it, which is 0
  where the row did not choose it: the sum over the chosen experts,
  computed as a sum over all of them with zeros.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def inv_freq_and_scale(rope: tuple, hd: int):
    """(inverse frequencies (hd/2,), the factor on cos and sin) of one
    ``rope_parameters`` entry, given as ``rope_of``'s tuple."""
    kind, theta, factor, original, beta_fast, beta_slow, attention = rope
    plain = theta ** -(jnp.arange(0, hd, 2, dtype=F32) / hd)
    if kind == "default":
        return plain, 1.0

    def correction_dim(rotations):
        return (hd * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    if attention is None:
        attention = 0.1 * math.log(factor) + 1.0
    return plain * (1.0 - ramp) + plain / factor * ramp, attention


def rope_of(entry: dict) -> tuple:
    """One entry of the config's ``rope_parameters`` as a hashable
    tuple: (rope_type, theta, then YaRN's five numbers or None)."""
    if entry["rope_type"] == "default":
        return ("default", float(entry["rope_theta"])) + (None,) * 5
    if entry["rope_type"] != "yarn":
        raise ValueError(f"rope_type {entry['rope_type']!r}")
    return ("yarn", float(entry["rope_theta"]), float(entry["factor"]),
            float(entry["original_max_position_embeddings"]),
            float(entry["beta_fast"]), float(entry["beta_slow"]),
            entry.get("attention_factor"))


def _rope(x, rope):
    # x (S, H, hd); positions 0..S-1
    s, _, hd = x.shape
    inv, scale = inv_freq_and_scale(rope, hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]      # (S, hd/2)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window):
    """q (S, H, hd), k/v (S, KVH, hd) -> (S, H, hd); ``window`` None: a
    full layer. QUERY_BLOCK query rows at a time against all keys."""
    s, n_heads, hd = q.shape
    n_kv = k.shape[1]
    blocks = -(-s // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - s
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, n_kv, n_heads // n_kv, hd)
    rows = jnp.arange(blocks * QUERY_BLOCK).reshape(blocks, QUERY_BLOCK)
    cols = jnp.arange(s)

    def block(args):
        qi, i = args                               # (B, KVH, G, hd), (B,)
        behind = i[:, None] - cols[None, :]        # i - j
        seen = behind >= 0
        if window is not None:
            seen &= behind < window
        logits = jnp.einsum("skgh,tkh->kgst", qi, k) / math.sqrt(hd)
        logits = jnp.where(seen[None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("kgst,tkh->skgh", probs, v)

    out = jax.lax.map(block, (qb, rows))
    return out.reshape(blocks * QUERY_BLOCK, n_heads, hd)[:s]


def _routed(h, router, w_gate, w_up, w_down, top_k, norm_topk):
    """h (S, D) -> (S, D): the sum over each row's chosen experts."""
    probs = jax.nn.softmax(h @ router.astype(F32), axis=-1)     # (S, E)
    gates, chosen = jax.lax.top_k(probs, top_k)
    if norm_topk:
        gates = gates / gates.sum(-1, keepdims=True)
    n_experts = probs.shape[-1]
    # each row's weight for every expert, 0 where it did not choose it
    weight = (jax.nn.one_hot(chosen, n_experts, dtype=F32)
              * gates[..., None]).sum(1)                        # (S, E)

    def expert(out, w):
        gate, up, down, weight_e = w
        y = (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
            @ down.astype(F32)
        return out + weight_e[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (w_gate, w_up, w_down, weight.T))
    return out


@partial(jax.jit, static_argnames=("rope", "window", "eps", "top_k",
                                   "norm_topk"))
def _layer(x, layer, *, rope, window, eps, top_k, norm_topk):
    """x (S, D) float32; layer: this layer's weights in any dtype."""
    with jax.default_matmul_precision("highest"):
        w = {k: layer[k].astype(F32)
             for k in ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm")}
        h = _rms_norm(x, w["attn_norm"], eps)
        q = _rope(jnp.einsum("sd,dhk->shk", h, w["wq"]), rope)
        k = _rope(jnp.einsum("sd,dhk->shk", h, w["wk"]), rope)
        v = jnp.einsum("sd,dhk->shk", h, w["wv"])
        x = x + jnp.einsum("shk,hkd->sd", _attention(q, k, v, window),
                           w["wo"])
        h = _rms_norm(x, w["mlp_norm"], eps)
        return x + _routed(h, layer["router"], layer["w_gate"],
                           layer["w_up"], layer["w_down"], top_k, norm_topk)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ lm_head.astype(F32)


def logits(params, tokens, hp: dict, last: int = 0):
    """(S, V) float32 logits of one sequence under the configuration
    ``hp`` (the config.json keys); ``last`` > 0 keeps only the last
    ``last`` positions (the head is the widest matmul)."""
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    ropes = {kind: rope_of(entry)
             for kind, entry in hp["rope_parameters"].items()}
    for i, kind in enumerate(hp["layer_types"][:hp["num_hidden_layers"]]):
        layer = {k: v[i] for k, v in params["blocks"].items()}
        x = _layer(
            x, layer, rope=ropes[kind], eps=float(hp["rms_norm_eps"]),
            window=(int(hp["sliding_window"])
                    if kind == "sliding_attention" else None),
            top_k=int(hp["num_experts_per_tok"]),
            norm_topk=bool(hp["norm_topk_prob"]))
    if last:
        x = x[-last:]
    return _head(x, params["final_norm"], params["lm_head"],
                 eps=float(hp["rms_norm_eps"]))
