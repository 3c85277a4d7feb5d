"""The ``jamba`` family's plain reference: one sequence through the
decoder in float32 ``jax.numpy`` at ``default_matmul_precision("highest")``,
with no kernel, no cache, no chunk and no carried state: the recurrence
is a ``lax.scan`` over the rows of the whole sequence from a zero
state. It imports nothing of ``ray_tpu``: it shares with the system only
the layout of the parameter tree (``embed`` (V, D), which is the head
too; ``mamba``, ``attn`` and ``mlp``, each kind's layers stacked on a
leading axis: a state layer's ``norm`` (D), ``w_in`` (D, 2E), ``conv_w``
(K, E), ``conv_b`` (E), ``w_x`` (E, R + 2n), ``dt_norm`` (R), ``b_norm``
/ ``c_norm`` (n), ``w_dt`` (R, E), ``dt_bias`` (E), ``a_log`` (n, E),
``d`` (E), ``w_out`` (E, D); an attention layer's ``attn_norm`` (D),
``wq`` (D, H, hd), ``wk`` / ``wv`` (D, KVH, hd), ``wo`` (H, hd, D);
every layer's ``mlp_norm`` (D), ``w_gate`` / ``w_up`` (D, F), ``w_down``
(F, D); ``final_norm``).

Equations (config.json of AI21-Jamba2-3B, ``model_type`` ``jamba``; what
the config leaves open stands under ``assumed`` in
``configs/ai21-jamba2-3b.json``). ``N`` is an RMS norm with its own
gain, ``silu(a) = a * sigmoid(a)``.

- Block: ``x = x + mixer_i(N1(x))``, then ``x = x + mlp(N2(x))``,
  ``mlp(h) = (silu(h W_gate) * (h W_up)) W_down``; ``mixer_i`` is
  attention where ``i % attn_layer_period == attn_layer_offset``, else
  Mamba. The final norm, then the embedding's transpose.
- Attention: ``q = h Wq`` in H heads, ``k = h Wk``, ``v = h Wv`` in KVH,
  no rotation and no position term, score ``q_t . k_s / sqrt(hd)``,
  causal softmax, ``Wo`` over the heads' results.
- Mamba (inner width E, state n, rank R, kernel K): ``[u | z] = h
  W_in``; ``c_t = silu(b_conv + sum_j w_conv[j] * u_{t-K+1+j})``, rows
  before the first being zero; ``[d | Bm | Cm] = c W_x``; ``d = Ndt(d)``,
  ``Bm = NB(Bm)``, ``Cm = NC(Cm)``; ``dt = softplus(d W_dt + b_dt)``;
  ``A = -exp(A_log)``; from ``h_{-1} = 0``: ``h_t[s, c] = exp(dt_t[c]
  A[s, c]) h_{t-1}[s, c] + dt_t[c] c_t[c] Bm_t[s]``; ``y_t[c] = sum_s
  h_t[s, c] Cm_t[s] + D[c] c_t[c]``; the output ``(y * silu(z)) W_out``.

Departures from the naive form, each so that a pass of some 3000 rows
fits beside the served weights and cache on one chip; none changes a
number that is computed: one sublayer is one jitted call, so the float32
copies of one sublayer's weights are alive at a time; attention runs
``QUERY_BLOCK`` query rows at a time against all keys.
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


def _causal(q, k, v):
    """q (S, H, hd), k and v (S, KVH, hd) -> (S, H, hd): softmax(q k^T /
    sqrt(hd)) v under the causal mask, QUERY_BLOCK rows at a time; head
    h attends to the keys of head h // (H / KVH)."""
    s, heads, hd = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    blocks = -(-s // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - s
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, heads, hd)
    rows = jnp.arange(blocks * QUERY_BLOCK).reshape(blocks, QUERY_BLOCK)
    cols = jnp.arange(s)

    def block(args):
        qi, i = args
        scores = jnp.einsum("thk,shk->hts", qi, k) / math.sqrt(hd)
        seen = cols[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hts,shk->thk", probs, v)

    out = jax.lax.map(block, (qb, rows))
    return out.reshape(blocks * QUERY_BLOCK, heads, hd)[:s]


@partial(jax.jit, static_argnames=("eps",))
def _attention(x, layer, *, eps):
    """x (S, D) float32 -> x + attn(N1(x))."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, layer["attn_norm"], eps)
        q = jnp.einsum("sd,dhk->shk", h, layer["wq"].astype(F32))
        k = jnp.einsum("sd,dhk->shk", h, layer["wk"].astype(F32))
        v = jnp.einsum("sd,dhk->shk", h, layer["wv"].astype(F32))
        return x + jnp.einsum("shk,hkd->sd", _causal(q, k, v),
                              layer["wo"].astype(F32))


@partial(jax.jit, static_argnames=("eps", "rank", "states"))
def _mamba(x, layer, *, eps, rank, states):
    """x (S, D) float32 -> x + mamba(N1(x)), the recurrence row by row
    from a zero state."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        h = _rms_norm(x, layer["norm"], eps)
        uz = h @ layer["w_in"].astype(F32)
        inner = uz.shape[1] // 2
        u, z = uz[:, :inner], uz[:, inner:]
        w = layer["conv_w"].astype(F32)                      # (K, E)
        taps = w.shape[0]
        behind = jnp.pad(u, ((taps - 1, 0), (0, 0)))
        c = jax.nn.silu(layer["conv_b"].astype(F32) + sum(
            w[j] * behind[j:j + s] for j in range(taps)))
        dbc = c @ layer["w_x"].astype(F32)
        d = _rms_norm(dbc[:, :rank], layer["dt_norm"], eps)
        b = _rms_norm(dbc[:, rank:rank + states], layer["b_norm"], eps)
        cm = _rms_norm(dbc[:, rank + states:], layer["c_norm"], eps)
        dt = jax.nn.softplus(d @ layer["w_dt"].astype(F32)
                             + layer["dt_bias"].astype(F32))
        a = -jnp.exp(layer["a_log"].astype(F32))             # (n, E)

        def row(state, r):
            c_t, dt_t, b_t, cm_t = r
            state = (jnp.exp(dt_t[None, :] * a) * state
                     + (dt_t * c_t)[None, :] * b_t[:, None])
            return state, (state * cm_t[:, None]).sum(axis=0)

        _, y = jax.lax.scan(row, jnp.zeros_like(a), (c, dt, b, cm))
        y = y + layer["d"].astype(F32) * c
        return x + (y * jax.nn.silu(z)) @ layer["w_out"].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def _mlp(x, layer, *, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, layer["mlp_norm"], eps)
        gate = h @ layer["w_gate"].astype(F32)
        up = h @ layer["w_up"].astype(F32)
        return x + (jax.nn.silu(gate) * up) @ layer["w_down"].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, embed, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ embed.astype(F32).T


def logits(params, tokens, hp: dict, last: int = 0):
    """(S, V) float32 logits of one sequence under the configuration
    ``hp`` (the config.json keys); ``last`` > 0 keeps only the last
    ``last`` positions (the head is the widest matmul)."""
    if not hp["tie_word_embeddings"] or hp["num_experts"] != 1:
        raise ValueError("this reference has a tied head and dense MLPs")
    eps = float(hp["rms_norm_eps"])
    period, offset = hp["attn_layer_period"], hp["attn_layer_offset"]
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    seen = {"mamba": 0, "attn": 0}
    for i in range(int(hp["num_hidden_layers"])):
        kind = "attn" if i % period == offset else "mamba"
        layer = {k: v[seen[kind]] for k, v in params[kind].items()}
        seen[kind] += 1
        if kind == "attn":
            x = _attention(x, layer, eps=eps)
        else:
            x = _mamba(x, layer, eps=eps, rank=int(hp["mamba_dt_rank"]),
                       states=int(hp["mamba_d_state"]))
        x = _mlp(x, {k: v[i] for k, v in params["mlp"].items()}, eps=eps)
    if last:
        x = x[-last:]
    return _head(x, params["final_norm"], params["embed"], eps=eps)
