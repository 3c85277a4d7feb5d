"""Decoders with latent attention and a shared expert beside routed
ones of which this chip holds its share: the DeepSeek-V3 key convention,
in the two configurations that run through here. **openPangu-Ultra-MoE**
(``LatentMoEConfig``'s defaults) has sandwich norms and attends to every
row at or before a row's own; **DeepSeek-V3.2-Exp** (``sandwich_norm``
off, ``index_*``, ``n_groups`` / ``groups_kept``, ``selection_bias``,
``yarn`` / ``score_mscale``) is pre-norm, has an indexer beside every
layer's attention and attends to the ``index_topk`` rows it scores
highest, chooses its experts by score + bias among its best groups, and
turns its rotary part by a YaRN table. The fields are architecture, not
switches: absent or zero, the programs are openPangu's. Served through
``llm/_internal/engine.py`` as the other families are; not trained.

**A layer** (``N`` an RMS norm with its own gain). With sandwich norms
(openPangu): ``x = x + N2(attn(N1(x)))``, then ``x = x + N4(mlp(
N3(x)))``: four gains a layer. Without (DeepSeek-V3.2-Exp): ``x = x +
attn(N1(x))``, ``x = x + mlp(N2(x))``: two. ``mlp``
is a dense SwiGLU in the ``n_dense_layers`` leading layers and
``ops/moe.py``'s dropless expert layer after them (sigmoid scores over
all ``n_experts``, the ``experts_per_token`` largest, or where the
configuration has them the largest score + bias among the kept groups,
renormalised and scaled, the experts in ``held_experts`` computed and
the others' part left out, one shared expert added once).

**Latent attention.** ``cq = Nq(h Wdq)``; a head's query is ``[q_nope |
q_rope] = cq Wuq``; ``[ckv | k_rope] = h Wdkv``, ``c = Nkv(ckv)``;
``k_rope`` is turned by the rotary table at the row's position and is
one vector for all heads, ``q_rope`` is turned head by head; a head's
key is ``[c Wuk | k_rope]`` and its value ``c Wuv``; the score of row t
on row s is ``(q_nope_t . k_nope_s + q_rope_t . k_rope_s) / sqrt(
nope + rope)``, causal, softmax in float32.

**The cache holds ``c`` and the turned ``k_rope`` of every row**: 576
values a token a layer and nothing per head, in two leaves, ``latent``
(L, B, max_seq, kv_rank) and ``rope_key`` (L, B, rope_dim, max_seq, the
rows last, as a score's matmul takes its keys). As one leaf of 576-wide
rows the chip laid it out with the rows last (576 is no whole number of
a register's 128 lanes, 16 384 is), whichever way it was declared, and
the chunk program, which wants the latent rows' width last, was
bracketed by two transposing copies of the whole shard, 3 GB each
(PERF.md section 6, PR 48). Attention has two forms over it, and which
one runs follows from the call alone:

- a call of more than one row a sequence (a prefill chunk) **expands**:
  block by block of the cache's rows it makes the heads' keys and values
  from the latent rows, once for all the chunk's rows, which attend to
  them a tile of rows at a time, each tile with a running maximum and
  sum, so no score of the chunk's rows x the cache's rows x the heads
  ever exists, and a block past the chunk's last row is never read.
  Where the widths tile (the published ones do) that is one Pallas
  kernel a layer, ``ops/pallas_latent_attention.py``, which reads the
  cache's stacks where they lie and keeps a head's score, softmax and
  accumulator in VMEM (scope ``attn_latent_prefill``); elsewhere a
  ``jax.numpy`` loop with the same arithmetic (``latent_expand`` +
  ``attn_latent_prefill``), which is also the kernel's reference;
- a call of one row a sequence (a decode) **absorbs**: ``q_nope . (c
  Wuk) = (q_nope Wuk^T) . c`` and ``sum_s p_s (c_s Wuv) = (sum_s p_s
  c_s) Wuv``, so every head attends to the latent rows themselves, one
  576-wide key and one 512-wide value for all of them
  (``attn_latent_decode``), block by block as well. Where the widths
  tile (kv_rank in 128s, the rotary part and the heads in 16s, the
  cache's rows in 128s: the published ones do) that is one Pallas kernel
  a layer between the two foldings, ``ops/pallas_latent_attention.py``
  ``latent_decode_attention``: a lane's heads attend together to a
  block of its rows, fetched once out of the stacks where they lie,
  each lane through the blocks up to its own last row and an idle lane
  through none (its result zeros, which the engine drops); elsewhere a
  ``jax.numpy`` loop with the same arithmetic
  (``attend_absorbed_blockwise``, every lane through the longest live
  lane's blocks), which is also the kernel's reference.

Above some 170 rows a call the expanded form is the cheaper (it pays
``2 x kv_rank x heads x (nope + v)`` FLOPs once a latent row; the
absorbed form pays the wider key and value at every pair).

**The indexer** (``index_topk`` > 0; ``_forward_indexed``). Beside the
latent row the cache keeps every row's **index key** ``kI = LN(h WkI)``
(``index_key`` (L, B, max_seq, index_dim)). A query row's index queries
``qI = cq WqI`` (``index_heads`` of them) and weights ``w = h Ww /
sqrt(index_heads x index_dim)`` score every row at or before its own,
``I(t, s) = sum_j w(t, j) relu(qI(t, j) . kI(s))`` (the first
``rope_dim`` dimensions of ``qI`` and ``kI`` turned by the layer's
table), and the row attends to the ``index_topk`` rows of the largest
score (all of them while there are no more; of equal scores the lower
index first) and to no other: ``ops/index_select.py`` has the score (a
Pallas kernel for a chunk, ``ops/pallas_index_score.py``) and the exact
selection. The two forms stay two. A chunk's rows each have their own
set, so the selection is a mask that the expanded form's kernel takes
beside the cache (``attend_expanded``'s ``allowed``): it still expands
every row up to the chunk's last once for all the chunk's rows and
masks what a row did not select. A decode lane's set is a list of rows:
they are gathered, ``index_topk`` x (kv_rank + rope) values a lane, and
attended to in the absorbed form (``attend_rows``), so the lane reads
its index keys and those rows and not every latent row up to its last.
Scopes: ``attn_index`` > ``index_q``, ``index_k``, ``index_score``,
``index_select``; the selected attention under ``attn_latent_prefill``
/ ``attn_latent_decode``; the index keys' write under ``kv_write``.
Such a model's programs also say what they chose (``read_choices``:
each layer's set at each of the call's rows as bits, each routed
layer's experts), so that a comparison can hold a float32 reference to
the choices bf16 made and judge the choices apart.

**Parameters are stacked by kind of layer**: ``dense`` and ``routed``
each hold their layers' attention and norms, and the MLP of their own
shape; ``decoder.scan_layers`` runs over the one and then over the
other, the cache riding in both carries. The call around the layers
(its rows, embedding, head, the counters' words) is
``models/decoder.py``'s.

**Counters** (``COUNTERS``, in the cache's ``counts``): the ``moe_*`` three of
``EngineStats`` (held experts only), ``moe_held_slabs`` (the passes
``ops/moe.py`` made over a slab of the held experts' assignments: one a
layer a chunk call whose held share fits the slab, none in a decode
call), ``moe_assignments_all`` (every live
row's ``experts_per_token``, so that the held share of the routing is
read and not assumed), and for the two attention forms
``attn_pairs_prefill`` (a chunk's live rows x the rows each attends to),
``attn_rows_prefill`` (the latent rows a chunk call attends to, each
expanded once), ``attn_rows_decode`` (the rows a live lane attends to)
and ``attn_blocks_decode`` (the rows of the blocks the decode form took
the call's lanes through, ``absorbed_blocks``: on the kernel's path
every live lane's own blocks, on the loop's the longest's for every
lane; ``attn_rows_decode`` over it is the share of the fetched rows that
some lane asked for), each summed over layers and calls. In an indexed
model the four count what was attended after the selection
(``attn_pairs_prefill`` and ``attn_rows_decode`` the selected pairs and
rows, ``attn_blocks_decode`` the ``index_topk`` places a live lane's
gather fetches), and two more words (``INDEX_COUNTERS``) count the
selection itself: ``attn_rows_indexed`` (the rows scored: a live row x
the rows at or before it) and ``attn_rows_selected`` (the rows kept for
them: the bits each row said, counted).
"""

from __future__ import annotations

import dataclasses
import math
import threading
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import index_select, moe

from . import decoder
from .decoder import layer_norm, rms_norm
from .llama import LlamaConfig, apply_rope, make_dense_init
from .rope import YarnRope, yarn_inv_freq


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig(LlamaConfig):
    # ``ffn_dim`` is the dense leading layers' width; ``n_kv_heads`` and
    # ``head_size`` mean nothing here
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    n_dense_layers: int = 1
    # the router's width, the experts a token goes to, an expert's width
    n_experts: int = 256
    experts_per_token: int = 8
    expert_dim: int = 2048
    # the ids of the experts this chip holds, in the order their weights
    # are stacked
    held_experts: Tuple[int, ...] = tuple(range(8))
    shared_dim: int = 2048      # the one shared expert's width
    norm_topk_prob: bool = True
    routed_scale: float = 2.5
    # What follows is architecture, and absent or zero is openPangu's.
    # ``sandwich_norm``: a norm behind each sublayer as well as before
    # it (four gains a layer); without, two
    sandwich_norm: bool = True
    # the indexer beside every layer's attention: ``index_heads`` index
    # queries of ``index_dim`` a row, one index key a row, and a row
    # attends to the ``index_topk`` rows of largest index score; 0: every
    # row at or before its own
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    # the router's experts in groups of which a token keeps some
    # (``ops/moe.py`` ``MoEConfig``), and a bias a routed layer adds to
    # the scores for the choice alone (``router_bias``)
    n_groups: int = 1
    groups_kept: int = 1
    selection_bias: bool = False
    # the rotary part's table where it is YaRN's (its own
    # ``attention_factor`` on cos and sin), and the factor ``m`` whose
    # square multiplies the attention scores' scale
    yarn: Optional[YarnRope] = None
    score_mscale: float = 1.0

    model_module = "ray_tpu.models.latent_moe"

    def __post_init__(self):
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(
                f"{self.n_dense_layers} dense layers of {self.n_layers}")
        if self.rope_dim % 2:
            raise ValueError("the rotary part turns pairs of dimensions")
        if self.index_topk and not (
                self.index_heads and self.index_dim >= self.rope_dim):
            raise ValueError(
                f"an indexer of {self.index_heads} heads of "
                f"{self.index_dim}, turned in its first {self.rope_dim}")

    @property
    def n_routed_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def n_held(self) -> int:
        return len(self.held_experts)

    @property
    def latent_dim(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def says_choices(self) -> bool:
        """Whether the programs leave in the cache what a call chose
        (``read_choices``): a model whose every row selects does."""
        return bool(self.index_topk)

    @property
    def score_scale(self) -> float:
        """What a score of row t on row s is multiplied by."""
        return self.score_mscale ** 2 / math.sqrt(self.nope_dim
                                                  + self.rope_dim)

    @property
    def moe(self) -> moe.MoEConfig:
        return moe.MoEConfig(
            d_model=self.dim, d_ff=self.expert_dim, n_experts=self.n_experts,
            k=self.experts_per_token, norm_topk_prob=self.norm_topk_prob,
            scoring="sigmoid", routed_scale=self.routed_scale,
            held=self.held_experts, n_groups=self.n_groups,
            groups_kept=self.groups_kept)


LATENT_MOE_TINY = LatentMoEConfig(
    vocab_size=512, dim=64, n_layers=3, n_heads=4, ffn_dim=128,
    max_seq_len=256, rope_theta=10000.0, remat=False,
    q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16,
    n_dense_layers=1, n_experts=16, experts_per_token=4, expert_dim=32,
    held_experts=(4, 5, 6, 7), shared_dim=32,
)
# the same with what an indexed model adds: two norms a layer, an indexer
# of 4 heads of 16 that keeps 16 rows, 4 groups of which 2 are kept, a
# selection bias, a YaRN table
LATENT_MOE_INDEXED_TINY = dataclasses.replace(
    LATENT_MOE_TINY, sandwich_norm=False, index_heads=4, index_dim=16,
    index_topk=16, n_groups=4, groups_kept=2, selection_bias=True,
    yarn=YarnRope(theta=10000.0, factor=4.0, original_max_position=64,
                  attention_factor=1.0),
    score_mscale=0.1 * math.log(4.0) + 1.0)

SHARED_WEIGHTS = ("shared_gate", "shared_up", "shared_down")
COUNTERS = ("moe_assignments", "moe_experts_touched", "moe_expert_slots",
            "moe_held_slabs", "moe_assignments_all", "attn_pairs_prefill",
            "attn_rows_prefill", "attn_rows_decode", "attn_blocks_decode")
# and, behind them in an indexed model's words, of the selection
INDEX_COUNTERS = ("attn_rows_indexed", "attn_rows_selected")
# (the most a call counts at once, 2048 rows x 16 384 x 5 layers, is
# 2^27: under the carry of ``decoder``'s counter words)
# What the ``jax.numpy`` loops take at a time (since PR 50 and PR 62
# they are the forms of the widths the kernels cannot tile, and the
# tests' reference): cache rows a block, and (the prefill form's loop,
# ``attend_expanded_blockwise``) the rows of a chunk that attend to them
# at a time: its
# score of one tile and block is heads x PREFILL_TILE x PREFILL_BLOCK
# float32 (34 MB at 128 heads), a decode's lanes x heads x DECODE_BLOCK.
# Read on a v5e at the published widths, when the loop was the cell's
# path: a 256-row chunk call 2.3 us an attended row at blocks of 256,
# 2.6 at 128, 3.3 at 512, 4.8 at 1024; a 1024-row call 11 us in tiles of
# 256, 14 in tiles of 128, 16 in tiles of 512 and 21 as one tile; a
# decode call of 32 lanes the same at 512, 1024 and 2048 (PERF.md
# section 6, PR 48 and PR 49). The kernels (``ops/
# pallas_latent_attention.py``) size themselves: there 1024 rows at row
# 3072, a layer, read 8.1 ms at blocks of 256 rows and 2 heads a step,
# 4.2 at 512 rows and 4 heads in tiles of 512, 4.1 at 1024 rows and at
# 8 heads, 4.5 in tiles of 256 (the row maxima's lane reductions are
# paid once a tile and block whatever its width; PERF.md section 6,
# PR 50)
PREFILL_BLOCK = 256
PREFILL_TILE = 256
DECODE_BLOCK = 1024


def _attn_shapes(c: LatentMoEConfig) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """name -> (a layer's shape, fan-in) of the attention projections."""
    H = c.n_heads
    return {
        "wdq": ((c.dim, c.q_rank), c.dim),
        "wuq": ((c.q_rank, H, c.nope_dim + c.rope_dim), c.q_rank),
        "wdkv": ((c.dim, c.latent_dim), c.dim),
        "wuk": ((c.kv_rank, H, c.nope_dim), c.kv_rank),
        "wuv": ((c.kv_rank, H, c.v_dim), c.kv_rank),
        "wo": ((H, c.v_dim, c.dim), H * c.v_dim),
    }


def _indexer_shapes(c: LatentMoEConfig):
    """name -> (a layer's shape, fan-in) of the indexer's projections;
    none where the model has no indexer."""
    if not c.index_topk:
        return {}
    return {
        "wq_index": ((c.q_rank, c.index_heads, c.index_dim), c.q_rank),
        "wk_index": ((c.dim, c.index_dim), c.dim),
        "w_index": ((c.dim, c.index_heads), c.dim),
    }


def chunk_terms(config: LatentMoEConfig, max_seq: int) -> Dict[str, float]:
    """What ``engine.derived_prefill_chunk`` is told beside the chip,
    from the configuration and the cache's length alone. Every row
    meets the attention projections, the dense layers' MLPs, the shared
    experts and the head; a held expert is met by the rows routed to
    it, ``experts_per_token / n_experts`` of them. Whichever of the two
    holds more of the model sets what a row's work is counted in
    (``row_share``). Where that is the former (8 of 256 experts held
    beside 7680-wide latent attention: 1.74 G parameters beside 1.51 G
    and an embedding that is gathered, not multiplied), two things more
    are paid once a call:

    - the held experts' read (``read_beside``: 1.51 G parameters over
      1.74 G, 0.87). At the rows that pay for the weights every row
      meets, an expert sees a thirty-second of them and is read all
      the same;
    - the expansion of every latent row the chunk attends to into the
      heads' keys and values (``latent_expand``: 2 x kv_rank x heads x
      (nope + v) FLOPs a row a layer), before a row of the chunk's own
      is scored. A lane of ``max_seq`` holds half of them on average
      over a prompt; over a row's 2 FLOPs a parameter it meets that is
      ``once_rows`` (394 rows' worth at 16 384).

    Where the held experts hold more, their read is what the rows pay
    for, each expert seeing its share of them; the weights every row
    meets are past their own ridge by then and the expansion is small
    beside rows so many."""
    c = config
    attention = sum(math.prod(shape) for shape, _ in (
        *_attn_shapes(c).values(), *_indexer_shapes(c).values()))
    every_row = (c.n_layers * attention
                 + c.n_dense_layers * 3 * c.dim * c.ffn_dim
                 + c.n_routed_layers * 3 * c.dim * c.shared_dim
                 + c.dim * c.vocab_size)
    routed = c.n_routed_layers * c.n_held * 3 * c.dim * c.expert_dim
    if every_row < routed:
        return {"row_share": c.experts_per_token / c.n_experts}
    expand = (c.n_layers * 2 * c.kv_rank * c.n_heads
              * (c.nope_dim + c.v_dim)) * max_seq / 2
    return {"read_beside": routed / every_row,
            "once_rows": expand / (2 * every_row)}


# -- parameters --------------------------------------------------------
BIAS_STD = 0.02     # a seeded selection bias's (sigmoid scores lie in 0..1)
NORMS = {"attn_norm": "dim", "attn_post_norm": "dim", "mlp_norm": "dim",
         "mlp_post_norm": "dim", "q_norm": "q_rank", "kv_norm": "kv_rank"}
POST_NORMS = ("attn_post_norm", "mlp_post_norm")    # a sandwich's alone


def param_specs(config: LatentMoEConfig) -> Dict[str, Any]:
    """Everything whole on every device: the family is served on one
    chip, which holds its share of a deployment's experts already."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), config))
    return jax.tree_util.tree_map(lambda a: P(*[None] * a.ndim), shapes)


def init_params(rng: jax.Array, config: LatentMoEConfig) -> Dict[str, Any]:
    """``dense`` and ``routed``: each kind's layers stacked, in
    ``param_dtype``; the router float32; every norm's gain 1. An
    indexer's three projections and its key's LayerNorm (gain 1, bias
    0) beside each layer's attention; a selection bias (``router_bias``,
    float32, drawn at ``BIAS_STD``: small, and not 0, so that a choice
    by score + bias differs from one by score at some rows) beside each
    router."""
    c = config
    dense = make_dense_init(c)
    keys = iter(jax.random.split(rng, 32))
    # what openPangu has not is drawn from keys of its own, so that its
    # parameters are what they were
    more = iter(jax.random.split(jax.random.fold_in(rng, 1), 16))

    def common(L):
        out = {name: jnp.ones((L, getattr(c, width)), c.param_dtype)
               for name, width in NORMS.items()
               if c.sandwich_norm or name not in POST_NORMS}
        for name, (shape, fan_in) in _attn_shapes(c).items():
            out[name] = dense(next(keys), (L, *shape), fan_in)
        for name, (shape, fan_in) in _indexer_shapes(c).items():
            out[name] = dense(next(more), (L, *shape), fan_in)
        if c.index_topk:
            out["k_index_norm"] = jnp.ones((L, c.index_dim), c.param_dtype)
            out["k_index_bias"] = jnp.zeros((L, c.index_dim), c.param_dtype)
        return out

    Ld, Lr, D, E = c.n_dense_layers, c.n_routed_layers, c.dim, c.n_held
    params = {
        "embed": dense(next(keys), (c.vocab_size, D), D),
        "dense": {
            **common(Ld),
            "w_gate": dense(next(keys), (Ld, D, c.ffn_dim), D),
            "w_up": dense(next(keys), (Ld, D, c.ffn_dim), D),
            "w_down": dense(next(keys), (Ld, c.ffn_dim, D), c.ffn_dim),
        },
        "routed": {
            **common(Lr),
            "router": jax.random.normal(
                next(keys), (Lr, D, c.n_experts), jnp.float32) / math.sqrt(D),
            "w_gate": dense(next(keys), (Lr, E, D, c.expert_dim), D),
            "w_up": dense(next(keys), (Lr, E, D, c.expert_dim), D),
            "w_down": dense(next(keys), (Lr, E, c.expert_dim, D),
                            c.expert_dim),
        },
        "final_norm": jnp.ones((D,), c.param_dtype),
        "lm_head": dense(next(keys), (D, c.vocab_size), D),
    }
    params["routed"].update(
        shared_gate=dense(next(keys), (Lr, D, c.shared_dim), D),
        shared_up=dense(next(keys), (Lr, D, c.shared_dim), D),
        shared_down=dense(next(keys), (Lr, c.shared_dim, D), c.shared_dim))
    if c.selection_bias:
        params["routed"]["router_bias"] = BIAS_STD * jax.random.normal(
            next(more), (Lr, c.n_experts), jnp.float32)
    return params


# -- the sublayers -----------------------------------------------------
def rope_cos_sin(c: LatentMoEConfig, pos: jax.Array):
    """cos and sin (..., rope_dim / 2) float32 at the positions ``pos``:
    the default table at ``rope_theta``, or the configuration's YaRN
    table, scaled by its attention factor."""
    if c.yarn is None:
        inv_freq = c.rope_theta ** -(
            np.arange(0, c.rope_dim, 2, dtype=np.float64) / c.rope_dim)
        scale = 1.0
    else:
        inv_freq = yarn_inv_freq(c.yarn, c.rope_dim)
        scale = c.yarn.cos_sin_scale
    freqs = pos[..., None].astype(jnp.float32) * jnp.asarray(
        inv_freq, jnp.float32)
    if scale == 1.0:
        return jnp.cos(freqs), jnp.sin(freqs)
    return jnp.cos(freqs) * scale, jnp.sin(freqs) * scale


def query_latent(c: LatentMoEConfig, h, layer):
    """h (B, T, D) -> the normed query latent (B, T, q_rank): what the
    heads' queries, and an indexer's, are made from."""
    with jax.named_scope("latent_q"):
        return rms_norm(h @ layer["wdq"].astype(c.dtype), layer["q_norm"],
                        c.norm_eps)


def latent_q(c: LatentMoEConfig, h, layer, cos, sin, cq=None):
    """h (B, T, D) -> the heads' queries (q_nope (B, T, H, nope), q_rope
    (B, T, H, rope) turned); ``cq``: ``query_latent``'s, where the
    caller has made it already."""
    if cq is None:
        cq = query_latent(c, h, layer)
    with jax.named_scope("latent_q"):
        q = jnp.einsum("btr,rhk->bthk", cq, layer["wuq"].astype(c.dtype))
        return q[..., :c.nope_dim], apply_rope(q[..., c.nope_dim:], cos, sin)


def latent_kv(c: LatentMoEConfig, h, layer, cos, sin):
    """h (B, T, D) -> (B, T, kv_rank + rope_dim): a row's normed latent
    and its turned rotary key, as the cache keeps them."""
    with jax.named_scope("latent_kv"):
        ckv = h @ layer["wdkv"].astype(c.dtype)
        latent = rms_norm(ckv[..., :c.kv_rank], layer["kv_norm"], c.norm_eps)
        k_rope = apply_rope(ckv[..., None, c.kv_rank:], cos, sin)[:, :, 0]
        return jnp.concatenate([latent, k_rope], axis=-1)


def _blocks(rows: int, block: int) -> int:
    """The block size that divides ``rows``: ``block`` or, where the
    cache is shorter or no multiple of it, what is."""
    block = min(block, rows)
    while rows % block:
        block //= 2
    return block


def _stack_reader(stack, layer, first, B: int):
    """``read(start, size)`` over the cache's two stacks: rows ``[start,
    start + size)`` of layer ``layer`` and sequences ``first .. first +
    B`` (latent rows (B, size, kv_rank), rotary keys (B, rope, size)),
    the block alone out of the layers' stacks."""
    latents, keys = stack

    def read(start, size):
        return (jax.lax.dynamic_slice(
                    latents, (layer, first, start, 0),
                    (1, B, size, latents.shape[3]))[0],
                jax.lax.dynamic_slice(
                    keys, (layer, first, 0, start),
                    (1, B, keys.shape[2], size))[0])
    return read


def attend_expanded(c: LatentMoEConfig, q_nope, q_rope, stack, index, first,
                    S: int, start_pos, layer, allowed=None):
    """The prefill form. q_nope (B, T, H, nope), q_rope (B, T, H, rope),
    sequence b's T rows at the positions ``start_pos[b] ..``; ``stack``
    the cache's two stacks (latent (L, B', max_seq, kv_rank), rope_key
    (L, B', rope, max_seq)) of which layer ``index``, sequences ``first
    .. first + B`` and the rows ``[0, S)`` are read, the call's own among
    them; ``layer`` holds ``wuk`` and ``wuv`` -> (B, T, H, v) in the
    compute type. ``allowed`` (B, T, S) bool, where the rows were
    selected: row t attends to the rows it marks among those at or
    before its own position, one at least, and to no other. Which of
    the two implementations runs follows from the shapes alone:
    ``ops/pallas_latent_attention.py``'s kernel where it can tile them,
    ``attend_expanded_blockwise`` elsewhere."""
    # imported beside whatever followed this module's own import
    # (``_import_kernel``); waits here for what is left of it
    from ray_tpu.ops import pallas_latent_attention as kernel

    B, T = q_nope.shape[:2]
    wuk, wuv = layer["wuk"].astype(c.dtype), layer["wuv"].astype(c.dtype)
    with jax.named_scope("latent_q"):
        # head-major, as the kernel takes them: the turn is folded into
        # the fusions that make the queries
        by_head = q_nope.transpose(0, 2, 1, 3), q_rope.transpose(0, 2, 1, 3)
    if kernel.untileable(*by_head, *stack, wuk, wuv, S) is None:
        with jax.named_scope("attn_latent_prefill"):
            selected = {} if allowed is None else {"allowed": allowed}
            return kernel.latent_prefill_attention(
                *by_head, *stack, wuk, wuv, layer=index, slot=first,
                start_pos=start_pos, rows=S,
                scale=c.score_scale, **selected)
    pos = start_pos[:, None] + jnp.arange(T)[None, :]
    return attend_expanded_blockwise(
        c, q_nope, q_rope, _stack_reader(stack, index, first, B), S, pos,
        layer, allowed)


def attend_expanded_blockwise(c: LatentMoEConfig, q_nope, q_rope, read,
                              S: int, pos, layer, allowed=None):
    """The prefill form in ``jax.numpy``, for the shapes the kernel
    cannot tile and as its numerical reference. q_nope (B, T, H, nope),
    q_rope (B, T, H, rope) at the positions ``pos`` (B, T); ``read(
    start, size)`` gives rows ``[start, start + size)`` of the
    sequences' ``S`` cache rows (latent rows (B, size, kv_rank), rotary
    keys (B, rope, size)), the call's own among them -> (B, T, H, v) in
    the compute type. ``PREFILL_BLOCK`` rows of the cache at a time:
    their keys and values are made from the latent rows once, the
    chunk's rows attend to them ``PREFILL_TILE`` at a time, each tile
    with a running maximum and sum of its own that carry its softmax;
    the loop ends with the block that holds the call's last position.
    ``allowed`` (B, T, S) bool as ``attend_expanded`` takes it."""
    B, T, H, _ = q_nope.shape
    block = _blocks(S, PREFILL_BLOCK)
    tile = _blocks(T, PREFILL_TILE)
    scale = c.score_scale
    wuk, wuv = layer["wuk"].astype(c.dtype), layer["wuv"].astype(c.dtype)
    # a tile's queries and positions, cut out here and not once a block
    queries = [(q_nope[:, t:t + tile], q_rope[:, t:t + tile],
                pos[:, t:t + tile, None],
                None if allowed is None else allowed[:, t:t + tile])
               for t in range(0, T, tile)]

    def step(i, carry):
        with jax.named_scope("attn_latent_prefill"), \
                jax.named_scope("kv_slice"):
            rows, k_rope = read(i * block, block)
        with jax.named_scope("latent_expand"):
            # in a call of one tile the compiler makes these inside the
            # score's fusions, which carry ``attn_latent_prefill``: a
            # trace reads the two scopes together. Behind an optimization
            # barrier they are this scope's own and read a seventh of a
            # 256-row chunk call, which then takes 14-19 % longer
            # (PERF.md section 6, PR 48)
            k_nope = jnp.einsum("bsc,chk->bshk", rows, wuk)
            v = jnp.einsum("bsc,chk->bshk", rows, wuv)
        at = i * block + jnp.arange(block)
        out = []
        for (q_nope_t, q_rope_t, pos_t, allowed_t), (m, l, acc) in zip(
                queries, carry):
            with jax.named_scope("attn_latent_prefill"):
                s = (jnp.einsum("bthk,bshk->bhts", q_nope_t, k_nope,
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("bthr,brs->bhts", q_rope_t, k_rope,
                                  preferred_element_type=jnp.float32)) * scale
                seen = at[None, None, :] <= pos_t              # (B, tile, blk)
                if allowed_t is not None:
                    seen &= jax.lax.dynamic_slice_in_dim(
                        allowed_t, i * block, block, axis=2)
                s = jnp.where(seen[:, None], s, -1e30)
                m_new = jnp.maximum(m, s.max(-1))
                p = jnp.exp(s - m_new[..., None])
                fade = jnp.exp(m - m_new)
                out.append((m_new, l * fade + p.sum(-1),
                            acc * fade[..., None] + jnp.einsum(
                                "bhts,bshk->bhtk", p.astype(c.dtype), v,
                                preferred_element_type=jnp.float32)))
        return tuple(out)

    # row 0 is seen by every query, so the first block sets every
    # maximum and a masked score weighs exp(-1e30 - m) = 0 exactly
    # (under ``allowed``, what a row gathered before its first marked
    # row fades by exp(-1e30 - m) = 0 when that row's score arrives)
    blocks = jnp.minimum(pos.max() // block + 1, S // block)
    first = (jnp.full((B, H, tile), -1e30, jnp.float32),
             jnp.zeros((B, H, tile), jnp.float32),
             jnp.zeros((B, H, tile, c.v_dim), jnp.float32))
    done = jax.lax.fori_loop(0, blocks, step, (first,) * len(queries))
    with jax.named_scope("attn_latent_prefill"):
        return jnp.concatenate(
            [(acc / l[..., None]).astype(c.dtype).transpose(0, 2, 1, 3)
             for _, l, acc in done], axis=1)


def absorbed_blocks(c: LatentMoEConfig, stack, S: int, seen):
    """What the decode form fetches for a call whose lane b attends to
    its ``seen[b]`` leading rows (B,; 0: an idle lane) at the read window
    ``S`` -> (the rows of a block, the blocks each lane is taken
    through (B,)). On the kernel's path a lane's own count, none for a
    lane that attends to nothing; on the loop's the count of the
    longest, for every lane."""
    from ray_tpu.ops import pallas_latent_attention as kernel

    if kernel.decode_untileable(c.n_heads, c.rope_dim, *stack) is None:
        block = kernel.decode_block(stack[0])
        return block, jnp.minimum(-(-seen // block),
                                  stack[0].shape[2] // block)
    block = _blocks(S, DECODE_BLOCK)
    return block, jnp.broadcast_to(
        jnp.clip(-(-seen.max() // block), 1, S // block), seen.shape)


def attend_absorbed(c: LatentMoEConfig, q_nope, q_rope, stack, index, first,
                    S: int, pos, layer, blocks):
    """The decode form, one query row a sequence. q_nope (B, 1, H,
    nope), q_rope (B, 1, H, rope) at ``pos`` (B, 1); ``stack``, ``index``,
    ``first`` and ``S`` as ``attend_expanded`` takes them; ``blocks``
    (B,) of ``absorbed_blocks``: the blocks each lane is taken through
    -> (B, 1, H, v). ``Wuk`` is folded into the query and ``Wuv`` into
    the output, so the heads attend to the latent rows themselves, a
    block at a time. Which of the two implementations runs follows from
    the shapes alone: ``ops/pallas_latent_attention.py``'s decode
    kernel where it can tile them (each lane through its own blocks, a
    lane of none given zeros), ``attend_absorbed_blockwise`` elsewhere
    (every lane through the longest's)."""
    from ray_tpu.ops import pallas_latent_attention as kernel

    with jax.named_scope("attn_latent_decode"):
        q = jnp.einsum("bhk,chk->bhc", q_nope[:, 0],
                       layer["wuk"].astype(c.dtype))
        if kernel.decode_untileable(c.n_heads, c.rope_dim, *stack) is None:
            mixed = kernel.latent_decode_attention(
                q, q_rope[:, 0], *stack, layer=index, slot=first,
                pos=pos[:, 0], blocks=blocks,
                scale=c.score_scale)
        else:
            mixed = attend_absorbed_blockwise(
                c, q, q_rope[:, 0], _stack_reader(stack, index, first,
                                                  q.shape[0]),
                S, pos, blocks.max())
        return jnp.einsum("bhc,chk->bhk", mixed,
                          layer["wuv"].astype(c.dtype))[:, None]


def attend_absorbed_blockwise(c: LatentMoEConfig, q, q_rope, read, S: int,
                              pos, blocks):
    """The decode form's attention in ``jax.numpy``, for the shapes the
    kernel cannot tile and as its numerical reference. q (B, H, kv_rank)
    the folded queries, q_rope (B, H, rope), at ``pos`` (B, 1); ``read``
    and ``S`` as ``attend_expanded_blockwise`` takes them -> the mixed
    latent rows (B, H, kv_rank). ``DECODE_BLOCK`` rows at a time, all
    lanes through the ``blocks`` leading blocks."""
    B, H, _ = q.shape
    block = _blocks(S, DECODE_BLOCK)
    scale = c.score_scale

    def step(i, carry):
        m, l, acc = carry
        with jax.named_scope("kv_slice"):
            rows, k_rope = read(i * block, block)
        s = (jnp.einsum("bhc,bsc->bhs", q, rows,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhr,brs->bhs", q_rope, k_rope,
                          preferred_element_type=jnp.float32)) * scale
        at = i * block + jnp.arange(block)
        s = jnp.where((at[None, :] <= pos)[:, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        fade = jnp.exp(m - m_new)
        l = l * fade + p.sum(-1)
        acc = acc * fade[..., None] + jnp.einsum(
            "bhs,bsc->bhc", p.astype(c.dtype), rows,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, blocks, step,
        (jnp.full((B, H), -1e30, jnp.float32),
         jnp.zeros((B, H), jnp.float32),
         jnp.zeros((B, H, c.kv_rank), jnp.float32)))
    return (acc / l[..., None]).astype(c.dtype)


def index_qkw(c: LatentMoEConfig, h, cq, layer, cos, sin):
    """The indexer over the normed input ``h`` (B, T, D) and the query
    latent ``cq`` (B, T, q_rank) -> (the index queries (B, T, Hi, di),
    the row's index key (B, T, di), the first ``rope_dim`` dimensions of
    both turned by the layer's rotary table; the heads' weights (B, T,
    Hi) float32)."""
    rope = c.rope_dim

    def turned(x):          # (B, T, heads, di)
        return jnp.concatenate(
            [apply_rope(x[..., :rope], cos, sin), x[..., rope:]], axis=-1)

    with jax.named_scope("index_q"):
        q = turned(jnp.einsum("btr,rhd->bthd", cq,
                              layer["wq_index"].astype(c.dtype)))
        w = (h @ layer["w_index"].astype(c.dtype)).astype(jnp.float32) * (
            1.0 / math.sqrt(c.index_heads * c.index_dim))
    with jax.named_scope("index_k"):
        k = layer_norm(h @ layer["wk_index"].astype(c.dtype),
                       layer["k_index_norm"], c.norm_eps
                       ) + layer["k_index_bias"].astype(c.dtype)
        k = turned(k[:, :, None])[:, :, 0]
    return q, k, w


def attend_rows(c: LatentMoEConfig, q_nope, q_rope, rows, k_rope, allowed,
                layer):
    """The absorbed form over rows handed in (a decode lane's selected
    ones): one query row a lane, q_nope (B, 1, H, nope), q_rope (B, 1,
    H, rope); ``rows`` (B, K, kv_rank) latent rows with their turned
    rotary keys ``k_rope`` (B, K, rope), of which lane b attends to
    those ``allowed`` (B, K) marks -> (B, 1, H, v). ``Wuk`` is folded
    into the query and ``Wuv`` into the output, as ``attend_absorbed``
    folds them; K is ``index_topk``, so the rows are scored at once."""
    q = jnp.einsum("bhk,chk->bhc", q_nope[:, 0], layer["wuk"].astype(c.dtype))
    score = (jnp.einsum("bhc,bkc->bhk", q, rows,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhr,bkr->bhk", q_rope[:, 0], k_rope,
                          preferred_element_type=jnp.float32)
             ) * c.score_scale
    p = jax.nn.softmax(jnp.where(allowed[:, None], score, -1e30), axis=-1)
    mixed = jnp.einsum("bhk,bkc->bhc", p.astype(c.dtype), rows,
                       preferred_element_type=jnp.float32).astype(c.dtype)
    return jnp.einsum("bhc,chk->bhk", mixed,
                      layer["wuv"].astype(c.dtype))[:, None]


def attn_out(c: LatentMoEConfig, x, attn, layer):
    """The heads' results through ``Wo``, the post-norm where the model
    has one, the residual."""
    with jax.named_scope("attn_out"):
        out = jnp.einsum("bthk,hkd->btd", attn, layer["wo"].astype(c.dtype))
    if not c.sandwich_norm:
        return x + out
    return x + rms_norm(out, layer["attn_post_norm"], c.norm_eps)


def dense_mlp(c: LatentMoEConfig, x, layer):
    with jax.named_scope("mlp"):
        h = rms_norm(x, layer["mlp_norm"], c.norm_eps)
        gate = h @ layer["w_gate"].astype(c.dtype)
        up = h @ layer["w_up"].astype(c.dtype)
        out = (jax.nn.silu(gate) * up) @ layer["w_down"].astype(c.dtype)
        if not c.sandwich_norm:
            return x + out
        return x + rms_norm(out, layer["mlp_post_norm"], c.norm_eps)


def moe_mlp(c: LatentMoEConfig, x, layer, experts, index, live=None):
    """The expert layer between its norms + residual -> (x, counts
    int32[5]: ``ops/moe.py``'s four and every live row's assignments,
    held or not; and, of a model that says what it chose (an indexed
    one), the experts the router chose at every row, int32 (B, T, k),
    else None). ``layer``: this layer's norms, router (and selection
    bias) and shared expert; ``experts``: every routed layer's held
    experts, stacked, of which this layer is ``index`` (``ops/moe.py``
    ``expert_ffn`` says why the stack goes whole)."""
    with jax.named_scope("moe"):
        h = rms_norm(x, layer["mlp_norm"], c.norm_eps)
        router = {"router": layer["router"]}
        if c.selection_bias:
            router["router_bias"] = layer["router_bias"]
        out, counts, *chose = moe.moe_ffn_dropless(
            {**router,
             **{k: layer[k].astype(c.dtype) for k in SHARED_WEIGHTS},
             **{k: w.astype(c.dtype) for k, w in experts.items()}},
            h, c.moe, layer=index, live=live, say_experts=bool(c.index_topk))
        rows = (math.prod(x.shape[:-1]) if live is None
                else jnp.broadcast_to(live, x.shape[:-1]).sum())
        counts = jnp.concatenate([counts, jnp.reshape(
            rows * c.experts_per_token, (1,)).astype(jnp.int32)])
        if c.sandwich_norm:
            out = rms_norm(out, layer["mlp_post_norm"], c.norm_eps)
        return x + out, counts, (chose[0] if chose else None)


# -- the cache ---------------------------------------------------------
def init_cache(config: LatentMoEConfig, batch: int, max_seq: int,
               chunk: Optional[int] = None):
    """``latent`` (L, B, max_seq, kv_rank) and ``rope_key`` (L, B,
    rope_dim, max_seq) in the compute type, a row a position;
    ``counts``: the device words of ``COUNTERS``. Of an indexed model
    also ``index_key`` (L, B, max_seq, index_dim), every row's index
    key; ``INDEX_COUNTERS``' words behind the others; and what the last
    call chose at each of its rows (``read_choices``): ``said_rows`` (L,
    R, words) uint32, a layer's selected set as bits
    (``index_select.mask_as_bits``), ``said_experts`` (routed layers, R,
    k) int32 and ``said_count``, how many rows that call had; R the most
    rows a call has (``chunk``: the most a call will write)."""
    c = config
    cache = {"latent": jnp.zeros((c.n_layers, batch, max_seq, c.kv_rank),
                                 c.dtype),
             "rope_key": jnp.zeros((c.n_layers, batch, c.rope_dim, max_seq),
                                   c.dtype),
             "counts": decoder.counter_words(len(COUNTERS))}
    if not c.index_topk:
        return cache
    said = max(chunk or max_seq, batch)
    return {
        **cache,
        "index_key": jnp.zeros((c.n_layers, batch, max_seq, c.index_dim),
                               c.dtype),
        "said_rows": jnp.zeros(
            (c.n_layers, said, index_select.said_words(max_seq)), jnp.uint32),
        "said_experts": jnp.zeros(
            (c.n_routed_layers, said, c.experts_per_token), jnp.int32),
        "said_count": jnp.int32(0),
        "counts": decoder.counter_words(len(COUNTERS) + len(INDEX_COUNTERS))}


def attn_rows_read(config: LatentMoEConfig, cache, rows: int) -> int:
    """Cache rows a sequence one call may read for attention at the read
    window ``rows``: every layer's bound is the window (both forms stop
    at the block of a sequence's last position, which the device
    counters see and this host-side count does not; an indexed model's
    indexer scores every row of the window, and a decode then attends
    to ``index_topk`` of them, which the device counters see too)."""
    del config, cache
    return rows


# what one cache shard's programs have counted (an indexed model's words
# are two more)
read_counters = partial(decoder.read_counters,
                        names=COUNTERS + INDEX_COUNTERS)


def read_choices(cache):
    """What the call that returned an indexed model's ``cache`` chose at
    each of its rows (a chunk's rows, or a decode's lanes), on the host:
    int32 (layers + 1, the call's rows, W), every entry of a row a
    number no other entry of the row can be, so that two rows hold the
    same numbers where the same was chosen and there alone. Entry l is
    layer l's selected set: the words ``index_select.mask_as_bits`` lays
    its bits into, each in two halves, ``position << 16 | sixteen bits``
    (half j of word w at position 2 w + j). The last entry holds every
    routed layer's experts, ``routed layer << 16 | expert``, layer after
    layer, and -1 behind them."""
    n = int(cache["said_count"])
    words = np.asarray(cache["said_rows"][:, :n])
    halves = np.stack([words & 0xFFFF, words >> 16], axis=-1).reshape(
        *words.shape[:2], -1)
    tag = np.arange(halves.shape[2], dtype=np.uint32) << 16
    experts = np.asarray(cache["said_experts"][:, :n])
    experts = (np.arange(len(experts))[:, None, None] << 16 | experts
               ).transpose(1, 0, 2).reshape(1, n, -1)
    if experts.shape[2] > halves.shape[2]:
        raise ValueError("the experts of a row are more than a set's words")
    return np.concatenate([(halves | tag).view(np.int32), np.pad(
        experts, ((0, 0), (0, 0), (0, halves.shape[2] - experts.shape[2])),
        constant_values=-1).astype(np.int32)])


def _say(said, new, layer):
    """``new`` (B, T, ...) into ``said`` (layers, R, ...) at ``layer``,
    from row 0: the call's rows, a sequence after the other (the first R
    of them, should there be more)."""
    new = new.reshape(1, -1, *new.shape[2:])[:, :said.shape[1]]
    return jax.lax.dynamic_update_slice(
        said, new, (layer,) + (0,) * (said.ndim - 1))


def _rows_first(x):
    """``x`` as it is, its last axis the one that runs fastest in
    memory. What is cut out of a cache's stack is held to that, so that
    the stack the layer scan carries keeps the layout it came in with:
    left to itself the compiler lays a whole stack out the way one
    reader of a slice would like it (the rotary keys' gather) and
    brackets every call with two transposing copies of the leaf (read
    off the compiled programs; ``tests/aot_compile_check.py`` holds them
    to none)."""
    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def _write_index_keys(stack, new, layer, first, start_pos):
    """``new`` (B, T, index_dim) into the stack (L, B', S, index_dim) at
    layer ``layer``: sequence b's T rows from row ``start_pos[b]`` of
    cache row ``first + b``, and nothing else."""
    new = _rows_first(new.astype(stack.dtype))
    for b in range(new.shape[0]):
        stack = jax.lax.dynamic_update_slice(
            stack, new[None, b:b + 1], (layer, first + b, start_pos[b], 0))
    return stack


def _gather_rows(stack, layer, first, rows, window: int):
    """The rows ``rows`` (B, K) of layer ``layer``, sequences ``first ..
    first + B`` out of the two stacks -> (latent rows (B, K, kv_rank),
    rotary keys (B, K, rope)): the latent rows straight out of the
    stack, the rotary keys (the rows last, as the expanded form's kernel
    reads them) out of the lanes' read window."""
    latents, keys = stack
    _, lanes, S, rank = latents.shape
    B = rows.shape[0]
    at = ((layer * lanes + first + jnp.arange(B)) * S)[:, None] + rows
    picked = jnp.take(latents.reshape(-1, rank), at, axis=0)
    turned = _rows_first(jax.lax.dynamic_slice(
        keys, (layer, first, 0, 0), (1, B, keys.shape[2], window))[0])
    return picked, jnp.take_along_axis(
        turned, rows[:, None, :], axis=2).swapaxes(1, 2)


def _write_rows(stack, new, layer, first, start_pos):
    """``new`` (B, T, kv_rank + rope) into the two stacks (latent (L,
    B', S, kv_rank), rope_key (L, B', rope, S)) at layer ``layer``:
    sequence b's T rows from row ``start_pos[b]`` of cache row ``first +
    b``, and nothing else."""
    latents, keys = stack
    rank = latents.shape[3]
    rows = new[..., :rank].astype(latents.dtype)
    turned = new[..., rank:].astype(keys.dtype).swapaxes(1, 2)
    for b in range(new.shape[0]):
        latents = jax.lax.dynamic_update_slice(
            latents, rows[None, b:b + 1], (layer, first + b, start_pos[b], 0))
        keys = jax.lax.dynamic_update_slice(
            keys, turned[None, b:b + 1], (layer, first + b, 0, start_pos[b]))
    return latents, keys


def _head(params, x, c: LatentMoEConfig, logits_at):
    """The final norm and the head over the rows whose logits are kept
    -> float32 logits."""
    with jax.named_scope("head"):
        # accumulated in float32, where the other families round the
        # product to the compute type and widen (ROADMAP Queue 3 item 1)
        x = decoder.final_rows(params, x, c, logits_at)
        return jnp.einsum("btd,dv->btv", x, params["lm_head"].astype(c.dtype),
                          preferred_element_type=jnp.float32)


def forward_with_cache(
    params: Dict[str, Any],
    tokens: jax.Array,
    cache: Dict[str, Any],
    start_pos: jax.Array,
    config: LatentMoEConfig,
    *,
    slot: Optional[jax.Array] = None,
    logits_at: Optional[jax.Array] = None,
    rows: Optional[int] = None,
):
    """``llama.forward_with_cache``'s signature and meaning (tokens
    (B, T) appended at ``start_pos`` (B,), ``slot``, ``logits_at``,
    ``rows``) over this family's cache (``init_cache``). T of 1 is a
    decode and attends in the absorbed form, T over 1 a chunk and
    attends in the expanded one; ``rows`` bounds either's read. The
    cache and the counters ride in the carries of the two layer scans
    and are updated in place under a jit that donates the cache.
    ``cache`` may be a tuple of several shards' caches
    (``models/decoder.py``); what the call counts goes into the first
    one's words."""
    c = config
    if c.index_topk:
        return _forward_indexed(params, tokens, cache, start_pos, c,
                                slot=slot, logits_at=logits_at, rows=rows)
    caches, back = decoder.caches_of(cache)
    call = decoder.Call(tokens, start_pos, caches[0]["latent"].shape[2],
                        slot=slot, logits_at=logits_at, rows=rows,
                        shards=len(caches))
    T, pos, first = call.T, call.pos, call.first
    x = decoder.embed(params, tokens, c)
    cos, sin = rope_cos_sin(c, pos)
    live = call.live()
    seen_by_live = jnp.where(live, pos + 1, 0)       # rows a live row sees
    zero = jnp.int32(0)
    if T == 1:
        # the blocks of rows each lane is taken through, a live lane's
        # own or the longest's for all, as the shapes decide
        block, blocks = absorbed_blocks(
            c, (caches[0]["latent"], caches[0]["rope_key"]), call.window,
            seen_by_live[:, 0])
        attended = jnp.stack([zero, zero, seen_by_live.sum(),
                              blocks.sum() * block])
    else:
        blocks = None
        attended = jnp.stack([seen_by_live.sum(),
                              seen_by_live.max(axis=1).sum(), zero, zero])
    attended = attended.astype(jnp.int32)

    def attention(x, shards, layer, i):
        """-> (x, the shards' stacks with layer i's new rows)."""
        def attend(part, latents, q_nope, q_rope, new, blocks):
            with jax.named_scope("kv_write"):
                latents = _write_rows(latents, new, i, first, part.start_pos)
            if T == 1:
                attn = attend_absorbed(c, q_nope, q_rope, latents, i, first,
                                       call.window, part.pos, layer, blocks)
            else:
                attn = attend_expanded(c, q_nope, q_rope, latents, i, first,
                                       call.window, part.start_pos, layer)
            return attn, latents

        with jax.named_scope("attn"):
            h = rms_norm(x, layer["attn_norm"], c.norm_eps)
            q_nope, q_rope = latent_q(c, h, layer, cos, sin)
            new = latent_kv(c, h, layer, cos, sin)
            attn, shards = call.by_shard(attend, shards, q_nope, q_rope, new,
                                         blocks)
            return attn_out(c, x, attn, layer), shards

    def dense_step(x, shards, layer, i):
        x, shards = attention(x, shards, layer, i)
        return dense_mlp(c, x, layer), shards, None

    scanned, experts = moe.split_experts(params["routed"])

    def routed_step(x, shards, layer, i):
        x, shards = attention(x, shards, layer, c.n_dense_layers + i)
        x, counted, _ = moe_mlp(c, x, layer, experts, i, live)
        return x, shards, counted

    x, shards, _ = decoder.scan_layers(
        dense_step, x,
        tuple((each["latent"], each["rope_key"]) for each in caches),
        params["dense"])
    x, shards, counted = decoder.scan_layers(
        routed_step, x, shards, scanned, 5)
    with jax.named_scope("layers"):     # counted beside the scans
        counted = jnp.concatenate([counted, attended * c.n_layers])
    logits = _head(params, x, c, logits_at)
    return logits, back(tuple(
        {"latent": latents, "rope_key": keys, "counts": words}
        for (latents, keys), words in zip(
            shards, decoder.folded(caches, counted))))


def _forward_indexed(params, tokens, cache, start_pos, c: LatentMoEConfig,
                     *, slot, logits_at, rows):
    """``forward_with_cache`` of a model with an indexer. Every layer
    scores the read window's index keys for each of the call's rows
    (``attn_index`` > ``index_q``, ``index_k``, ``index_score``), selects
    each row's ``index_topk`` (``index_select``) and attends to those
    alone: a chunk in the expanded form under the selection's mask
    (``attn_latent_prefill``), a decode lane in the absorbed form over
    its rows gathered (``attn_latent_decode``). What each layer selected
    and each routed layer's router chose is left in the cache
    (``read_choices``)."""
    caches, back = decoder.caches_of(cache)
    call = decoder.Call(tokens, start_pos, caches[0]["latent"].shape[2],
                        slot=slot, logits_at=logits_at, rows=rows,
                        shards=len(caches))
    T, pos, first, window = call.T, call.pos, call.first, call.window
    x = decoder.embed(params, tokens, c)
    cos, sin = rope_cos_sin(c, pos)
    live = call.live()

    def attend(part, state, layer, i, q_nope, q_rope, new, q_index, k_index,
               w_index):
        """-> ((attn, each sequence's live rows' (rows handed to the
        selection, rows it kept) int32 (B, 2)), the shard's stacks with
        layer i's new rows and what the layer selected)."""
        latents, index_keys, said = state[:2], state[2], state[3]
        B = q_nope.shape[0]
        with jax.named_scope("kv_write"):
            latents = _write_rows(latents, new, i, first, part.start_pos)
            index_keys = _write_index_keys(index_keys, k_index, i, first,
                                           part.start_pos)
        with jax.named_scope("attn_index"), jax.named_scope("index_score"):
            scores = index_select.index_scores(
                q_index, w_index, _rows_first(jax.lax.dynamic_slice(
                    index_keys, (i, first, 0, 0),
                    (1, B, window, c.index_dim))[0]), part.start_pos)
        before = jnp.arange(window)[None, None, :] <= part.pos[:, :, None]
        if T > 1:
            with jax.named_scope("attn_index"), \
                    jax.named_scope("index_select"):
                allowed = index_select.select_mask(scores, before,
                                                   c.index_topk)
                bits = index_select.mask_as_bits(allowed, said.shape[2])
            attn = attend_expanded(c, q_nope, q_rope, latents, i, first,
                                   window, part.start_pos, layer, allowed)
        else:
            with jax.named_scope("attn_index"), \
                    jax.named_scope("index_select"):
                chosen_rows, chosen = index_select.select_rows(
                    scores[:, 0], before[:, 0], c.index_topk)
                bits = index_select.rows_as_bits(chosen_rows, chosen,
                                                 said.shape[2])[:, None]
            with jax.named_scope("attn_latent_decode"):
                with jax.named_scope("kv_slice"):
                    picked = _gather_rows(latents, i, first, chosen_rows,
                                          window)
                attn = attend_rows(c, q_nope, q_rope, *picked, chosen, layer)
        with jax.named_scope("attn_index"), jax.named_scope("index_select"):
            mine = part.live()
            counted = jnp.stack([
                jnp.where(mine[..., None], before, False).sum((1, 2)),
                jnp.where(mine, jax.lax.population_count(bits).sum(-1), 0
                          ).sum(1)], axis=1).astype(jnp.int32)
            said = _say(said, bits, i)
        return (attn, counted), (*latents, index_keys, said, state[4])

    def attention(x, shards, layer, i):
        """-> (x, the shards' states with layer i's new rows, the call's
        live rows' (rows handed to a selection, rows it kept) int32
        (2,))."""
        with jax.named_scope("attn"):
            h = rms_norm(x, layer["attn_norm"], c.norm_eps)
            cq = query_latent(c, h, layer)
            q_nope, q_rope = latent_q(c, h, layer, cos, sin, cq)
            new = latent_kv(c, h, layer, cos, sin)
            with jax.named_scope("attn_index"):
                index = index_qkw(c, h, cq, layer, cos, sin)
            (attn, counted), shards = call.by_shard(
                lambda part, state, *rows: attend(part, state, layer, i,
                                                  *rows),
                shards, q_nope, q_rope, new, *index)
            return attn_out(c, x, attn, layer), shards, counted.sum(0)

    def dense_step(x, shards, layer, i):
        x, shards, selected = attention(x, shards, layer, i)
        return dense_mlp(c, x, layer), shards, selected

    scanned, experts = moe.split_experts(params["routed"])

    def routed_step(x, shards, layer, i):
        x, shards, selected = attention(x, shards, layer,
                                        c.n_dense_layers + i)
        x, counted, chose = moe_mlp(c, x, layer, experts, i, live)
        with jax.named_scope("moe"), jax.named_scope("moe_router"):
            _, shards = call.by_shard(
                lambda part, state, mine: (
                    mine[:, :0], (*state[:4], _say(state[4], mine, i))),
                shards, chose)
        return x, shards, jnp.concatenate([counted, selected])

    x, shards, selected = decoder.scan_layers(
        dense_step, x,
        tuple((each["latent"], each["rope_key"], each["index_key"],
               each["said_rows"], each["said_experts"]) for each in caches),
        params["dense"], 2)
    x, shards, counted = decoder.scan_layers(
        routed_step, x, shards, scanned, 7)
    with jax.named_scope("layers"):     # counted beside the scans
        counted, selected = counted[:5], selected + counted[5:]
        zero = jnp.int32(0)
        if T == 1:
            # the rows a lane's gather fetches: index_topk places, of
            # which a lane of fewer rows fills its own
            fetched = live.sum() * min(c.index_topk, window) * c.n_layers
            attended = [zero, zero, selected[1], fetched]
        else:
            expanded = jnp.where(live, pos + 1, 0).max(axis=1).sum()
            attended = [selected[1], expanded * c.n_layers, zero, zero]
        counted = jnp.concatenate([
            counted, jnp.stack([*attended, *selected]).astype(jnp.int32)])
    logits = _head(params, x, c, logits_at)
    n_said = min(call.B // len(caches) * T, caches[0]["said_rows"].shape[1])
    return logits, back(tuple(
        {"latent": state[0], "rope_key": state[1], "index_key": state[2],
         "said_rows": state[3], "said_experts": state[4],
         "said_count": jnp.int32(n_said), "counts": words}
        for state, words in zip(shards, decoder.folded(caches, counted))))


def _import_kernel():
    from ray_tpu.ops import pallas_latent_attention  # noqa: F401
    from ray_tpu.ops import pallas_index_score  # noqa: F401


# Pallas takes 1.2 s to import on a replica's host, a chunk or decode
# program's first trace needs it, and ``setup_s`` is a metric with a
# bound. A process that imports this module to serve goes on to open its
# chip, nine seconds in which Python has nothing to do: the import runs
# beside that, and the two forms' own imports find it done (or wait on
# the module's lock for the rest). PERF.md section 6, PR 50.
threading.Thread(target=_import_kernel, name="import-latent-kernel",
                 daemon=True).start()
