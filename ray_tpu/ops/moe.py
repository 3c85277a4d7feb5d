"""Mixture-of-Experts: top-k routing + expert-parallel dispatch.

Absent from the reference (SURVEY.md §2.5 — MoE delegated to
vLLM/deepspeed downstream); built TPU-native. The dispatch/combine are
dense einsums against a capacity-bounded one-hot dispatch tensor — the
MXU-friendly formulation (no gathers/scatters, static shapes), with the
expert dimension sharded over the `expert` mesh axis so XLA lowers the
dispatch einsum into an all-to-all over ICI.

Pieces:
- ``top_k_gating``: softmax router with top-k, capacity dropping, and
  the standard load-balancing auxiliary loss.
- ``moe_ffn``: routed expert FFN (SwiGLU experts) usable inside any
  jitted model; shard params' leading E dim on the `expert` axis.
- ``moe_ffn_dropless``: the same layer for inference, with no capacity
  and no token dropped: the assignments are sorted by expert and each
  expert multiplies its own rows (``expert_ffn``: a grouped matmul that
  visits an expert's weights once and only the row tiles that hold its
  rows: the Pallas kernel of ``ops/pallas_grouped_matmul.py`` wherever
  it can tile the shapes, ``jax.lax.ragged_dot``, the grouped kernel
  the TPU compiler puts in, elsewhere). A call of a
  few rows whose assignments reach every expert anyway (a decode call)
  multiplies its rows by every expert's weights in one batched matmul
  and weighs what an expert was not chosen for by 0: the weights' read
  bounds both, and the batched matmul comes close to it
  (``EVERY_EXPERT_ROWS``). A chip that holds its share of a layer's
  experts (``MoEConfig.held``) routes over all of them and computes its
  own experts' part of the sum; what the absent experts would add is
  left out, and nothing stands in for the chips that hold them. Its
  grouped passes are sized by what it holds: the assignments to held
  experts sort first, and only they are gathered, multiplied, zeroed
  behind and summed back, a slab of them a pass of a loop that runs as
  many passes as the routing needs (``_held_slabs``; one where the
  router spreads evenly), so the result is whole for every routing and
  the rest of T x k goes through no pass at all. The
  scores are a softmax's or a sigmoid's (``scoring``), the chosen
  weights scaled by ``routed_scale``, and a shared expert, where the
  parameters bring one, is added once. No backward pass is written for
  it; training keeps ``moe_ffn``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ._partition import ambient_partition


class GatingResult(NamedTuple):
    dispatch: jax.Array  # (T, E, C) one-hot-ish dispatch weights in {0,1}
    combine: jax.Array  # (T, E, C) combine weights (gate probs)
    aux_loss: jax.Array  # scalar load-balance loss
    expert_load: jax.Array  # (E,) fraction of tokens per expert


def top_k_gating(
    logits: jax.Array,  # (T, E) router logits
    *,
    k: int = 2,
    capacity_factor: float = 1.25,
    min_capacity: int = 4,
    token_mask: jax.Array = None,  # (T,) 1=real token, 0=padding
) -> GatingResult:
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    capacity = max(min_capacity, int(math.ceil(T * k * capacity_factor / E)))

    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (T, k)
    # renormalize the selected gates
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's capacity:
    # cumulative count of prior assignments to the same expert
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # (T, k, E)
    if token_mask is not None:
        # padding tokens get no expert: they consume no capacity, emit
        # zero output, and are excluded from the balance statistics —
        # otherwise the router learns to balance pad tokens
        m32 = token_mask.astype(jnp.float32).reshape(T)
        gate_vals = gate_vals * m32[:, None]
        onehot = onehot * token_mask.astype(jnp.int32).reshape(T, 1, 1)
    flat = onehot.reshape(T * k, E)
    # priority order: all k=0 choices first, then k=1 (standard
    # switch/gshard ordering keeps top-1 assignments dense)
    order = jnp.arange(T * k).reshape(T, k).T.reshape(-1)  # choice-major
    flat_ordered = flat[order]
    pos_ordered = jnp.cumsum(flat_ordered, axis=0) - flat_ordered  # (T*k, E)
    inv = jnp.argsort(order)
    pos = pos_ordered[inv].reshape(T, k, E)
    slot = (pos * onehot).sum(-1)  # (T, k) slot within expert
    keep = slot < capacity

    keep_f = keep[:, :, None, None].astype(jnp.float32)
    if token_mask is not None:
        keep_f = keep_f * token_mask.astype(jnp.float32).reshape(T, 1, 1, 1)
    disp = (
        jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)[..., None]
        * jax.nn.one_hot(slot, capacity, dtype=jnp.float32)[:, :, None, :]
        * keep_f
    )  # (T, k, E, C)
    dispatch = disp.sum(1)  # (T, E, C)
    combine = (disp * gate_vals[:, :, None, None]).sum(1)

    # load-balance aux loss (Switch Transformer): E * sum(f_e * p_e),
    # statistics over REAL tokens only when a mask is given
    if token_mask is not None:
        m32 = token_mask.astype(jnp.float32).reshape(T)
        denom = jnp.maximum(m32.sum(), 1.0)
        me = (probs * m32[:, None]).sum(0) / denom
        ce = onehot.sum(1).astype(jnp.float32).sum(0) / denom
    else:
        me = probs.mean(0)  # mean router prob per expert
        ce = onehot.sum(1).astype(jnp.float32).mean(0)  # fraction routed (pre-drop)
    aux = (me * ce).sum() * E
    return GatingResult(dispatch, combine, aux, ce)


@dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    n_experts: int
    k: int = 2
    capacity_factor: float = 1.25  # the training layer's alone
    # the chosen experts' weights renormalised to sum to 1 (the
    # dropless layer; the training layer always does)
    norm_topk_prob: bool = True
    # the dropless layer's alone. ``scoring``: an expert's score is its
    # "softmax" share over all experts or its own "sigmoid"; the chosen
    # weights are multiplied by ``routed_scale`` (after renormalising).
    # ``held``: the ids, among the router's ``n_experts``, of the experts
    # whose weights the parameters hold, in the order they are stacked;
    # None: every one
    scoring: str = "softmax"
    routed_scale: float = 1.0
    held: Optional[Tuple[int, ...]] = None
    # what the shared expert's result is multiplied by before it is
    # added: 1 / n where the parameters stack n shared experts along the
    # width and the layer adds their mean
    shared_scale: float = 1.0
    # the dropless layer's alone, and architecture: the router's experts
    # stand in ``n_groups`` equal groups, a group's score is the sum of
    # its two largest selection scores, and a token's experts are chosen
    # among its ``groups_kept`` best groups. 1 and 1: no groups
    n_groups: int = 1
    groups_kept: int = 1

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {self.scoring!r}")
        if (self.n_experts % self.n_groups
                or not 1 <= self.groups_kept <= self.n_groups
                or (self.n_groups > 1 and (
                    self.n_experts // self.n_groups < 2
                    or self.groups_kept * (self.n_experts // self.n_groups)
                    < self.k))):
            raise ValueError(
                f"{self.n_experts} experts in {self.n_groups} groups of "
                f"which {self.groups_kept} are kept, {self.k} a token")
        if self.held is not None and (
                len(set(self.held)) != len(self.held)
                or not all(0 <= e < self.n_experts for e in self.held)):
            raise ValueError(
                f"held {self.held} are not distinct ids under "
                f"{self.n_experts}")


def init_moe_params(key, config: MoEConfig, dtype=jnp.bfloat16):
    kw, k1, k2, k3 = jax.random.split(key, 4)
    E, D, F = config.n_experts, config.d_model, config.d_ff
    s_in = 1.0 / math.sqrt(D)
    s_out = 1.0 / math.sqrt(F)
    return {
        "router": (jax.random.normal(kw, (D, E)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(k1, (E, D, F)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(k2, (E, D, F)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k3, (E, F, D)) * s_out).astype(dtype),
    }


def moe_ffn(
    params: dict,
    x: jax.Array,  # (B, S, D)
    config: MoEConfig,
    mask: jax.Array = None,  # (B, S) 1=real token, 0=padding
) -> Tuple[jax.Array, jax.Array]:
    """Routed SwiGLU expert FFN. Returns (out (B,S,D), aux_loss).

    Shard ``params['w_*']`` dim 0 on the `expert` mesh axis and the
    dispatched tokens follow via GSPMD all-to-all; activations stay
    sharded over batch/sequence axes.
    """
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    logits = xt.astype(jnp.float32) @ params["router"]
    gate = top_k_gating(
        logits,
        k=config.k,
        capacity_factor=config.capacity_factor,
        token_mask=None if mask is None else mask.reshape(T),
    )
    # dispatch: (T,D),(T,E,C) -> (E,C,D)
    xe = jnp.einsum("td,tec->ecd", xt, gate.dispatch.astype(x.dtype))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", xe, params["w_up"]
    )
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"])  # (E,C,D)
    # combine back: (E,C,D),(T,E,C) -> (T,D)
    out = jnp.einsum("ecd,tec->td", ye, gate.combine.astype(x.dtype))
    return out.reshape(B, S, D), gate.aux_loss


# ---------------------------------------------------------------------
# The dropless layer (inference). Four named scopes, so that a device
# trace says what routing costs beside the matmuls: moe_router,
# moe_dispatch, moe_experts, moe_combine.
# ---------------------------------------------------------------------

def route_top_k(x: jax.Array, router: jax.Array, config: MoEConfig,
                bias: Optional[jax.Array] = None):
    """x (T, D), router (D, E) -> (weights (T, k) float32, experts
    (T, k) int32): the scores of all experts in float32 (a softmax over
    them, or each one's sigmoid), the k largest, renormalised to sum to
    1 where the configuration says so, then scaled. ``bias`` (E,)
    float32, where the layer has a selection bias: the choice is made on
    ``score + bias`` and the weights are the scores alone. Where the
    configuration has groups, a group is scored by the sum of its two
    largest selection scores, the ``groups_kept`` best groups are kept
    (of equal groups the lower first) and the k experts are the largest
    among them."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    scores = (jax.nn.sigmoid(logits) if config.scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    if bias is None and config.n_groups == 1:
        weights, experts = jax.lax.top_k(scores, config.k)
    else:
        select = scores if bias is None else scores + bias.astype(jnp.float32)
        if config.n_groups > 1:
            by_group = select.reshape(-1, config.n_groups,
                                      config.n_experts // config.n_groups)
            of_group = jax.lax.top_k(by_group, 2)[0].sum(-1)
            _, kept = jax.lax.top_k(of_group, config.groups_kept)
            is_kept = (kept[:, :, None] == jnp.arange(config.n_groups)).any(1)
            select = jnp.where(is_kept[:, :, None], by_group,
                               -jnp.inf).reshape(select.shape)
        _, experts = jax.lax.top_k(select, config.k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if config.norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    if config.routed_scale != 1.0:
        weights = weights * config.routed_scale
    return weights, experts.astype(jnp.int32)


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def split_experts(stack: dict):
    """A stack of routed layers' parameters -> (the leaves a layer scan
    slices, the experts' weights, which ``expert_ffn`` takes whole)."""
    return ({k: a for k, a in stack.items() if k not in EXPERT_WEIGHTS},
            {k: stack[k] for k in EXPERT_WEIGHTS})


def expert_ffn(xs, w_gate, w_up, w_down, group_sizes, layer=None):
    """The grouped SwiGLU: rows ``xs`` (N, D) sorted by expert,
    ``group_sizes`` (E,) rows each; (E, D, F) / (E, F, D) weights ->
    (N, D) float32. An expert with no rows costs nothing and its weights
    are not read; all rows at one expert is one plain matmul. Rows
    behind the last group are no expert's, and what comes out there is
    not a number to keep.

    With ``layer`` (a traced index) the weights are a model's stacked
    (L, E, D, F) / (L, E, F, D) and the experts are that layer's: the
    stack goes in whole. A layer's experts sliced out of the stack would
    be copied, all of them, before every call (a grouped matmul is a
    custom call, which no slice fuses into): at 64 experts of 2304 x 896
    that is 0.8 GB a layer a call.

    Which of the two implementations runs follows from the shapes alone:
    ``ops/pallas_grouped_matmul.py``'s kernel where it can tile them
    (row tiles that follow the groups, an expert's weights read once,
    the layer an index of its block: a served model's chunk calls), and
    elsewhere three ``jax.lax.ragged_dot``, the grouped matmul the TPU
    compiler puts in, over ``L * E`` groups of which all but this
    layer's E have no rows."""
    # Pallas takes 1.2 s to import: a served family's module starts it
    # in a thread of its own import, and this waits for what is left
    from . import pallas_grouped_matmul as kernel

    if kernel.untileable(xs, w_gate, w_down) is None:
        return kernel.grouped_swiglu(xs, w_gate, w_up, w_down, group_sizes,
                                     layer)
    if layer is not None:
        L, E = w_gate.shape[:2]
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros(L * E, group_sizes.dtype), group_sizes, (layer * E,))
        w_gate, w_up, w_down = (
            w.reshape(L * E, *w.shape[2:]) for w in (w_gate, w_up, w_down))
    gate = jax.lax.ragged_dot(xs, w_gate, group_sizes)
    up = jax.lax.ragged_dot(xs, w_up, group_sizes)
    return jax.lax.ragged_dot(
        jax.nn.silu(gate) * up, w_down, group_sizes,
        preferred_element_type=jnp.float32)


# The most rows of a call that go through every expert
# (``expert_ffn_every``) where they make as many assignments as there are
# experts. Under the ridge (240 rows a weight on a v5e) a weight's read
# bounds its matmul whatever rows it multiplies, and T * k >= E
# assignments leave few experts without a row (16 rows, top-8 of 64: 88 %
# have one), so every expert over every row asks for the read the grouped
# matmul asks for; the compiler's grouped kernel (``ragged_dot``) at two
# rows an expert took six times that read on a v5e (PERF.md section 6,
# PR 46), and the program's own (``ops/pallas_grouped_matmul.py``, PR 53)
# visits a row tile an expert, 128 rows of matmul for two. Past these
# rows the (E, T, D) float32 products are no longer small and the
# matmuls' own time shows, so a prefill chunk stays grouped.
EVERY_EXPERT_ROWS = 64


def expert_ffn_every(x, w_gate, w_up, w_down, combine, layer=None):
    """Every row through every expert: ``x`` (T, D), ``combine`` (T, E)
    float32 a row's weight at each expert it chose and 0 at the others
    -> (T, D) float32, the sum ``expert_ffn`` and the combine give. The
    weights are read once, as one batched matmul; ``layer`` takes a
    layer's experts out of a model's stack (a slice that fuses into the
    matmul, which a grouped kernel's does not)."""
    if layer is not None:
        w_gate, w_up, w_down = (
            jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
            for w in (w_gate, w_up, w_down))
    gate = jnp.einsum("td,edf->etf", x, w_gate)
    up = jnp.einsum("td,edf->etf", x, w_up)
    ys = jnp.einsum("etf,efd->etd", jax.nn.silu(gate) * up, w_down,
                    preferred_element_type=jnp.float32)
    # weighed and summed in float32 as the grouped path's combine is (a
    # float32 matmul at the default precision would round both to bf16)
    return (ys * combine.T[:, :, None]).sum(0)


# What a pass over a held share's assignments is sized by (``_slab``):
# this many times what a router that spreads evenly sends to the experts
# held here. The passes that cost by their rows (the gather, the zeroing,
# the sum back) are paid at the slab's size in every call, a second pass
# only by the calls whose routing sends more here than a slab takes, and
# then over the few rows left: at twice the even share openPangu's
# seeded router (2.6 to 2.9 % of its assignments held where 8 of 256 is
# 3.1 %, but not evenly a layer) takes a second pass in a tenth of its
# layer-calls and command-a-plus's in one of a thousand, and a 1024-row
# call's expert layer went from 3.56 ms to 1.63 and from 4.37 to 3.48
# (PERF.md section 6, PR 59); a wider slab would charge every call for
# that tenth. Where every assignment lands here the passes are T * k /
# slab and the layer 13 % slower than one pass over all of it was.
HELD_SLAB_OVER_EVEN = 2


def _slab(config: MoEConfig, assignments: int) -> int:
    """The assignments one pass over a held share takes, of a call's
    ``assignments`` (T * k): ``HELD_SLAB_OVER_EVEN`` times the held
    experts' even share, a whole number of the grouped kernel's row
    tiles (so that the slab's shapes stay on the kernel wherever the
    call's did); all of them where every expert is held or the call is
    too small to compact."""
    if config.held is None or len(config.held) == config.n_experts:
        return assignments
    from .pallas_grouped_matmul import _ROW_TILE

    even = assignments * len(config.held) / config.n_experts
    tiles = math.ceil(HELD_SLAB_OVER_EVEN * even / _ROW_TILE)
    return min(assignments, tiles * _ROW_TILE)


def _add_rows(out, rows, ys):
    """``out`` (T, D) float32 with ``ys`` (C, D) float32 added at the
    rows ``rows`` (C,), which may name a row more than once: a (T, C)
    one-hot of the rows times ``ys`` on the MXU, at the precision that
    keeps float32. A scatter-add of the rows is serial on a v5e (a
    layer of 1024 rows, 7680 wide, read 4.40 ms with it at a slab of
    512 and 1.63 with this: 5.4 us a scattered row), the float32 rows
    split by hand into three bf16 parts cost more in the splitting than
    the three passes they save (1.83), and ``Precision.HIGH`` keeps 16
    bits (PERF.md section 6, PR 59)."""
    hot = (jnp.arange(out.shape[0])[:, None] == rows[None, :]).astype(
        jnp.float32)
    return out + jnp.dot(hot, ys, precision=jax.lax.Precision.HIGHEST)


def _held_slabs(params: dict, x: jax.Array, weights, order, group_sizes,
                slab: int, layer):
    """The routed sum of a layer that holds a share of the experts, over
    the assignments that reach them and no others -> (the sum (T, D)
    float32, the passes run int32). ``order`` (T * k,): the call's
    assignments sorted by expert, those to held experts first (``n`` of
    them: ``group_sizes.sum()``); ``weights`` (T, k) float32.

    A loop over slabs of ``slab`` assignments of the sorted order, as
    many as hold ``n``: one where the router spreads evenly, T * k /
    slab where every assignment lands here, none where none does. A
    pass gathers its assignments' rows, multiplies them by their experts
    (each expert's interval of the order clipped to the slab: one that
    straddles two slabs is read in both), puts 0 behind the last held
    assignment and adds the rows, weighed, into the sum at their
    tokens'. Every held assignment is in exactly one pass, so the sum is
    the whole layer's for every routing."""
    T, D = x.shape
    k = weights.shape[1]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    n = ends[-1]
    # whole slabs, so that the last one's slice starts where it says
    order = jnp.pad(order, (0, -order.shape[0] % slab))
    weights = weights.reshape(T * k)

    def one(carry):
        i, out = carry
        first = i * slab
        with jax.named_scope("moe_dispatch"):
            took = jax.lax.dynamic_slice(order, (first,), (slab,))
            rows = took // k
            xs = x[rows]                              # (slab, D)
            sizes = jnp.clip(jnp.minimum(ends, first + slab)
                             - jnp.maximum(starts, first), 0)
        with jax.named_scope("moe_experts"):
            ys = expert_ffn(xs, params["w_gate"], params["w_up"],
                            params["w_down"], sizes, layer)
            # rows behind the last group are no expert's: whatever the
            # grouped matmul leaves there is not a number to keep, and
            # a weight of 0 would not make it one
            ys = jnp.where((jnp.arange(slab) < n - first)[:, None], ys, 0.0)
        with jax.named_scope("moe_combine"):
            out = _add_rows(out, rows, ys * weights[took][:, None])
        return i + 1, out

    passes = (n + slab - 1) // slab
    _, out = jax.lax.while_loop(
        lambda carry: carry[0] < passes, one,
        (jnp.int32(0), jnp.zeros((T, D), jnp.float32)))
    return out, passes


def _dropless_rows(params: dict, x: jax.Array, live: jax.Array,
                   config: MoEConfig, layer=None, sum_over=None,
                   say_experts: bool = False):
    """x (T, D), live (T,) bool -> (out (T, D), counts int32[4]: the
    live rows' assignments to experts held here, experts held here with
    a live row or more, experts held here, passes over a held share's
    slab); ``sum_over``: the mesh axes the rows are split over, inside a
    shard_map.

    Three ways, by the shapes and the share held alone: a few rows
    through every expert (``EVERY_EXPERT_ROWS``); the assignments sorted
    by expert and all of them gathered, multiplied and gathered back;
    and, where a share of the experts is held and the call is past the
    first, only the assignments to held experts, a slab at a time
    (``_held_slabs``): the rest of T * k weighs 0 and goes through no
    pass at all. ``say_experts``: a third result, the experts the router
    chose for every row, held or not: its own ids, int32 (T, k)."""
    T, D = x.shape
    k = config.k
    E = config.n_experts if config.held is None else len(config.held)
    with jax.named_scope("moe_router"):
        weights, experts = route_top_k(x, params["router"], config,
                                       params.get("router_bias"))
        chose = experts
        if config.held is not None:
            # an assignment's place among the experts held here; one to
            # an absent expert gets the place past them, weighs 0, sorts
            # behind every group and is multiplied by no weight
            place = np.full(config.n_experts, E, np.int32)
            place[list(config.held)] = np.arange(E)
            experts = jnp.asarray(place)[experts]
            weights = jnp.where(experts < E, weights, 0.0)
    # where experts are absent, the scatters below have their place too,
    # which is cut off again: no index is ever out of bounds
    places = E if config.held is None else E + 1
    passes = jnp.int32(0)
    if E <= T * k and T <= EVERY_EXPERT_ROWS:
        with jax.named_scope("moe_dispatch"):
            combine = jnp.zeros((T, places), jnp.float32).at[
                jnp.arange(T)[:, None], experts].set(weights)[:, :E]
        with jax.named_scope("moe_experts"):
            out = expert_ffn_every(
                x, params["w_gate"], params["w_up"], params["w_down"],
                combine, layer).astype(x.dtype)
    else:
        with jax.named_scope("moe_dispatch"):
            flat = experts.reshape(T * k)
            order = jnp.argsort(flat, stable=True)    # assignments by expert
            group_sizes = jnp.zeros(places, jnp.int32).at[flat].add(1)[:E]
        slab = _slab(config, T * k)
        if slab < T * k:
            out, passes = _held_slabs(params, x, weights, order, group_sizes,
                                      slab, layer)
            with jax.named_scope("moe_combine"):
                out = out.astype(x.dtype)
        else:
            with jax.named_scope("moe_dispatch"):
                xs = x[order // k]                    # (T*k, D)
            with jax.named_scope("moe_experts"):
                ys = expert_ffn(xs, params["w_gate"], params["w_up"],
                                params["w_down"], group_sizes, layer)
                if config.held is not None:
                    # rows behind the last group are no expert's:
                    # whatever the grouped matmul leaves there is not a
                    # number to keep
                    ys = jnp.where((flat[order] < E)[:, None], ys, 0.0)
            with jax.named_scope("moe_combine"):
                back = jnp.argsort(order)             # each assignment's row
                ys = ys[back].reshape(T, k, D)
                out = (ys * weights[..., None]).sum(1).astype(x.dtype)
    if "shared_gate" in params:
        with jax.named_scope("moe_shared"):
            # the expert every row goes through, added once: on every
            # chip of a deployment alike, so not a part of the routed sum
            gate = x @ params["shared_gate"]
            up = x @ params["shared_up"]
            shared = (jax.nn.silu(gate) * up) @ params["shared_down"]
            if config.shared_scale != 1.0:
                # several shared experts side by side along the width:
                # their sum, scaled to their mean
                shared = shared * jnp.asarray(config.shared_scale, x.dtype)
            out = out + shared
    with jax.named_scope("moe_combine"):
        # what was asked for: a padded chunk's rows behind its tokens and
        # an idle decode lane are computed and not counted
        asked = jnp.zeros(places, jnp.int32).at[experts].add(
            live[:, None].astype(jnp.int32))[:E]
        counts = jnp.stack([asked.sum().astype(jnp.int32),
                            (asked > 0).sum().astype(jnp.int32),
                            jnp.int32(E), passes])
        if sum_over:
            # every shard of the rows visits its own experts
            counts = jax.lax.psum(counts, sum_over)
    return (out, counts, chose) if say_experts else (out, counts)


def moe_ffn_dropless(params: dict, x: jax.Array, config: MoEConfig,
                     layer=None, live=None, say_experts: bool = False):
    """Routed SwiGLU expert FFN with no capacity: every token goes to
    its ``config.k`` experts. x (..., D) -> (out (..., D), counts): the
    int32 vector (assignments, experts touched, experts held) of this
    call, for the engine's ``moe_*`` counters. ``params``: ``router``
    (D, ``config.n_experts``) (and ``router_bias`` (``config.n_experts``,),
    where the layer chooses its experts by score + bias), ``w_gate`` /
    ``w_up`` (E, D, F) and ``w_down`` (E, F, D) of the E experts held (``config.held``; all of
    them where it is None: an assignment to an expert that is not held
    adds nothing and is not counted), and, where the layer has a shared
    expert, ``shared_gate`` / ``shared_up`` (D, Fs) and ``shared_down``
    (Fs, D). ``layer``: the expert
    weights are a model's stacked ones and this is the layer among them
    (``expert_ffn``); the router is the layer's own. ``live`` (...) bool:
    the rows that are somebody's tokens (default: all); the others are
    computed like them and left out of the counts. ``say_experts``: a
    third result, the experts the router chose for every row, held or
    not, int32 (..., ``config.k``) (a program that says what it chose,
    to be compared under its own choices; one device's rows only).

    The grouped matmul is a Mosaic custom call whichever of
    ``expert_ffn``'s two implementations the shapes choose (the
    program's own Pallas kernel, or the one the TPU compiler makes of
    ``ragged_dot``), which GSPMD cannot partition: under a mesh with
    more than one device the rows are split over the batch axes by hand,
    each shard sorting and multiplying its own rows against the whole of
    the experts (the weights replicated), as ``ops/attention.py`` maps
    its kernel."""
    lead, D = x.shape[:-1], x.shape[-1]
    rows = x.reshape(-1, D)
    live = (jnp.ones(rows.shape[0], bool) if live is None
            else jnp.broadcast_to(live, lead).reshape(-1))
    part = ambient_partition()
    if part is None or part.batch is None:
        out, counts, *chose = _dropless_rows(params, rows, live, config,
                                             layer, say_experts=say_experts)
    elif say_experts:
        raise NotImplementedError("say_experts with the rows split by hand")
    else:
        out, counts = jax.shard_map(
            lambda p, r, a, i: _dropless_rows(p, r, a, config, i,
                                              sum_over=part.batch),
            in_specs=(P(), P(part.batch, None), P(part.batch), P()),
            out_specs=(P(part.batch, None), P()),
            axis_names=part.axes, check_vma=False,
        )(params, rows, live, layer)
    if say_experts:
        return out.reshape(*lead, D), counts, chose[0].reshape(*lead, config.k)
    return out.reshape(*lead, D), counts
