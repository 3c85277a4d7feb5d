"""A routed family's arithmetic, and the two programs an engine has of
it: dense leading layers and then routed ones, each a pre-norm causal
mixer over the positions and then a dense SwiGLU or a top-k mixture of
SwiGLU experts of which this chip holds a few (sigmoid or softmax
scores, a correction bias in the selection, groups of experts of which
the best few are kept by the sum of their two best scores, the chosen
weights renormalised and scaled, a shared expert), an embedding and a
head. ``pattern`` says which mixer each layer of a period has:

``L``  latent attention: queries (through a normed low-rank bottleneck
       where ``q_rank`` is set) and keys / values through a normed
       low-rank bottleneck, a second part of ``rope_dim`` beside each
       head's (turned by its row's position unless ``rotary`` is 0), so
       that its logits are as flat at these seeded weights as such a
       model's are. What a slot keeps of it is a row a position.
``S``  a gated delta rule over a state: q, k and v through a causal
       depthwise convolution of 4 rows and SiLU, q and k of unit length
       a head, a decay a channel from a gate of rank ``state_dim``, a
       beta a head; ``S_t = (I - b k k^T) diag(a) S_{t-1} + b k v^T``,
       ``o = S_t^T q``; each head's output normed, gated by a sigmoid
       from a second such gate, and projected. What a slot keeps of it
       is one float32 state ``(heads, dk, dv)`` and the convolution's
       last 3 input rows, whatever its length: a call made
       twice moves it twice (``server.probe_rows`` makes none twice).

``forward`` is one function of seeded bf16 weights over whole sequences,
evaluated in float32 at ``highest`` (``reference_logits``: what a
family's reference does), in bf16 with float32 accumulation, and in bf16
with the shared expert's matmul operands in fp8 (the control). It
returns the logits, each routed layer's choices, and the margin by which
an expert held here was chosen or passed over, least over the held
experts and the layers, in units of the router's logits (PERF.md section
6, PR 34: what a rule that spares near-tied positions would have had to
mark them by). ``readings`` lays the sides beside each other position by
position; ``reach`` makes a flip on purpose and reads the rows behind.
``reference_routed`` is the float32 side with every routed layer's
experts handed to it (``choices``: what an engine said it chose), and
``_handed_margin`` how far the worst of them lies under its own k-th
best: the reference of a cell compared under the engine's own routing
choices (benchmarks/README.md, "A served family"; PERF.md section 6, PR
61).

``Engine`` is what ``benchmarks/server.py`` ``reference_readings`` holds
of an engine and nothing more of one (benchmarks/README.md, "A served
family", has the list): ``_prefill`` of one chunk of one sequence into a
slot of its cache with ``length`` traced, ``_decode`` of one greedy
token a lane; attention in the absorbed form over the cached latent rows
(another order of summation than ``forward``'s, as an engine's is), a
state layer's state and convolution tail carried through the chunk's
real rows and left alone by the rows at or behind ``length``, both
zeroed by a call that starts a sequence; every call leaves what its
routed layers chose in the cache's ``choices`` leaf, which
``read_choices`` reads; built with ``router_fault`` it takes, at one row
in 32 of its first routed layer, the best held expert it had passed
over (and says so). No scheduler and no shards:
``serve`` is a loop of decodes over the lanes, which gives served tokens
for ``served_readings``.

Imports nothing of ``ray_tpu``, of ``benchmarks`` nor of any family.
``serving_control.py standin`` reads it on the chip at ``CHIP``'s,
``CHIP_GROUPED``'s and ``CHIP_HYBRID``'s widths;
``test_serving_reference.py`` keeps it at ``TOY``'s, ``TOY_GROUPED``'s
and ``TOY_HYBRID``'s.
"""

import itertools
import math
import threading
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32, BF16 = jnp.float32, jnp.bfloat16

# what a stack of latent attention alone states of the keys that came
# with the state layers (a set of sizes from before them states none)
NO_STATE = dict(pattern="L", rotary=1, state_heads=0, state_dim=0)
# a routed decoder's widths as one of 32 chips that share each layer
# holds them: 12 of 384 experts, an eighth of the vocabulary's rows
CHIP = dict(NO_STATE, width=7168, experts=384, expert_width=2048, top_k=8, held=12,
            groups=1, groups_kept=1, layers=6, dense_layers=0, dense_width=0,
            vocab=20480, heads=64, head_dim=128, rope_dim=0, q_rank=1536,
            kv_rank=512, seq=2048, seqs=8, score="sigmoid", scale=2.827,
            std=0.02)
# and with the second discrete choice, as one of 16 chips holds them: 16
# of 256 experts (half of one of 8 groups, of which 4 are kept), a dense
# leading layer, a rotary part of 64 beside each head's 128, an eighth
# of the vocabulary: 5.5 G weights, 11 GB of bf16
CHIP_GROUPED = dict(NO_STATE, width=7168, experts=256, expert_width=2048, top_k=8,
                    held=16, groups=8, groups_kept=4, layers=6,
                    dense_layers=1, dense_width=18432, vocab=16160,
                    heads=128, head_dim=128, rope_dim=64, q_rank=1536,
                    kv_rank=512, seq=1024, seqs=2, score="sigmoid",
                    scale=2.5, std=0.02)
# a test's sizes: the weights wider, so that a sublayer still adds about
# what the residual stream carries
TOY = dict(NO_STATE, width=128, experts=32, expert_width=64, top_k=4, held=4, groups=1,
           groups_kept=1, layers=3, dense_layers=0, dense_width=0, vocab=128,
           heads=2, head_dim=16, rope_dim=0, q_rank=64, kv_rank=32, seq=64,
           seqs=8, score="sigmoid", scale=2.0, std=0.09)
TOY_GROUPED = dict(TOY, groups=4, groups_kept=2, layers=4, dense_layers=1,
                   dense_width=256, rope_dim=8, seq=128)
# a hybrid: three state layers to one of latent attention, as one of 4
# chips that share each layer holds its first 8 layers: a dense leading
# layer, 64 of 256 experts, a quarter of the vocabulary, the queries not
# through a bottleneck and no part turned: 3.77 G weights, 7.5 GB of
# bf16; a lane keeps 12.6 MB of state and 2304 B a token of latent rows
CHIP_HYBRID = dict(width=2304, experts=256, expert_width=1024, top_k=8,
                   held=64, groups=1, groups_kept=1, layers=8, dense_layers=1,
                   dense_width=9216, vocab=40960, heads=32, head_dim=128,
                   rope_dim=64, rotary=0, q_rank=0, kv_rank=512,
                   pattern="SSSL", state_heads=32, state_dim=128, seq=1024,
                   seqs=2, score="sigmoid", scale=2.446, std=0.02)
# a test's: as ``CHIP_HYBRID``, state layers BEHIND routed layers (a
# dense leading layer, then seven routed ones, SSSL twice) and half of
# the experts held, so that a routed layer's flip is carried through the
# states into the rows behind it: against the reference's own choices
# most of a sound engine's compared rows read over any limit that the
# fp8 control still reads over (the wall: PERF.md section 6, PR 44), and
# under the engine's own choices every one reads under it (PR 61)
TOY_HYBRID = dict(TOY, layers=8, dense_layers=1, dense_width=256, rope_dim=8,
                  rotary=0, q_rank=0, pattern="SSSL", state_heads=2,
                  state_dim=16, seq=128, held=16)
SIZES = tuple(CHIP)     # the keys a set of sizes has
CONV = 4                # rows a state layer's convolution spans
KINDS = "LS"            # the mixers a pattern is written in


def frozen(sizes: dict) -> tuple:
    """``sizes`` (or a configuration's dict that holds them, its
    ``vocab_size`` the vocabulary) as a jitted function's static
    argument."""
    sizes = dict(NO_STATE, **sizes)
    sizes["vocab"] = sizes.get("vocab", sizes.get("vocab_size"))
    return tuple(sorted((k, sizes[k]) for k in SIZES))


def kinds_of(s: dict) -> list:
    """The mixer of each layer: ``pattern``, period after period."""
    return [s["pattern"][i % len(s["pattern"])] for i in range(s["layers"])]


def runs_of(s: dict) -> list:
    """The routed layers in runs of one mixer: [(mixer, layers)]."""
    return [(kind, len(list(run))) for kind, run in itertools.groupby(
        kinds_of(s)[s["dense_layers"]:])]


def key_of(seed: int):
    """A key from any whole-number seed (the driver's pass 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def fp8(a):
    """Rounded to 4 exponent and 3 mantissa bits under a per-tensor
    scale."""
    f = a.astype(F32)
    scale = jnp.max(jnp.abs(f)) / 240.0
    return (jax.lax.reduce_precision(f / scale, exponent_bits=4,
                                     mantissa_bits=3) * scale).astype(a.dtype)


def _mm(a, b, side):
    if side == "f32":
        return jnp.matmul(a.astype(F32), b.astype(F32), precision="highest")
    return jnp.matmul(a.astype(BF16), b,
                      preferred_element_type=F32).astype(BF16)


def _ein(spec, a, b, side):
    if side == "f32":
        return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                          precision="highest")
    a, b = a.astype(BF16), b.astype(BF16)
    if jax.default_backend() == "cpu":
        # its dot has no bf16 x bf16 -> f32 for every shape; bf16
        # products are exact in float32, so this is the same sum
        return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                          precision="highest")
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def _norm(x):
    f = x.astype(F32)
    return (f * jax.lax.rsqrt(jnp.mean(f * f, -1, keepdims=True) + 1e-6)
            ).astype(x.dtype)


def _swiglu(h, w, side, lower=lambda a: a):
    """``w`` (3, d, f): gate, up and the down projection transposed;
    ``lower`` rounds every matmul operand."""
    gate, up = (_mm(lower(h), lower(w[i]), side) for i in (0, 1))
    return _mm(lower(jax.nn.silu(gate) * up), lower(w[2].T), side)


def _rope(x, pos):
    """x (n, ..., r) turned by its row's position, in float32."""
    r = x.shape[-1]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = pos.astype(F32)[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    f = x.astype(F32)
    a, b = f[..., :r // 2], f[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)],
                           -1).astype(x.dtype)


@partial(jax.jit, static_argnames=("sizes",))
def init_params(key, *, sizes):
    """Every weight from the key in one program, bf16 N(0, std) (the
    embedding N(0, 1); a dense layer's down projection narrower, so that
    the layer adds what an expert does; the router's correction bias
    float32 N(0, 0.01); a state layer's convolution N(0, 1 / 4), and
    its decay's float32 ``rate``, a head, from 1 to 16 and ``dt_bias``,
    a channel, the softplus' inverse of 0.001 to 0.1, both spread evenly
    in the logarithm, as such layers start), an expert at a time so that
    no float32 copy of a stack is ever alive: ``embed``, ``head``,
    ``dense`` (a list of layers) and ``routed`` (a list of runs: the
    routed layers that follow each other with one mixer, stacked)."""
    s = dict(sizes)
    d, f, hd, r = s["width"], s["expert_width"], s["head_dim"], s["rope_dim"]
    hs, c = s["state_heads"], s["state_heads"] * s["state_dim"]
    queries = {"wq_a": (d, s["q_rank"]),
               "wq_b": (s["q_rank"], s["heads"] * (hd + r))} if s["q_rank"] \
        else {"wq": (d, s["heads"] * (hd + r))}
    mixer = {"L": {**queries, "wkv_a": (d, s["kv_rank"] + r),
                   "wkv_b": (s["kv_rank"], s["heads"] * 2 * hd),
                   "wo": (s["heads"] * hd, d)},
             "S": {"wqkv": (d, 3 * c), "wconv": (CONV, 3 * c),
                   "wf_a": (d, s["state_dim"]), "wf_b": (s["state_dim"], c),
                   "wbeta": (d, hs), "wg_a": (d, s["state_dim"]),
                   "wg_b": (s["state_dim"], c), "wo": (c, d)}}

    def normal(k, shape, std=s["std"]):
        return (std * jax.random.normal(k, shape, F32)).astype(BF16)

    def layer(k, kind, more):
        shapes = {**mixer[kind], **more}
        keys = jax.random.split(k, len(shapes) + 2)
        w = {name: normal(kk, shape)
             for kk, (name, shape) in zip(keys, shapes.items())}
        if kind == "S":
            k_conv, k_rate, k_dt = jax.random.split(
                jax.random.fold_in(k, len(shapes)), 3)
            w["wconv"] = normal(k_conv, shapes["wconv"], 1.0 / CONV)
            w["rate"] = jnp.exp(jax.random.uniform(
                k_rate, (hs,), F32, 0.0, math.log(16.0)))
            dt = jnp.exp(jax.random.uniform(
                k_dt, (c,), F32, math.log(0.001), math.log(0.1)))
            w["dt_bias"] = jnp.log(jnp.expm1(dt))
        return w, keys[-2], keys[-1]

    def dense(k, kind):
        w, k_mlp, _ = layer(k, kind, {})
        fd = s["dense_width"]
        ks = jax.random.split(k_mlp, 3)
        w["dense"] = jnp.stack([
            normal(ks[0], (d, fd)), normal(ks[1], (d, fd)),
            normal(ks[2], (d, fd), s["std"] * math.sqrt(f / fd))])
        return w

    def routed(k, kind):
        w, k_held, k_bias = layer(k, kind, {"router": (d, s["experts"]),
                                            "shared": (3, d, f)})
        w["held"] = jax.lax.map(lambda kk: normal(kk, (3, d, f)),
                                jax.random.split(k_held, s["held"]))
        w["bias"] = 0.01 * jax.random.normal(k_bias, (s["experts"],), F32)
        return w

    n_dense, kinds = s["dense_layers"], kinds_of(s)
    keys = jax.random.split(key, s["layers"] + 2)
    ends = list(itertools.accumulate([n for _, n in runs_of(s)],
                                     initial=n_dense))
    return {"embed": normal(keys[-2], (s["vocab"], d), 1.0),
            "head": normal(keys[-1], (d, s["vocab"])),
            "dense": [dense(k, kind) for k, kind in zip(
                keys[:n_dense], kinds)],
            "routed": [jax.lax.map(partial(routed, kind=kind), keys[lo:hi])
                       for (kind, _), lo, hi in zip(
                           runs_of(s), ends, ends[1:])]}


def _route(h, w, s, force, choices=None):
    """The router in float32 on every side, as such models run it:
    (chosen (S, k), the held experts' weights (S, held), margin (S,)).
    Where the experts come in groups, only those of the ``groups_kept``
    groups whose two best selection scores sum highest can be chosen.
    ``force`` (S,) moves the best held expert that was passed over into
    the choice: a flip made on purpose. The margin is the experts' alone:
    it does not see a group's near-tie. ``choices`` (S, k) takes the
    place of the top-k: the experts another side chose, weighted by this
    side's own scores of them; the margin is then ``_handed_margin``'s,
    how far the worst of them lies under this side's own k-th best."""
    k, held = s["top_k"], s["held"]
    z = jnp.matmul(h.astype(F32), w["router"].astype(F32), precision="highest")
    scores = jax.nn.sigmoid(z) if s["score"] == "sigmoid" \
        else jax.nn.softmax(z, -1)
    select = every = scores + w["bias"]
    if s["groups"] > 1:
        by_group = select.reshape(select.shape[0], s["groups"], -1)
        best = jax.lax.top_k(by_group, 2)[0].sum(-1)            # (S, groups)
        last_kept = jax.lax.top_k(best, s["groups_kept"])[0][:, -1:]
        select = jnp.where((best >= last_kept)[:, :, None], by_group,
                           -jnp.inf).reshape(select.shape)
    if force is not None:
        last_in = jax.lax.top_k(select, k)[0][:, -1:]
        passed = jnp.where(select[:, :held] >= last_in, -jnp.inf,
                           scores[:, :held])
        select = jnp.where(
            (force[:, None] > 0) & (jax.nn.one_hot(
                passed.argmax(-1), s["experts"]) > 0), jnp.inf, select)
    ranked, order = jax.lax.top_k(select, k + 1)
    chosen = order[:, :k] if choices is None else choices
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = s["scale"] * picked / picked.sum(-1, keepdims=True)
    of_held = jnp.where(chosen[:, :, None] == jnp.arange(held), weights[
        :, :, None], 0.0).sum(1)
    slope = scores * (1.0 - scores)
    if choices is not None:
        return chosen, of_held, _handed_margin(every, slope, choices, s)
    # a held expert in the choice: over the best passed over; one
    # passed over: under the last chosen. In units of the router's
    # logits: the gap of the two selection scores over their slopes (a
    # saturated score moves little for the same noise in its logit)
    edge = jnp.take_along_axis(slope, order[:, k - 1:], -1)   # last in, out
    mine, inside = select[:, :held], select[:, :held] >= ranked[:, k - 1:k]
    gap = jnp.where(inside, mine - ranked[:, k:], ranked[:, k - 1:k] - mine)
    across = jnp.where(inside, edge[:, 1:], edge[:, :1])
    margin = (gap / jnp.sqrt(slope[:, :held] ** 2 + across ** 2)).min(-1)
    return chosen, of_held, margin


def _handed_margin(select, slope, choices, s):
    """(S,): how far the worst of the experts ``choices`` (S, k) lies
    under this side's own k-th best selection score ``select`` (S, E,
    before any group is masked), in units of the router's logits as
    ``_route`` takes a margin (the gap of the two scores over their
    slopes); 0 where the two sets are equal. Where the experts come in
    groups a handed expert shows that its group was kept: such a group
    this side would have dropped is judged the same way, its score (the
    sum of its two best) under the last group this side kept, over the
    slopes of the four experts that make the two sums; the experts are
    then ranked among the groups this side keeps once the handed ones
    are (all of the handed ones, should they be more than are kept),
    and the margin is the larger of the two."""
    k = s["top_k"]
    of_groups = 0.0
    if s["groups"] > 1:
        n = select.shape[0]
        by_group = select.reshape(n, s["groups"], -1)
        two, at = jax.lax.top_k(by_group, 2)
        best = two.sum(-1)                                      # (S, groups)
        steep = (jnp.take_along_axis(slope.reshape(by_group.shape), at, -1)
                 ** 2).sum(-1)
        kept, kept_at = jax.lax.top_k(best, s["groups_kept"])
        last, last_steep = kept[:, -1:], jnp.take_along_axis(
            steep, kept_at[:, -1:], -1)
        handed = (choices[:, :, None] // by_group.shape[-1]
                  == jnp.arange(s["groups"])).any(1)            # (S, groups)
        of_groups = jnp.where(handed, (last - best) / jnp.sqrt(
            steep + last_steep), 0.0).max(-1)
        forced = jnp.where(handed, jnp.inf, best)
        edge = jax.lax.top_k(forced, s["groups_kept"])[0][:, -1:]
        select = jnp.where((forced >= edge)[:, :, None], by_group,
                           -jnp.inf).reshape(select.shape)
    ranked, order = jax.lax.top_k(select, k)
    across = jnp.take_along_axis(slope, order[:, k - 1:], -1)
    gap = ranked[:, k - 1:] - jnp.take_along_axis(select, choices, -1)
    mine = jnp.take_along_axis(slope, choices, -1)
    of_experts = (gap / jnp.sqrt(mine ** 2 + across ** 2)).max(-1)
    return jnp.maximum(jnp.maximum(of_experts, of_groups), 0.0)


def _qkv(h, w, s, side, pos):
    """Of normed rows h (n, D) at positions ``pos``: the queries' plain
    part (n, H, hd), their rotary part (n, H, r) or None, and the row a
    cache keeps: the normed latent and, behind it, the rotary key (the
    second part is not turned where ``rotary`` is 0)."""
    n, hd, r, c = h.shape[0], s["head_dim"], s["rope_dim"], s["kv_rank"]
    q = _mm(_norm(_mm(h, w["wq_a"], side)), w["wq_b"], side) if s["q_rank"] \
        else _mm(h, w["wq"], side)
    q = q.reshape(n, s["heads"], hd + r)
    kv = _mm(h, w["wkv_a"], side)
    if not r:
        return q, None, _norm(kv)
    turned = partial(_rope, pos=pos) if s["rotary"] else lambda x: x
    return q[..., :hd], turned(q[..., hd:]), jnp.concatenate(
        [_norm(kv[:, :c]), turned(kv[:, c:])], -1)


def _attend_whole(qn, qr, latent, w, s, side):
    """A whole sequence's causal attention, keys and values expanded
    from the latent rows: (n, H * hd)."""
    n, heads, hd, c = qn.shape[0], s["heads"], s["head_dim"], s["kv_rank"]
    k, v = jnp.split(_mm(latent[:, :c], w["wkv_b"], side).reshape(
        n, heads, 2 * hd), 2, -1)
    att = _ein("shk,thk->hst", qn, k, side)
    if qr is not None:
        att = att + _ein("shr,tr->hst", qr, latent[:, c:], side)
    att = jnp.where(jnp.tril(jnp.ones((n, n), bool)),
                    att / math.sqrt(hd + s["rope_dim"]), -jnp.inf)
    probs = jax.nn.softmax(att, -1).astype(qn.dtype)
    return _ein("hst,thk->shk", probs, v, side).astype(qn.dtype).reshape(
        n, heads * hd)


def _attend_cached(qn, qr, rows, pos, w, s, side):
    """Queries (n, H, hd) at positions ``pos`` (n,) over one slot's
    cached rows (T, c + r), those at or before each query's position; in
    the absorbed form: the keys' expansion folded into the queries, the
    values' applied after the rows are mixed."""
    heads, hd, c = s["heads"], s["head_dim"], s["kv_rank"]
    wk, wv = jnp.split(w["wkv_b"].reshape(c, heads, 2 * hd), 2, -1)
    q_lat = _ein("nhk,chk->nhc", qn, wk, side).astype(qn.dtype)
    att = _ein("nhc,tc->hnt", q_lat, rows[:, :c], side)
    if qr is not None:
        att = att + _ein("nhr,tr->hnt", qr, rows[:, c:], side)
    seen = jnp.arange(rows.shape[0])[None, :] <= pos[:, None]
    att = jnp.where(seen[None], att / math.sqrt(hd + s["rope_dim"]), -jnp.inf)
    probs = jax.nn.softmax(att, -1).astype(qn.dtype)
    mixed = _ein("hnt,tc->nhc", probs, rows[:, :c], side).astype(qn.dtype)
    return _ein("nhc,chk->nhk", mixed, wv, side).astype(qn.dtype).reshape(
        qn.shape[0], heads * hd)


def _delta_step(state, q, k, v, a, b):
    """One position of the gated delta rule, float32 at ``highest``:
    state (H, dk, dv), q, k, a (H, dk), v (H, dv), b (H,):
    ``S = (I - b k k^T) diag(a) S + b k v^T``, ``o = S^T q``."""
    state = a[..., None] * state
    seen = jnp.einsum("hk,hkv->hv", k, state, precision="highest")
    state = state + k[..., None] * (b[:, None] * (v - seen))[:, None, :]
    return state, jnp.einsum("hk,hkv->hv", q, state, precision="highest")


def _state_rows(h, w, s, side):
    """What a state layer takes of its normed rows h (n, D) row by row:
    q, k and v before the convolution (n, 3 H d), the decay a channel
    (n, H, d) and the beta a head (n, H), both float32, and the gate on
    the output (n, H d)."""
    n, hs, ds = h.shape[0], s["state_heads"], s["state_dim"]
    gate = _mm(_mm(h, w["wf_a"], side), w["wf_b"], side).astype(F32)
    decay = jnp.exp(-jnp.repeat(w["rate"], ds) * jax.nn.softplus(
        gate + w["dt_bias"])).reshape(n, hs, ds)
    beta = jax.nn.sigmoid(_mm(h, w["wbeta"], side).astype(F32))
    return (_mm(h, w["wqkv"], side), decay, beta,
            _mm(_mm(h, w["wg_a"], side), w["wg_b"], side))


def _state_scan(x, decay, beta, state, tail, w, s, length):
    """One sequence's rows through the convolution and the recurrence,
    from ``state`` (H, dk, dv) and ``tail`` (CONV - 1, 3 H d), the rows
    before: each head's output (n, H, dv) float32, and the state and the
    tail as row ``length`` - 1 leaves them (``length`` None: the last
    row); a row at or behind ``length`` moves neither."""
    n, hs, ds = x.shape[0], s["state_heads"], s["state_dim"]
    rows = jnp.concatenate([tail.astype(x.dtype), x])
    mixed = jax.nn.silu(sum(
        rows[i:i + n].astype(F32) * w["wconv"][i].astype(F32)
        for i in range(CONV))).astype(x.dtype).astype(F32)
    q, k, v = (part.reshape(n, hs, ds) for part in jnp.split(mixed, 3, -1))
    q, k = (part * jax.lax.rsqrt(jnp.sum(part * part, -1, keepdims=True)
                                 + 1e-6) for part in (q, k))

    def step(state, row):
        at, q, k, v, a, b = row
        moved, out = _delta_step(state, q, k, v, a, b)
        return (moved if length is None
                else jnp.where(at < length, moved, state)), out

    state, out = jax.lax.scan(step, state,
                              (jnp.arange(n), q, k, v, decay, beta))
    return out, state, jax.lax.dynamic_slice_in_dim(
        rows, n if length is None else length, CONV - 1, 0)


def _state_out(out, gate, w, side):
    """Each head's output normed, gated and projected: (n, D)."""
    n = out.shape[0]
    gated = _norm(out).reshape(n, -1) * jax.nn.sigmoid(gate.astype(F32))
    return _mm(gated.astype(gate.dtype), w["wo"], side)


def _no_state(s, dtype):
    """A sequence's state and convolution tail before its first row."""
    hs, ds = s["state_heads"], s["state_dim"]
    return (jnp.zeros((hs, ds, ds), F32),
            jnp.zeros((CONV - 1, 3 * hs * ds), dtype))


def _mlp(x, w, s, side, force, choices=None):
    """The layer's second half on rows x (n, D): (x after it, chosen
    (n, k) or None in a dense layer, margin (n,) or None)."""
    h = _norm(x)
    if "dense" in w:
        return x + _swiglu(h, w["dense"], side), None, None
    chosen, of_held, margin = _route(h, w, s, force, choices)
    out = _swiglu(h, w["shared"], side, fp8 if side == "fp8" else lambda a: a)

    def add(total, expert):
        weights, gate_of = expert
        return total + gate_of[:, None].astype(total.dtype) * _swiglu(
            h, weights, side), None

    out = jax.lax.scan(add, out, (w["held"], of_held.T))[0]
    return x + out, chosen, margin


def _layers(x, params, s, side, attend, caches, force, choices=None):
    """x (n, D) through the dense layers and then the routed ones (each
    run of them scanned over its stacked weights).
    ``attend(kind, h, w, cache)`` is a layer's mixer over its normed
    input and its share of ``caches`` ({kind: what the layers of that
    mixer keep, their leading axis}; None without a cache), returning
    what the mixer adds and the layer's cache as it leaves it. ``force``
    (n,) flips a choice in the first routed layer; ``choices`` (routed
    layers, n, k) are the experts every routed layer takes in place of
    its own top-k. Returns x, the caches stacked again, chosen (routed
    layers, n, k) and each routed layer's margin (routed layers, n)."""
    done = dict.fromkeys(KINDS, 0)          # layers of each mixer so far
    left = {kind: [] for kind in KINDS}     # and their caches, in order

    def share(kind, n):
        lo = done[kind]
        done[kind] += n
        return None if caches is None else jax.tree_util.tree_map(
            lambda a: a[lo:lo + n], caches[kind])

    for kind, w in zip(kinds_of(s), params["dense"]):
        add, cache = attend(kind, _norm(x), w, jax.tree_util.tree_map(
            lambda a: a[0], share(kind, 1)))
        x, _, _ = _mlp(x + add, w, s, side, None)
        left[kind].append(jax.tree_util.tree_map(lambda a: a[None], cache))

    chosen, margins, before = [], [], 0
    for (kind, n), weights in zip(runs_of(s), params["routed"]):
        forces = None if force is None else jnp.zeros(
            (n,) + force.shape, F32).at[0].set(0.0 if chosen else force)
        handed = None if choices is None else choices[before:before + n]
        before += n

        def body(x, layer, kind=kind):
            w, cache, f, take = layer
            add, cache = attend(kind, _norm(x), w, cache)
            x, picked, margin = _mlp(x + add, w, s, side, f, take)
            return x, (cache, picked, margin)

        x, (cache, picked, margin) = jax.lax.scan(
            body, x, (weights, share(kind, n), forces, handed))
        left[kind].append(cache)
        chosen.append(picked)
        margins.append(margin)
    if caches is not None:
        caches = {kind: jax.tree_util.tree_map(
            lambda *parts: jnp.concatenate(parts), *left[kind])
            for kind in caches}
    return x, caches, jnp.concatenate(chosen), jnp.concatenate(margins)


def _whole(side, s):
    def attend(kind, h, w, cache):
        if kind == "S":
            x, decay, beta, gate = _state_rows(h, w, s, side)
            out, _, _ = _state_scan(x, decay, beta, *_no_state(s, x.dtype),
                                    w, s, None)
            return _state_out(out, gate, w, side), cache
        qn, qr, latent = _qkv(h, w, s, side, jnp.arange(h.shape[0]))
        return _mm(_attend_whole(qn, qr, latent, w, s, side), w["wo"],
                   side), cache
    return attend


@partial(jax.jit, static_argnames=("sizes", "side", "last"))
def _forward(params, tokens, force, choices, *, sizes, side, last):
    s = dict(sizes)

    def one(row):
        toks, f, handed = row
        x = params["embed"][toks].astype(F32 if side == "f32" else BF16)
        x, _, chosen, margin = _layers(x, params, s, side, _whole(side, s),
                                       None, f, handed)
        x = x[-last:] if last else x
        return _mm(_norm(x), params["head"], side).astype(F32), chosen, margin

    return jax.lax.map(one, (tokens, force, choices))


def forward(params, tokens, sizes: dict, side: str, force=None,
            last: int = 0, choices=None) -> dict:
    """The stack over whole sequences ``tokens`` (B, S): logits (B, S or
    ``last``, V) float32, ``chosen`` (routed layers, B, S, k),
    ``margins`` (B, routed layers, S) and ``margin`` (B, S), the least
    over the layers. ``force`` (B, S) flips a choice in the first routed
    layer at the positions it marks; ``choices`` (B, routed layers, S,
    k) are taken in place of every routed layer's own top-k, and the
    margins are then ``_handed_margin``'s."""
    tokens = jnp.asarray(tokens, jnp.int32)
    logits, chosen, margins = _forward(
        params, tokens, None if force is None else jnp.asarray(force, F32),
        None if choices is None else jnp.asarray(choices, jnp.int32),
        sizes=frozen(sizes), side=side, last=last)
    return {"logits": logits, "chosen": jnp.moveaxis(chosen, 0, 1),
            "margins": margins, "margin": margins.min(1)}


def reference_logits(params, tokens, hp: dict, last: int = 0):
    """What a family module gives: the float32 side at ``highest`` over
    one sequence, (S or ``last``, V)."""
    return forward(params, np.asarray(tokens)[None], hp, "f32",
                   last=last)["logits"][0]


def reference_routed(params, tokens, hp: dict, choices, last: int = 0):
    """What a family whose cell says ``"routing": "engine"`` gives
    beside ``reference_logits``: the same float32 pass in which every
    routed layer takes the experts ``choices`` (routed layers, S, k)
    that the engine says it chose, weighted by this pass's own scores of
    them, and ``margin`` (routed layers, S): how far the worst of them
    lies under this pass's own k-th best, in units of the router's
    logits, 0 where it would have chosen the same set."""
    out = forward(params, np.asarray(tokens)[None], hp, "f32", last=last,
                  choices=np.asarray(choices)[None])
    return out["logits"][0], out["margins"][0]


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


def seeded(seed: int, sizes: dict):
    """The weights and ``seqs`` sequences of ``seq`` tokens of a seed."""
    key = key_of(seed)
    return init_params(key, sizes=frozen(sizes)), jax.random.randint(
        jax.random.fold_in(key, 10**6), (sizes["seqs"], sizes["seq"]), 0,
        sizes["vocab"])


def readings(seed: int, sizes: dict) -> dict:
    """Position by position (B, S): the bf16 side's relative RMS and
    choice gap against the float32 side, whether a choice that involves
    a held expert flipped between them, the float32 side's margin, and
    the fp8 control's relative RMS and choice gap; ``logit_rms``, the
    float32 logits' own size, for a gap to be read against."""
    params, tokens = seeded(seed, sizes)
    ref = forward(params, tokens, sizes, "f32")
    out = {"margin": np.asarray(ref["margin"]),
           "logit_rms": float(jnp.sqrt(jnp.mean(ref["logits"] ** 2)))}
    for side in ("bf16", "fp8"):
        got = forward(params, tokens, sizes, side)
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
    the first routed layer of the float32 side: the relative RMS it
    moves that row by, and the rows before and behind it (through
    attention); of the rows behind, the sound side's margin and whether
    a choice of their own flipped with their moved input."""
    params, tokens = seeded(seed, sizes)
    force = np.zeros((sizes["seqs"], sizes["seq"]), np.float32)
    force[:, at] = 1.0
    sound = forward(params, tokens, sizes, "f32")
    moved = forward(params, tokens, sizes, "f32", force=force)
    by = np.asarray(rel_rms(moved["logits"], sound["logits"]))
    return {"at": by[:, at], "before": by[:, :at], "behind": by[:, at + 1:],
            "behind_margin": np.asarray(sound["margin"])[:, at + 1:],
            "behind_flipped": flipped(sound["chosen"], moved["chosen"],
                                      sizes["held"])[:, at + 1:]}


# ------------------------------------------------ the engine's two programs
# the router fault: the rows (by their position in the sequence) at which
# an engine built with ``router_fault`` takes, in its first routed layer,
# the best held expert it had passed over
FAULT_EVERY, FAULT_AT = 32, 7


def _faulted(pos):
    return (pos % FAULT_EVERY == FAULT_AT).astype(F32)


def _said(cache: dict, chosen):
    """The call's choices (routed layers, rows, k) written at the start
    of the cache's ``choices`` leaf, which holds as many rows as the
    largest bucket or the lanes, whichever is more: what
    ``Engine.read_choices`` reads."""
    return jax.lax.dynamic_update_slice(cache["choices"], chosen, (0, 0, 0))


def _by_kind(cache: dict) -> dict:
    """An engine's cache, ``latent`` (layers of L, B, T, c + r) and,
    where the pattern has state layers, ``state`` (layers of S, B, H,
    dk, dv) float32 and ``conv`` (layers of S, B, CONV - 1, 3 H d), as
    ``_layers`` takes it: what the layers of each mixer keep (its
    ``choices`` leaf is no layer's: ``_said``)."""
    out = {"L": cache["latent"]} if "latent" in cache else {}
    if "state" in cache:
        out["S"] = (cache["state"], cache["conv"])
    return out


def _by_name(caches: dict) -> dict:
    """And back."""
    out = {"latent": caches["L"]} if "L" in caches else {}
    if "S" in caches:
        out["state"], out["conv"] = caches["S"]
    return out


def _cached(side, s, slot_rows, write, pos, through):
    """A layer's mixer through the cache. Latent attention: the new
    rows' latent written into ``cache`` (``write``), then the queries at
    ``pos`` over the rows of ``slot_rows(cache)``. A state layer:
    ``through(x, decay, beta, cache)`` carries the state and the tail
    the cache holds through the rows and returns each head's output and
    the cache."""
    def attend(kind, h, w, cache):
        if kind == "S":
            x, decay, beta, gate = _state_rows(h, w, s, side)
            out, cache = through(x, decay, beta, cache, w)
            return _state_out(out, gate, w, side), cache
        qn, qr, latent = _qkv(h, w, s, side, pos.reshape(-1))
        cache = write(cache, latent.astype(BF16))
        return _mm(slot_rows(cache, qn, qr, w), w["wo"], side), cache
    return attend


@partial(jax.jit, static_argnames=("sizes", "side", "bucket", "fault"),
         donate_argnums=(1,))
def _prefill(params, cache, tokens, slot_onehot, start, length, *, sizes,
             side, bucket, fault=False):
    """One chunk ``tokens`` (1, bucket) of one sequence into the slot
    ``slot_onehot`` marks, at rows ``start`` (1,) on: all ``bucket``
    latent rows are written (those at or behind ``length`` are never
    read), a state layer's state and tail are carried through the first
    ``length`` rows, from nothing where ``start`` is 0, and the logits
    (V,) of row ``length`` - 1 of the chunk returned; every routed
    layer's choices at the chunk's rows are left in the cache's
    ``choices`` leaf. ``fault``: the router fault at ``_faulted`` rows."""
    s = dict(sizes)
    slot = slot_onehot.argmax()
    pos = start[0] + jnp.arange(bucket)

    def write(cache, latent):       # (B, T, c + r): the slot's rows
        rows = jax.lax.dynamic_update_slice(
            cache[slot], latent, (start[0], 0))
        return jax.lax.dynamic_update_index_in_dim(cache, rows, slot, 0)

    def over(cache, qn, qr, w):
        return _attend_cached(qn, qr, cache[slot], pos, w, s, side)

    def through(x, decay, beta, cache, w):      # (B, ...) each: the slot's
        held = [jnp.where(start[0] == 0, fresh, mine[slot])
                for fresh, mine in zip(_no_state(s, x.dtype), cache)]
        out, *held = _state_scan(x, decay, beta, *held, w, s, length)
        return out, tuple(
            jax.lax.dynamic_update_index_in_dim(mine, new, slot, 0)
            for mine, new in zip(cache, held))

    x = params["embed"][tokens[0]]
    x, kept, chosen, _ = _layers(x, params, s, side,
                                 _cached(side, s, over, write, pos, through),
                                 _by_kind(cache),
                                 _faulted(pos) if fault else None)
    row = jax.lax.dynamic_index_in_dim(x, length - 1, 0, keepdims=True)
    logits = _mm(_norm(row), params["head"], side).astype(F32)[0]
    return logits, dict(_by_name(kept), choices=_said(cache, chosen))


@partial(jax.jit, static_argnames=("sizes", "side", "fault"),
         donate_argnums=(1,))
def _decode(params, cache, last_tokens, lengths, temps, rng, *, sizes, side,
            fault=False):
    """One greedy token a lane: lane b's ``last_tokens[b]`` written at
    row ``lengths[b]`` of its slot and attended from there (an idle lane
    writes the scratch row ``max_seq`` - 1), every lane's state and tail
    moved by its token (an idle lane's too: the call that next starts a
    sequence there begins from nothing); every routed layer's choices, a
    row a lane, are left in the cache's ``choices`` leaf."""
    s = dict(sizes)
    lanes = jnp.arange(lengths.shape[0])

    def write(cache, latent):       # one row a lane
        return cache.at[lanes, lengths].set(latent)

    def over(cache, qn, qr, w):
        return jax.vmap(lambda q, r, rows, at: _attend_cached(
            q[None], None if r is None else r[None], rows, at[None], w, s,
            side)[0])(qn, qr, cache, lengths)

    def through(x, decay, beta, cache, w):      # one row a lane
        out, *held = jax.vmap(lambda x, a, b, state, tail: _state_scan(
            x[None], a[None], b[None], state, tail, w, s, None))(
                x, decay, beta, *cache)
        return out[:, 0], tuple(held)

    x = params["embed"][last_tokens]
    x, kept, chosen, _ = _layers(
        x, params, s, side, _cached(side, s, over, write, lengths, through),
        _by_kind(cache), _faulted(lengths) if fault else None)
    logits = _mm(_norm(x), params["head"], side).astype(F32)
    return (logits.argmax(-1).astype(jnp.int32),
            dict(_by_name(kept), choices=_said(cache, chosen)), rng)


class Engine:
    """What ``server.reference_readings`` and ``serving_control.broken``
    hold of an engine: the two programs on ``side`` (``bf16``, or
    ``fp8``: the control), one cache shard of ``max_batch`` slots (of
    ``max_seq`` latent rows a layer of latent attention; of a float32
    state and a convolution's tail a state layer), and an engine that is
    always idle."""

    def __init__(self, sizes: dict, params, side: str = "bf16",
                 max_batch: int = 8, max_seq: int = 2048,
                 buckets=(16, 256), router_fault: bool = False):
        self.sizes, self.side, self.params = frozen(sizes), side, params
        self.router_fault = router_fault
        self.buckets, self.prefill_chunk = list(buckets), buckets[-1]
        self.max_batch, self.max_seq = max_batch, max_seq
        s = dict(self.sizes)
        of = {kind: kinds_of(s).count(kind) for kind in KINDS}
        hs, ds = s["state_heads"], s["state_dim"]
        cache = {"latent": jnp.zeros((of["L"], max_batch, max_seq, s[
            "kv_rank"] + s["rope_dim"]), BF16)} if of["L"] else {}
        if of["S"]:
            cache["state"] = jnp.zeros((of["S"], max_batch, hs, ds, ds), F32)
            cache["conv"] = jnp.zeros(
                (of["S"], max_batch, CONV - 1, 3 * hs * ds), BF16)
        # what every call chose (``read_choices``): 4 B an expert, 0.6 MB
        # at 7 routed layers, a chunk of 256 rows and 8 of them a row
        cache["choices"] = jnp.zeros(
            (s["layers"] - s["dense_layers"], max(max_batch, buckets[-1]),
             s["top_k"]), jnp.int32)
        self.shards = [types.SimpleNamespace(cache=cache)]
        self._lock, self._rng = threading.Lock(), jax.random.PRNGKey(0)

    def num_active(self) -> int:
        return 0

    def _prefill(self, params, cache, tokens, slot_onehot, start, length,
                 bucket):
        return _prefill(params, cache, jnp.asarray(tokens),
                        jnp.asarray(slot_onehot), jnp.asarray(start), length,
                        sizes=self.sizes, side=self.side, bucket=bucket,
                        fault=self.router_fault)

    def _decode(self, params, cache, last_tokens, lengths, temps, rng):
        return _decode(params, cache, jnp.asarray(last_tokens),
                       jnp.asarray(lengths), temps, rng, sizes=self.sizes,
                       side=self.side, fault=self.router_fault)

    def read_choices(self, cache):
        """Of the cache a ``_prefill`` or ``_decode`` returned: the
        experts every routed layer chose in that call, int32 (routed
        layers, rows, k), the bucket's rows or the lanes first (what
        lies behind them is an earlier call's)."""
        return cache["choices"]

    def serve(self, prompts: list, max_tokens: list) -> list:
        """Greedy answers of ``max_tokens[i]`` tokens to ``prompts[i]``,
        as many requests at once as there are lanes, a finished lane
        given the next: a prompt goes in chunk by chunk and its last
        row's logits choose the first token; then one ``_decode`` a step
        over every lane, each fed the token the call before returned for
        it. Returns the tokens and the lane of each request."""
        shard, idle = self.shards[0], self.max_seq - 1
        out, lane_of = [[] for _ in prompts], [None] * len(prompts)
        at = [None] * self.max_batch            # the request in each lane
        lens = np.full(self.max_batch, idle, np.int32)
        last = np.zeros(self.max_batch, np.int32)
        temps, todo = np.zeros(self.max_batch, np.float32), 0
        while todo < len(prompts) or any(r is not None for r in at):
            for lane in range(self.max_batch):
                if at[lane] is None and todo < len(prompts):
                    at[lane], lane_of[todo] = todo, lane
                    prompt = np.asarray(prompts[todo], np.int32)
                    onehot = np.zeros(self.max_batch, np.float32)
                    onehot[lane] = 1.0
                    for pos in range(0, len(prompt), self.prefill_chunk):
                        part = prompt[pos:pos + self.prefill_chunk]
                        bucket = next(b for b in self.buckets
                                      if b >= len(part))
                        padded = np.zeros((1, bucket), np.int32)
                        padded[0, :len(part)] = part
                        logits, shard.cache = self._prefill(
                            self.params, shard.cache, padded, onehot,
                            np.asarray([pos], np.int32), len(part),
                            bucket=bucket)
                    last[lane], lens[lane] = int(logits.argmax()), len(prompt)
                    out[todo].append(int(last[lane]))
                    todo += 1
            for lane, request in enumerate(at):     # ended by its count
                if request is not None and len(
                        out[request]) >= max_tokens[request]:
                    at[lane], lens[lane] = None, idle
            if all(r is None for r in at):
                continue
            tokens, shard.cache, self._rng = self._decode(
                self.params, shard.cache, last, lens, temps, self._rng)
            tokens = np.asarray(tokens)
            for lane, request in enumerate(at):
                if request is not None:
                    last[lane] = tokens[lane]
                    lens[lane] += 1
                    out[request].append(int(tokens[lane]))
        return out, lane_of
