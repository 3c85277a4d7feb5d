"""The deepseek family's control (``controls.py`` says what a family's
control file gives): the attention sublayer's projections of
``models/latent_moe.py`` in an indexed model (the query's down- and
up-projection, the latent row's down-projection, the keys' and values'
up-projections from the latent rows in either form of attention, ``Wo``,
and the indexer's three: its queries', its key's and its weights'
projections) with the operands of their matmuls in fp8 and nothing else
changed: the norms, the rotary turn, the indexer's score and the
selection made of it, the attention scores, the softmax, the caches and
every MLP stay as they are. Every row meets them in every layer. Patched
over the program in the test's (or ``serving_control.py``'s) own process
for as long as ``fp8()`` is open, never in the program.

``router_fault()`` plants the faults only a cell compared under the
engine's own choices can have: an engine that chooses what the
reference would not have, and says so. Of this family's two kinds of
choice: in every layer a query row whose count of attendable rows is a
multiple of 64 (and over ``index_topk``) leaves out its BEST row and
takes the best row passed over, in both forms of the selection (a high
row dropped for a low one); and every 32nd row of a chunk call (row 16,
48, ...) leaves out the last expert it chose for one from the middle of
its ranking (the first expert of the lower half of all ``n_experts`` by
score + bias that is not held here, whatever group it stands in: an
expert the reference would not have chosen, taken and said; not a held
one, so that the rows move by the renormalised weights alone and the
served tokens, which are read against the reference's own choices, stay
a sound engine's), in every routed layer. The reference follows both, so
the rows read as a sound engine's; the margins alone are far."""

import contextlib
import dataclasses

import control_llama


def to_fp8(a):
    """``control_llama.to_fp8`` of a tensor that is not zeros alone. A
    decode call whose lanes are all idle over an empty cache (warm-up
    makes one) attends to zeros, and the per-tensor scale of nothing but
    zeros is 0: 0 / 0 put NaN into the idle lanes' scratch rows, every
    later idle lane read it, and the scale of a tensor with such a lane
    in it turned every live lane's row to NaN too (the control's rows
    behind a decode at the cell's sizes; my chip run, PR 63:
    ``chiprun_out/pr63/read.out``). Zeros stay zeros here."""
    import jax.numpy as jnp

    return jnp.where(jnp.any(a != 0), control_llama.to_fp8(a), a)


def _weights_in_fp8(layer, names):
    return {**layer, **{k: to_fp8(layer[k]) for k in names}}


@contextlib.contextmanager
def fp8():
    """What is traced while this is open runs the attention projections
    and the indexer's in fp8: the three that take the hidden stream or
    give it back with both operands rounded, the up-projections of
    queries, keys and values with their weights rounded."""
    from ray_tpu.models import latent_moe as lm

    names = ("query_latent", "latent_q", "latent_kv", "attend_expanded",
             "attend_rows", "attn_out", "index_qkw")
    sound = {name: getattr(lm, name) for name in names}

    def index_qkw(c, h, cq, layer, cos, sin):
        return sound["index_qkw"](c, to_fp8(h), to_fp8(cq), _weights_in_fp8(
            layer, ("wq_index", "wk_index", "w_index")), cos, sin)

    def query_latent(c, h, layer):
        return sound["query_latent"](c, to_fp8(h), _weights_in_fp8(
            layer, ("wdq",)))

    def latent_q(c, h, layer, cos, sin, cq=None):
        return sound["latent_q"](c, h, _weights_in_fp8(layer, ("wuq",)), cos,
                                 sin, cq)

    def latent_kv(c, h, layer, cos, sin):
        return sound["latent_kv"](c, to_fp8(h), _weights_in_fp8(
            layer, ("wdkv",)), cos, sin)

    def attn_out(c, x, attn, layer):
        return sound["attn_out"](c, x, to_fp8(attn), _weights_in_fp8(
            layer, ("wo",)))

    def attend(name):
        def patched(*args, **kw):
            # ``layer`` is the one dict among the arguments
            args = [_weights_in_fp8(a, ("wuk", "wuv"))
                    if isinstance(a, dict) and "wuk" in a else a
                    for a in args]
            return sound[name](*args, **kw)
        return patched

    patches = {"query_latent": query_latent, "latent_q": latent_q,
               "latent_kv": latent_kv, "attn_out": attn_out,
               "index_qkw": index_qkw,
               "attend_expanded": attend("attend_expanded"),
               "attend_rows": attend("attend_rows")}
    for name, fn in patches.items():
        setattr(lm, name, fn)
    try:
        yield
    finally:
        for name, fn in sound.items():
            setattr(lm, name, fn)


@contextlib.contextmanager
def router_fault():
    """What is traced while this is open chooses otherwise than the
    model at one row in 64 (its rows) and one in 32 (its experts; the
    module docstring), and ``read_choices`` says what was chosen."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import index_select, moe

    sound = {(mod, name): getattr(mod, name) for mod, name in (
        (index_select, "select_mask"), (index_select, "select_rows"),
        (moe, "route_top_k"))}

    def hit(valid, k):
        n = valid.sum(-1)
        return (n % 64 == 0) & (n > k)

    def select_mask(scores, valid, k):
        wider = sound[index_select, "select_mask"](scores, valid, k + 1)
        best = jnp.argmax(jnp.where(valid, scores, -jnp.inf), axis=-1)
        but_best = wider & (jnp.arange(scores.shape[-1]) != best[..., None])
        return jnp.where(hit(valid, k)[..., None], but_best,
                         sound[index_select, "select_mask"](scores, valid, k))

    def select_rows(scores, valid, k):
        if scores.shape[-1] <= k:
            return sound[index_select, "select_rows"](scores, valid, k)
        rows, chosen = sound[index_select, "select_rows"](scores, valid, k + 1)
        here = hit(valid, k)[:, None]
        return (jnp.where(here, rows[:, 1:], rows[:, :k]),
                jnp.where(here, chosen[:, 1:], chosen[:, :k]))

    def route_top_k(x, router, config, bias=None):
        k, route = config.k, sound[moe, "route_top_k"]
        weights, experts = route(x, router, config, bias)
        plain = dataclasses.replace(config, norm_topk_prob=False,
                                    routed_scale=1.0)
        raw, _ = route(x, router, plain, bias)     # the chosen ones' scores
        # every expert in the order of its selection score, no group dropped
        every, ranked = route(x, router, dataclasses.replace(
            plain, k=config.n_experts, n_groups=1, groups_kept=1), bias)
        # the first expert of the lower half of that order that is not
        # held here: what it adds is left out either way, so the rows
        # change by the weights' renormalisation alone
        held = jnp.asarray(config.held if config.held is not None
                           else range(config.n_experts))
        low = jnp.arange(config.n_experts) >= config.n_experts // 2
        passed = ~(ranked[:, :, None] == held).any(-1) & low
        at = jnp.argmax(passed, axis=1)[:, None]
        raw = jnp.concatenate(
            [raw[:, :k - 1], jnp.take_along_axis(every, at, 1)], 1)
        taken = jnp.concatenate(
            [experts[:, :k - 1], jnp.take_along_axis(ranked, at, 1)], 1)
        if config.norm_topk_prob:
            raw = raw / raw.sum(-1, keepdims=True)
        raw = raw * config.routed_scale
        here = ((jnp.arange(x.shape[0]) % 32 == 16) & passed.any(1))[:, None]
        return (jnp.where(here, raw, weights),
                jnp.where(here, taken, experts))

    patches = {(index_select, "select_mask"): select_mask,
               (index_select, "select_rows"): select_rows,
               (moe, "route_top_k"): route_top_k}
    for (mod, name), fn in patches.items():
        setattr(mod, name, fn)
    jax.clear_caches()      # the sound programs, traced before this
    try:
        yield
    finally:
        for (mod, name), fn in sound.items():
            setattr(mod, name, fn)
        jax.clear_caches()
