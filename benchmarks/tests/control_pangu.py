"""The pangu family's control (``controls.py`` says what a family's
control file gives): the attention sublayer's five projections of
``models/latent_moe.py`` (the query's down- and up-projection, the
576-wide row's down-projection, the keys' and values' up-projections
from the latent rows in either form of attention, and ``Wo``) with the
operands of their matmuls in fp8 and nothing else changed (the norms,
the rotary turn, the scores, the softmax, the cache and every MLP stay
as they are), patched over the program in the test's (or
``serving_control.py``'s) own process for as long as ``fp8()`` is open,
never in the program. The five hold 197 M of a routed layer's 623 M
parameters and, being met by every row, the largest share of the model's
matmul work (PERF.md section 5).

``fp8_experts()`` is a second control, which ``serving_control.py`` does
not ask for and this file run as a script reads (the review of PR 48:
whether the cell's limits see the expert layer in a lower precision,
where 3 % of a row's assignments reach an expert held here):

    python benchmarks/tests/control_pangu.py <cell> <seeds> [--rehearse]

reads ``serving_control.Probe`` under it on each seed (``n:first``) and
writes ``chiprun_out/serving_control_experts.<cell>.json``."""

import contextlib

from control_llama import to_fp8


def _weights_in_fp8(layer, names):
    return {**layer, **{k: to_fp8(layer[k]) for k in names}}


@contextlib.contextmanager
def fp8():
    """What is traced while this is open runs the attention sublayer's
    projections in fp8: the three that take the hidden stream or give
    it back with both operands rounded, the up-projections of keys and
    values (which the decode form folds into query and output) with
    their weights rounded."""
    from ray_tpu.models import latent_moe as lm

    sound = {name: getattr(lm, name) for name in (
        "latent_q", "latent_kv", "attn_out", "attend_expanded",
        "attend_absorbed")}

    def latent_q(c, h, layer, cos, sin):
        return sound["latent_q"](c, to_fp8(h), _weights_in_fp8(
            layer, ("wdq", "wuq")), cos, sin)

    def latent_kv(c, h, layer, cos, sin):
        return sound["latent_kv"](c, to_fp8(h), _weights_in_fp8(
            layer, ("wdkv",)), cos, sin)

    def attn_out(c, x, attn, layer):
        return sound["attn_out"](c, x, to_fp8(attn), _weights_in_fp8(
            layer, ("wo",)))

    def attend(name):
        def patched(c, q_nope, q_rope, *rest, **kw):
            # ``layer`` is the argument behind the positions
            rest = list(rest)
            at = next(i for i, a in enumerate(rest)
                      if isinstance(a, dict) and "wuk" in a)
            rest[at] = _weights_in_fp8(rest[at], ("wuk", "wuv"))
            return sound[name](c, q_nope, q_rope, *rest, **kw)
        return patched

    patches = {"latent_q": latent_q, "latent_kv": latent_kv,
               "attn_out": attn_out,
               **{n: attend(n) for n in ("attend_expanded",
                                         "attend_absorbed")}}
    for name, fn in patches.items():
        setattr(lm, name, fn)
    try:
        yield
    finally:
        for name, fn in sound.items():
            setattr(lm, name, fn)


def held_expert_ffn_in_fp8(xs, w_gate, w_up, w_down, group_sizes, layer=None):
    """``moe.expert_ffn`` with every operand of its three matmuls in fp8
    (``control_mellum.expert_ffn_in_fp8``), for a layer that holds a
    share: the rows behind the last group are no expert's, the grouped
    matmul leaves there whatever the chip's memory held, and a tensor's
    scale is taken over all of it, so they are zeroed before the
    rounding (the program zeroes them behind the layer)."""
    import jax
    import jax.numpy as jnp

    if layer is not None:
        w_gate, w_up, w_down = (
            jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
            for w in (w_gate, w_up, w_down))
    held = (jnp.arange(xs.shape[0]) < group_sizes.sum())[:, None]
    xs = to_fp8(xs)
    gate = jax.lax.ragged_dot(xs, to_fp8(w_gate), group_sizes)
    up = jax.lax.ragged_dot(xs, to_fp8(w_up), group_sizes)
    return jax.lax.ragged_dot(
        to_fp8(jnp.where(held, jax.nn.silu(gate) * up, 0.0)), to_fp8(w_down),
        group_sizes, preferred_element_type=jnp.float32)


@contextlib.contextmanager
def fp8_experts():
    """What is traced while this is open runs the expert layer in fp8
    and attention as it is: the held experts' three matmuls with both
    operands rounded, whichever way a call multiplies (the two functions
    of ``ops/moe.py`` that ``control_mellum.fp8()`` replaces), and the
    shared expert's three weights rounded (its rows are the router's
    too, which stays float32 on the rows as they are)."""
    import control_mellum
    from ray_tpu.models import latent_moe as lm
    from ray_tpu.ops import moe

    sound = lm.moe_mlp, moe.expert_ffn, moe.expert_ffn_every

    def moe_mlp(c, x, layer, *rest):
        return sound[0](c, x, _weights_in_fp8(layer, lm.SHARED_WEIGHTS),
                        *rest)

    lm.moe_mlp, moe.expert_ffn, moe.expert_ffn_every = (
        moe_mlp, held_expert_ffn_in_fp8,
        control_mellum.expert_ffn_every_in_fp8)
    try:
        yield
    finally:
        lm.moe_mlp, moe.expert_ffn, moe.expert_ffn_every = sound


def main(argv) -> int:
    import json
    import os

    import serving_control as sc
    from benchmarks import serve_load

    rehearse = "--rehearse" in argv
    cell_name, seeds = [a for a in argv if a != "--rehearse"]
    out = {}
    with fp8_experts():
        probe = sc.Probe(cell_name, rehearse)
        for seed in sc.seeds_of(seeds):
            got = probe.read(seed)
            out[seed] = {
                "summary": serve_load.summary(got, probe.check),
                "counted": serve_load.counted(got, probe.check),
                "correct": serve_load.matches_reference(got, probe.check),
                "readings": got}
            print("experts in fp8", seed, "correct", out[seed]["correct"],
                  {k: [round(v[s], 5) for s in ("median", "max")]
                   for k, v in out[seed]["summary"].items()},
                  "counted beside allowed", out[seed]["counted"], flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    tag = ".rehearsal" if rehearse else ""
    with open(f"chiprun_out/serving_control_experts.{cell_name}{tag}.json",
              "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv[1:]))
