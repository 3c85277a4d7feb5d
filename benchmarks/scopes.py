"""The scope of each traced op: which part of the model, which
transform, from the ``jax.named_scope`` paths the program's jitted
functions carry (``ray_tpu/models/llama.py``, ``parallel/train_step.py``,
the engine's programs).

A device trace names an op by its instruction (``fusion.380``) and holds
no scope; the compiled program's text does
(``ray_tpu._private.jax_utils.scope_map``). Instruction names are unique
within one program only, so joining by name alone, as ``scope_seconds``
does, is right where the traced part runs ONE program: the train cells.
The serving cells run several (``jit_prefill`` per bucket,
``jit_decode``) and need the join by (program, instruction) of
``trace_programs.py``.

A program from before the scopes (the parent of the PR that added them)
has none: every share of a scope is then 0 and everything is
unattributed, which is what these functions return without compiling
anything.
"""

from __future__ import annotations

import re
from functools import partial
from typing import Dict, Iterable, Optional

from . import spec, trace_reduce

_WORD = re.compile(r"[A-Za-z_][A-Za-z_0-9]*")
_memo: Dict[str, Dict[str, str]] = {}


def words(path: str) -> frozenset:
    """The names in a scope path: ``jit(step)/loss_and_grad/transpose(
    jvp(head))/dot_general`` holds loss_and_grad, transpose, jvp, head
    (JAX wraps a scope in the transforms it went through)."""
    return frozenset(_WORD.findall(path))


def program_has_scopes() -> bool:
    try:
        from ray_tpu._private.jax_utils import scope_map  # noqa: F401
    except ImportError:
        return False
    return True


def train_step_scopes(cell: dict) -> Dict[str, str]:
    """{instruction: scope path} of the cell's train step, compiled here
    once more the way ``train_loop.train_loop`` builds it, with the
    metadata in the compile cache's key (``compile_with_scopes`` says
    why): a compile the first time in a checkout, a load after that,
    and after the window either way. Call it in the process that holds
    the chips. {} for a program without scopes."""
    if not program_has_scopes():
        return {}
    if cell["name"] in _memo:
        return _memo[cell["name"]]
    import jax
    import jax.numpy as jnp

    from ray_tpu import parallel
    from ray_tpu._private.jax_utils import compile_with_scopes, scope_map
    from ray_tpu.models import llama
    from ray_tpu.parallel.train_step import state_shardings

    hp, tr, opts = cell["hp"], cell["traffic"], cell["train"]
    devices = jax.devices()
    cfg = spec.llama_config(
        hp, remat=opts["remat"], attention_impl=opts["attention_impl"],
        ce_impl=opts["ce_impl"])
    mesh = parallel.make_mesh(devices=devices)
    opt = parallel.default_optimizer(
        opts["learning_rate"], warmup_steps=opts["warmup_steps"],
        total_steps=opts["total_steps"])

    def init(key):
        params = llama.init_params(key, cfg)
        return parallel.TrainState(
            jnp.zeros((), jnp.int32), params, opt.init(params))

    state_sh, shapes = state_shardings(
        mesh, llama.param_specs(cfg), partial(init, jax.random.PRNGKey(0)))
    step = parallel.make_train_step(
        partial(llama.loss_fn, config=cfg), opt, mesh, state_sh)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, state_sh)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (tr["seqs_per_chip"] * len(devices), tr["seq"] + 1), jnp.int32,
        sharding=parallel.batch_sharding(mesh))}
    _memo[cell["name"]] = scope_map(
        compile_with_scopes(step.lower(state, batch)))
    return _memo[cell["name"]]


def scope_seconds(trace: trace_reduce.Trace, scopes: Dict[str, str],
                  wanted: Iterable[str]) -> float:
    """Summed durations of the core's ops whose scope path holds any of
    the names in ``wanted``, mean over the chips."""
    wanted = frozenset(wanted)
    hit = {name for name, path in scopes.items() if words(path) & wanted}
    return _mean_core_seconds(trace, lambda name: name in hit)


def unattributed_share(trace: trace_reduce.Trace, scopes: Dict[str, str],
                       known: Iterable[str], named: str) -> Optional[float]:
    """Share of the core's op time whose op carries none of the ``known``
    scope names and does not match ``named`` (kernels and collectives,
    which say what they are by their own names)."""
    known, rx = frozenset(known), re.compile(named)
    told = {name for name, path in scopes.items() if words(path) & known}
    total = _mean_core_seconds(trace, lambda name: True)
    if not total:
        return None
    lost = _mean_core_seconds(
        trace, lambda name: name not in told and not rx.search(name))
    return lost / total


def _mean_core_seconds(trace: trace_reduce.Trace, keep) -> float:
    per_chip = []
    for c in trace.chips:
        dur = c.end - c.start
        per_chip.append(sum(
            float(d) for name, d, core in zip(c.names, dur, c.core)
            if core and keep(name)))
    return sum(per_chip) / len(per_chip) if per_chip else 0.0
