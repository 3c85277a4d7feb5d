"""Sharded train-state construction and jitted train steps.

This is the compute core the JaxTrainer drives. Where the reference's
DataParallelTrainer relies on torch DDP doing gradient allreduce inside
torch (reference: python/ray/train/torch/config.py:66,153 +
rllib/core/learner/torch/torch_learner.py:533), here the whole training
step — forward, backward, gradient reduction, optimizer update — is ONE
jitted XLA program over the mesh: param shardings (fsdp/model axes) make
GSPMD emit all-gather/reduce-scatter/psum over ICI automatically.

Donation: params and opt_state are donated so the update is in-place in
HBM (no double-buffering of the model).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import batch_spec


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


def default_optimizer(
    learning_rate: float = 3e-4,
    *,
    warmup_steps: int = 100,
    total_steps: int = 10000,
    weight_decay: float = 0.1,
    grad_clip: float = 1.0,
    b1: float = 0.9,
    b2: float = 0.95,
) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1)
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def state_shardings(
    mesh: Mesh,
    param_specs: Any,
    init_fn: Callable[[], TrainState],
) -> Tuple[TrainState, Any]:
    """Compute NamedShardings for a TrainState produced by init_fn.

    Optimizer-state subtrees that are param-shaped pytrees (adam
    moments, ema copies) get the parameter shardings, matched
    STRUCTURALLY — any subtree whose treedef equals the params' treedef
    takes param_specs wholesale. Everything else (counts, schedule
    scalars) replicates.
    """
    shape_tree = jax.eval_shape(init_fn)
    params_treedef = jax.tree_util.tree_structure(shape_tree.params)

    def to_sharding(spec_tree):
        return jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec),
            spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    def map_opt(node):
        if jax.tree_util.tree_structure(node) == params_treedef:
            return to_sharding(param_specs)
        if isinstance(node, tuple) and hasattr(node, "_fields"):  # namedtuple
            return type(node)(*[map_opt(x) for x in node])
        if isinstance(node, (tuple, list)):
            return type(node)(map_opt(x) for x in node)
        if isinstance(node, dict):
            return {k: map_opt(v) for k, v in node.items()}
        return jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), node
        )

    param_sh = to_sharding(param_specs)
    opt_sh = map_opt(shape_tree.opt_state)
    step_sh = NamedSharding(mesh, P())
    return TrainState(step_sh, param_sh, opt_sh), shape_tree


def create_train_state(
    mesh: Mesh,
    rng: jax.Array,
    init_params_fn: Callable[[jax.Array], Any],
    optimizer: optax.GradientTransformation,
    param_specs: Any,
) -> Tuple[TrainState, TrainState]:
    """Initialize a sharded TrainState directly on the mesh.

    Init runs under jit with out_shardings, so every parameter is
    created already-sharded (no host-memory staging of an 8B model).
    Returns (state, state_shardings).
    """

    def init_fn():
        params = init_params_fn(rng)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
        )

    shardings, _ = state_shardings(mesh, param_specs, init_fn)
    state = jax.jit(init_fn, out_shardings=shardings)()
    return state, shardings


def make_train_step(
    loss_fn: Callable[[Any, Dict[str, jax.Array]], jax.Array],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    state_sh: TrainState,
    *,
    batch_ndim_extra: int = 1,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build the donated, sharded train step.

    loss_fn(params, batch) -> scalar. Batch arrays are sharded on dim0
    over the (data, fsdp) axes. The step is traced with ``mesh`` as the
    ambient mesh, so ops that must map themselves over it by hand (the
    Pallas kernels, ring attention) find it whether or not the caller
    entered ``jax.sharding.set_mesh``.
    """
    bspec = NamedSharding(mesh, batch_spec(batch_ndim_extra))

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        # scopes name the ops in a device trace (jax_utils.scope_map)
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh), \
                jax.named_scope("loss_and_grad"):
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(
                grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            gnorm = optax.global_norm(grads)
        metrics = {"loss": loss, "grad_norm": gnorm, "step": state.step + 1}
        return TrainState(state.step + 1, new_params, new_opt), metrics

    return jax.jit(
        step,
        in_shardings=(state_sh, bspec),
        out_shardings=(state_sh, NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )


def make_eval_step(
    loss_fn: Callable[[Any, Dict[str, jax.Array]], jax.Array],
    mesh: Mesh,
    state_sh: TrainState,
    *,
    batch_ndim_extra: int = 1,
) -> Callable:
    bspec = NamedSharding(mesh, batch_spec(batch_ndim_extra))

    def step(state: TrainState, batch):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return {"loss": loss_fn(state.params, batch)}

    return jax.jit(step, in_shardings=(state_sh, bspec),
                   out_shardings=NamedSharding(mesh, P()))
