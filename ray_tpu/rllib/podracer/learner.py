"""Shared jitted programs for both Podracer layouts.

Everything here is a *factory of pure functions*: the acting scan
(vectorized env interaction producing a time-major V-trace batch) and
the SGD update (IMPALA or APPO loss, reused from the existing rllib
algorithms). Anakin inlines both into one fused superstep; Sebulba
jits the acting scan on the actor workers and wraps the update in a
shard_map over the learner collective group's mesh so the gradient
all-reduce rides the cached jitted collective path.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..appo import make_appo_loss
from ..core import MLPSpec, forward
from ..impala import make_impala_loss


def select_loss(config, spec: MLPSpec):
    if config.loss == "appo":
        return make_appo_loss(config, spec)
    return make_impala_loss(config, spec)


def make_optimizer(config):
    import optax

    return optax.chain(
        optax.clip_by_global_norm(config.grad_clip),
        optax.adam(config.lr),
    )


def make_acting_fns(env_cls, rollout_len: int):
    """(init_envs, act): the vectorized interaction programs.

    ``init_envs(key, n)`` -> (env_state, obs, ep_ret) for n envs.
    ``act(params, env_state, obs, ep_ret, key)`` scans ``rollout_len``
    steps and returns ``(env_state, obs, ep_ret, batch, ep_sum, ep_n)``
    where ``batch`` is the time-major (T, N) V-trace batch and
    ``ep_sum``/``ep_n`` aggregate episode returns completed during the
    fragment (the lag-free learning-progress signal).
    """
    reset_v = jax.vmap(env_cls.reset)
    step_v = jax.vmap(env_cls.step)

    def init_envs(key, n: int):
        env_state, obs = reset_v(jax.random.split(key, n))
        return env_state, obs, jnp.zeros((n,), jnp.float32)

    def act(params, env_state, obs, ep_ret, key):
        def body(carry, key_t):
            env_state, obs, ep_ret = carry
            logits, _ = forward(params, obs)  # (N, A)
            key_act, key_env = jax.random.split(key_t)
            actions = jax.random.categorical(key_act, logits)
            logp_all = jax.nn.log_softmax(logits)
            logp = jnp.take_along_axis(logp_all, actions[:, None], 1)[:, 0]
            env_keys = jax.random.split(key_env, actions.shape[0])
            env_state, next_obs, rewards, dones = step_v(
                env_state, actions, env_keys
            )
            ep_ret = ep_ret + rewards
            done_sum = jnp.sum(ep_ret * dones)
            done_n = jnp.sum(dones)
            ep_ret = ep_ret * (1.0 - dones)
            step_out = {
                "obs": obs,
                "actions": actions,
                "rewards": rewards,
                "dones": dones,
                "logp_mu": logp,
            }
            return (env_state, next_obs, ep_ret), (step_out, done_sum, done_n)

        keys = jax.random.split(key, rollout_len)
        (env_state, obs, ep_ret), (batch, done_sums, done_ns) = jax.lax.scan(
            body, (env_state, obs, ep_ret), keys
        )
        batch["final_obs"] = obs  # bootstrap obs; masked by dones in V-trace
        return env_state, obs, ep_ret, batch, jnp.sum(done_sums), jnp.sum(done_ns)

    return init_envs, act


def make_update_fn(config, spec: MLPSpec):
    """(optimizer, update): one un-jitted SGD step over a time-major
    batch — callers jit (Sebulba) or inline into a larger jitted
    program (Anakin's fused superstep)."""
    import optax

    optimizer = make_optimizer(config)
    loss_fn = select_loss(config, spec)

    def update(params, opt_state, batch):
        (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, metrics

    return optimizer, update


_SHARDED_UPDATE_CACHE: Dict[Tuple, Any] = {}


def make_sharded_update(config, spec: MLPSpec, group):
    """(optimizer, jitted update) with the batch sharded over the
    learner collective ``group`` (util.collective XlaGroup): each shard
    computes grads on its slice of the env axis, the all-reduce is a
    ``psum`` over the group's mesh axis — one cached compiled program
    per (hyperparams, spec, world), exactly the XlaGroup contract.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    key = (
        config.loss, config.lr, config.gamma, config.vtrace_clip_rho,
        config.vtrace_clip_c, config.vf_loss_coeff, config.entropy_coeff,
        config.grad_clip, config.clip_param, spec, group.world_size,
    )
    cached = _SHARDED_UPDATE_CACHE.get(key)
    if cached is not None:
        return cached

    import optax

    optimizer = make_optimizer(config)
    loss_fn = select_loss(config, spec)
    mesh = group.mesh
    axis = mesh.axis_names[0]  # "group"
    world = group.world_size

    def shard_step(params, opt_state, batch):
        (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        # the learner all-reduce: mean local grads over the group axis
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, axis) / world, grads
        )
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.psum(m, axis) / world, metrics
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, metrics

    # params/opt_state replicated, batch sharded on the env axis (axis
    # 1 of the time-major (T, N) arrays; final_obs is (N, obs_dim) so
    # its env axis is 0)
    batch_specs = {
        k: P(None, axis)
        for k in ("obs", "actions", "rewards", "dones", "logp_mu")
    }
    batch_specs["final_obs"] = P(axis)

    update = jax.jit(
        shard_map(
            shard_step,
            mesh=mesh,
            in_specs=(P(), P(), batch_specs),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )

    _SHARDED_UPDATE_CACHE[key] = (optimizer, update)
    return optimizer, update
