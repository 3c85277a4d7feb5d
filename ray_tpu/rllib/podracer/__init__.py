"""Podracer architectures (Hessel et al. 2021): Anakin & Sebulba.

Two TPU-native RL layouts behind one ``PodracerConfig``:

- **Anakin** — environment step and learner update co-jitted into one
  on-chip program (``jax.lax.scan`` over vectorized pure-JAX envs, SPMD
  over ``parallel/mesh.py``), driven by a compiled-DAG resident exec
  loop so the host never re-dispatches per step.
- **Sebulba** — actor workers and a learner gang-placed on separate
  slices; trajectory hand-off rides ``fn.map`` bulk submission and the
  direct object plane (rollout batches never relay through the hub),
  the learner all-reduces gradients over a cached jitted collective
  group, and parameters broadcast back on a version-tagged KV channel.

Both run end to end on CPU (``JAX_PLATFORMS=cpu``); neither has run
on a chip.
"""

from .config import PodracerConfig
from .jax_env import JaxCartPole, get_jax_env, register_jax_env
from .anakin import AnakinDriver
from .sebulba import SebulbaDriver

__all__ = [
    "PodracerConfig",
    "JaxCartPole",
    "get_jax_env",
    "register_jax_env",
    "AnakinDriver",
    "SebulbaDriver",
]
