"""Mapping a Pallas call over the ambient mesh by hand.

GSPMD refuses to partition a Mosaic custom call ("Mosaic kernels cannot
be automatically partitioned. Please wrap the call in a shard_map"), so
a kernel traced under a mesh with more than one device must run inside
``jax.shard_map`` with every axis GSPMD could still partition over made
manual. The kernels' callers share the question "which axes are those,
and how are batch and heads laid out on them"; each builds its own
specs from the answer.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

from jax.sharding import get_abstract_mesh


class Partition(NamedTuple):
    axes: frozenset  # mesh axes not yet manual: the shard_map's axis_names
    batch: Optional[Tuple[str, ...]]  # axes the batch dim is split over
    tp: int  # size of the tensor-parallel ("model") axis among them


def ambient_partition() -> Optional[Partition]:
    """How a kernel call traced here must be mapped, or None when it can
    be called straight: no ambient mesh, or one whose axes are all
    trivial or already manual (the caller is inside its own shard_map).
    ``parallel.make_train_step`` supplies the mesh; other callers enter
    ``jax.sharding.set_mesh``."""
    mesh = get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    axes = frozenset(mesh.axis_names) - frozenset(mesh.manual_axes)
    if all(mesh.shape[a] == 1 for a in axes):
        return None
    batch = tuple(a for a in ("data", "fsdp") if a in axes)
    tp = mesh.shape["model"] if "model" in axes else 1
    return Partition(axes, batch or None, tp)
