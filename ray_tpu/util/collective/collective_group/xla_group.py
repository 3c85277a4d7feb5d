"""XLA collective group: eager collectives as cached jitted programs.

The TPU-native replacement for the reference's NCCLGroup
(python/ray/util/collective/collective_group/nccl_collective_group.py:128).
Where NCCL caches a communicator per device list (:402-432), we cache a
*compiled XLA program* per (op, shape, dtype, reduce_op): the group is a
1-D `jax.sharding.Mesh` over its devices, each eager call assembles the
per-device shards into one sharded jax.Array and runs a shard_map'd
psum/all_gather/psum_scatter/ppermute over the group axis — XLA lowers
those to ICI collectives on real TPU slices.

This is the single-controller, in-process path (one Python process
driving all chips of a host/slice — JAX's native model). The
cross-process path is StoreGroup.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from ..types import (
    AllGatherOptions,
    AllReduceOptions,
    BarrierOptions,
    BroadcastOptions,
    RecvOptions,
    ReduceOp,
    ReduceOptions,
    ReduceScatterOptions,
    SendOptions,
)
from .base import BaseGroup

_AXIS = "group"


def _reduce_fn(op: ReduceOp):
    if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
        return partial(jax.lax.psum, axis_name=_AXIS)
    if op == ReduceOp.MAX:
        return partial(jax.lax.pmax, axis_name=_AXIS)
    if op == ReduceOp.MIN:
        return partial(jax.lax.pmin, axis_name=_AXIS)
    if op == ReduceOp.PRODUCT:
        # No pprod primitive; log-space is lossy — use allgather+prod.
        def pprod(x, axis_name=_AXIS):
            gathered = jax.lax.all_gather(x, axis_name)
            return jnp.prod(gathered, axis=0)

        return pprod
    raise ValueError(f"unsupported reduce op {op}")


class XlaGroup(BaseGroup):
    """A collective group over N in-process devices.

    Tensor convention for eager ops: a list of N per-rank arrays (rank i
    lives on device i of the group), all the same shape/dtype. Each op
    returns a new list of N arrays, one per device. A single sharded
    jax.Array whose leading-axis sharding matches the group mesh is also
    accepted and returned as such.
    """

    def __init__(
        self,
        world_size: int,
        rank: int,
        group_name: str,
        devices: Sequence[jax.Device] | None = None,
    ):
        super().__init__(world_size, rank, group_name)
        if devices is None:
            devices = jax.devices()[:world_size]
        if len(devices) != world_size:
            raise ValueError(
                f"group of world_size {world_size} needs {world_size} devices, "
                f"got {len(devices)}"
            )
        self._devices = list(devices)
        self._mesh = Mesh(np.asarray(self._devices), (_AXIS,))
        # (op_name, shape, dtype, extra) -> compiled callable
        self._programs: Dict[Tuple, Any] = {}

    @property
    def backend(self) -> str:
        return "xla"

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    def destroy_group(self) -> None:
        self._programs.clear()

    # -- shard assembly ------------------------------------------------

    def _stack(self, tensors: List[Any]) -> jax.Array:
        """Per-rank tensors -> one array [world, ...] sharded over the mesh."""
        if len(tensors) != self._world_size:
            raise ValueError(
                f"expected {self._world_size} per-rank tensors, got {len(tensors)}"
            )
        shape = jnp.shape(tensors[0])
        shards = [
            jax.device_put(jnp.asarray(t)[None], d)
            for t, d in zip(tensors, self._devices)
        ]
        sharding = NamedSharding(self._mesh, P(_AXIS))
        return jax.make_array_from_single_device_arrays(
            (self._world_size, *shape), sharding, shards
        )

    def _unstack(self, arr: jax.Array) -> List[jax.Array]:
        shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start)
        return [s.data[0] for s in shards]

    def _program(self, key: Tuple, build):
        prog = self._programs.get(key)
        if prog is None:
            prog = build()
            self._programs[key] = prog
        return prog

    def _run(self, name: str, tensors, body, out_specs=P(_AXIS)):
        """Compile-and-cache an eager collective: body runs per-shard
        under shard_map with axis `group`."""
        is_list = isinstance(tensors, (list, tuple))
        arr = self._stack(list(tensors)) if is_list else tensors
        key = (name, arr.shape, str(arr.dtype))
        prog = self._program(
            key,
            lambda: jax.jit(
                shard_map(
                    body,
                    mesh=self._mesh,
                    in_specs=P(_AXIS),
                    out_specs=out_specs,
                    check_vma=False,
                )
            ),
        )
        out = prog(arr)
        return self._unstack(out) if is_list else out

    # -- collectives ---------------------------------------------------

    def allreduce(self, tensors, opts: AllReduceOptions = AllReduceOptions()):
        red = _reduce_fn(opts.reduceOp)
        world = self._world_size

        def body(x):  # x: [1, ...] local shard
            y = red(x)
            if opts.reduceOp == ReduceOp.AVERAGE:
                y = y / world
            return y

        return self._run(("allreduce", opts.reduceOp), tensors, body)

    def reduce(self, tensors, opts: ReduceOptions = ReduceOptions()):
        red = _reduce_fn(opts.reduceOp)
        root = opts.root_rank

        def body(x):
            y = red(x)
            if opts.reduceOp == ReduceOp.AVERAGE:
                y = y / self._world_size
            idx = jax.lax.axis_index(_AXIS)
            return jnp.where(idx == root, y, x)

        return self._run(("reduce", opts.reduceOp, root), tensors, body)

    def broadcast(self, tensors, opts: BroadcastOptions = BroadcastOptions()):
        root = opts.root_rank
        world = self._world_size

        def body(x):
            # one-hot psum: every rank gets root's shard
            idx = jax.lax.axis_index(_AXIS)
            contrib = jnp.where(idx == root, x, jnp.zeros_like(x))
            return jax.lax.psum(contrib, _AXIS)

        return self._run(("broadcast", root), tensors, body)

    def allgather(self, tensors, opts: AllGatherOptions = AllGatherOptions()):
        """Each rank contributes [k...]; each rank receives [world, k...]."""
        is_list = isinstance(tensors, (list, tuple))
        arr = self._stack(list(tensors)) if is_list else tensors
        key = ("allgather", arr.shape, str(arr.dtype))
        world = self._world_size

        def body(x):  # x: [1, k...] -> [world, k...] per rank
            return jax.lax.all_gather(x[0], _AXIS)

        prog = self._program(
            key,
            lambda: jax.jit(
                shard_map(
                    body,
                    mesh=self._mesh,
                    in_specs=P(_AXIS),
                    out_specs=P(_AXIS),
                    check_vma=False,
                )
            ),
        )
        out = prog(arr)  # global [world*world, k...]
        if not is_list:
            return out
        shards = sorted(out.addressable_shards, key=lambda s: s.index[0].start)
        return [s.data for s in shards]

    def reducescatter(
        self, tensors, opts: ReduceScatterOptions = ReduceScatterOptions()
    ):
        red_op = opts.reduceOp
        world = self._world_size
        # per-rank input is the full tensor; shape check before tracing
        if isinstance(tensors, (list, tuple)):
            dim0 = jnp.shape(tensors[0])[0]
        else:
            dim0 = tensors.shape[1]  # stacked [world, m, ...]
        if dim0 % world != 0:
            raise ValueError(
                f"reducescatter dim0 {dim0} not divisible by world_size {world}"
            )

        def body(x):  # x: [1, world*k...] per rank holds full input
            y = jax.lax.psum(x, _AXIS) if red_op in (ReduceOp.SUM, ReduceOp.AVERAGE) else _reduce_fn(red_op)(x)
            if red_op == ReduceOp.AVERAGE:
                y = y / world
            idx = jax.lax.axis_index(_AXIS)
            chunk = y.shape[1] // world
            return jax.lax.dynamic_slice_in_dim(y, idx * chunk, chunk, axis=1)

        return self._run(("reducescatter", red_op), tensors, body)

    def barrier(self, opts: BarrierOptions = BarrierOptions()):
        ones = [jnp.zeros((), jnp.int32) for _ in range(self._world_size)]
        out = self.allreduce(ones)
        jax.block_until_ready(out)

    # -- eager p2p ------------------------------------------------------
    # Single-controller semantics: send() eagerly copies the tensor onto
    # the destination rank's DEVICE (the actual D2D/ICI transfer — what
    # p2p exists for) and parks it in a per-destination FIFO mailbox;
    # recv(rank) pops the oldest tensor delivered to that rank. The
    # reference's worker-resident send/recv (collective.py:541-625) maps
    # to StoreGroup across processes; inside jitted programs use
    # lax.ppermute.
    def send(self, tensors, opts: SendOptions):
        if not hasattr(self, "_p2p_mailbox"):
            self._p2p_mailbox = {}
        tensor = tensors[0] if isinstance(tensors, (list, tuple)) else tensors
        dst_dev = self._devices[opts.dst_rank]
        delivered = jax.device_put(jnp.asarray(tensor), dst_dev)
        self._p2p_mailbox.setdefault(opts.dst_rank, []).append(delivered)

    def recv(self, tensors_or_opts=None, opts: RecvOptions = None):
        # tolerate both recv(opts) and recv(tensors, opts) call shapes
        if opts is None:
            opts = tensors_or_opts
        box = getattr(self, "_p2p_mailbox", {})
        queue = box.get(opts.src_rank)
        if not queue:
            raise RuntimeError(
                f"no pending p2p message for rank {opts.src_rank} "
                f"(single-controller group: send() must precede recv())"
            )
        return queue.pop(0)
