"""TPUAcceleratorManager (reference:
python/ray/_private/accelerators/tpu.py:109).

Chips are counted from the host's device files and pod identity read
from the TPU VM standard variables; the reference's GCE-metadata
fallback needs egress air-gapped pods don't have. Neither opens a
device: the process that counts is never one that computes. Emits the
same resource shape:
``TPU`` chips, ``TPU-<accelerator_type>`` (:352) and the per-pod name
resource ``TPU-<pod>-head`` style gang-affinity key (:375).
"""

from __future__ import annotations

import math
import os
import sys
from typing import Dict, List, Optional, Sequence

from .accelerator import AcceleratorManager


class TPUAcceleratorManager(AcceleratorManager):
    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return "TPU_VISIBLE_CHIPS"

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        from ray_tpu.util.accelerators import tpu as helpers

        return helpers.get_num_tpu_chips_on_node()

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        acc = os.environ.get("TPU_ACCELERATOR_TYPE")
        return f"TPU-{acc}" if acc else None

    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        from ray_tpu.util.accelerators import tpu as helpers

        pod = helpers.get_current_pod_name()
        if pod:
            # pod-name resource: schedule a gang onto one specific pod
            # (reference tpu.py:375 TPU-{name} affinity resource)
            return {f"TPU-{pod}": 1.0}
        return {}

    @staticmethod
    def validate_resource_request_quantity(quantity: float):
        if quantity != int(quantity):
            return (False, "TPU chip requests must be whole chips")
        return (True, None)

    @staticmethod
    def set_current_process_visible_accelerators(ids: List[str]) -> None:
        os.environ["TPU_VISIBLE_CHIPS"] = ",".join(str(i) for i in ids)


# Peak dense bf16 FLOP/s and HBM bytes/s of one chip, by the
# ``device_kind`` jax reports on it (Google Cloud's "TPU v4", "TPU v5e",
# "TPU v5p" and "TPU v6e" pages; v5p answers to either spelling).
# Published figures: only the v5e's row has been run against (PERF.md).
CHIP_PEAKS = {
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
}


def flops_per_hbm_byte(device_kind: str) -> float:
    """The ridge of a chip's roofline: how many FLOPs it can do in the
    time it reads one byte of HBM. Work that does fewer per byte it
    reads waits for memory. A kind the table lacks (the CPU of a test, a
    newer chip) takes the smallest ratio in it: whoever sizes work by
    the ridge then errs towards less of it."""
    ratios = {kind: f / b for kind, (f, b) in CHIP_PEAKS.items()}
    return ratios.get(device_kind, min(ratios.values()))


# One process per chip. libtpu gives a chip to the first process that
# opens it and holds it until that process exits; what a second process
# gets is an error at best. So every worker is told, before it runs any
# user code, exactly what it may open, through the environment JAX and
# libtpu read when they start. Established on a v5e host, 2026-09-26:
# TPU_VISIBLE_CHIPS with per-process bounds gives four processes a chip
# each at once, or one process two adjacent chips, with no port or
# address variable; opening a chip another process holds fails with
# "Device or resource busy" or a lockfile error, never by falling back;
# the chip is free again as soon as its holder exits, however it exits.


def deny_chips() -> None:
    """A worker that has been given no chips computes on the CPU: JAX
    never loads libtpu in it, so it cannot open (and then sit on) chips
    that belong to another worker. Called first thing in a worker."""
    os.environ["JAX_PLATFORMS"] = "cpu"


def claim_chips(chips: Sequence[int], node_chips: int) -> None:
    """Make ``chips`` (of this node's ``node_chips``) the only devices
    this process can open, and the TPU its only platform: if they cannot
    be opened, jax raises instead of computing on the CPU, where the
    Pallas kernels would quietly run interpreted."""
    visible = ",".join(str(c) for c in chips)
    if (
        os.environ.get("TPU_VISIBLE_CHIPS") == visible
        and os.environ.get("JAX_PLATFORMS") == "tpu"
    ):
        return  # the next task of a worker pinned to these chips
    if "jax" in sys.modules:
        # jax read JAX_PLATFORMS when it was imported; the hub only
        # gives chips to a worker that has run nothing
        raise RuntimeError(
            f"worker {os.getpid()} imported jax before it was given TPU "
            f"chips {visible}; it cannot take them"
        )
    env = {"TPU_VISIBLE_CHIPS": visible, "JAX_PLATFORMS": "tpu"}
    if len(chips) < node_chips:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = _box_bounds(chips, node_chips)
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    os.environ.update(env)


def _box_bounds(chips: Sequence[int], node_chips: int) -> str:
    """"x,y,z" extent of a subset of a host's chips. libtpu takes a
    process's share only as a box of the host's mesh."""
    if len(chips) == 1:
        return "1,1,1"
    coords = get_chip_topology(node_chips)
    if not all(c in coords for c in chips):
        raise RuntimeError(
            f"cannot give a process chips {list(chips)} of {node_chips}: "
            "this host's chip topology is unknown (set TPU_TOPOLOGY)"
        )
    points = [tuple(coords[c]) + (0,) * (3 - len(coords[c])) for c in chips]
    dims = [max(p[i] for p in points) - min(p[i] for p in points) + 1
            for i in range(3)]
    if math.prod(dims) != len(chips):
        raise RuntimeError(
            f"cannot give a process chips {list(chips)} of {node_chips}: "
            f"at {points} they do not fill a box of the host's mesh"
        )
    return ",".join(str(d) for d in dims)


def get_chip_topology(n_chips: int) -> Dict[int, tuple]:
    """ICI topology of this host's chips: {chip_id: (x, y) or (x, y, z)}.

    The SLICE placement strategy reserves ICI-contiguous chips; that
    needs physical coordinates, which the reference never models (its
    TPU support stops at per-pod gang resources, reference
    python/ray/_private/accelerators/tpu.py:352-375).

    Sources, in priority order:
      - ``TPU_CHIP_COORDS``: explicit "id:x,y[,z];id:x,y[,z]" (tests,
        exotic wiring),
      - ``TPU_TOPOLOGY``: "XxY" or "XxYxZ" grid, chips numbered with x
        varying fastest, as libtpu numbers them: a v5e "2x2" host
        reports chips 0..3 at (0,0) (1,0) (0,1) (1,1) (chip machine,
        2026-09-26),
      - chip-count defaults for single-host slices (v5e hosts carry 1,
        4, or 8 chips in 1x1 / 2x2 / 2x4 meshes).

    Returns {} when the topology is unknown — SLICE is then rejected
    rather than silently degraded.
    """
    spec = os.environ.get("TPU_CHIP_COORDS")
    if spec:
        try:
            out: Dict[int, tuple] = {}
            for part in spec.split(";"):
                part = part.strip()
                if not part:
                    continue
                cid, _, coord = part.partition(":")
                out[int(cid)] = tuple(int(c) for c in coord.split(","))
            return out
        except ValueError:
            return {}  # unknown topology; SLICE is rejected at creation
    topo = os.environ.get("TPU_TOPOLOGY")
    if not topo:
        topo = {1: "1x1", 4: "2x2", 8: "2x4"}.get(n_chips)
    if not topo:
        return {}
    try:
        dims = [int(d) for d in topo.lower().split("x")]
    except ValueError:
        return {}
    total = 1
    for d in dims:
        total *= d
    if total != n_chips:
        return {}
    coords: Dict[int, tuple] = {}
    for cid in range(n_chips):
        rem, coord = cid, []
        for d in dims:
            coord.append(rem % d)
            rem //= d
        coords[cid] = tuple(coord)
    return coords
