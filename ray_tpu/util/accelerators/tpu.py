"""Public TPU pod helpers.

Parity: python/ray/util/accelerators/tpu.py:7-33
(get_current_pod_name / get_current_pod_worker_count /
get_num_tpu_chips_on_node over TPUAcceleratorManager). Pod identity
reads the standard TPU VM environment (TPU_NAME, TPU_WORKER_HOSTNAMES,
TPU_ACCELERATOR_TYPE) — the GCE metadata server the reference also
falls back to is unreachable in air-gapped pods. The chip count does
not: it is what this host's device files say.
"""

from __future__ import annotations

import os
import re
from typing import Optional


def get_current_pod_name() -> Optional[str]:
    """The TPU pod's name resource (gang-affinity key: the reference
    exposes TPU-{name} as a custom resource for pod-wide placement)."""
    name = os.environ.get("TPU_NAME") or os.environ.get("TPU_POD_NAME")
    return name or None


def get_current_pod_worker_count() -> int:
    """Number of hosts in this pod (1 on a single-host slice)."""
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if hosts:
        return len([h for h in hosts.split(",") if h.strip()])
    return 1


def get_accelerator_type() -> Optional[str]:
    """e.g. "v5litepod", "v5p" — from TPU_ACCELERATOR_TYPE
    ("v5litepod-16")."""
    acc = os.environ.get("TPU_ACCELERATOR_TYPE")
    return acc.split("-")[0] if acc else None


def get_num_tpu_chips_on_node() -> int:
    """Chips this host can open: the explicit override, else one per
    device file — ``/dev/vfio/<n>`` (v5e and newer) or ``/dev/accel<n>``
    (older generations). Counting files opens no device, so the caller
    holds no chip afterwards. Neither the accelerator type nor the PCI
    bus is asked: a machine handed one chip of a four-chip host still
    advertises ``v5litepod-4`` and lists four PCI functions (chip
    machine, 2026-09-26), but has one file."""
    env = os.environ.get("RAY_TPU_NUM_TPUS") or os.environ.get("TPU_NUM_DEVICES")
    if env:
        return int(env)
    n = 0
    for directory, pattern in (("/dev/vfio", r"\d+"), ("/dev", r"accel\d+")):
        try:
            n += sum(1 for f in os.listdir(directory) if re.fullmatch(pattern, f))
        except OSError:
            pass
    return n
