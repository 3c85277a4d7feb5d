"""Where the runtime meets JAX: the compile cache and the device report.

Neither function may be the first thing to initialise a backend in a
process that was not given chips. ``ensure_compilation_cache_dir`` only
touches the environment; ``device_report`` is called by code that is
already computing on its devices.
"""

from __future__ import annotations

import os
from typing import Any, Dict

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def ensure_compilation_cache_dir() -> str:
    """Give this process, and every worker it spawns from here on, one
    persistent compile cache. A directory placed from outside through
    ``JAX_COMPILATION_CACHE_DIR`` is left alone and no other is set in
    code. Without one the cache is ``<checkout>/.jax_cache``: a fixed
    path, because the path is part of the cache key and a directory
    that moves never hits. JAX reads the variable when it is imported,
    so this runs before any worker is spawned and before the caller
    imports jax."""
    if not os.environ.get(_CACHE_ENV):
        checkout = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        os.environ[_CACHE_ENV] = os.path.join(checkout, ".jax_cache")
    return os.environ[_CACHE_ENV]


def device_report() -> Dict[str, Any]:
    """What this process computes on, as JAX reports it, and which
    process that is. chip_smoke.py asserts on it; the engine's stats
    and a train loop's report carry it."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "pid": os.getpid(),
    }
