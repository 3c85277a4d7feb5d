"""The benchmark's own tests: CPU only, run by hand with

    python -m pytest benchmarks/tests -q

They are not part of ``tests/`` (the repo's tier-1 suite)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
