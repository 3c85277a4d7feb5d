"""A routed family, for the benchmark's own tests only: the stand-in of
``tests/routed_standin.py`` (latent attention with a rotary part, a
dense leading layer, a sigmoid router over groups of experts of which
this chip holds a few, a shared expert) under the names a family module
gives, so that a serve cell of the tests' tree states the two shares of
``serve_load.OVER_SHARES`` and goes through ``spec.load_cell``,
``server.reference_readings``, ``server.served_readings`` and
``serve_load.matches_reference`` as a routed family's cell will. The
program cannot serve such a family yet (``LLMServer`` builds the engine
of ``models/llama.py``: PERF.md section 7), so the tests' side of the
family (``tests/tree/control_routed.py``) gives the stand-in's own two
programs as its engine and no test runs this cell through run.py. No
cell of BENCHMARK.json uses it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))           # benchmarks/tests

import routed_standin  # noqa: E402


def model_config(hp, options=None):
    return routed_standin.frozen(hp)


def init_params(key, cfg):
    return routed_standin.init_params(key, sizes=cfg)


# the float32 side at ``highest`` of the same seeded weights, and the
# same under the experts an engine says it chose (a cell that says
# ``"routing": "engine"``)
reference_logits = routed_standin.reference_logits
reference_routed = routed_standin.reference_routed


# its programs carry no named scope
SCOPES = ()
NAMED_OPS = "^$"
KV_SCOPES = ()
COMPUTE_SCOPES = ()
