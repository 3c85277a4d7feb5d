"""A family that ``test_files_alone.py`` declares in its scratch
checkout, as ``families/<family>.py`` of the tests' tree there: the
tree's ``routed`` under a schema of its own. Its configuration states
each layer's mixer under ``mixers``, a key no fixture has, so whatever
the tests parametrised over cells hold of its cell they hold by what
they observe and by what the cell states, not by a key of the fixture
family's configurations. Its cell is compared under the engine's own
routing choices, so it gives ``reference_routed`` beside
``reference_logits``, as the next hybrid routed family's module will.
No cell of BENCHMARK.json or of the tests' tree uses it."""

from benchmarks.families import routed as _of


def as_the_trees(hp: dict) -> dict:
    return {**hp, "pattern": hp["mixers"]}


def model_config(hp, options=None):
    return _of.model_config(as_the_trees(hp), options)


init_params = _of.init_params


def reference_logits(params, tokens, hp, last: int = 0):
    return _of.reference_logits(params, tokens, as_the_trees(hp), last=last)


def reference_routed(params, tokens, hp, choices, last: int = 0):
    return _of.reference_routed(params, tokens, as_the_trees(hp), choices,
                                last=last)


SCOPES, NAMED_OPS = _of.SCOPES, _of.NAMED_OPS
KV_SCOPES, COMPUTE_SCOPES = _of.KV_SCOPES, _of.COMPUTE_SCOPES
