"""The tests' side of the family that ``test_files_alone.py`` declares
in its scratch checkout (``control_<family>.py`` beside the tree's
there): the stand-in's two programs as its engine, with what a slot
holds (and the leaf its calls write their routing choices into) kept
under names of its own, the stand-in's fp8 side as its control and its
router fault. A test, or a harness, that asked an engine's cache for a
leaf by the stand-in's name would fail on this one: what a call chose
is ``read_choices``'s to say."""

import contextlib
import functools
import sys

import routed_standin

NAMES = {"latent": "rows", "state": "memory", "conv": "window",
         "choices": "picked"}
BACK = {ours: theirs for theirs, ours in NAMES.items()}


def _named(cache: dict, names: dict) -> dict:
    return {names[leaf]: x for leaf, x in cache.items()}


class Engine(routed_standin.Engine):
    def __init__(self, hp, *args, **kwargs):
        super().__init__({**hp, "pattern": hp["mixers"]}, *args, **kwargs)
        self.shards[0].cache = _named(self.shards[0].cache, NAMES)

    def _prefill(self, params, cache, *args, **kwargs):
        logits, cache = super()._prefill(params, _named(cache, BACK), *args,
                                         **kwargs)
        return logits, _named(cache, NAMES)

    def _decode(self, params, cache, *args):
        tokens, cache, rng = super()._decode(params, _named(cache, BACK),
                                             *args)
        return tokens, _named(cache, NAMES), rng

    def read_choices(self, cache):
        return cache[NAMES["choices"]]


def engine(hp, params, serve: dict, side: str = "bf16", **more):
    return Engine(hp, params, side, max_batch=serve["max_batch_size"],
                  max_seq=serve["max_seq_len"],
                  buckets=tuple(serve["engine_kwargs"]["buckets"]), **more)


@contextlib.contextmanager
def _built_with(**more):
    me = sys.modules[__name__]
    sound = me.engine
    me.engine = functools.partial(sound, **more)
    try:
        yield
    finally:
        me.engine = sound


def fp8():
    """As ``control_routed.fp8``: an engine built while this is open
    runs the programs on their ``fp8`` side."""
    return _built_with(side="fp8")


def router_fault():
    """As ``control_routed.router_fault``."""
    return _built_with(router_fault=True)
