"""The fixture family ``routed``'s side of the tests: the program
cannot serve such a family yet (``LLMServer`` builds the engine of
``models/llama.py``: PERF.md section 7), so ``engine`` gives the
stand-in's own two programs at a serve cell's sizes, and the control is
the stand-in's own third side: the shared expert's matmul operands in
fp8 (``routed_standin._mlp``). ``router_fault()`` is the fault a cell
compared under the engine's own routing choices can have and no other:
an engine that chooses an expert the reference would not have and says
so (``routed_standin._faulted``)."""

import contextlib
import functools
import sys

import routed_standin


def engine(hp, params, serve: dict, side: str = "bf16", **more):
    """The stand-in's two programs at a serve cell's sizes."""
    return routed_standin.Engine(
        hp, params, side, max_batch=serve["max_batch_size"],
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
    """An engine built while this is open runs the stand-in's programs
    on their ``fp8`` side (a static argument of its jitted programs: a
    patch under them would meet the sound side's compiled ones)."""
    return _built_with(side="fp8")


def router_fault():
    """An engine built while this is open takes, at one row in 32 of its
    first routed layer, the best held expert it had passed over, and
    ``read_choices`` says so."""
    return _built_with(router_fault=True)
