"""Where the runtime meets JAX: the compile cache, the device report,
and the scope of each instruction of a compiled program.

None of them may be the first thing to initialise a backend in a
process that was not given chips. ``ensure_compilation_cache_dir`` only
touches the environment; ``device_report`` is called by code that is
already computing on its devices; ``scope_map`` reads text.
"""

from __future__ import annotations

import os
import re
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


def compile_with_scopes(lowered):
    """``lowered.compile()`` whose text is sure to carry this build's
    scopes. Two caches stand between a program and its own metadata.
    The persistent compile cache keys a program without its metadata,
    so an executable loaded from it carries the metadata of whichever
    build compiled it first (one from before the scopes, say), and a
    device trace joined with that text names nothing: here the metadata
    is part of the key, so the first call compiles and later ones load.
    And JAX keeps the executable of a module it has compiled in this
    process: ``lowered`` must come from a function jitted afresh (a new
    function object, as ``LlamaEngine.compiled_programs`` makes), not
    from the jitted function that is running. The instructions are
    those of the program that runs: the same HLO, compiled again."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return lowered.compile()
    finally:
        jax.config.update(flag, before)


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def scope_map(compiled) -> Dict[str, str]:
    """{instruction name: scope path} of a compiled executable, read
    from ``compiled.as_text()`` (or from that text itself).

    A device trace names each op by its instruction (``fusion.174``) and
    carries no scope; the optimized HLO does, as ``metadata={op_name=
    "jit(step)/loss_and_grad/jvp(head)/dot_general"}``: the
    ``jax.named_scope`` path, with the transforms JAX adds by itself
    (``jvp``, ``transpose``, ``rematted_computation``). Instructions
    inside fused computations are included, and a fusion whose own
    metadata is empty takes its root's (else the first scope found in
    its computation). Instruction names are unique within one program
    only: join with a trace by (program, instruction)."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    scopes: Dict[str, str] = {}
    calls: Dict[str, str] = {}       # instruction -> called computation
    roots: Dict[str, str] = {}       # computation -> its root's scope
    firsts: Dict[str, str] = {}      # computation -> first scope inside
    computation = ""
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            head = _COMPUTATION.match(line)
            if head is not None:
                computation = head.group(1)
            continue
        name = m.group(2)
        found = _OP_NAME.search(line)
        scope = found.group(1) if found else ""
        scopes[name] = scope
        called = _CALLS.search(line)
        if called is not None:
            calls[name] = called.group(1)
        if scope:
            firsts.setdefault(computation, scope)
            if m.group(1):
                roots[computation] = scope
    for name, called in calls.items():
        if not scopes[name]:
            scopes[name] = roots.get(called) or firsts.get(called, "")
    return {name: scope for name, scope in scopes.items() if scope}
