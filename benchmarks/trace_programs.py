"""A device trace of a process that runs SEVERAL programs (a serving
replica: ``jit_prefill`` per chunk bucket, ``jit_decode``), reduced by
program and by scope, with the host's ``ray_tpu.*`` spans beside it.

    python -m benchmarks.trace_programs <trace.xplane.pb[.gz]> [<scopes.json>]

``trace_reduce.py`` names an op by its instruction alone, which is right
for one program; ``fusion.174`` of the prefill program is not
``fusion.174`` of the decode program. Here every op of the ``XLA Ops``
line gets the program whose ``XLA Modules`` event (``jit_decode(<id>)``,
same plane, same clock) contains it, and its scope comes from that
program's own map (``ray_tpu._private.jax_utils.scope_map`` of
``engine.compiled_programs()``), joined by (program, instruction).

Nothing in ``run.py`` reads this yet: ``holder.Tracer.reduce`` deletes
the trace once ``trace_reduce.load`` has dropped the module line and
every host span not named ``bench.*`` (PERF.md, open questions, says
which lines of which file would change). It is the tool the by-hand
numbers of PERF.md section 5 were made with, and it is tested against a
trace recorded on the chip (tests/record_programs_fixture.py).
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import re
import sys
import tempfile
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from . import trace_reduce
from .scopes import words

MODULE_LINE = "XLA Modules"
SPAN_PREFIXES = ("ray_tpu.", "bench.")
KV_SCOPES = ("kv_slice", "kv_merge", "kv_write")
COMPUTE_SCOPES = ("embed", "attn", "mlp", "head", "ce", "sample", "optimizer")
_SHAPE = re.compile(r"^%\S+ = (\w+\[[\d,]*\])")


@dataclasses.dataclass
class Ops:
    """The core's ops of one chip (containers and asynchronous spans
    left out), each with the module event that contains it."""

    names: List[str]
    opcodes: List[str]
    shapes: List[str]          # result type, "bf16[24,8,2048,8,128]"
    start: np.ndarray          # seconds
    end: np.ndarray
    modules: List[str]         # "jit_decode(3)"; "" outside any module


@dataclasses.dataclass
class ProgramTrace:
    chips: List[Ops]
    spans: List[Tuple[str, float, float, dict]]   # name, start, end, stats


def program_of(module: str) -> str:
    """``jit_decode(3)`` -> ``decode``: the name the function was jitted
    under."""
    base = module.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else base


def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f, tempfile.NamedTemporaryFile(
                suffix=".xplane.pb") as out:
            out.write(f.read())
            out.flush()
            return load(out.name)
    chips, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            spans.extend(
                (ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9, dict(ev.stats))
                for line in plane.lines for ev in line.events
                if ev.name.startswith(SPAN_PREFIXES))
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        mods = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in (lines[MODULE_LINE].events
                       if MODULE_LINE in lines else ()))
        mod_start = np.asarray([m[0] for m in mods], float)
        ops = Ops([], [], [], None, None, [])
        start, end = [], []
        events = lines[trace_reduce.OP_LINE].events \
            if trace_reduce.OP_LINE in lines else ()
        for ev in sorted(events, key=lambda e: e.start_ns):
            name, opcode = trace_reduce.parse_op(ev.name)
            if opcode in trace_reduce.CONTAINERS:
                continue
            i = int(np.searchsorted(mod_start, ev.start_ns, "right")) - 1
            inside = i >= 0 and ev.start_ns < mods[i][1]
            shape = _SHAPE.match(ev.name)
            ops.names.append(name)
            ops.opcodes.append(opcode)
            ops.shapes.append(shape.group(1) if shape else "")
            ops.modules.append(mods[i][2] if inside else "")
            start.append(ev.start_ns * 1e-9)
            end.append((ev.start_ns + ev.duration_ns) * 1e-9)
        ops.start, ops.end = np.asarray(start, float), np.asarray(end, float)
        chips.append(ops)
    return ProgramTrace(chips, sorted(spans, key=lambda s: s[1]))


# ---------------------------------------------------------------- numbers
def _mean(values: Iterable[float]) -> float:
    values = list(values)
    return float(np.mean(values)) if values else 0.0


def busy_seconds(trace: ProgramTrace) -> float:
    return _mean(trace_reduce.union_seconds(c.start, c.end)
                 for c in trace.chips)


def program_seconds(trace: ProgramTrace) -> Dict[str, float]:
    """{program: seconds of its ops}, mean over the chips; "" holds the
    ops no module event contains."""
    out: Dict[str, float] = {}
    k = max(len(trace.chips), 1)
    for c in trace.chips:
        for module, d in zip(c.modules, c.end - c.start):
            key = program_of(module) if module else ""
            out[key] = out.get(key, 0.0) + float(d) / k
    return out


def assign_maps(trace: ProgramTrace,
                maps: Dict[str, Dict[str, str]]) -> Dict[str, str]:
    """{module event name: key of ``maps``}. ``maps`` is keyed as
    ``engine.compiled_programs()`` is (``decode``, ``prefill_64``); the
    buckets of one jitted function share a program name, so a module
    takes, of the maps whose key starts with its program's name, the one
    that knows most of its instructions."""
    seen: Dict[str, set] = {}
    for c in trace.chips:
        for module, name in zip(c.modules, c.names):
            seen.setdefault(module, set()).add(name)
    out = {}
    for module, names in seen.items():
        program = program_of(module)
        fits = [k for k in maps if k == program or k.startswith(program + "_")]
        if module and fits:
            out[module] = max(fits, key=lambda k: len(names & set(maps[k])))
    return out


def scope_of(trace: ProgramTrace, maps: Dict[str, Dict[str, str]]):
    """A function (chip, i) -> scope path of that op, "" if unknown."""
    which = assign_maps(trace, maps)

    def scope(c: Ops, i: int) -> str:
        key = which.get(c.modules[i])
        return maps[key].get(c.names[i], "") if key else ""

    return scope


def scope_seconds(trace: ProgramTrace, maps, wanted: Iterable[str],
                  also=lambda c, i: False) -> float:
    """Seconds of the ops whose scope holds any name in ``wanted`` (or
    that ``also`` accepts), mean over the chips."""
    wanted, scope = frozenset(wanted), scope_of(trace, maps)
    return _mean(
        sum(float(c.end[i] - c.start[i]) for i in range(len(c.names))
            if words(scope(c, i)) & wanted or also(c, i))
        for c in trace.chips)


def kv_cache_move_seconds(trace: ProgramTrace, maps,
                          cache_shapes: Iterable[str]) -> float:
    """Device time that moves the KV cache and not the model: ops scoped
    kv_slice, kv_merge or kv_write, and ops outside every compute scope
    whose result has the shape of a whole cache shard or of one layer of
    it. Those are the ``copy`` of a shard XLA makes where the program
    does not donate it, and the scan's own slice of a layer's cache out
    of the stack and its write back (scope ``layers`` alone)."""
    shapes = set(cache_shapes)
    shapes |= {re.sub(r"\[\d+,", "[", s, count=1) for s in shapes}
    scope, compute = scope_of(trace, maps), frozenset(COMPUTE_SCOPES)

    def moved(c: Ops, i: int) -> bool:
        return c.shapes[i] in shapes and not words(scope(c, i)) & compute

    return scope_seconds(trace, maps, KV_SCOPES, also=moved)


def unattributed_seconds(trace: ProgramTrace, maps, known: Iterable[str],
                         named: str) -> float:
    known, rx, scope = frozenset(known), re.compile(named), scope_of(trace, maps)
    return _mean(
        sum(float(c.end[i] - c.start[i]) for i in range(len(c.names))
            if not words(scope(c, i)) & known and not rx.search(c.names[i]))
        for c in trace.chips)


def idle_gaps(trace: ProgramTrace, n: int = 10) -> List[list]:
    """[span, idle seconds] over the idle gaps of the first chip, by the
    innermost host span of either kind (``ray_tpu.*`` or ``bench.*``)
    at the middle of each gap; "host:unspanned" where there is none."""
    if not trace.chips or not len(trace.chips[0].start):
        return []
    c = trace.chips[0]
    s, e = trace_reduce._union(c.start, c.end)
    starts = np.asarray([sp[1] for sp in trace.spans], float)
    totals: Dict[str, float] = {}
    for gs, ge in zip(e[:-1], s[1:]):
        mid, name = (gs + ge) / 2, "host:unspanned"
        # innermost = the latest-starting span that still covers the middle
        i = int(np.searchsorted(starts, mid, side="right")) - 1
        while i >= 0 and mid - starts[i] < 60.0:
            if trace.spans[i][2] >= mid:
                name = trace.spans[i][0]
                break
            i -= 1
        totals[name] = totals.get(name, 0.0) + float(ge - gs)
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def top_ops(trace: ProgramTrace, maps=None, n: int = 12) -> List[list]:
    """[program, instruction, scope, seconds] by device time, mean over
    chips: the (program, instruction) pairs ``trace_reduce.top_ops``
    sums under one name."""
    scope = scope_of(trace, maps or {})
    totals: Dict[tuple, float] = {}
    for c in trace.chips:
        for i, d in enumerate(c.end - c.start):
            key = (program_of(c.modules[i]), c.names[i], scope(c, i))
            totals[key] = totals.get(key, 0.0) + float(d) / len(trace.chips)
    return [[*k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


SCOPES = ("embed", "layers", "attn", "mlp", "head", "ce", "loss_and_grad",
          "optimizer", "kv_write", "attn_cached", "kv_slice", "kv_merge",
          "sample")
NAMED = ("^(flash_attention_|fused_ce|all-gather|all-reduce|reduce-scatter"
         "|collective-permute|all-to-all|async-collective)")


def summary(trace: ProgramTrace, maps: Optional[dict] = None,
            cache_shapes: Iterable[str] = ()) -> dict:
    maps = maps or {}
    busy = busy_seconds(trace)
    by_scope = {s: scope_seconds(trace, maps, [s]) for s in SCOPES}
    return {
        "busy_s": busy,
        "program_s": program_seconds(trace),
        "scope_s": {k: v for k, v in by_scope.items() if v},
        "kv_cache_move_s": kv_cache_move_seconds(trace, maps, cache_shapes),
        "unattributed_s": unattributed_seconds(trace, maps, SCOPES, NAMED),
        "idle_gaps": idle_gaps(trace),
        "top_ops": top_ops(trace, maps),
    }


if __name__ == "__main__":
    found = json.load(open(sys.argv[2])) if len(sys.argv) > 2 else {}
    print(json.dumps(summary(
        load(sys.argv[1]), found.get("scopes", found),
        found.get("cache_shapes", ())), indent=1))
