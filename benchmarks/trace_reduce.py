"""From a profiler trace (.xplane.pb) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. One device
plane per chip (``/device:TPU:<n>``). Of its lines (looked at by hand
in a v5e trace, PR 23: ``Steps``, ``XLA Modules``, ``XLA Ops``, ``Async
XLA Ops``, ``TC Overlay``) two are used. ``XLA Ops`` is the op-level
line: what the core executes, one event per op, named by the op's whole
HLO text (``%flash_attention_fwd.18 = (...) custom-call(...)``), with
``while``/``conditional`` events spanning their bodies' events. ``Async
XLA Ops`` holds the start-to-done span of each asynchronous copy or
collective, which runs beside the core's ops. The module- and
step-level lines cover idle time inside a program too and are not
used. All times are seconds.

A CPU trace has no device plane. For the rehearsal only, the host
plane's events that carry an ``hlo_op`` stat stand in as one pseudo
chip, so that the same code runs to the same last line.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
HOST_SPAN_PREFIX = "bench."
# ops that only contain other ops of the same line: their time is their
# children's, and counting them would count it twice
CONTAINERS = ("while", "conditional", "call")
# On a TPU an asynchronous collective is a pair of fusions on the op
# line, async-collective-start.N and async-collective-done.N, with the
# transfer under way between them and no event of its own on the async
# line (v5e trace of four chips, PR 23). load() adds that span.
ASYNC_PAIR = re.compile(r"^async-collective-(start|done)((?:\.\d+)?)$")


@dataclasses.dataclass
class Chip:
    """One chip's op events. ``names`` are instruction names
    (``flash_attention_fwd.18``), ``core`` marks the events the core
    executed itself: on the op-level line and not a container. The
    rest are containers and asynchronous spans."""

    name: str
    names: List[str]
    start: np.ndarray         # seconds
    end: np.ndarray
    core: np.ndarray          # bool
    in_flight: np.ndarray     # bool: core ops and asynchronous spans

    def select(self, pattern) -> np.ndarray:
        rx = re.compile(pattern) if isinstance(pattern, str) else pattern
        return np.fromiter((bool(rx.search(n)) for n in self.names), bool,
                           len(self.names))


def parse_op(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) from an event's name: the whole HLO
    text on a TPU, ``%name = type opcode(operands)...``, or just the
    name where the backend gives no more (CPU)."""
    if not text.startswith("%") or " = " not in text:
        return text, re.split(r"[.\d]*$", text, maxsplit=1)[0]
    name, rest = text[1:].split(" = ", 1)
    if rest.startswith("("):  # a tuple type: skip to its closing bracket
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return name, rest.strip().split("(", 1)[0]


def _chip(plane_name: str, events) -> Chip:
    """events: (text, start_ns, end_ns, is_async) tuples."""
    names, start, end, core, in_flight = [], [], [], [], []
    open_starts: Dict[str, float] = {}
    for text, t0, t1, is_async in sorted(events, key=lambda e: e[1]):
        name, opcode = parse_op(text)
        leaf = opcode not in CONTAINERS
        names.append(name)
        start.append(t0)
        end.append(t1)
        core.append(leaf and not is_async)
        in_flight.append(leaf)
        pair = ASYNC_PAIR.match(name)
        if pair and pair.group(1) == "start":
            open_starts[pair.group(2)] = t0
        elif pair and pair.group(2) in open_starts:
            names.append("async-collective" + pair.group(2))
            start.append(open_starts.pop(pair.group(2)))
            end.append(t1)
            core.append(False)
            in_flight.append(True)
    return Chip(plane_name, names,
                np.asarray(start, float) * 1e-9, np.asarray(end, float) * 1e-9,
                np.asarray(core, bool), np.asarray(in_flight, bool))


@dataclasses.dataclass
class Trace:
    chips: List[Chip]
    host_spans: List[Tuple[str, float, float]]  # benchmark's own spans
    window_s: float = 0.0    # set by whoever timed the traced part


def _union(start: np.ndarray, end: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merged, sorted, disjoint intervals."""
    if len(start) == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(s) - 1)
    return s[idx], run_end[last]


def union_seconds(start, end, lo: Optional[float] = None,
                  hi: Optional[float] = None) -> float:
    start, end = np.asarray(start, float), np.asarray(end, float)
    if lo is not None:
        start, end = np.maximum(start, lo), np.maximum(end, lo)
    if hi is not None:
        start, end = np.minimum(start, hi), np.minimum(end, hi)
    s, e = _union(start, end)
    return float((e - s).sum())


def _minus(a: Tuple[np.ndarray, np.ndarray],
           b: Tuple[np.ndarray, np.ndarray]) -> float:
    """Seconds of the disjoint intervals ``a`` not covered by the
    disjoint intervals ``b``."""
    total = float((a[1] - a[0]).sum())
    if len(b[0]) == 0 or total == 0.0:
        return total
    covered = 0.0
    for s, e in zip(*a):
        i = np.searchsorted(b[1], s, side="right")
        j = np.searchsorted(b[0], e, side="left")
        if j > i:
            covered += float(
                (np.minimum(b[1][i:j], e) - np.maximum(b[0][i:j], s)).sum()
            )
    return total - covered


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    chips = [
        _chip(plane.name, [
            (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             line.name == ASYNC_LINE)
            for line in plane.lines if line.name in (OP_LINE, ASYNC_LINE)
            for ev in line.events])
        for plane in planes if DEVICE_PLANE.match(plane.name)
    ]
    host_spans, host_ops = [], []
    for plane in planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                end_ns = ev.start_ns + ev.duration_ns
                if ev.name.startswith(HOST_SPAN_PREFIX):
                    host_spans.append(
                        (ev.name, ev.start_ns * 1e-9, end_ns * 1e-9))
                elif not chips and ev.duration_ns > 0 and any(
                        k == "hlo_op" for k, _ in ev.stats):
                    host_ops.append((ev.name, ev.start_ns, end_ns, False))
    if host_ops:
        chips.append(_chip("/host:CPU (rehearsal stand-in)", host_ops))
    chips.sort(key=lambda c: c.name)
    return Trace(chips, sorted(host_spans, key=lambda s: s[1]))


def find_xplane(log_dir: str) -> str:
    import glob
    import os

    found = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


# ---------------------------------------------------------------- numbers
def busy_seconds(trace: Trace) -> float:
    """Union of the intervals in which the core ran an op, per chip,
    then the mean over the chips."""
    if not trace.chips:
        return 0.0
    return float(np.mean([union_seconds(c.start[c.core], c.end[c.core])
                          for c in trace.chips]))


def span_seconds(trace: Trace) -> float:
    """First op start to last op end over every chip."""
    chips = [c for c in trace.chips if c.core.any()]
    if not chips:
        return 0.0
    return float(max(c.end[c.core].max() for c in chips)
                 - min(c.start[c.core].min() for c in chips))


def matching_seconds(trace: Trace, pattern) -> Tuple[float, float]:
    """(summed durations, event count) of the core's ops whose name
    matches, mean over the chips."""
    secs, counts = [], []
    for c in trace.chips:
        sel = c.select(pattern) & c.core
        secs.append(float((c.end[sel] - c.start[sel]).sum()))
        counts.append(int(sel.sum()))
    if not secs:
        return 0.0, 0.0
    return float(np.mean(secs)), float(np.mean(counts))


def in_flight_seconds(trace: Trace, pattern) -> float:
    """Seconds in which an op whose name matches is under way, on the
    core or asynchronously beside it (a collective between its start
    and its done), mean over the chips."""
    out = []
    for c in trace.chips:
        sel = c.select(pattern) & c.in_flight
        out.append(union_seconds(c.start[sel], c.end[sel]))
    return float(np.mean(out)) if out else 0.0


def exposed_seconds(trace: Trace, pattern) -> float:
    """Of ``in_flight_seconds``, the part in which the core runs no
    other op: the time the matching ops are not hidden behind compute."""
    out = []
    for c in trace.chips:
        sel = c.select(pattern)
        mine = _union(c.start[sel & c.in_flight], c.end[sel & c.in_flight])
        others = _union(c.start[~sel & c.core], c.end[~sel & c.core])
        out.append(_minus(mine, others))
    return float(np.mean(out)) if out else 0.0


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """[name, seconds] of the core's ops with most device time, mean
    over the chips, under their instruction names."""
    totals: Dict[str, float] = {}
    for c in trace.chips:
        dur = c.end - c.start
        for name, d, core in zip(c.names, dur, c.core):
            if core:
                totals[name] = totals.get(name, 0.0) + float(d)
    k = max(len(trace.chips), 1)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / k] for name, secs in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> List[list]:
    """[what the host was doing, idle seconds] summed over the idle gaps
    of the first chip's core, by the benchmark's own innermost host span
    at the middle of each gap ("host:unspanned" where there is none).
    The device's clock and the host's agree to about a millisecond."""
    if not trace.chips or not trace.chips[0].core.any():
        return []
    c = trace.chips[0]
    s, e = _union(c.start[c.core], c.end[c.core])
    spans = trace.host_spans
    starts = np.asarray([sp[1] for sp in spans], float)
    totals: Dict[str, float] = {}
    for gs, ge in zip(e[:-1], s[1:]):
        mid = (gs + ge) / 2
        name = "host:unspanned"
        # innermost = the latest-starting span that still covers the middle
        i = int(np.searchsorted(starts, mid, side="right")) - 1
        while i >= 0 and mid - starts[i] < 60.0:
            if spans[i][2] >= mid:
                name = spans[i][0]
                break
            i -= 1
        totals[name] = totals.get(name, 0.0) + float(ge - gs)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in ranked]


def breakdown(trace: Trace) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}
