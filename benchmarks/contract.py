"""The last line's contract, checked before the line is printed.

``check_last_line`` returns what is wrong with an object about to be
printed as the run's result ([] when nothing is). PR 22 was refused for
a traced four-chip line whose device block broke ``0 < busy_s <=
window_s``; run.py refuses to print such a line and exits non-zero, so
that the fault shows in the builder's own run.
"""

from __future__ import annotations

import math
import re
from typing import Dict, List

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("window_s", "busy_s")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def check_last_line(obj, declared: Dict[str, str], *, traced: bool,
                    chips: int, optional=frozenset()) -> List[str]:
    """``declared`` maps every metric this cell reports in this kind of
    run (end-to-end when untraced, per-layer when traced) to its unit.
    ``optional`` names the per-layer metrics of a traced run whose
    reader found nothing to read (run.py notes them as
    ``metrics_not_read``): they may be missing. An untraced run has
    none: an end-to-end metric is always there."""
    if not isinstance(obj, dict):
        return ["the result is not a JSON object"]
    bad = []
    allowed = set(KEYS) | {"compared"} | ({"breakdown"} if traced else set())
    for key in KEYS:
        if key not in obj:
            bad.append(f"key {key!r} is missing")
    for key in obj:
        if key not in allowed:
            bad.append(f"key {key!r} does not belong on the last line")
    if bad:
        return bad

    if not isinstance(obj["correct"], bool):
        bad.append("correct is not a boolean")
    for key in ("attempted", "failed"):
        v = obj[key]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            bad.append(f"{key} is not a whole number >= 0")
    if not bad and obj["failed"] > obj["attempted"]:
        bad.append("failed exceeds attempted")

    metrics = obj["metrics"]
    if not isinstance(metrics, dict):
        bad.append("metrics is not an object")
        metrics = {}
    if optional and not traced:
        bad.append("an end-to-end metric may not be optional")
    for name, unit in declared.items():
        if name not in metrics and name not in optional:
            bad.append(f"metric {name!r} is declared for this run and missing")
    for name, m in metrics.items():
        if not NAME.match(name):
            bad.append(f"metric name {name!r} has characters a name may not")
        if name not in declared:
            kind = "traced" if traced else "untraced"
            bad.append(f"metric {name!r} is not declared for a {kind} run "
                       "of this cell")
            continue
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            bad.append(f"metric {name!r} is not {{value, unit}}")
            continue
        if not _number(m["value"]):
            bad.append(f"metric {name!r} has no finite number as value")
        if not isinstance(m["unit"], str) or not UNIT.match(m["unit"]):
            bad.append(f"metric {name!r} has a unit a unit may not be: "
                       f"{m['unit']!r}")
        elif m["unit"] != declared[name]:
            bad.append(f"metric {name!r} has unit {m['unit']!r}, declared "
                       f"{declared[name]!r}")

    device = obj["device"]
    if not isinstance(device, dict):
        return bad + ["device is not an object"]
    for key in DEVICE_KEYS + (TRACED_DEVICE_KEYS if traced else ()):
        if key not in device:
            bad.append(f"device.{key} is missing")
    if not isinstance(device.get("platform"), str) or not device.get("platform"):
        bad.append("device.platform is not a string")
    if not isinstance(device.get("kind"), str) or not device.get("kind"):
        bad.append("device.kind is not a string")
    count = device.get("count")
    if not isinstance(count, int) or isinstance(count, bool) or count != chips:
        bad.append(f"device.count is {count!r}, the cell asks for {chips}")
    peak = device.get("memory_peak_bytes")
    if not isinstance(peak, int) or isinstance(peak, bool) or peak <= 0:
        bad.append("device.memory_peak_bytes is not a whole number > 0")
    if traced and all(k in device for k in TRACED_DEVICE_KEYS):
        window, busy = device["window_s"], device["busy_s"]
        if not _number(window) or window <= 0:
            bad.append("device.window_s is not a number > 0")
        elif not _number(busy) or busy <= 0:
            bad.append("device.busy_s is not above 0: the trace found no "
                       "device operation (was it taken in the process that "
                       "holds the chips?)")
        elif busy > window:
            bad.append(f"device.busy_s {busy} exceeds device.window_s "
                       f"{window}: is it summed over the chips instead of "
                       "averaged?")
    if "compared" in obj:
        c = obj["compared"]
        if list(obj)[-1] != "compared":
            bad.append("compared is not the line's last key")
        if not isinstance(c, dict) or not c or not all(
                isinstance(k, str) and NAME.match(k)
                and isinstance(v, list) and len(v) == 2
                and all(x is None or _number(x) for x in v)  # None: not finite
                for k, v in c.items()):
            bad.append("compared is not {name: [number, limit]}")
    if "breakdown" in obj:
        b = obj["breakdown"]
        if not isinstance(b, dict) or set(b) - {"device_ops", "idle_gaps"}:
            bad.append("breakdown is not {device_ops, idle_gaps}")
        else:
            for key, rows in b.items():
                if (not isinstance(rows, list) or len(rows) > 10 or not all(
                        isinstance(r, list) and len(r) == 2
                        and isinstance(r[0], str) and _number(r[1])
                        for r in rows)):
                    bad.append(f"breakdown.{key} is not at most 10 "
                               "[name, seconds] pairs")
    return bad
