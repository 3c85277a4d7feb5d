"""Finding a cell's files by name, and evaluating its metrics.

Nothing here knows a cell, a configuration, a model family or a metric
by name: a cell is ``workloads/<cell>.json``, its configuration
``configs/<config>.json``, the configuration's architecture
``families/<family>.py``, a metric ``metrics/<metric>.json`` naming a
reader ``readers/<reader>.py`` with ``read(ctx, args) -> number, None or
NotRead(why)``. Which metrics a cell reports is what ``BENCHMARK.json``
says.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# A second tree, laid out as this one, that holds only what the
# benchmark's own tests add through the doors a later PR uses (a toy
# family, its configuration, cells, metrics and declarations): files
# found by name here too, after this tree's own. families/__init__.py
# looks there for a family module in the same way.
FIXTURE_TREE = os.path.join(HERE, "tests", "tree")


def load_json(*parts: str) -> dict:
    for tree in (HERE, FIXTURE_TREE):
        path = os.path.join(tree, *parts)
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise FileNotFoundError(
        f"no {os.path.join(*parts)} under benchmarks/: the name it is found "
        "by is the one BENCHMARK.json or the cell's file gives")


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def declared(key: str) -> list:
    """BENCHMARK.json's ``end_to_end`` or ``per_layer`` entries, then the
    fixture tree's (each of which lists the fixture's cells and no
    other)."""
    entries = benchmark_json()[key]
    fixture = os.path.join(FIXTURE_TREE, "BENCHMARK.json")
    if os.path.exists(fixture):
        with open(fixture) as f:
            entries = entries + json.load(f).get(key, [])
    return entries


class NotRead(str):
    """What a reader returns in place of a number when it finds nothing
    to read, with the reason in a line: ``NotRead("the engine has no
    engine_stats()")``. A bare ``None`` says the same without one."""


def _module(dotted: str, asked_by: str):
    """``benchmarks.<dotted>``, or a ValueError that says who named it
    (an ImportError from inside the module stays what it is)."""
    try:
        return importlib.import_module(f"benchmarks.{dotted}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmarks.{dotted}":
            raise
        raise ValueError(
            f"{asked_by} and there is no "
            f"benchmarks/{dotted.replace('.', '/')}.py") from None


def family_of(hp: dict):
    """The module that knows this configuration's architecture:
    ``families/<hp["family"]>.py``, ``llama`` where the configuration
    names none (README.md, "A family", lists what it gives)."""
    name = hp.get("family", "llama")
    return _module(f"families.{name}", f"configuration {hp.get('name')!r} "
                   f"names the family {name!r}")


def kind_of(cell: dict):
    """The module that runs this kind of cell, ``run(cell, args,
    per_layer)``: ``train`` and ``serve`` are train_loop.py and
    serve_load.py, any other kind is ``benchmarks/<kind>.py``."""
    kind = cell["kind"]
    name = {"train": "train_loop", "serve": "serve_load"}.get(kind, kind)
    return _module(name, f"cell {cell.get('name')!r} is of kind {kind!r} "
                   "(a module with run(cell, args, per_layer))")


def apply_environment() -> None:
    """The process environment the cells run in (environment.json, with
    the reason for each entry); what the machine already sets, stays.
    Before ray_tpu.init(): the workers inherit it."""
    for key, value in load_json("environment.json")["defaults"].items():
        os.environ.setdefault(key, value)


def load_cell(name: str, rehearse: bool) -> dict:
    """The cell's file with its configuration resolved under ``hp``
    (the sizes as run). A cell may override only keys its configuration
    lists under ``reduced``; the rehearsal swaps in the toy presets.
    What its kind's module holds of a cell's file (``check_cell(cell)``,
    where it gives one) is held here, on the cell as loaded."""
    cell = load_json("workloads", f"{name}.json")
    hp = load_json("configs", f"{cell['config']}.json")
    overrides = cell.get("config_overrides", {})
    not_allowed = set(overrides) - set(hp.get("reduced", []))
    if not_allowed:
        raise ValueError(
            f"cell {name} overrides {sorted(not_allowed)}, which its "
            "configuration does not list under 'reduced'")
    hp = {**hp, **overrides}
    if rehearse:
        hp = {**hp, **hp["rehearsal"]}
        cell = _merge(cell, cell.get("rehearsal", {}))
    cell["hp"] = hp
    held = getattr(kind_of(cell), "check_cell", None)
    if held is not None:
        held(cell)
    return cell


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if (
            isinstance(v, dict) and isinstance(base.get(k), dict)) else v
    return out


def cell_metrics(cell_name: str, traced: bool) -> dict:
    """{metric name: its file's dict} for this cell and kind of run, as
    BENCHMARK.json declares them; the unit is BENCHMARK.json's."""
    out = {}
    for entry in declared("per_layer" if traced else "end_to_end"):
        if "workloads" in entry and cell_name not in entry["workloads"]:
            continue
        metric = load_json("metrics", f"{entry['name']}.json")
        out[entry["name"]] = {**metric, "unit": entry["unit"]}
    return out


def evaluate(metrics: dict, ctx: dict) -> tuple:
    """Each metric through its reader: ({name: {value, unit}}, {name:
    why}) where the second holds the metrics whose reader found nothing
    to read (None, or NotRead with the reason); they are left out of
    the first."""
    out, not_read = {}, {}
    for name, metric in metrics.items():
        reader = importlib.import_module(
            f"benchmarks.readers.{metric['reader']}")
        value = reader.read(ctx, metric.get("args", {}))
        if value is None or isinstance(value, NotRead):
            not_read[name] = str(
                value or f"readers/{metric['reader']}.py found nothing to read")
        else:
            out[name] = {"value": float(value), "unit": metric["unit"]}
    return out, not_read


def peak_for(kind: str, rehearse: bool) -> dict:
    peaks = load_json("peaks.json")
    if kind not in peaks:
        if rehearse:  # so that the rehearsal reaches the same readers
            return {**next(iter(peaks.values())), "source": "rehearsal"}
        raise KeyError(
            f"no peaks recorded for device_kind {kind!r}; add it to "
            "benchmarks/peaks.json with its source")
    return peaks[kind]


def prng_key(seed: int):
    """A key from any whole-number seed (the driver's pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
