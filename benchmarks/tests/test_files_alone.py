"""The question a PR that brings a served family asks of the benchmark's
own tests, kept: may it add its serve cell by files alone? In a scratch
copy of ``benchmarks/`` and ``BENCHMARK.json`` this declares what such a
PR would, as new files and new entries and no edit to any file that is
there but ``BENCHMARK.json``:

(a) a further serve cell of a family the engine serves (the tests'
    tree's ``toy-moe.serve`` and its configuration under other names,
    stating no share), and
(b) a serve cell of a routed family that states the two shares of its
    sound engine's readings over the limits (the tree's
    ``routed-standin.serve`` and its configuration under other names),
    and
(c) one of a hybrid routed family, whose engine keeps a recurrent state
    and a convolution's tail beside its latent rows, a cache that is
    not addressed by position, with state layers behind its routed
    layers, so that its rows are compared under the engine's own
    routing choices: its ``reference_check`` says ``"routing":
    "engine"``, states ``route_margin_tol`` and of the two shares the
    served tokens' alone, its engine gives ``read_choices`` and its
    family ``reference_routed`` (the tree's ``hybrid-standin.serve``;
    the next ``model_config`` PR's way in since PR 61), and with it a
    family of its own, as such a PR brings one:
    ``files_alone/family.py`` and ``files_alone/control.py`` as
    ``tree/families/<family>.py`` and ``tree/control_<family>.py`` (the
    tests' own families live in their tree; the name lookups are the
    ones ``benchmarks/families/`` and ``tests/`` go through). It is the
    tree's ``routed`` under another schema: the configuration states its
    layers' mixers under ``mixers`` where the fixture's says
    ``pattern``, and the engine keeps what a slot holds, and the leaf
    its calls write their choices into, under other names than the
    stand-in's, so a test (or a harness) shared by every cell that
    turned on a key or a leaf of the fixture's would fail here,

each with a handful of per-layer metrics (its name added to the
``workloads`` list of the entries the serving cells share, and no file:
a reading is one entry since PR 60) and a probe of its own (32
positions behind a whole chunk and 16 decodes: what
``test_serving_reference.py`` asks of a cell of BENCHMARK.json by
property, and other sizes than any cell here has). Then it runs
``python -m pytest benchmarks/tests`` there, this file left out, and
holds the outcome: every case passes but those listed under
``NEEDS_THE_PROGRAM``, each of which drives (b)'s or (c)'s cell through
``run.py``, where ``LLMServer`` builds an engine of the program's and
the program has none for the fixture family: since PR 46 it serves
routed families of its own (``config.model_module``), but a family is
served by a module of ``ray_tpu/models/`` that its ``model_config``
names, which the tests' stand-in is not, and for (c) no engine of the
program gives ``read_choices`` yet (``ops/moe.py`` ``route_top_k``
returns its experts to ``_dropless_rows`` and no further: PERF.md
section 7, "What PR 61 leaves"; the ``model_config`` PR's to bring in
its own module). Everything the tests themselves hold of such a cell
(the control and the router fault found by the family's name, the
decision by its counts or under the engine's choices, the probe's sizes
by property) passes.

Slow (it is the whole of ``benchmarks/tests`` once more, with three more
cells); ``benchmarks/tests`` is not tier-1.

    python benchmarks/tests/test_files_alone.py <checkout>

declares the same in a checkout of any commit (at PR 36's the run
fails in ``test_contract.py``, ``test_doors.py`` and
``test_serving_reference.py``: PERF.md section 6, PR 37)."""

import json
import os
import shutil
import subprocess
import sys
import xml.etree.ElementTree

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

# (a), (b) and (c): the fixture cell each is a copy of, its new name,
# the shared per-layer entries whose lists it joins, and what its
# ``reference_check`` states otherwise than the fixture's
SERVED = {
    "of": "toy-moe.serve", "config": "fourth-served", "traffic": "serve-mix",
    "metrics": ["engine_tokens_per_step", "engine_host_ms",
                "decode_occupancy_pct", "prefill_wait_p95_ms",
                "kv_cache_move_share_pct", "scope_unattributed_pct"],
    "check": {"length": 64, "positions": 32, "decode_steps": 16,
              "served_requests": 8, "tolerance_why": (
                  "a scratch cell at a test's sizes on the CPU, so no chip "
                  "run is named: the limits are the fixture cell's "
                  "(toy-moe.serve), whose sound engine reads under 0.6 x "
                  "rel_rms_tol at every row and whose control reads over it "
                  "at every row")},
}
ROUTED = {
    "of": "routed-standin.serve", "config": "fifth-routed",
    "traffic": "serve",
    "metrics": ["engine_tokens_per_step", "engine_host_ms",
                "decode_occupancy_pct"],
    "check": {"length": 96, "positions": 32, "decode_steps": 16},
}
HYBRID = {
    "of": "hybrid-standin.serve", "config": "sixth-hybrid",
    "traffic": "serve",
    # a family of its own (files_alone/), and the keys of the fixture's
    # configuration that its schema states under another name
    "family": "sixth_kind", "renamed": {"pattern": "mixers"},
    "metrics": ["engine_tokens_per_step", "engine_host_ms",
                "decode_occupancy_pct"],
    "check": {"length": 96, "positions": 32, "decode_steps": 16},
}
# the cases that need a program that serves (b)'s and (c)'s family: each
# runs its cell through run.py and so through ``LLMServer``
NEEDS_THE_PROGRAM = {
    ("test_scopes", "test_rehearsal_reaches_a_valid_last_line"
     f"[{new['config']}.{new['traffic']}]") for new in (ROUTED, HYBRID)
}


def declare(root: str) -> list:
    """Adds ``SERVED``, ``ROUTED`` and ``HYBRID`` to the checkout at
    ``root`` as a PR would: ``configs/<config>.json`` and
    ``workloads/<cell>.json`` as new files (for ``HYBRID`` its family's
    two modules too), their entries in ``BENCHMARK.json``, and the
    cell's name in the ``workloads`` list of each metric it reports.
    Returns the new cells' names."""
    bench = os.path.join(root, "benchmarks")
    tree = os.path.join(bench, "tests", "tree")

    def load(*parts):
        with open(os.path.join(*parts)) as f:
            return json.load(f)

    def add(obj, *parts):
        path = os.path.join(bench, *parts)
        assert not os.path.exists(path), f"{path} is there already"
        with open(path, "w") as f:
            json.dump(obj, f, indent=1)

    declared = load(root, "BENCHMARK.json")
    shared = {e["name"]: e for e in declared["per_layer"]}
    serve_rate = next(e for e in declared["end_to_end"]
                      if e["name"] == "serve_tokens_per_s")
    names = []
    for new in (SERVED, ROUTED, HYBRID):
        cell = load(tree, "workloads", f"{new['of']}.json")
        config = load(tree, "configs", f"{cell['config']}.json")
        name = f"{new['config']}.{new['traffic']}"
        names.append(name)
        config.update(name=new["config"], source=(
            f"none: a copy of the tests' tree's {cell['config']}, declared "
            "in a scratch checkout by tests/test_files_alone.py"))
        if "family" in new:
            config["family"] = new["family"]
            for theirs, ours in new["renamed"].items():
                config[ours] = config.pop(theirs)
            for ours, there in (
                    ("family.py", ("families", f"{new['family']}.py")),
                    ("control.py", (f"control_{new['family']}.py",))):
                path = os.path.join(tree, *there)
                assert not os.path.exists(path), f"{path} is there already"
                shutil.copy(os.path.join(HERE, "files_alone", ours), path)
        add(config, "configs", f"{new['config']}.json")
        cell.update(name=name, config=new["config"])
        cell["serve"]["reference_check"].update(new["check"])
        add(cell, "workloads", f"{name}.json")
        declared["configs"].append({
            "name": new["config"], "source": config["source"],
            "file": f"benchmarks/configs/{new['config']}.json",
            "reduced": config["reduced"],
            "why": f"scratch: {cell['config']} of the tests' tree under "
                   "another name"})
        declared["workloads"].append({
            "name": name, "config": new["config"], "traffic": new["traffic"],
            "chips": 1,
            "why": f"scratch: {new['of']} of the tests' tree under another "
                   "name, with a probe of its own"})
        serve_rate["workloads"].append(name)
        for metric in new["metrics"]:
            shared[metric]["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(declared, f, indent=1)
    return names


def outcome(report: str) -> tuple:
    """([(test file, case)] that ran, {(test file, case)} that failed or
    erred) of a junit report."""
    cases = list(xml.etree.ElementTree.parse(report).getroot().iter(
        "testcase"))

    def named(case):
        return case.get("classname").rsplit(".", 1)[-1], case.get("name")

    return [named(c) for c in cases], {
        named(c) for c in cases
        if c.find("failure") is not None or c.find("error") is not None}


def test_a_served_family_brings_its_cell_by_files_alone(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    # the program, which the copy's run.py finds beside it
    os.symlink(os.path.join(ROOT, "ray_tpu"), os.path.join(root, "ray_tpu"))
    names = declare(root)
    report = str(tmp_path / "report.xml")
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "benchmarks/tests", "-q",
         "-p", "no:cacheprovider", f"--junitxml={report}",
         "--ignore", os.path.join("benchmarks", "tests",
                                  os.path.basename(__file__))],
        cwd=root, capture_output=True, text=True, timeout=3000)
    assert os.path.exists(report), done.stdout[-3000:] + done.stderr[-3000:]
    cases, bad = outcome(report)
    assert bad == NEEDS_THE_PROGRAM, done.stdout[-6000:]
    # the new cells were there to be asked: the tests parametrised over
    # cells ran each of them, the serving comparison's among them
    for name in names:
        asked = [(where, case) for where, case in cases if name in case]
        assert len(asked) >= 10, (name, asked)
        assert sum(where == "test_serving_reference"
                   for where, _ in asked) >= 8, (name, asked)


if __name__ == "__main__":
    print("declared", declare(os.path.abspath(sys.argv[1])))
