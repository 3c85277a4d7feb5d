"""contract.check_last_line against good and bad last lines."""

import copy
import json
import os
import re

import pytest

from benchmarks import contract, spec

E2E = {"train_tokens_per_s_per_chip": "tokens/s/chip", "setup_s": "s"}
LAYER = {"train_step_ms": "ms", "collective_ms": "ms"}
UNTRACED = {
    "correct": True, "attempted": 40, "failed": 0,
    "metrics": {
        "train_tokens_per_s_per_chip": {"value": 3211.5, "unit": "tokens/s/chip"},
        "setup_s": {"value": 41.2, "unit": "s"}},
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
               "memory_peak_bytes": 13958643712},
}
TRACED = {
    "correct": True, "attempted": 40, "failed": 0,
    "metrics": {"train_step_ms": {"value": 2500.1, "unit": "ms"},
                "collective_ms": {"value": 310.0, "unit": "ms"}},
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
               "memory_peak_bytes": 13958643712,
               "window_s": 7.6, "busy_s": 7.2},
    "breakdown": {"device_ops": [["fusion.1", 1.5]],
                  "idle_gaps": [["bench.step", 0.2]]},
}


def check(obj, traced):
    return contract.check_last_line(
        obj, LAYER if traced else E2E, traced=traced, chips=4)


def test_good_lines_pass():
    assert check(UNTRACED, False) == []
    assert check(TRACED, True) == []
    assert check(json.loads(json.dumps(TRACED)), True) == []


COMPARED = {"prefill_rel_rms": [0.0141, 0.03], "decode_choice_gap": [0.0, 0.1]}


@pytest.mark.parametrize("compared,problem", [
    (COMPARED, None),
    ({"first_nll_rms": [0.0138, 0.038]}, None),
    ({"prefill_rel_rms": [None, 0.03]}, None),      # a reading not finite
    ({}, "compared is not"),
    ({"prefill_rel_rms": 0.0141}, "compared is not"),
    ({"prefill_rel_rms": [0.0141]}, "compared is not"),
    ({"prefill_rel_rms": ["0.0141", 0.03]}, "compared is not"),
    ({"prefill rel rms": [0.0141, 0.03]}, "compared is not"),
    ([0.0141, 0.03], "compared is not"),
])
def test_the_numbers_compared_ride_last_on_either_line(compared, problem):
    """Each number `correct` compared, beside its limit, under a key of
    its own that comes last on the line (run.py prints the same as the
    last lines of standard error)."""
    for line, traced in ((UNTRACED, False), (TRACED, True)):
        bad = check({**line, "compared": compared}, traced)
        assert (bad == []) if problem is None else any(
            problem in b for b in bad), bad
    if problem is None:
        first = {"compared": compared, **UNTRACED}
        assert any("last key" in b for b in check(first, False))


def edit(obj, path, value="__delete__"):
    obj = copy.deepcopy(obj)
    node = obj
    for key in path[:-1]:
        node = node[key]
    if value == "__delete__":
        del node[path[-1]]
    else:
        node[path[-1]] = value
    return obj


@pytest.mark.parametrize("obj,traced,needle", [
    (edit(TRACED, ["device", "window_s"]), True, "window_s is missing"),
    (edit(TRACED, ["device", "busy_s"], 0.0), True, "busy_s is not above 0"),
    # four chips summed instead of averaged: what PR 22 printed
    (edit(TRACED, ["device", "busy_s"], 4 * 7.2), True, "exceeds"),
    (edit(TRACED, ["device", "busy_s"], float("nan")), True, "busy_s"),
    (edit(UNTRACED, ["metrics", "setup_s"], {"value": 41.2}), False,
     "not {value, unit}"),
    (edit(UNTRACED, ["metrics", "setup_s", "unit"], "wall seconds"), False,
     "a unit may not be"),
    (edit(UNTRACED, ["metrics", "setup_s", "unit"], "ms"), False, "declared"),
    (edit(UNTRACED, ["metrics", "train_step_ms"],
          {"value": 1.0, "unit": "ms"}), False, "not declared for a untraced"),
    (edit(UNTRACED, ["metrics", "setup_s"]), False, "missing"),
    (edit(UNTRACED, ["metrics", "setup_s", "value"], float("inf")), False,
     "finite"),
    (edit(UNTRACED, ["metrics", "setup_s", "value"], "41"), False, "finite"),
    (edit(UNTRACED, ["device", "count"], 1), False, "asks for 4"),
    (edit(UNTRACED, ["device", "memory_peak_bytes"], 0), False,
     "memory_peak_bytes"),
    (edit(UNTRACED, ["correct"], "yes"), False, "boolean"),
    (edit(UNTRACED, ["failed"], 41), False, "exceeds attempted"),
    (edit(UNTRACED, ["attempted"]), False, "'attempted' is missing"),
    (edit(UNTRACED, ["notes"], {}), False, "does not belong"),
    (edit(UNTRACED, ["breakdown"], {}), False, "does not belong"),
    (edit(TRACED, ["breakdown", "device_ops"], [["x", 1.0]] * 11), True,
     "at most 10"),
    ([1, 2], False, "not a JSON object"),
])
def test_bad_lines_are_named(obj, traced, needle):
    problems = check(obj, traced)
    assert problems and any(needle in p for p in problems), problems


def test_rehearsal_may_leave_out_trace_metrics_only_when_told():
    line = edit(TRACED, ["metrics", "collective_ms"])
    assert check(line, True)
    assert contract.check_last_line(
        line, LAYER, traced=True, chips=4, optional={"collective_ms"}) == []


# ------------------------------------------- BENCHMARK.json and the files
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_agrees_with_the_files():
    bench = spec.benchmark_json()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = spec.load_cell(w["name"], False)
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert os.path.exists(os.path.join(
            spec.HERE, "configs", f"{w['config']}.json"))
        for traced in (False, True):
            assert len(spec.cell_metrics(w["name"], traced)) >= 1
        assert len(spec.cell_metrics(w["name"], False)) >= 2  # setup_s + one
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(cells) // 4)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"])
        assert contract.UNIT.match(m["unit"]), m
        assert set(m.get("workloads", cells)) <= cells
        on_file = spec.load_json("metrics", f"{m['name']}.json")
        assert os.path.exists(os.path.join(
            spec.HERE, "readers", f"{on_file['reader']}.py"))
        if "layer" in m:
            assert on_file["layer"] == m["layer"]
            assert on_file["moves"] == m["moves"] and m["moves"] in e2e
            moved = next(e for e in bench["end_to_end"]
                         if e["name"] == m["moves"])
            assert set(m.get("workloads", cells)) <= set(
                moved.get("workloads", cells))
    for c in bench["configs"]:
        on_file = spec.load_json("configs", os.path.basename(c["file"]))
        assert on_file["source"] == c["source"]
        assert on_file["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_a_cell_may_override_only_reduced_keys(tmp_path, monkeypatch):
    cell = spec.load_cell("internlm2-1.8b.train-2k", False)
    assert cell["hp"]["num_hidden_layers"] == 16
    assert cell["hp"]["hidden_size"] == 2048  # no width is cut
    full = spec.load_cell("internlm2-1.8b.serve-chat", False)
    assert full["hp"]["num_hidden_layers"] == 24
    real = spec.load_json

    def fake(*parts):
        out = real(*parts)
        if parts[0] == "workloads":
            out = {**out, "config_overrides": {"hidden_size": 1024}}
        return out

    monkeypatch.setattr(spec, "load_json", fake)
    with pytest.raises(ValueError, match="reduced"):
        spec.load_cell("internlm2-1.8b.train-2k", False)


def stated_shares(cell: dict) -> dict:
    """The shares of readings over the limits that a serve cell states,
    held as ``serve_load.check_cell`` holds them: each under its
    ceiling, both or neither, and with them no compared row before row
    32 (the probe's and the traffic's least prompt). A cell compared
    under the engine's own routing choices (``"routing": "engine"``)
    states none of its rows, a ``route_margin_tol``, and may state the
    served tokens' alone; its family gives ``reference_routed``."""
    from benchmarks import serve_load

    check = cell["serve"]["reference_check"]
    stated = {k: check[k] for k in serve_load.SHARE_CEILINGS if k in check}
    for share, value in stated.items():
        assert 0 < value <= serve_load.SHARE_CEILINGS[share]
    if serve_load.routed(check):
        assert set(stated) <= {"choice_gap_over_share"}
        assert 0 < check["route_margin_tol"]
        assert callable(spec.family_of(cell["hp"]).reference_routed)
    elif stated:
        assert set(stated) == set(serve_load.SHARE_CEILINGS)
    if stated:
        assert check["length"] - check["positions"] \
            >= serve_load.SHARES_FROM_ROW
        assert cell["traffic"]["prompt_len"]["min"] \
            >= serve_load.SHARES_FROM_ROW
    return stated


def test_the_fixture_trees_cells_are_held_as_the_benchmarks_are():
    """Every cell of the tests' tree loads, real and rehearsed, with
    what its kind holds of a cell's file held (``check_cell``), its
    configuration found by name and its family by the configuration's;
    a serve cell's shares of readings over the limits, where it states
    them, lie under the harness's ceilings, and one of the tree's cells
    does. A cell of BENCHMARK.json that states them is held in the same
    way, whatever its family; the llama family's cells state none."""
    import glob

    names = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(spec.FIXTURE_TREE, "workloads", "*.json")))
    assert len(names) >= 3
    stating = []
    for name in names:
        for rehearse in (False, True):
            cell = spec.load_cell(name, rehearse)
            assert NAME.match(cell["name"]) and cell["name"] == name
            assert spec.kind_of(cell).run
            assert spec.family_of(cell["hp"]).reference_logits
            assert len(spec.cell_metrics(name, False)) >= 1
            assert len(spec.cell_metrics(name, True)) >= 1
        if cell["kind"] == "serve" and stated_shares(cell):
            stating.append(name)
    assert stating == ["hybrid-standin.serve", "routed-standin.serve"]
    for w in spec.benchmark_json()["workloads"]:
        for rehearse in (False, True):
            cell = spec.load_cell(w["name"], rehearse)
            if cell["kind"] == "serve":
                stated = stated_shares(cell)
                # a dense decoder reads under the limits at every position
                if cell["hp"].get("family", "llama") == "llama":
                    assert not stated
