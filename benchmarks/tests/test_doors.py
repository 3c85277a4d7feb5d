"""The three doors a later PR adds through, files only: a model family
found by name (the toy family of ``tests/tree``), a declared per-layer
metric that may read nothing and says so, and a reduced trace that keeps
what program-level readers need. Every number a new metric reads from
the recorded fixtures is made a second time by hand.

No test here knows which cells train and which serve: that is each
cell's ``kind``. A cell is tried on its family's own recording for that
kind, ``trace_<family>.<kind>.xplane.pb.gz`` with its sidecar
``.json.gz`` (here or in ``tests/tree``; tree/record_toy_moe_fixture.py
says what the sidecar holds), and where the family brings none, on the
llama recordings of PR 23 and PR 24. So a PR that adds a family and a
cell adds its recording, and no test file changes."""

import ast
import functools
import glob
import gzip
import json
import math
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from benchmarks import contract, holder, spec, traffic
from benchmarks import trace_programs as tp
from benchmarks import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CHAT, DOCS, OVER = ("internlm2-1.8b.serve-chat",
                    "mistral-7b-v0.3.serve-docbatch",
                    "internlm2-1.8b.serve-chat-over")
# the llama family's train recording of PR 23 (record_fixture.py, four
# chips), as a family's own sidecar would describe it; its toy step was
# not compiled with scopes: an empty map is a program none of whose ops
# is scoped
LLAMA_TRAIN = {"trace": "trace_4chip.xplane.pb", "chips": 4, "seq": 256,
               "seqs_per_step": 8, "traced_steps": 2,
               "scopes": {"step": {}}, "reads_nothing": {}}
# the samples only a program since PR 24 records (engine_stats(),
# iter_stats: holder.engine_deltas, holder.phase_deltas)
PROGRAM_SAMPLES = ("stats.", "phase_s.", "phase_n.", "req.")


def reads_the_programs_samples(metric):
    """Whether a metric's ``args`` name a sample that only the program's
    counters, spans or stamps give: read from its own file alone, so a
    data-only metric over a new counter needs no edit here. (A reader
    that joins with the programs' scope maps says so itself, by what it
    returns where there are none.)"""
    def strings(x):
        if isinstance(x, str):
            yield x
        elif isinstance(x, dict):
            x = list(x.values())
        if isinstance(x, (list, tuple)):
            for item in x:
                yield from strings(item)

    return any(name.startswith(PROGRAM_SAMPLES)
               for name in strings(metric.get("args", {})))


def harness_files():
    return sorted(
        p for pattern in ("*.py", "readers/*.py", "families/*.py")
        for p in glob.glob(os.path.join(BENCH, pattern)))


def unzipped(name, tmp_path):
    """``name`` beside this file (or a whole path), without its .gz."""
    out = tmp_path / os.path.basename(name)
    with gzip.open(os.path.join(HERE, name + ".gz"), "rb") as f:
        out.write_bytes(f.read())
    return str(out)


def tree_cells():
    """The cells of the tests' own tree."""
    return sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(spec.FIXTURE_TREE, "workloads", "*.json")))


def cells():
    """BENCHMARK.json's cells, then the fixture tree's."""
    return [w["name"] for w in spec.benchmark_json()["workloads"]] \
        + tree_cells()


def cells_of_kind(kind, among=None):
    return [c for c in (cells() if among is None else among)
            if spec.load_cell(c, True)["kind"] == kind]


def recording_of(cell_name):
    """The stem of the recording this cell's family brings for this kind
    of cell, or None: the cell is then tried on the llama recordings."""
    cell = spec.load_cell(cell_name, True)
    family = cell["hp"].get("family", "llama")
    for where in (HERE, spec.FIXTURE_TREE):
        stem = os.path.join(where, f"trace_{family}.{cell['kind']}")
        if os.path.exists(stem + ".xplane.pb.gz"):
            return stem
    return None


@pytest.fixture(scope="module")
def serving(tmp_path_factory):
    """The recorded serving run: its reduced trace, the programs' scope
    maps, the cache's shape and the two engine_stats() snapshots."""
    trace = tr.load(unzipped("trace_programs.xplane.pb",
                             tmp_path_factory.mktemp("serving")))
    with open(os.path.join(HERE, "trace_programs.scopes.json")) as f:
        found = json.load(f)
    with open(os.path.join(HERE, "engine_stats_pair.json")) as f:
        pair = json.load(f)
    return trace, found, pair


@functools.lru_cache(maxsize=None)
def serving_recording(stem):
    """A family's own serving recording: its reduced trace and its
    sidecar (scope maps, cache shapes, the two snapshots, the steps, and
    what it says it cannot hold)."""
    with gzip.open(stem + ".json.gz", "rt") as f:
        rec = json.load(f)
    with gzip.open(stem + ".xplane.pb.gz", "rb") as f, \
            tempfile.NamedTemporaryFile(suffix=".xplane.pb") as out:
        out.write(f.read())
        out.flush()
        trace = tr.load(out.name)
    return trace, rec


def serving_ctx(cell_name, serving, with_program_stats=True):
    """On the family's own recording where it brings one
    (``trace_<family>.serve.*``; tree/record_toy_moe_serve_fixture.py is
    the shape), else on the llama recording of PR 24."""
    stem = recording_of(cell_name)
    if stem:
        trace, found = serving_recording(stem)
        pair, reads_nothing = found, found["reads_nothing"]
    else:
        (trace, found, pair), reads_nothing = serving, {}
    steps = found["steps"]
    samples = {"engine_steps": steps, "engine_tokens": 2 * steps,
               "engine_step_s": 0.5, "prefill_s": 0.1,
               "engine_step_ms": [5.0] * steps, "prefill_chunks": 5}
    if with_program_stats:
        samples.update(holder.engine_deltas(pair["before"], pair["after"]))
    return {"cell": spec.load_cell(cell_name, True), "chips": 1,
            "samples": samples, "trace": trace, "peak": {},
            "scopes": found["scopes"] if with_program_stats else None,
            "cache_shapes": found["cache_shapes"],
            # not for the readers: what this recording cannot hold
            "reads_nothing": reads_nothing}


def train_ctx(cell_name, tmp_path, with_program_stats=True):
    stem, rec = recording_of(cell_name), LLAMA_TRAIN
    if stem:
        with gzip.open(stem + ".json.gz", "rt") as f:
            rec = {"trace": stem + ".xplane.pb", **json.load(f)}
    trace = tr.load(unzipped(rec["trace"], tmp_path))
    samples = {"window_s": 2.0, "steps": 4,
               "tokens_in_window": 4 * rec["seqs_per_step"] * rec["seq"],
               "seq": rec["seq"], "seqs_per_step": rec["seqs_per_step"],
               "step_ms": [500.0] * 4, "input_wait_ms": [0.1] * 4,
               "traced_steps": rec["traced_steps"],
               # what train_step.py's step reports besides its loss
               "step.grad_norm": [4.0, 1.0, 3.0, 2.0],
               "step.step": [3.0, 4.0, 5.0, 6.0]}
    if with_program_stats:
        samples.update(holder.phase_deltas(
            {"data.stage_batch": {"seconds": 1.0, "count": 10}},
            {"data.stage_batch": {"seconds": 1.5, "count": 60},
             "data.next_batch": {"seconds": 0.01, "count": 50}}))
    return {"cell": spec.load_cell(cell_name, True), "chips": rec["chips"],
            "samples": samples, "trace": trace,
            "peak": spec.load_json("peaks.json")["TPU v5 lite"],
            "scopes": rec["scopes"] if with_program_stats else None,
            # not for the readers: what this recording cannot hold
            "reads_nothing": rec["reads_nothing"]}


def ctx_for(cell_name, serving, tmp_path, **kw):
    """By the cell's kind; a kind with a runner of its own
    (``benchmarks/<kind>.py``) brings its family's recording and is
    read as a train cell's is: samples, trace, scopes."""
    if spec.load_cell(cell_name, True)["kind"] == "serve":
        return serving_ctx(cell_name, serving, **kw)
    return train_ctx(cell_name, tmp_path, **kw)


# ------------------------------------------------- door 1: a family by name
@pytest.mark.parametrize("cell,read,not_read", [
    ("toy-moe.train",
     {"train_step_ms", "train_mfu_pct", "optimizer_ms", "input_stage_ms",
      "scope_unattributed_pct.train", "grad_norm_p50.toy"},
     {"flash_attention_roofline"}),
    ("toy-moe.serve",
     {"engine_tokens_per_step", "engine_host_ms",
      "decode_occupancy_pct", "prefill_wait_p95_ms",
      "kv_cache_move_share_pct", "scope_unattributed_pct",
      "decode_ahead_pct.toy", "sample_share_pct.toy"},
     {"prefill_device_share_pct"}),
])
def test_a_second_family_runs_through_every_door(cell, read, not_read):
    """The toy family's cells (its training side a mixture of experts
    against its own float32 reference, its serving side the engine's
    decoder under a second name) reach a validated last line through
    run.py, with every check behind ``correct`` true, the metric a CPU
    trace cannot read named in ``metrics_not_read``, and no harness file
    knowing the family's name."""
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483777", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    checks = next(x["checks"] for x in lines if "checks" in x)
    assert all(checks.values()), checks      # the reference comparison too
    # each number compared beside its limit: last on the line, and the
    # last lines of standard error
    assert list(last)[-1] == "compared"
    assert all(value <= limit for value, limit in last["compared"].values())
    assert [x.split(":")[0] for x in done.stderr.splitlines()[
        -len(last["compared"]):]] == [f"compared {k}" for k in last["compared"]]
    notes = next(x["notes"] for x in lines if "notes" in x)
    loaded = spec.load_cell(cell, rehearse=True)
    if loaded["kind"] == "serve":
        # the comparison read at every position the cell asks for
        check, ref = loaded["serve"]["reference_check"], notes["reference"]
        assert len(ref["prefill_rel_rms"]) == check["positions"]
        assert len(ref["after_decode_rel_rms"]) == len(
            ref["decode_choice_gap"]) == check["decode_steps"]
        assert set(ref["summary"]) == set(last["compared"])
        for name, of in ref["summary"].items():
            assert of["n"] == len(ref[name]) and of["outlier_share"] == 0.0
            assert of["median"] <= of["max"] <= of["limit"]
            assert last["compared"][name] == [of["max"], of["limit"]]
        # and what the window itself served, more than one request of it
        assert len(ref["served_by_request"]) > 1
        assert len(ref["served_choice_gap"]) == sum(
            n for _, n, _ in ref["served_by_request"]) > 8
    else:
        # beside the RMS, how the per-token differences are spread
        first = notes["first_forward"]
        assert 0 < first["nll_abs_median"] <= first["nll_abs_p80"] \
            <= first["nll_abs_max"]
        assert first["nll_abs_median"] < first["nll_rms"] < first["nll_abs_max"]
    assert set(last["metrics"]) == read
    noted = next(x["metrics_not_read"] for x in lines
                 if "metrics_not_read" in x)
    assert set(noted) == not_read and all(noted.values())
    for path in harness_files():
        with open(path) as f:
            assert "toy_moe" not in f.read(), path


def test_only_the_llama_family_imports_the_model_and_its_reference():
    banned = ("ray_tpu.models.llama", "benchmarks.reference")
    for path in harness_files():
        if path.endswith(os.path.join("families", "llama.py")):
            continue
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                module = node.module or ""
                if node.level:  # relative: inside benchmarks/
                    module = "benchmarks." + module if module else "benchmarks"
                names = [module] + [f"{module}.{a.name}" for a in node.names]
            assert not [n for n in names if n.startswith(banned)], (path, names)


def test_an_unknown_family_or_kind_is_a_clear_error():
    with pytest.raises(ValueError, match="families/nope.py"):
        spec.family_of({"name": "x", "family": "nope"})
    with pytest.raises(ValueError, match="benchmarks/resume.py"):
        spec.kind_of({"name": "x.resume", "kind": "resume"})
    assert spec.kind_of({"kind": "train"}).__name__ == "benchmarks.train_loop"
    assert spec.family_of({}).__name__ == "benchmarks.families.llama"


# ------------------------------- door 2: a metric may read nothing, and says so
LINE = {
    "correct": True, "attempted": 40, "failed": 0,
    "metrics": {"engine_step_ms": {"value": 9.8, "unit": "ms"}},
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
               "memory_peak_bytes": 7030000000, "window_s": 3.0,
               "busy_s": 2.3},
}


def test_contract_admits_an_unread_per_layer_metric_and_no_other():
    declared = {"engine_step_ms": "ms", "engine_host_ms.chat": "ms"}
    assert contract.check_last_line(
        LINE, declared, traced=True, chips=1,
        optional={"engine_host_ms.chat"}) == []
    missing = contract.check_last_line(LINE, declared, traced=True, chips=1)
    assert any("engine_host_ms.chat" in p for p in missing)
    # an end-to-end metric is never optional
    untraced = {**LINE, "metrics": {"setup_s": {"value": 28.0, "unit": "s"}},
                "device": {k: v for k, v in LINE["device"].items()
                           if k not in ("window_s", "busy_s")}}
    e2e = {"setup_s": "s", "ttft_p95_ms": "ms"}
    assert any("ttft_p95_ms" in p for p in contract.check_last_line(
        untraced, e2e, traced=False, chips=1))
    assert contract.check_last_line(
        untraced, e2e, traced=False, chips=1, optional={"ttft_p95_ms"})


def test_evaluate_leaves_out_what_was_not_read_with_the_reason():
    metrics = {"a": {"reader": "quotient", "unit": "ms",
                     "args": {"num": "x", "den": "n"}},
               "b": {"reader": "quotient", "unit": "ms",
                     "args": {"num": "phase_s.llm.step", "den": "n"}},
               "c": {"reader": "percentile", "unit": "ms",
                     "args": {"key": "empty", "q": 95}}}
    out, not_read = spec.evaluate(
        metrics, {"samples": {"x": 6.0, "n": 3, "empty": []}})
    assert out == {"a": {"value": 2.0, "unit": "ms"}}
    assert "phase_s.llm.step" in not_read["b"]        # the reader's reason
    assert "percentile" in not_read["c"]              # a bare None


@pytest.mark.parametrize("cell", cells())
def test_every_declared_per_layer_metric_reads_a_number(cell, serving,
                                                        tmp_path):
    """No reader may go silent on the head of the tree: from the
    recorded traces, scope maps and snapshots (the family's own where it
    brings them), each per-layer metric a cell declares reads a finite
    number, but for what the recording's sidecar names as not in it."""
    declared = spec.cell_metrics(cell, traced=True)
    assert declared
    ctx = ctx_for(cell, serving, tmp_path)
    out, not_read = spec.evaluate(declared, ctx)
    assert set(not_read) == set(ctx["reads_nothing"])
    assert set(out) == set(declared) - set(not_read)
    assert all(math.isfinite(m["value"]) for m in out.values())
    for name, m in out.items():
        if m["unit"] == "%" and "mfu" not in name:
            assert 0.0 <= m["value"] <= 100.0, name


@pytest.mark.parametrize(
    "cell", [c for c in cells() if recording_of(c) is None
             or c in cells_of_kind("serve")])
def test_the_parent_of_pr_24_ends_in_a_valid_line(cell, serving, tmp_path):
    """An engine without engine_stats() and compiled_programs(), a
    Dataset without iter_stats, a program without scope_map: the metrics
    that read them are named as not read, every other reads as before,
    and the traced line passes the contract. For the cells whose program
    existed then: those tried on the llama recordings, which PR 23 and
    PR 24 made, and a served family's on its own recording (an engine is
    an engine). A trained family that brings a recording of its own came
    later, and what its program lacked at that parent is everything.
    Which metrics read nothing follows from each metric's own file."""
    declared = spec.cell_metrics(cell, traced=True)
    ctx = ctx_for(cell, serving, tmp_path, with_program_stats=False)
    out, not_read = spec.evaluate(declared, ctx)
    assert set(out) | set(not_read) == set(declared)
    assert all(not_read.values())                   # each with its reason
    # what reads nothing: whatever names one of the program's own
    # samples, by its file; whatever joins with the programs' scope
    # maps, by what its reader returns where there are none; what the
    # recording cannot hold; and nothing else
    whole = ctx_for(cell, serving, tmp_path)
    assert set(spec.evaluate(declared, whole)[1]) == set(ctx["reads_nothing"])
    from_scopes = set(spec.evaluate(declared, {**whole, "scopes": None})[1])
    from_samples = {n for n, m in declared.items()
                    if reads_the_programs_samples(m)}
    assert from_samples and from_samples <= set(not_read)
    assert set(not_read) == from_samples | from_scopes | set(
        ctx["reads_nothing"])
    line = {**LINE, "metrics": out,
            "device": {**LINE["device"], "count": ctx_for(
                cell, serving, tmp_path)["chips"]}}
    assert contract.check_last_line(
        line, {n: m["unit"] for n, m in declared.items()}, traced=True,
        chips=line["device"]["count"], optional=set(not_read)) == []


def test_a_server_and_a_shard_from_before_the_stats_give_none():
    from benchmarks.server import BenchLLMServer

    class OldEngine:
        pass

    class OldServer:
        engine = OldEngine()

    assert BenchLLMServer._engine_stats(OldServer()) is None
    assert BenchLLMServer._program_scopes(OldServer()) is None
    assert holder.engine_deltas(None, None) == {}
    # PR 23's engine_stats(): slots and shards, no counters
    older = {"active": 0, "peak_active": 8, "shards": 2}
    assert holder.engine_deltas(older, older) == {}
    assert holder.phase_deltas(None, {"x": {"seconds": 1.0, "count": 1}}) == {}


# ------------------------ doors 2 and 3: the PR 24 quantities, made by hand
def test_metrics_from_the_engines_stats_equal_the_hand_count(serving):
    _, _, pair = serving
    was, now = pair["before"]["engine"], pair["after"]["engine"]
    ctx = serving_ctx(CHAT, serving)
    out, not_read = spec.evaluate(spec.cell_metrics(CHAT, True), ctx)
    assert not_read == {}

    def secs(name):
        return now["phases"][name]["seconds"] - was["phases"].get(
            name, {"seconds": 0.0})["seconds"]

    steps = now["steps"] - was["steps"]
    assert steps > 0
    host = (secs("llm.step") - secs("llm.decode_sync")
            - secs("llm.first_token_sync")) / steps
    assert out["engine_host_ms.chat"]["value"] == pytest.approx(1e3 * host)
    lanes = (now["decode_lanes_active"] - was["decode_lanes_active"]) / (
        now["decode_lanes_total"] - was["decode_lanes_total"])
    assert out["decode_occupancy_pct.chat"]["value"] == pytest.approx(
        100 * lanes)
    finished = now["requests_finished"] - was["requests_finished"]
    rows = now["requests"][-finished:]
    assert finished == len(rows) == 3
    for name, column in (("queue_wait_p95_ms.chat", 1),
                         ("prefill_wait_p95_ms.chat", 2),
                         ("prefill_p95_ms.chat", 3)):
        want = np.percentile([1e3 * r[column] for r in rows], 95)
        assert out[name]["value"] == pytest.approx(want)
    # the same reader under the entry the other cells share
    docs, _ = spec.evaluate(spec.cell_metrics(DOCS, True),
                            serving_ctx(DOCS, serving))
    assert docs["engine_host_ms"]["value"] == pytest.approx(1e3 * host)


def test_input_stage_ms_is_the_windows_seconds_a_batch(tmp_path):
    cell = cells_of_kind("train")[0]
    out, _ = spec.evaluate(spec.cell_metrics(cell, True),
                           train_ctx(cell, tmp_path))
    assert out["input_stage_ms"]["value"] == pytest.approx(1e3 * 0.5 / 50)


def test_a_train_cell_of_a_second_family_is_tried_on_its_own_recording(
        serving, tmp_path):
    """``toy-moe.train``, its metric ``grad_norm_p50.toy`` and its
    recording exist under tests/tree alone. The helpers above find the
    cell by its kind and the recording by its family; its own metric
    reads the step's ``step.grad_norm`` samples; what the llama
    recording could never give it (a scope map of its own program) is
    read from its own. (Of the tree's own cells it is the one that
    brings a recording; a family of BENCHMARK.json may bring its own.)"""
    toy = [c for c in cells_of_kind("train", tree_cells()) if recording_of(c)]
    assert toy == ["toy-moe.train"]
    assert recording_of(toy[0]).startswith(spec.FIXTURE_TREE)
    assert "toy-moe.train" not in [
        w["name"] for w in spec.benchmark_json()["workloads"]]
    declared = spec.cell_metrics(toy[0], traced=True)
    own = [e for e in spec.declared("per_layer")
           if e["name"] == "grad_norm_p50.toy"]
    assert len(own) == 1 and own[0]["workloads"] == toy
    assert own[0] not in spec.benchmark_json()["per_layer"]
    ctx = ctx_for(toy[0], serving, tmp_path)
    assert ctx["chips"] == 1 and ctx["scopes"]["step"]
    out, not_read = spec.evaluate(declared, ctx)
    assert set(not_read) == {"flash_attention_roofline"} == set(
        ctx["reads_nothing"])
    # the median of [4, 1, 3, 2], by hand
    assert out["grad_norm_p50.toy"]["value"] == 2.5
    assert out["optimizer_ms"]["value"] > 0
    llama_cell = next(c for c in cells_of_kind("train")
                      if not recording_of(c))
    on_llama, _ = spec.evaluate(
        {"optimizer_ms": declared["optimizer_ms"]},
        train_ctx(llama_cell, tmp_path))
    assert on_llama["optimizer_ms"]["value"] == 0.0     # an empty scope map


def test_a_serve_cell_of_a_second_family_is_tried_on_its_own_recording(
        serving, tmp_path):
    """``toy-moe.serve``, its two metrics (one over counters no harness
    file names, one over a scope through ``trace_scope_share_pct``) and
    its serving recording exist under tests/tree alone, made by
    tree/record_toy_moe_serve_fixture.py. ``serving_ctx`` finds the
    recording by the family's name; every number the two metrics read
    from it is made a second time by hand; what the recording cannot
    hold is what its sidecar says. (Of the tree's own cells it is the
    one that brings a recording; a served family of BENCHMARK.json may
    bring its own.)"""
    toy = [c for c in cells_of_kind("serve", tree_cells()) if recording_of(c)]
    assert toy == ["toy-moe.serve"]
    assert recording_of(toy[0]) == os.path.join(
        spec.FIXTURE_TREE, "trace_toy_moe.serve")
    own = {e["name"]: e for e in spec.declared("per_layer")
           if e["name"].endswith(".toy") and e["workloads"] == toy}
    assert set(own) == {"decode_ahead_pct.toy", "sample_share_pct.toy"}
    assert not [e for e in spec.benchmark_json()["per_layer"]
                if e["name"] in own]
    for path in harness_files() + glob.glob(os.path.join(HERE, "*.py")):
        if path != os.path.abspath(__file__):
            with open(path) as f:
                text = f.read()
            assert "decode_ahead_pct" not in text, path
            assert "sample_share_pct" not in text, path

    ctx = ctx_for(toy[0], serving, tmp_path)
    trace, rec = serving_recording(recording_of(toy[0]))
    reads_nothing = rec["reads_nothing"]
    assert ctx["trace"] is trace and trace is not serving[0]
    assert ctx["scopes"] == rec["scopes"] and set(rec["scopes"]) >= {
        "decode", "first_token"}
    assert ctx["cache_shapes"] == rec["cache_shapes"] == ["bf16[2,4,2,128,16]"]
    declared = spec.cell_metrics(toy[0], traced=True)
    out, not_read = spec.evaluate(declared, ctx)
    assert set(not_read) == set(reads_nothing) == set(ctx["reads_nothing"])
    was, now = rec["before"]["engine"], rec["after"]["engine"]
    calls = now["decode_calls"] - was["decode_calls"]
    ahead = now["decode_ahead"] - was["decode_ahead"]
    assert 0 < ahead < calls
    assert out["decode_ahead_pct.toy"]["value"] == pytest.approx(
        100 * ahead / calls)
    programs = tp.of(trace)
    under = tp.scope_seconds(programs, rec["scopes"], ["sample"])
    assert out["sample_share_pct.toy"]["value"] == pytest.approx(
        100 * under / tp.op_seconds(programs))
    if rec["platform"] == "tpu":    # a CPU trace names no program
        assert 0 < under < tp.op_seconds(programs)
        assert not reads_nothing
    # what the llama recording could never give it: PR 24's engine had
    # no such counter
    _, on_llama = spec.evaluate(declared, {**serving_ctx(DOCS, serving),
                                           "cell": ctx["cell"]})
    assert "stats.decode_ahead" in on_llama["decode_ahead_pct.toy"]


@pytest.mark.parametrize("name", ["routed-standin.serve",
                                  "hybrid-standin.serve"])
def test_a_routed_serve_cell_states_its_shares_by_files_alone(name):
    """``routed-standin.serve`` and ``hybrid-standin.serve`` (the same
    stand-in with state layers behind its routed layers: a cache that
    is not addressed by position, compared under the engine's own
    routing choices since PR 61, so that one kept cell goes each way),
    their configurations and their family ``routed`` exist under
    tests/tree alone: the cell is found by its kind, the family by the
    configuration's name, the shares it states (the routed one both,
    the hybrid one the served tokens' alone beside its
    ``route_margin_tol``) are held by ``spec.load_cell`` through the
    kind's own ``check_cell``, and no harness file knows the family,
    the cell or a share's value. It brings no recording, so the tests parametrised
    over cells try it on the llama family's. Of the tree's own cells
    these are the ones that state them; what a cell of BENCHMARK.json
    that states them is held to is ``test_contract.py``'s and
    ``test_serving_reference.py``'s, by that property alone."""
    from benchmarks import serve_load

    stating = [c for c in cells_of_kind("serve", tree_cells()) if any(
        share in spec.load_cell(c, True)["serve"]["reference_check"]
        for share in serve_load.SHARE_CEILINGS)]
    assert stating == ["hybrid-standin.serve", "routed-standin.serve"]
    cell = spec.load_cell(name, True)
    assert recording_of(name) is None
    assert name not in [w["name"] for w in spec.benchmark_json()["workloads"]]
    family = spec.family_of(cell["hp"])
    assert family.__name__ == "benchmarks.families.routed"
    assert os.path.realpath(family.__file__).startswith(
        os.path.realpath(spec.FIXTURE_TREE))
    assert spec.kind_of(cell).check_cell is serve_load.check_cell
    check = cell["serve"]["reference_check"]
    assert serve_load.routed(check) is (name == "hybrid-standin.serve")
    for share, ceiling in serve_load.SHARE_CEILINGS.items():
        if serve_load.routed(check) and share == "rel_rms_over_share":
            assert share not in check and check["route_margin_tol"] > 0
            assert family.reference_routed
        else:
            assert 0 < check[share] <= ceiling
    assert len(check["tolerance_why"]) > 100
    for path in harness_files():
        with open(path) as f:
            text = f.read()
        assert "-standin" not in text and "families.routed" not in text
        assert "routed_standin" not in text
    # the cell reports through the declarations of the tests' tree alone
    assert set(spec.cell_metrics(name, traced=False)) == {
        "setup_s", "serve_tokens_per_s"}
    assert len(spec.cell_metrics(name, traced=True)) >= 3


def test_the_new_shares_by_scope_equal_the_hand_count(serving):
    """``attn_cached_share_pct.*`` and ``mlp_share_pct.*`` on the chip
    recording of PR 24: each the scope's op seconds over all op seconds,
    every op's scope from its own program's map; the two and the cache's
    movement are parts of one whole. ``prefill_tokens_per_chunk.*`` is
    the engine's two counters of PR 29, one over the other."""
    trace, found, pair = serving
    programs = tp.of(trace)
    total = tp.op_seconds(programs)
    which = tp.assign_maps(programs, found["scopes"])
    was, now = pair["before"]["engine"], pair["after"]["engine"]
    for cell, suffix in ((CHAT, ".chat"), (DOCS, ""), (OVER, "")):
        out, not_read = spec.evaluate(
            spec.cell_metrics(cell, True), serving_ctx(cell, serving))
        assert not_read == {}
        shares = {}
        for scope in ("attn_cached", "mlp"):
            by_hand = 0.0
            for c in programs.chips:
                for i, module in enumerate(c.modules):
                    path = found["scopes"].get(which.get(module), {}).get(
                        c.names[i], "")
                    if scope in path.split("/"):
                        by_hand += float(c.end[i] - c.start[i])
            shares[scope] = out[f"{scope}_share_pct{suffix}"]["value"]
            assert shares[scope] == pytest.approx(
                100 * by_hand / len(programs.chips) / total)
        assert 1 < shares["attn_cached"] < 60 and 1 < shares["mlp"] < 60
        moved = out.get(f"kv_cache_move_share_pct{suffix}", {"value": 0.0})
        assert sum(shares.values()) + moved["value"] < 100
        assert out[f"prefill_tokens_per_chunk{suffix}"]["value"] \
            == pytest.approx(
                (now["prefill_tokens"] - was["prefill_tokens"])
                / (now["prefill_chunks"] - was["prefill_chunks"]))
    # a program without maps reads nothing and says why; maps that hold
    # none of the names read 0.0
    ctx = serving_ctx(CHAT, serving)
    metric = {"x": {"reader": "trace_scope_share_pct", "unit": "%",
                    "args": {"scopes": ["experts"]}}}
    assert spec.evaluate(metric, ctx)[0]["x"]["value"] == 0.0
    assert "compiled_programs" in spec.evaluate(
        metric, {**ctx, "scopes": None})[1]["x"]


def test_cache_shapes_are_every_leaf_of_a_shard_once():
    """``_cache_shapes`` names every leaf of ``shards[0].cache``, each
    shape once, in the pytree's order: keys and values share one; a
    cache that is not keys and values brings its own."""
    from benchmarks.server import BenchLLMServer

    def replica(cache):
        return type("Replica", (), {"engine": type("Engine", (), {
            "shards": [type("Shard", (), {"cache": cache})()]})()})()

    f16, f32 = np.dtype("float16"), np.dtype("float32")
    kv = {"k": np.zeros((2, 4, 2, 8, 16), f16),
          "v": np.zeros((2, 4, 2, 8, 16), f16)}
    assert BenchLLMServer._cache_shapes(replica(kv)) == ["f16[2,4,2,8,16]"]
    latent = {"latent": np.zeros((7, 8, 64, 576), f16),
              "window": {"k": np.zeros((1, 8, 2, 16, 64), f16),
                         "v": np.zeros((1, 8, 2, 16, 64), f16)},
              "state": np.zeros((7, 8, 128), f32)}
    assert BenchLLMServer._cache_shapes(replica(latent)) == [
        "f16[7,8,64,576]", "f32[7,8,128]", "f16[1,8,2,16,64]"]


def test_metrics_from_the_trace_by_program_equal_the_hand_count(serving):
    trace, found, _ = serving
    family = spec.family_of({})
    programs = tp.of(trace)
    seconds = tp.program_seconds(programs)
    total = sum(seconds.values())
    out, _ = spec.evaluate(spec.cell_metrics(CHAT, True),
                           serving_ctx(CHAT, serving))
    assert out["prefill_device_share_pct.chat"]["value"] == pytest.approx(
        100 * seconds["prefill"] / total)
    assert 5 < out["prefill_device_share_pct.chat"]["value"] < 95
    moved = tp.kv_cache_move_seconds(
        programs, found["scopes"], found["cache_shapes"], family.KV_SCOPES,
        family.COMPUTE_SCOPES)
    assert out["kv_cache_move_share_pct.chat"]["value"] == pytest.approx(
        100 * moved / total)
    lost = tp.unattributed_seconds(
        programs, found["scopes"], family.SCOPES, family.NAMED_OPS)
    assert out["scope_unattributed_pct.chat"]["value"] == pytest.approx(
        100 * lost / total)
    assert 0 < moved < total and 0 < lost < total


def test_the_reduced_trace_keeps_programs_and_the_engines_spans(
        serving, tmp_path):
    """trace_reduce.load keeps each op's module and the ``ray_tpu.*``
    spans, so the same file read through trace_programs.load and through
    trace_reduce.load gives the same ops, and the breakdown names the
    innermost span and tells same-named ops of two programs apart."""
    trace, _, _ = serving
    direct = tp.load(os.path.join(HERE, "trace_programs.xplane.pb.gz"))
    assert tp.program_seconds(tp.of(trace)) == tp.program_seconds(direct)
    chip = trace.chips[0]
    assert {tr.program_of(m) for m in chip.modules} == {"prefill", "decode"}
    names = {s[0] for s in trace.host_spans}
    assert {"ray_tpu.llm.step", "ray_tpu.llm.decode_sync",
            "bench.engine_step"} <= names
    assert any(s[3].get("request_id") for s in trace.host_spans)
    gaps = tr.idle_gaps(trace)
    assert gaps == tp.idle_gaps(direct)
    # bench.engine_step wraps every step: the span inside it wins
    assert gaps[0][0].startswith("ray_tpu.llm.")
    inside = sum(v for k, v in gaps if k.startswith("ray_tpu.llm."))
    assert inside > 0.8 * sum(v for _, v in gaps)
    # top ops by (program, instruction), and so named
    top = tr.top_ops(trace, 10)
    assert all(name.split("/")[0] in ("prefill", "decode") for name, _ in top)
    by_hand = {}
    for name, module, core, s, e in zip(chip.names, chip.modules, chip.core,
                                        chip.start, chip.end):
        if core:
            key = f"{tr.program_of(module)}/{name}"
            by_hand[key] = by_hand.get(key, 0.0) + e - s
    want = sorted(by_hand.items(), key=lambda kv: -kv[1])[:10]
    assert top == [[k, pytest.approx(v)] for k, v in want]
    shared = {n.split("/", 1)[1] for n in by_hand if n.startswith("decode/")} \
        & {n.split("/", 1)[1] for n in by_hand if n.startswith("prefill/")}
    assert shared       # which the old sum by name alone ran together


def test_idle_gaps_take_the_spans_of_the_thread_that_drives_the_device():
    chip = tr._chip("/device:TPU:0", [
        ("%fusion.1 = f32[8] fusion(f32[8] %a)", 0.0, 1e9, False),
        ("%fusion.2 = f32[8] fusion(f32[8] %a)", 3e9, 4e9, False)])
    spans = sorted([
        ("bench.step", 0.0, 5.0, {tr.THREAD: "python#0"}),
        ("ray_tpu.llm.decode_sync", 1.5, 3.5, {tr.THREAD: "python#0"}),
        # a prefetch thread staging a batch over the middle of the gap
        ("ray_tpu.data.stage_batch", 1.9, 2.2, {tr.THREAD: "python#1"}),
    ], key=lambda s: s[1])
    assert tr.idle_gaps(tr.Trace([chip], spans)) == [
        ["ray_tpu.llm.decode_sync", pytest.approx(2.0)]]


# --------------------------------------------- the new cell's traffic
def test_serve_chat_over_offers_serve_chats_cycle_faster():
    chat = spec.load_cell(CHAT, False)["traffic"]
    over = spec.load_cell(OVER, False)["traffic"]
    for key in ("prompt_len", "output_len", "ramp_s", "schedule_seed",
                "loop"):
        assert over[key] == chat[key]
    seed, seconds = 2147483659, 50.0
    slow = [r for r in traffic.open_loop(chat, seed, seconds) if r.due_s >= 0]
    fast = [r for r in traffic.open_loop(over, seed, seconds) if r.due_s >= 0]
    cycle = len(slow)
    assert cycle == over["cycle"] == 200
    assert len(fast) == round(over["rate_per_s"] * seconds)
    # the same lengths in the same order, round and round ...
    for i, r in enumerate(fast):
        assert (r.prompt_len, r.max_tokens) == (
            slow[i % cycle].prompt_len, slow[i % cycle].max_tokens)
    # ... and the same gaps, shrunk by the ratio of the rates
    ratio = over["rate_per_s"] / chat["rate_per_s"]
    gaps_slow = np.diff([r.due_s for r in slow])
    gaps_fast = np.diff([r.due_s for r in fast[:cycle]])
    np.testing.assert_allclose(gaps_fast * ratio, gaps_slow, rtol=1e-9)
    assert fast[-1].due_s < seconds
    # another seed enters the same cycle at another place
    other = [r for r in traffic.open_loop(over, seed + 1, seconds)
             if r.due_s >= 0]
    assert sorted(r.prompt_len for r in other[:cycle]) == sorted(
        r.prompt_len for r in fast[:cycle])
