"""``per_layer`` since PR 60: a reading is one entry with its cells in a
list, and where its cells report different end-to-end metrics one entry
a ``moves`` (``engine_host_ms`` moves ``serve_tokens_per_s`` and lists
six cells, ``engine_host_ms.chat`` moves a tail of serve-chat).

``merged_readings.json`` beside this file is the table of the merge:
for each reading the reader and arguments its suffixed files shared, and
which cell reported it under which name until then. Each row is held
here: the one file that stands for them reads, on the recorded fixture
of the cell, the number the suffixed file read (the file itself where a
checkout still has it, else its reader and arguments as the table kept
them), and the old name is declared nowhere."""

import importlib
import json
import os

import pytest
from test_doors import ctx_for, serving  # noqa: F401  (a fixture)

from benchmarks import spec

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(spec.HERE, "metrics")
SUFFIXES = ("chat", "docs", "ide", "longdoc", "over", "rag", "reason",
            "train")
MOST = 80       # of the contract's 128: room for three families' entries
# the files under metrics/ that no entry declares yet, each with why
_LLAMA = ("reads a span of the serve stack that the llama recording "
          "(trace_programs.*, engine_stats_pair.json: PR 24's and PR 26's "
          "engine) predates, and test_doors.py holds the llama cells to "
          "not_read == {} on it: waits for that recording's re-record")
WAITING = {name: _LLAMA for name in (
    "accept_ms.chat", "first_token_handoff_ms.chat",
    "first_token_handoff_ms.over", "idle_sleep_share_pct.chat",
    "ingress_ms.chat", "ingress_ms.docs", "ingress_ms.over",
    "token_handoff_ms.chat", "token_handoff_ms.over")}

with open(os.path.join(HERE, "merged_readings.json")) as f:
    MERGED = json.load(f)
ROWS = [(reading, old, cell) for reading, of in sorted(MERGED.items())
        for old, cell in sorted(of["was"].items())]


def entries():
    return spec.benchmark_json()["per_layer"]


def reading_of(name):
    base, _, suffix = name.rpartition(".")
    return base if base and suffix in SUFFIXES else name


@pytest.mark.parametrize("reading,old,cell", ROWS)
def test_the_one_file_reads_what_the_suffixed_file_read(
        reading, old, cell, serving, tmp_path):  # noqa: F811
    by_name = {e["name"]: e for e in entries()}
    assert old not in by_name
    entry = by_name[reading]
    assert cell in entry["workloads"] and entry["moves"] == "serve_tokens_per_s"
    new = spec.load_json("metrics", f"{reading}.json")
    old_path = os.path.join(METRICS, f"{old}.json")
    if os.path.exists(old_path):      # a checkout from before the merge
        with open(old_path) as f:
            was = json.load(f)
        assert was["moves"] == new["moves"] and was["layer"] == new["layer"]
    else:
        was = MERGED[reading]
    assert (was["reader"], was["args"]) == (new["reader"], new["args"])
    assert new["name"] == reading
    ctx = ctx_for(cell, serving, tmp_path)
    got = spec.evaluate({old: {**was, "unit": entry["unit"]},
                         reading: {**new, "unit": entry["unit"]}}, ctx)
    out, not_read = got
    assert (old in out) == (reading in out)
    if reading in out:
        assert out[old] == out[reading]
    else:       # what the recording cannot hold, under the new name
        assert not_read[old] == not_read[reading]
        assert reading in ctx["reads_nothing"]


def test_the_table_names_every_reading_that_lists_several_cells():
    """Whatever moves ``serve_tokens_per_s`` in more cells than one came
    out of the merge or was let in with it, and the table holds every
    cell an entry of the merge lists."""
    let_in = {"token_backlog_ms", "token_wake_ms", "token_yield_ms",
              "decode_lanes_prefilling_pct", "decode_lanes_free_pct",
              "moe_held_slabs_per_call"}
    several = {e["name"] for e in entries() if len(e["workloads"]) > 1
               and e["moves"] == "serve_tokens_per_s"}
    assert several == set(MERGED) | let_in
    for e in entries():
        if e["name"] in MERGED:
            assert set(MERGED[e["name"]]["was"].values()) <= set(
                e["workloads"])
            # a cell that joined the list since reports nothing new by it
            assert "." not in e["name"]
    retired = [e["name"] for e in entries()
               if reading_of(e["name"]) == "prefill_share_pct"]
    assert not retired


def test_every_entry_lists_its_cells_and_every_file_is_declared_or_waits():
    bench = spec.benchmark_json()
    per = bench["per_layer"]
    assert len(per) <= MOST
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {e["name"]: e for e in bench["end_to_end"]}
    names = [e["name"] for e in per]
    assert len(set(names)) == len(names)
    # one entry a reading and a `moves`: no quantity is split by cell
    assert len({(reading_of(e["name"]), e["moves"]) for e in per}) == len(per)
    for e in per:
        listed = e.get("workloads")
        assert isinstance(listed, list) and listed, e["name"]
        assert len(set(listed)) == len(listed) and set(listed) <= set(cells)
        # in the benchmark's order, so a diff that adds a cell is a line
        assert listed == [c for c in cells if c in listed], e["name"]
        with open(os.path.join(METRICS, f"{e['name']}.json")) as f:
            on_file = json.load(f)
        assert on_file["name"] == e["name"]
        assert (on_file["layer"], on_file["moves"]) == (e["layer"], e["moves"])
        assert len(on_file["what"]) > 20
        reader = importlib.import_module(
            f"benchmarks.readers.{on_file['reader']}")
        assert callable(reader.read)
        # every listed cell reports the end-to-end metric this one moves
        moved = e2e[e["moves"]]
        assert set(listed) <= set(moved.get("workloads", cells)), e["name"]
        for cell in listed:
            assert e["name"] in spec.cell_metrics(cell, traced=True)
    for cell in cells:
        assert spec.cell_metrics(cell, traced=True), cell
    files = {name[:-len(".json")] for name in os.listdir(METRICS)}
    assert files - set(names) - set(e2e) == set(WAITING)
    assert not (set(names) | set(e2e)) - files
    assert all(len(why) > 60 for why in WAITING.values())


def test_what_was_let_in_reads_the_programs_own_counters():
    """The entries PR 60 let in need no reader of their own: each is a
    ``quotient`` over samples the program records (``stats.*``, and the
    batching loop's ``phase_s.*`` / ``phase_n.*``).
    ``test_token_path_files.py`` holds the five of PR 58 to their
    entries; here the sixth, and the two lists serve-rag joined."""
    by_name = {e["name"]: e for e in entries()}
    for name in ("token_backlog_ms", "token_wake_ms", "token_yield_ms",
                 "decode_lanes_prefilling_pct", "decode_lanes_free_pct",
                 "moe_held_slabs_per_call"):
        on_file = spec.load_json("metrics", f"{name}.json")
        assert on_file["reader"] == "quotient"
        assert all(key.startswith(("stats.", "phase_s.", "phase_n."))
                   for key in (on_file["args"]["num"], on_file["args"]["den"]))
    assert by_name["moe_held_slabs_per_call"]["layer"] == "model programs"
    assert by_name["moe_held_slabs_per_call"]["workloads"] == [
        "openpangu-ultra-moe-718b.serve-longdoc",
        "command-a-plus-05-2026.serve-rag"]
    for name in ("prefill_wait_p95_ms", "prefill_p95_ms"):
        assert "command-a-plus-05-2026.serve-rag" in by_name[name]["workloads"]
    samples = {"stats.moe_held_slabs": 36, "stats.prefill_chunks": 8}
    out, _ = spec.evaluate({"x": {**spec.load_json(
        "metrics", "moe_held_slabs_per_call.json"), "unit": "1"}},
        {"samples": samples})
    assert out["x"]["value"] == 4.5
