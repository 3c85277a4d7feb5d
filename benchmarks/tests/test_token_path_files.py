"""The five metric files over a token's way back and a decode call's
empty lanes (``token_backlog_ms``, ``token_wake_ms``, ``token_yield_ms``,
``decode_lanes_prefilling_pct``, ``decode_lanes_free_pct``): no cell in
their names, since PR 60 one entry each in BENCHMARK.json with its cells
in a list, each read through ``spec.evaluate`` from two
``engine_stats()`` snapshots of a toy server as ``holder.engine_deltas``
flattens them, and made a second time by hand. On the snapshots of a
program from before the counters every one of them says what it did not
find, and raises nothing."""

import dataclasses
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from benchmarks import holder, spec

HERE = os.path.dirname(os.path.abspath(__file__))
FILES = {"token_backlog_ms": "ms", "token_wake_ms": "ms",
         "token_yield_ms": "ms", "decode_lanes_prefilling_pct": "%",
         "decode_lanes_free_pct": "%"}
# the answers' lengths: the first two requests share the shard (one
# decodes while the other's prompt goes in), the third has it alone
TOKENS, NAP_S = (12, 5, 5), 0.02


def metrics():
    """The five files as ``spec.cell_metrics`` hands them over."""
    return {name: {**spec.load_json("metrics", f"{name}.json"), "unit": unit}
            for name, unit in FILES.items()}


@pytest.fixture(scope="module")
def snapshots():
    """A toy server, in process: two lanes, prompts of three chunks,
    readers that nap between tokens; ``engine_stats()`` before and
    after."""
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serve import LLMServer
    from ray_tpu.models import llama

    server = LLMServer(LLMConfig(
        model_config=dataclasses.replace(llama.LLAMA_TINY, remat=False),
        max_batch_size=2, max_seq_len=64,
        engine_kwargs={"prefill_chunk": 16, "max_slots": 2}))

    def read(tokens):
        for _ in server.generate_stream(list(range(1, 40)), tokens):
            time.sleep(NAP_S)

    try:
        before = server.engine_stats()
        with ThreadPoolExecutor(2) as pool:
            for done in [pool.submit(read, n) for n in TOKENS[:2]]:
                done.result()
        read(TOKENS[2])
        after = server.engine_stats()
    finally:
        server.shutdown()
    return before, after


def test_the_files_read_what_the_snapshots_counted(snapshots):
    before, after = snapshots
    samples = holder.engine_deltas(before, after)
    out, not_read = spec.evaluate(metrics(), {"samples": samples})
    assert not_read == {} and set(out) == set(FILES)
    assert {name: m["unit"] for name, m in out.items()} == FILES

    def phase(name):
        now = after["loop_phases"][name]
        was = before["loop_phases"].get(name, {"seconds": 0.0, "count": 0})
        return now["seconds"] - was["seconds"], now["count"] - was["count"]

    for short in ("backlog", "wake", "yield"):
        seconds, count = phase(f"llm.token_{short}")
        assert count == sum(TOKENS)
        assert out[f"token_{short}_ms"]["value"] == pytest.approx(
            1e3 * seconds / count)
    # the hand-off's two parts make it up, and the reader's naps are
    # the backlog and the yields
    handoff_s, handoff_n = phase("llm.token_handoff")
    assert (out["token_backlog_ms"]["value"] + out["token_wake_ms"]["value"]
            == pytest.approx(1e3 * handoff_s / handoff_n))
    assert out["token_yield_ms"]["value"] >= 1e3 * NAP_S * 0.9
    assert out["token_backlog_ms"]["value"] > out["token_wake_ms"]["value"]

    def grew(name):
        return after["engine"][name] - before["engine"][name]

    total = grew("decode_lanes_total")
    assert total > 0
    for short in ("prefilling", "free"):
        assert grew(f"decode_lanes_{short}") > 0
        assert out[f"decode_lanes_{short}_pct"]["value"] == pytest.approx(
            100.0 * grew(f"decode_lanes_{short}") / total)
    # with the occupancy the accepted files read, the lanes are whole
    assert (grew("decode_lanes_active") + grew("decode_lanes_prefilling")
            + grew("decode_lanes_free")) == total
    occupancy = spec.load_json("metrics", "decode_occupancy_pct.json")
    read, _ = spec.evaluate({"occupancy": {**occupancy, "unit": "%"}},
                            {"samples": samples})
    assert (read["occupancy"]["value"]
            + out["decode_lanes_prefilling_pct"]["value"]
            + out["decode_lanes_free_pct"]["value"]) == pytest.approx(100.0)


def test_a_program_from_before_the_counters_reads_nothing():
    """PR 24's recorded pair of snapshots: no such phase, no such
    counter. Each file's reader says what the run did not record."""
    with open(os.path.join(HERE, "engine_stats_pair.json")) as f:
        pair = json.load(f)
    samples = holder.engine_deltas(pair["before"], pair["after"])
    assert samples                       # the engine's counters of then
    out, not_read = spec.evaluate(metrics(), {"samples": samples})
    assert out == {} and set(not_read) == set(FILES)
    for name, why in not_read.items():
        assert "recorded no" in why, name


@pytest.mark.parametrize("name", sorted(FILES))
def test_a_file_has_one_entry_over_several_cells(name):
    """No cell's suffix in the name; one entry, with a ``workloads``
    list, whose unit is the one the file is read in here. (The serving
    cells of the llama family are not in the lists yet: the recording
    they are tried on predates the counters. PERF.md section 7.)"""
    on_file = spec.load_json("metrics", f"{name}.json")
    assert on_file["name"] == name and "." not in name
    assert on_file["reader"] == "quotient"
    assert on_file["moves"] == "serve_tokens_per_s"
    assert on_file["layer"] in ("serve stack", "engine loop")
    entries = [e for e in spec.declared("per_layer") if e["name"] == name]
    assert len(entries) == 1
    entry = entries[0]
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        FILES[name], on_file["layer"], on_file["moves"])
    assert len(entry["workloads"]) > 1
    assert "ai21-jamba2-3b.serve-reason" in entry["workloads"]
    for cell in entry["workloads"]:
        assert spec.cell_metrics(cell, traced=True)[name]["unit"] \
            == FILES[name]
