"""trace_programs.py: the join by (program, instruction), time by
program and by scope, the KV-cache movement and the idle gaps by the
innermost span of either kind. By hand on made-up events, then on the
trace of a toy engine recorded on a v5e (record_programs_fixture.py),
where every number is made a second time by plain loops over the raw
profile."""

import collections
import gzip
import json
import os
import re

import numpy as np
import pytest

from benchmarks import trace_programs as tp
from benchmarks.scopes import words

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "trace_programs.xplane.pb.gz")
SCOPES = os.path.join(HERE, "trace_programs.scopes.json")


def ops(rows):
    """rows: (instruction, opcode, shape, start, end, module)."""
    names, opcodes, shapes, start, end, modules = zip(*rows)
    return tp.Ops(list(names), list(opcodes), list(shapes),
                  np.asarray(start, float), np.asarray(end, float),
                  list(modules))


HAND = tp.ProgramTrace(
    [ops([
        # the same instruction name in two programs, with two meanings
        ("fusion.1", "fusion", "bf16[1,64]", 0.0, 1.0, "jit_prefill(7)"),
        ("copy.2", "copy", "bf16[2,4,256,1,128]", 1.0, 1.5, "jit_prefill(7)"),
        ("fusion.1", "fusion", "bf16[4,1]", 3.0, 5.0, "jit_decode(9)"),
        ("copy.2", "copy", "bf16[4,1]", 5.0, 5.25, "jit_decode(9)"),
        ("fusion.3", "fusion", "bf16[4,1]", 6.0, 7.0, ""),
    ])],
    sorted([("bench.engine_step", 0.0, 8.0, {}),
            ("ray_tpu.llm.step", 0.1, 7.9, {}),
            ("ray_tpu.llm.decode_prepare", 1.6, 2.9, {"shard": 0}),
            ("ray_tpu.llm.emit", 8.1, 9.0, {})], key=lambda s: s[1]))
MAPS = {
    "prefill_64": {"fusion.1": "jit(prefill)/kv_slice/dynamic_slice",
                   "copy.2": ""},
    "prefill_16": {"fusion.9": "jit(prefill)/mlp/dot_general"},
    "decode": {"fusion.1": "jit(decode)/mlp/dot_general",
               "copy.2": "jit(decode)/sample/argmax"},
}


def test_join_is_by_program_and_instruction():
    assert tp.program_of("jit_prefill(7)") == "prefill"
    # the bucket whose map knows the module's instructions
    assert tp.assign_maps(HAND, MAPS) == {
        "jit_prefill(7)": "prefill_64", "jit_decode(9)": "decode"}
    assert tp.scope_seconds(HAND, MAPS, ["mlp"]) == pytest.approx(2.0)
    assert tp.scope_seconds(HAND, MAPS, ["kv_slice"]) == pytest.approx(1.0)
    assert tp.program_seconds(HAND) == {
        "prefill": pytest.approx(1.5), "decode": pytest.approx(2.25),
        "": pytest.approx(1.0)}
    assert tp.busy_seconds(HAND) == pytest.approx(4.75)
    top = tp.top_ops(HAND, MAPS, 2)
    assert top[0][:2] == ["decode", "fusion.1"] and "mlp" in top[0][2]
    assert top[1][:2] == ["prefill", "fusion.1"] and "kv_slice" in top[1][2]


def test_kv_cache_movement_counts_whole_shard_copies_by_shape():
    shard = ["bf16[2,4,256,1,128]"]
    # kv_slice (1.0) and the copy of a whole shard (0.5), not the small copy
    assert tp.kv_cache_move_seconds(HAND, MAPS, shard) == pytest.approx(1.5)
    assert tp.kv_cache_move_seconds(HAND, MAPS, []) == pytest.approx(1.0)
    # one layer of a shard counts too, unless a compute scope made it
    layer = ["bf16[9,4,1]"]
    assert tp.kv_cache_move_seconds(HAND, MAPS, layer) == pytest.approx(2.0)
    # copy.2 of prefill and fusion.3 outside any module have no scope
    assert tp.unattributed_seconds(
        HAND, MAPS, tp.SCOPES, tp.NAMED) == pytest.approx(1.5)


def test_idle_gaps_go_to_the_innermost_span_of_either_kind():
    # gaps 1.5-3.0 (middle 2.25: decode_prepare), 5.25-6.0 (middle 5.6:
    # llm.step, inside bench.engine_step)
    assert tp.idle_gaps(HAND) == [
        ["ray_tpu.llm.decode_prepare", pytest.approx(1.5)],
        ["ray_tpu.llm.step", pytest.approx(0.75)]]


# ------------------------------------------------------ the recorded trace
@pytest.fixture(scope="module")
def recorded():
    if not (os.path.exists(FIXTURE) and os.path.exists(SCOPES)):
        pytest.skip("trace_programs fixture was not recorded")
    with open(SCOPES) as f:
        found = json.load(f)
    return tp.load(FIXTURE), found


@pytest.fixture(scope="module")
def raw(tmp_path_factory):
    """The device plane's two lines and the host's spans, as plain
    tuples straight from the profile."""
    from jax.profiler import ProfileData

    if not os.path.exists(FIXTURE):
        pytest.skip("trace_programs fixture was not recorded")
    path = tmp_path_factory.mktemp("raw") / "t.xplane.pb"
    with gzip.open(FIXTURE, "rb") as f:
        path.write_bytes(f.read())
    modules, op_events, spans = [], [], []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                t = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                if plane.name == "/device:TPU:0" and line.name == "XLA Modules":
                    modules.append(t)
                elif plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    op_events.append(t)
                elif plane.name.startswith("/host:CPU") and \
                        ev.name.startswith(("ray_tpu.", "bench.")):
                    spans.append(t)
    return modules, op_events, spans


def by_hand(raw, found):
    """[(program key, instruction, opcode, shape, seconds)] by plain
    loops: each leaf op, the module event that holds its start, and for
    a prefill module the bucket map that knows most of its names."""
    modules, op_events, _ = raw
    rows = []
    for text, t0, t1 in op_events:
        name = text[1:].split(" = ", 1)[0]
        # the first lower-case word before a bracket, after the type
        # (a type's own brackets follow upper-case letters or digits)
        opcode = re.search(r"\s([a-z][a-z0-9\-]*)\(", text).group(1)
        if opcode in ("while", "conditional", "call"):
            continue
        shape = text.split(" = ", 1)[1].split("{", 1)[0]
        module = next((m for m, a, b in modules if a <= t0 < b), "")
        rows.append((module, name, opcode, shape, (t1 - t0) * 1e-9))
    names_of = collections.defaultdict(set)
    for module, name, *_ in rows:
        names_of[module].add(name)
    key_of = {}
    for module, names in names_of.items():
        base = module.split("(")[0][4:]
        fits = [k for k in found["scopes"]
                if k == base or k.startswith(base + "_")]
        key_of[module] = max(
            fits, key=lambda k: len(names & set(found["scopes"][k])))
    return [(key_of[m], n, o, s, d) for m, n, o, s, d in rows]


def test_recorded_trace_has_two_programs_sharing_names(recorded, raw):
    trace, found = recorded
    rows = by_hand(raw, found)
    assert {k for k, *_ in rows} == {"decode", "prefill_16", "prefill_64"}
    assert all(m for m in trace.chips[0].modules)   # every op in a module
    shared = {n for k, n, *_ in rows if k == "decode"} & \
        {n for k, n, *_ in rows if k.startswith("prefill")}
    differ = [n for n in shared
              if found["scopes"]["decode"].get(n, "")
              != found["scopes"]["prefill_64"].get(n, "")]
    assert differ, "no instruction name means two things: a poor fixture"
    # steps of the recorder: 9 engine steps, three prompts of 2 + 1 + 2
    # chunks, each with one bench.* and one ray_tpu.llm.step span
    spans = collections.Counter(s[0] for s in trace.spans)
    assert spans["ray_tpu.llm.step"] == spans["bench.engine_step"] \
        == found["steps"]
    assert spans["ray_tpu.llm.prefill_dispatch"] == 5
    assert spans["ray_tpu.llm.first_token_sync"] == 3
    assert {s[3].get("request_id") for s in trace.spans
            if s[0] == "ray_tpu.llm.first_token_sync"} == {"r0", "r1", "r2"}


def test_recorded_numbers_equal_the_hand_count(recorded, raw):
    trace, found = recorded
    maps, shard = found["scopes"], set(found["cache_shapes"])
    rows = by_hand(raw, found)
    total = sum(d for *_, d in rows)
    assert sum(tp.program_seconds(trace).values()) == pytest.approx(total)
    prefill = sum(d for k, *_, d in rows if k.startswith("prefill"))
    assert tp.program_seconds(trace)["prefill"] == pytest.approx(prefill)
    assert 0.05 < prefill / total < 0.95

    def scoped(k, n, wanted):
        return bool(words(maps[k].get(n, "")) & set(wanted))

    for wanted in (["mlp"], ["attn"], ["sample"], ["kv_slice", "kv_merge"]):
        want = sum(d for k, n, o, s, d in rows if scoped(k, n, wanted))
        assert want > 0
        assert tp.scope_seconds(trace, maps, wanted) == pytest.approx(want)
    # sample exists in the decode program only, kv_slice in prefill only
    assert not any(scoped(k, n, ["sample"]) for k, n, *_ in rows
                   if k != "decode")
    shard |= {re.sub(r"\[\d+,", "[", s, count=1) for s in shard}
    kv = sum(d for k, n, o, s, d in rows
             if scoped(k, n, tp.KV_SCOPES)
             or (s in shard and not scoped(k, n, tp.COMPUTE_SCOPES)))
    assert tp.kv_cache_move_seconds(trace, maps, shard) == pytest.approx(kv)
    lost = sum(d for k, n, o, s, d in rows
               if not scoped(k, n, tp.SCOPES))
    assert tp.unattributed_seconds(
        trace, maps, tp.SCOPES, tp.NAMED) == pytest.approx(lost)
    assert 0 < lost < total


def test_recorded_idle_gaps_name_the_engines_spans(recorded, raw):
    trace, _ = recorded
    _, op_events, spans = raw
    gaps = dict(tp.idle_gaps(trace))
    assert gaps
    # bench.engine_step wraps every step: the innermost span inside it
    # must win wherever there is one
    inside_llm = sum(v for k, v in gaps.items() if k.startswith("ray_tpu.llm."))
    assert inside_llm > 0.8 * sum(gaps.values())
    # by hand: every gap between merged op intervals, its middle, the
    # latest-starting span covering it
    leaf = sorted((a, b) for t, a, b in op_events
                  if " while(" not in t and " conditional(" not in t
                  and " call(" not in t)
    merged = [list(leaf[0])]
    for a, b in leaf[1:]:
        if a > merged[-1][1]:
            merged.append([a, b])
        else:
            merged[-1][1] = max(merged[-1][1], b)
    want = collections.Counter()
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        mid = (e0 + s1) / 2
        cover = [s for s in spans if s[1] <= mid <= s[2]]
        name = max(cover, key=lambda s: s[1])[0] if cover else "host:unspanned"
        want[name] += (s1 - e0) * 1e-9
    assert gaps == {k: pytest.approx(v) for k, v in want.most_common(10)}
