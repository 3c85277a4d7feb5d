"""trace_reduce.py against traces recorded on a v5e (record_fixture.py:
two steps of a toy decoder, 2 layers, flash attention, remat, fsdp over
the chips) and against intervals made by hand."""

import gzip
import os

import numpy as np
import pytest

from benchmarks import spec
from benchmarks import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FLASH = ("flash_attention_fwd", "flash_attention_bwd_dkv",
         "flash_attention_bwd_dq")


def recorded(name, tmp_path):
    path = os.path.join(HERE, name + ".gz")
    if not os.path.exists(path):
        pytest.skip(f"{name}.gz was not recorded")
    out = tmp_path / name
    with gzip.open(path, "rb") as f:
        out.write_bytes(f.read())
    return tr.load(str(out))


def chip(events):
    """events: (instruction text, start s, end s, is_async)."""
    return tr._chip("/device:TPU:0", [
        (t, s * 1e9, e * 1e9, a) for t, s, e, a in events])


def test_parse_op():
    assert tr.parse_op(
        "%flash_attention_fwd.18 = (bf16[2,2,256,128]{3,2,1,0:T(8,128)(2,1)}, "
        "f32[2,2,256,128]{3,2,1,0}) custom-call(bf16[2,2,256,128] %x), "
        "custom_call_target=\"tpu_custom_call\""
    ) == ("flash_attention_fwd.18", "custom-call")
    assert tr.parse_op(
        "%while.11 = (s32[]{:T(128)}, bf16[2,256,256]{1,2,0}) "
        "while((s32[]{:T(128)}, bf16[2,256,256]{1,2,0}) %tuple.3), "
        "condition=%c, body=%b") == ("while.11", "while")
    assert tr.parse_op("%iota.12 = s32[2,256,1,1]{1,0,3,2} iota(), "
                       "iota_dimension=1") == ("iota.12", "iota")
    assert tr.parse_op(
        "%all-gather-start.3 = (bf16[8]{0}, bf16[32]{0}) "
        "all-gather-start(bf16[8]{0} %p)") == (
            "all-gather-start.3", "all-gather-start")
    assert tr.parse_op("dot.165") == ("dot.165", "dot")  # the CPU's names


def test_union_and_minus_by_hand():
    s, e = np.array([0.0, 1.0, 5.0, 5.5]), np.array([2.0, 3.0, 6.0, 5.7])
    assert tr.union_seconds(s, e) == pytest.approx(4.0)
    assert tr.union_seconds(s, e, lo=2.5, hi=5.25) == pytest.approx(0.75)
    a = tr._union(np.array([0.0, 10.0]), np.array([4.0, 12.0]))
    b = tr._union(np.array([1.0, 3.5, 11.0]), np.array([2.0, 10.5, 20.0]))
    assert tr._minus(a, b) == pytest.approx(1.0 + 1.5 + 0.5)


def test_busy_is_per_chip_then_averaged_and_skips_containers():
    c0 = chip([("%while.1 = (s32[]) while((s32[]) %t)", 0.0, 10.0, False),
               ("%fusion.1 = f32[8] fusion(f32[8] %a)", 1.0, 3.0, False),
               ("%fusion.2 = f32[8] fusion(f32[8] %a)", 2.0, 4.0, False),
               ("%copy-start.1 = (f32[8]) copy-start(f32[8] %a)", 0.0, 9.0, True)])
    c1 = chip([("%fusion.1 = f32[8] fusion(f32[8] %a)", 1.0, 2.0, False)])
    trace = tr.Trace([c0, c1], [])
    # 3 s on one chip and 1 s on the other: the mean, not the sum
    assert tr.busy_seconds(trace) == pytest.approx(2.0)
    assert tr.span_seconds(trace) == pytest.approx(3.0)
    assert tr.top_ops(trace)[0] == ["fusion.1", pytest.approx(1.5)]
    assert all(name != "while.1" for name, _ in tr.top_ops(trace))


def test_exposed_collective_time_by_hand():
    gather = r"^(all-gather|all-reduce)"
    c = chip([
        ("%all-gather-start.1 = (bf16[8]) all-gather-start(bf16[2] %p)", 0.0, 0.1, False),
        ("%all-gather-start.1 = (bf16[8]) all-gather-start(bf16[2] %p)", 0.0, 4.0, True),
        ("%fusion.1 = f32[8] fusion(f32[8] %a)", 0.1, 3.0, False),
        ("%all-gather-done.1 = bf16[8] all-gather-done((bf16[8]) %s)", 3.0, 4.0, False),
        ("%all-reduce.7 = f32[8] all-reduce(f32[8] %g)", 6.0, 7.0, False),
    ])
    trace = tr.Trace([c], [])
    assert tr.in_flight_seconds(trace, gather) == pytest.approx(5.0)
    # hidden behind fusion.1 from 0.1 to 3.0
    assert tr.exposed_seconds(trace, gather) == pytest.approx(5.0 - 2.9)
    secs, count = tr.matching_seconds(trace, gather)
    assert (secs, count) == (pytest.approx(2.1), 3)


def test_async_collective_pairs_become_spans():
    c = chip([
        ("%async-collective-start.3 = (bf16[8]) fusion(bf16[2] %p), kind=kCustom", 0.0, 0.2, False),
        ("%fusion.1 = f32[8] fusion(f32[8] %a)", 0.2, 2.0, False),
        ("%async-collective-done.3 = bf16[8] fusion((bf16[8]) %s), kind=kCustom", 2.0, 3.0, False),
    ])
    assert "async-collective.3" in c.names
    trace = tr.Trace([c], [])
    assert tr.in_flight_seconds(trace, "^async-collective") == pytest.approx(3.0)
    assert tr.exposed_seconds(trace, "^async-collective") == pytest.approx(1.2)
    assert tr.busy_seconds(trace) == pytest.approx(3.0)


def test_idle_gaps_go_to_the_innermost_host_span():
    c = chip([("%fusion.1 = f32[8] fusion(f32[8] %a)", 0.0, 1.0, False),
              ("%fusion.2 = f32[8] fusion(f32[8] %a)", 3.0, 4.0, False),
              ("%fusion.3 = f32[8] fusion(f32[8] %a)", 4.5, 5.0, False)])
    spans = [("bench.engine_step", 0.5, 4.2), ("bench.prefill_chunk", 1.5, 2.8)]
    gaps = tr.idle_gaps(tr.Trace([c], spans))
    assert gaps == [["bench.prefill_chunk", pytest.approx(2.0)],
                    ["host:unspanned", pytest.approx(0.5)]]


@pytest.mark.parametrize("name,chips", [
    ("trace_1chip.xplane.pb", 1), ("trace_4chip.xplane.pb", 4)])
def test_recorded_trace(name, chips, tmp_path):
    trace = recorded(name, tmp_path)
    assert [c.name for c in trace.chips] == [
        f"/device:TPU:{i}" for i in range(chips)]
    busy, span = tr.busy_seconds(trace), tr.span_seconds(trace)
    per_chip = [tr.union_seconds(c.start[c.core], c.end[c.core])
                for c in trace.chips]
    assert busy == pytest.approx(np.mean(per_chip))
    assert 0 < busy <= span                      # a mean, never a sum
    if chips > 1:
        assert sum(per_chip) > busy
    # two traced steps x 2 layers: the forward kernel runs again in the
    # backward pass (remat), the two backward kernels once
    for kernel, calls in zip(FLASH, (8, 4, 4)):
        secs, count = tr.matching_seconds(trace, rf"^{kernel}(\.\d+)?$")
        assert count == calls and secs > 0
    total, count = tr.matching_seconds(trace, r"^flash_attention_")
    assert count == 16
    names = [n for n, _ in tr.top_ops(trace, 1000)]
    assert not any(n.startswith(("while", "conditional")) for n in names)
    assert {s[0] for s in trace.host_spans} == {"bench.step", "bench.input_wait"}
    gaps = dict(tr.idle_gaps(trace))
    assert gaps and set(gaps) <= {
        "bench.step", "bench.input_wait", "host:unspanned"}
    if chips == 1:  # steps of a millisecond: the clocks' offset shows on four
        assert max(gaps, key=gaps.get) == "bench.step"
    coll = spec.load_json("metrics", "collective_ms.json")["args"]["pattern"]
    if chips == 1:
        assert tr.in_flight_seconds(trace, coll) == 0.0
    else:
        in_flight = tr.in_flight_seconds(trace, coll)
        assert 0 < tr.exposed_seconds(trace, coll) <= in_flight <= span
