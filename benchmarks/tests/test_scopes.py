"""scopes.py and its two readers: by hand on made-up intervals, on a CPU
trace of the toy train step (the same join a chip run makes, with the
map of the step as train_loop.py hands it over), on a program without
scopes (what the parent of PR 24 gives), and the declarations that go
with them. The recorded v5e traces must still read
what they read before any of this was added."""

import gzip
import json
import os
import subprocess
import sys

import pytest

from benchmarks import scopes, spec
from benchmarks import trace_reduce as tr
from benchmarks.readers import (trace_scope_ms_per_step,
                                trace_scope_unattributed_pct)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = {w["name"]: spec.load_cell(w["name"], True)
         for w in spec.benchmark_json()["workloads"]}
# by each cell's kind and its configuration's family, never by name
TRAIN_CELLS = [c for c, cell in CELLS.items() if cell["kind"] == "train"]
LLAMA_TRAIN_CELLS = [c for c in TRAIN_CELLS
                     if CELLS[c]["hp"].get("family", "llama") == "llama"]
# the scopes of models/llama.py and train_step.py: a train cell of
# another family declares those of them its program has
NEW_TRAIN_METRICS = ("remat_recompute_ms", "head_ce_ms", "mlp_ms",
                     "optimizer_ms", "scope_unattributed_pct.train")


def chip(events):
    return tr._chip("/device:TPU:0", [
        (f"%{n} = f32[8] fusion(f32[8] %a)", s * 1e9, e * 1e9, False)
        for n, s, e in events])


def test_words_of_a_scope_path():
    path = "jit(step)/loss_and_grad/transpose(jvp(head))/bsd,dv->bsv/dot_general"
    assert {"loss_and_grad", "transpose", "jvp", "head"} <= scopes.words(path)
    assert "ce" not in scopes.words(path)       # no part of a longer word
    assert "attn" not in scopes.words("jit(f)/attn_cached/dot_general")


def test_scope_seconds_and_unattributed_share_by_hand():
    c0 = chip([("fusion.1", 0.0, 1.0), ("fusion.2", 1.0, 3.0),
               ("copy.3", 3.0, 3.5), ("flash_attention_fwd.4", 4.0, 5.0)])
    c1 = chip([("fusion.1", 0.0, 3.0)])
    trace = tr.Trace([c0, c1], [])
    found = {"fusion.1": "jit(step)/loss_and_grad/jvp()/while/body/mlp/dot",
             "fusion.2": "jit(step)/loss_and_grad/transpose(jvp())/while/"
                         "body/checkpoint/rematted_computation/mlp/dot",
             "flash_attention_fwd.4": ""}
    # mean over the chips: (1 + 2) and 3 seconds of mlp
    assert scopes.scope_seconds(trace, found, ["mlp"]) == pytest.approx(3.0)
    assert scopes.scope_seconds(
        trace, found, ["rematted_computation"]) == pytest.approx(1.0)
    assert scopes.scope_seconds(trace, found, ["head", "ce"]) == 0.0
    # chip 0: copy.3 (0.5 s) of 4.5 s is neither scoped nor a kernel
    share = scopes.unattributed_share(
        trace, found, ["mlp"], "^flash_attention_")
    assert share == pytest.approx((0.5 / 2) / ((4.5 + 3.0) / 2))
    assert scopes.unattributed_share(tr.Trace([], []), found, ["mlp"], "^x") \
        is None


@pytest.fixture(scope="module")
def toy_step_trace():
    """Two traced steps of train-2k's rehearsal preset, compiled and run
    the way train_loop.train_loop does, on the CPU."""
    from functools import partial

    import jax
    import numpy as np

    from benchmarks import holder, traffic
    from ray_tpu import parallel
    from ray_tpu._private.jax_utils import compile_with_scopes, scope_map

    cell = spec.load_cell(LLAMA_TRAIN_CELLS[0], rehearse=True)
    hp, tf, opts = cell["hp"], cell["traffic"], cell["train"]
    llama = spec.family_of(hp)
    cfg = llama.model_config(hp, opts)
    devices = jax.devices()[:1]
    mesh = parallel.make_mesh(devices=devices)
    opt = parallel.default_optimizer(
        opts["learning_rate"], warmup_steps=opts["warmup_steps"],
        total_steps=opts["total_steps"])
    state, state_sh = parallel.create_train_state(
        mesh, jax.random.PRNGKey(0), partial(llama.init_params, cfg=cfg),
        opt, llama.param_specs(cfg))
    step = parallel.make_train_step(
        partial(llama.loss_fn, config=cfg), opt, mesh, state_sh)
    probe = traffic.probe_sequence(3, tf["seq"] + 1, hp["vocab_size"])
    batch = {"tokens": jax.device_put(np.ascontiguousarray(np.broadcast_to(
        probe, (tf["seqs_per_chip"], tf["seq"] + 1))),
        parallel.batch_sharding(mesh))}
    compiled = compile_with_scopes(step.lower(state, batch))
    state, _ = compiled(state, batch)
    tracer = holder.Tracer()
    tracer.start()
    for _ in range(2):
        state, metrics = compiled(state, batch)
        float(metrics["loss"])
    tracer.stop()
    return cell, tracer.reduce(), {"step": scope_map(compiled)}


def read_all(cell, trace, found):
    ctx = {"cell": cell, "chips": 1, "trace": trace, "peak": {},
           "scopes": found, "samples": {"traced_steps": 2}}
    out = {}
    for name in NEW_TRAIN_METRICS:
        metric = spec.load_json("metrics", f"{name}.json")
        reader = {"trace_scope_ms_per_step": trace_scope_ms_per_step,
                  "trace_scope_unattributed_pct":
                      trace_scope_unattributed_pct}[metric["reader"]]
        out[name] = reader.read(ctx, metric["args"])
    return out


def test_readers_join_a_traced_step_with_its_scopes(toy_step_trace):
    cell, trace, found = toy_step_trace
    got = read_all(cell, trace, found)
    total_ms = 1e3 * scopes._mean_core_seconds(trace, lambda n: True) / 2
    assert all(v is not None and v > 0 for v in got.values()), got
    # remat's second forward holds an mlp; the parts stay under the whole
    assert got["remat_recompute_ms"] < total_ms
    assert got["mlp_ms"] + got["head_ce_ms"] + got["optimizer_ms"] < total_ms
    assert 0 < got["scope_unattributed_pct.train"] < 75
    traced = set(trace.chips[0].names)
    assert len(traced & set(found["step"])) > 0.5 * len(traced)


def test_a_program_without_scopes_reads_nothing_and_says_why(
        toy_step_trace):
    """What the parent of the PR that added the scopes gives: no
    ``jax_utils.scope_map`` to import, so ``ctx["scopes"]`` is None and
    each reader by scope returns its reason in place of a number."""
    cell, trace, _ = toy_step_trace
    got = read_all(cell, trace, None)
    assert set(got) == set(NEW_TRAIN_METRICS)
    assert all(isinstance(v, spec.NotRead) and "scope_map" in v
               for v in got.values())


def test_readers_return_none_without_a_trace():
    ctx = {"cell": {}, "chips": 1, "trace": None, "samples": {}}
    assert trace_scope_ms_per_step.read(ctx, {"scopes": ["mlp"]}) is None
    assert trace_scope_unattributed_pct.read(
        ctx, {"known": ["mlp"], "named": "^x"}) is None


def test_new_metrics_are_declared_for_the_train_cells_only():
    bench = spec.benchmark_json()
    by_name = {e["name"]: e for e in bench["per_layer"]}
    for name in NEW_TRAIN_METRICS:
        entry = by_name[name]
        assert set(LLAMA_TRAIN_CELLS) <= set(entry["workloads"]) <= set(
            TRAIN_CELLS)
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "train_tokens_per_s_per_chip"
        metric = spec.load_json("metrics", f"{name}.json")
        assert metric["layer"] == entry["layer"] == "step program"
    for cell in LLAMA_TRAIN_CELLS:
        assert set(NEW_TRAIN_METRICS) <= set(spec.cell_metrics(cell, True))
    assert LLAMA_TRAIN_CELLS and set(CELLS) - set(TRAIN_CELLS)
    for cell in set(CELLS) - set(TRAIN_CELLS):
        assert not set(NEW_TRAIN_METRICS) & set(
            spec.cell_metrics(cell, True))


# What trace_reduce.py read on the two recorded v5e traces when PR 23
# recorded them. Nothing this PR adds may move them.
RECORDED = {
    "trace_1chip.xplane.pb": dict(
        busy=0.0002824339999999981, span=0.004305600999999999,
        flash=(6.116300000000296e-05, 16.0), in_flight=0.0, exposed=0.0,
        top=["flash_attention_fwd.18", 1.8665000000001042e-05],
        gap=["bench.step", 0.004001793999999996]),
    "trace_4chip.xplane.pb": dict(
        busy=0.0007259012499999593, span=0.005711212999999993,
        flash=(6.119725000001963e-05, 16.0), in_flight=0.0004239627500000412,
        exposed=0.000315609750000001,
        top=["fusion.366", 9.577100000000838e-05],
        gap=["bench.input_wait", 0.004969366000000003]),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_traces_read_what_they_read_before(name, tmp_path):
    path = tmp_path / name
    with gzip.open(os.path.join(HERE, name + ".gz"), "rb") as f:
        path.write_bytes(f.read())
    trace, want = tr.load(str(path)), RECORDED[name]
    coll = spec.load_json("metrics", "collective_ms.json")["args"]["pattern"]
    assert tr.busy_seconds(trace) == want["busy"]
    assert tr.span_seconds(trace) == want["span"]
    assert tr.matching_seconds(trace, "^flash_attention_") == want["flash"]
    assert tr.in_flight_seconds(trace, coll) == want["in_flight"]
    assert tr.exposed_seconds(trace, coll) == want["exposed"]
    assert tr.top_ops(trace, 1)[0] == want["top"]
    assert tr.idle_gaps(trace, 1)[0] == want["gap"]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_reaches_a_valid_last_line(cell):
    """run.py checks the line against contract.py before printing it:
    exit 0 means every declared metric a rehearsal can read is there."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "2147483700", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["device"]["platform"] == "cpu"
    if cell in LLAMA_TRAIN_CELLS:
        assert set(NEW_TRAIN_METRICS) <= set(last["metrics"])
        assert last["metrics"]["mlp_ms"]["value"] > 0
        assert last["metrics"]["scope_unattributed_pct.train"]["value"] < 50
