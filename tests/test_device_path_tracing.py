"""Observation of the device path: ``tracing.phase`` spans and
``PhaseStats``, the engine's request stamps and ``EngineStats``, the
``ray_tpu.llm.*`` events of a profiler trace, the named scopes inside
the jitted programs and ``jax_utils.scope_map``, and the data
iterator's counters. All on the CPU: what is checked is what is
recorded, never how long it took."""

import dataclasses
import glob
import os
import re
from functools import partial

import numpy as np
import pytest

from ray_tpu._private.jax_utils import compile_with_scopes, scope_map
from ray_tpu.llm import GenRequest, LLMConfig, LlamaEngine
from ray_tpu.llm._internal.engine import REQUEST_PHASES, EngineStats
from ray_tpu.models import llama
from ray_tpu.util import tracing


def tiny_cfg(**over):
    return dataclasses.replace(llama.LLAMA_TINY, **{"remat": False, **over})


@pytest.fixture(scope="module")
def tiny_params():
    import jax

    return llama.init_params(jax.random.PRNGKey(0), tiny_cfg())


def make_engine(params, **kw):
    kw = {"max_batch": 2, "max_seq": 128, "prefill_chunk": 16,
          "max_slots": 4, **kw}
    return LlamaEngine(tiny_cfg(), params, **kw)


def run_dry(eng):
    while eng.num_active():
        eng.step()


def scopes_in(path: str) -> set:
    """The words of a scope path: ``jit(step)/loss_and_grad/jvp(head)/mul``
    holds loss_and_grad and head (JAX wraps a scope in its transforms)."""
    return set(re.findall(r"[A-Za-z_][A-Za-z_0-9]*", path))


# ------------------------------------------------------------ the primitive
def test_phase_adds_seconds_and_a_count_and_nests():
    stats = tracing.PhaseStats()
    with tracing.phase("outer", stats, shard=1):
        with tracing.phase("inner", stats, request_id="r0"):
            pass
        with tracing.phase("inner", stats):
            pass
    with tracing.phase("unrecorded"):
        pass
    snap = stats.snapshot()
    assert {n: v["count"] for n, v in snap.items()} == {"outer": 1, "inner": 2}
    assert snap["outer"]["seconds"] >= snap["inner"]["seconds"] >= 0.0
    # a snapshot is a copy: a later phase does not move it
    with tracing.phase("inner", stats):
        pass
    assert snap["inner"]["count"] == 2
    assert stats.snapshot()["inner"]["count"] == 3


def test_llm_request_has_a_stage_precedence():
    prec = tracing.STAGE_PRECEDENCE
    assert prec["serve.execute"] < prec["llm.request"] < prec["serve.batch_wait"]


# ------------------------------------------------------ request phases
def test_request_phases_in_order_and_sum_for_a_request_behind_another(
        tiny_params):
    """Two prompts land in one shard: the second has its slot at once
    and waits behind the first one's chunks (prefill_wait)."""
    import time

    eng = make_engine(tiny_params, max_batch=2)
    first = GenRequest("first", list(range(1, 41)), max_tokens=3)
    behind = GenRequest("behind", list(range(1, 20)), max_tokens=3,
                        submitted=time.monotonic())
    assert eng.add_request(first) and eng.add_request(behind)
    assert behind.shard == first.shard
    run_dry(eng)
    for req in (first, behind):
        stamps = [req.routed, req.received, req.submitted, req.admitted,
                  req.prefill_started, req.first_token, req.finished]
        assert all(s > 0 for s in stamps)
        assert stamps == sorted(stamps)
        phases = req.phases()
        assert tuple(phases) == REQUEST_PHASES
        assert sum(phases.values()) == pytest.approx(
            req.finished - req.routed, abs=1e-9)
        # no handle routed it: the two phases before the queue read 0.0
        # and the four old ones what they read before
        assert (phases["ingress"], phases["accept"]) == (0.0, 0.0)
        assert sum(phases.values()) == pytest.approx(
            req.finished - req.submitted, abs=1e-9)
    # handed to the engine directly: submitted is its admission
    assert first.phases()["queue_wait"] == 0.0
    assert behind.phases()["queue_wait"] > 0.0
    # the first prompt's three chunks were dispatched before its first
    assert behind.prefill_started >= first.first_token
    assert behind.phases()["prefill_wait"] > first.phases()["prefill_wait"]
    assert (first.prefill_chunks, behind.prefill_chunks) == (3, 2)
    ring = {r[0]: r for r in eng.stats.requests}
    # the six fields a reader by index knows, the new phases behind them
    engine_phases = [behind.phases()[n] for n in REQUEST_PHASES[2:]]
    assert ring[behind.submitted][1:] == (*engine_phases, 2, 0.0, 0.0)


# --------------------------------------------------------- EngineStats
def test_engine_stats_equal_the_hand_count(tiny_params):
    """max_batch 2, at most 4 slots, chunks of 16. Five prompts of 39
    tokens asking for 4 tokens each: four are admitted (the third grows
    a shard), the fifth is refused."""
    eng = make_engine(tiny_params)
    reqs = [GenRequest(f"r{i}", list(range(1, 40)), max_tokens=4)
            for i in range(5)]
    assert [eng.add_request(r) for r in reqs] == [True] * 4 + [False]
    steps = 0
    while eng.num_active():
        eng.step()
        steps += 1
    s = eng.stats.snapshot()
    assert s["shards_grown"] == 1 and s["requests_refused"] == 1
    assert s["requests_finished"] == 4 and len(s["requests"]) == 4
    assert s["steps"] == steps
    # 39 tokens = chunks of 16, 16, 7: real tokens, not the bucket of 16
    assert s["prefill_chunks"] == 4 * 3
    assert s["prefill_tokens"] == 4 * 39
    # every request: 1 token off its prefill, 3 from decodes
    assert s["tokens_emitted"] == 4 * 4
    assert s["decode_lanes_active"] == 4 * 3
    # per shard: prompt A decodes alone while B prefills (3 decodes),
    # then B decodes alone (3 decodes); the two shards go in step, so
    # every one of the 6 calls runs over the pair, prepared, read and
    # booked a shard
    assert (s["decode_calls"], s["decode_shards"]) == (6, 12)
    assert s["decode_lanes_total"] == s["decode_shards"] * eng.max_batch
    # per shard: B's slot is mid-prefill during A's 3 decodes, and A's
    # is nobody's during B's 3 (in the first of them A's last token is
    # dispatched and not read: no request could have used the lane)
    assert (s["decode_lanes_prefilling"], s["decode_lanes_free"]) == (6, 6)
    assert s["decode_ahead"] == 5       # every call but the first
    counts = {n: v["count"] for n, v in s["phases"].items()}
    assert counts == {
        "llm.step": steps, "llm.prefill_dispatch": 12,
        "llm.first_token_sync": 4, "llm.decode_prepare": 12,
        "llm.decode_dispatch": 6, "llm.decode_sync": 12,
        "llm.decode_bookkeep": 12}
    inner = sum(v["seconds"] for n, v in s["phases"].items()
                if n != "llm.step")
    assert inner <= s["phases"]["llm.step"]["seconds"]
    assert set(EngineStats.COUNTERS) <= set(s)


def test_an_empty_lane_is_counted_as_prefilling_or_free(tiny_params):
    """Four lanes in one shard: a short prompt that decodes from the
    first step on, a prompt of four chunks behind it, two slots nobody
    holds. Every decode call's lanes are the request's, the prompt's
    that is not all in yet, or free, and the three make up the call."""
    eng = make_engine(tiny_params, max_batch=4, max_slots=4)
    short = GenRequest("short", list(range(1, 6)), max_tokens=12)
    long = GenRequest("long", list(range(1, 61)), max_tokens=3)
    assert eng.add_request(short) and eng.add_request(long)
    by_call = []
    while eng.num_active():
        was = eng.stats.snapshot()
        eng.step()
        now = eng.stats.snapshot()
        grew = [now[f"decode_lanes_{k}"] - was[f"decode_lanes_{k}"]
                for k in ("active", "prefilling", "free", "total")]
        if grew[3]:
            by_call.append(tuple(grew[:3]))
            assert sum(grew[:3]) == grew[3] == eng.max_batch
    # the short prompt decodes from the first step on; 60 tokens are 4
    # chunks, a step each behind it: the long prompt's lane waits through
    # 4 decode calls, and is a live lane in the step that writes its
    # last chunk
    assert by_call[:5] == [(1, 1, 2)] * 4 + [(2, 0, 2)]
    assert by_call[-1] == (1, 0, 3)                # the short one alone
    s = eng.stats.snapshot()
    assert s["decode_lanes_prefilling"] == 4
    assert (s["decode_lanes_active"] + s["decode_lanes_prefilling"]
            + s["decode_lanes_free"]) == s["decode_lanes_total"]


def test_engine_programs_are_exposed_for_their_text(tiny_params):
    eng = make_engine(tiny_params)
    # bucket 16 only; 19 tokens lie in the cache's first half, 64 rows
    eng.generate(list(range(1, 20)), max_tokens=2)
    programs = eng.compiled_programs()
    assert sorted(programs) == ["decode_64", "first_token", "prefill_16_64"]
    assert all(scope_map(c) for c in programs.values())


def test_engine_programs_carry_scopes_over_a_stale_compile_cache(
        tiny_params, monkeypatch):
    """The persistent compile cache keys a program without its metadata.
    An engine built with every scope switched off fills it; a normal
    engine of the same shapes then runs those executables, whose text
    names nothing. compiled_programs() must still carry the scopes, and
    the instructions of the program that runs."""
    import contextlib

    import jax

    shapes = {"max_batch": 3, "max_seq": 96}   # no other test's programs
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        make_engine(tiny_params, **shapes).generate(
            list(range(1, 20)), max_tokens=2)
    eng = make_engine(tiny_params, **shapes)
    eng.generate(list(range(1, 20)), max_tokens=2)
    programs = eng.compiled_programs()
    words = {k: set().union(*(scopes_in(p) for p in scope_map(c).values()))
             for k, c in programs.items()}
    assert {"mlp", "attn", "kv_write", "sample"} <= words["decode_48"]
    assert {"mlp", "kv_slice", "kv_write"} <= words["prefill_16_48"]
    # the same instructions as the program the jitted function runs
    args = (np.zeros(3, np.int32), np.zeros(3, np.int32),
            np.zeros(3, np.float32), eng._rng)
    running = eng._jit_decode.lower(
        eng.params, eng.shards[0].cache, *args, rows=48).compile().as_text()
    names = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ", re.M)
    assert names.findall(running) == names.findall(
        programs["decode_48"].as_text())


# ------------------------------------------------- the profiler's trace
def test_profiler_trace_holds_the_engine_spans_nested(tiny_params, tmp_path):
    import jax
    from jax.profiler import ProfileData

    eng = make_engine(tiny_params)
    eng.generate([1, 2, 3], max_tokens=2)            # compile outside
    for i in range(2):
        assert eng.add_request(
            GenRequest(f"r{i}", list(range(1, 20)), max_tokens=4))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    assert not tracing.recording()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    assert tracing.recording()
    for _ in range(3):
        eng.step()
    jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = sorted(
        ((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats))
         for plane in ProfileData.from_file(path).planes
         for line in plane.lines for ev in line.events
         if ev.name.startswith("ray_tpu.")), key=lambda e: e[0])
    names = [e[2] for e in events]
    steps = [e for e in events if e[2] == "ray_tpu.llm.step"]
    assert len(steps) == 3
    # r0: chunks in steps 1 and 2; step 2 also dispatches its first
    # decode and then reads its first token; step 3 dispatches r1's first
    # chunk and r0's second decode, and only then reads the first one
    assert names.count("ray_tpu.llm.prefill_dispatch") == 3
    assert names.count("ray_tpu.llm.first_token_sync") == 1
    for part, times in (("decode_prepare", 2), ("decode_dispatch", 2),
                        ("decode_sync", 1), ("decode_bookkeep", 1)):
        assert names.count(f"ray_tpu.llm.{part}") == times
    for start, end, name, ids in events:
        if name == "ray_tpu.llm.step":
            continue
        # every part lies inside one step, and says whose it is
        assert any(s[0] <= start and end <= s[1] for s in steps), name
        assert ids["shard"] == 0
        if "prefill" in name or "first_token" in name:
            assert ids["request_id"] in ("r0", "r1")
    # what a trace's reader wants of a call's work: the row a chunk's
    # tokens start at, the rows a decode's live lanes attend to (r0's 19
    # prompt tokens and the row it writes, then one more)
    assert [(ids["start"], ids["rows"]) for _, _, name, ids in events
            if name.endswith("prefill_dispatch")] == [(0, 16), (16, 3), (0, 16)]
    assert [ids["attended"] for _, _, name, ids in events
            if name.endswith("decode_dispatch")] == [20, 21]
    # and why its other lane stood empty: r1 holds it, its prompt not in
    assert [(ids["rows"], ids["prefilling"], ids["free"])
            for _, _, name, ids in events
            if name.endswith("decode_dispatch")] == [(1, 1, 0)] * 2
    in_step = [[e[2].rsplit(".", 1)[1] for e in events
                if step[0] <= e[0] and e[1] <= step[1]] for step in steps]
    # everything is dispatched before anything is read
    assert in_step[1] == ["step", "prefill_dispatch", "decode_prepare",
                          "decode_dispatch", "first_token_sync"]
    assert in_step[2] == ["step", "prefill_dispatch", "decode_prepare",
                          "decode_dispatch", "decode_sync", "decode_bookkeep"]


# --------------------------------------------------------- named scopes
def _train_step_text(remat: bool) -> str:
    import jax

    from ray_tpu import parallel

    cfg = tiny_cfg(remat=remat)
    mesh = parallel.make_mesh(devices=jax.devices()[:1])
    opt = parallel.default_optimizer(1e-3)
    state, state_sh = parallel.create_train_state(
        mesh, jax.random.PRNGKey(0), partial(llama.init_params, config=cfg),
        opt, llama.param_specs(cfg))
    step = parallel.make_train_step(
        partial(llama.loss_fn, config=cfg), opt, mesh, state_sh)
    batch = {"tokens": np.zeros((2, 33), np.int32)}
    return compile_with_scopes(step.lower(state, batch)).as_text()


def _engine_text(program: str) -> str:
    import jax

    eng = make_engine(llama.init_params(jax.random.PRNGKey(0), tiny_cfg()))
    eng.generate(list(range(1, 20)), max_tokens=2)
    return eng.compiled_programs()[program].as_text()


MODEL_SCOPES = {"embed", "layers", "attn", "mlp", "head"}
PROGRAM_SCOPES = {
    "train": (partial(_train_step_text, False),
              MODEL_SCOPES | {"ce", "loss_and_grad", "optimizer",
                              "transpose", "jvp"}),
    "train_remat": (partial(_train_step_text, True),
                    MODEL_SCOPES | {"ce", "loss_and_grad", "optimizer",
                                    "rematted_computation"}),
    "prefill": (partial(_engine_text, "prefill_16_64"),
                MODEL_SCOPES | {"kv_slice", "kv_write", "attn_cached"}),
    "decode": (partial(_engine_text, "decode_64"),
               MODEL_SCOPES | {"kv_write", "attn_cached", "sample"}),
}


@pytest.mark.parametrize("program", sorted(PROGRAM_SCOPES))
def test_compiled_programs_carry_their_scopes(program):
    make_text, want = PROGRAM_SCOPES[program]
    found = set()
    for path in scope_map(make_text()).values():
        found |= scopes_in(path)
    assert want <= found, sorted(want - found)
    if program == "train":
        assert "rematted_computation" not in found


FUSED_TEXT = '''HloModule jit_f, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %multiply.3 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(f)/mlp/mul" stack_frame_id=3}
  ROOT %add.4 = f32[8]{0} add(%multiply.3, %param_0.1), metadata={op_name="jit(f)/head/add" stack_frame_id=4}
}

%fused_computation.2 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %negate.1 = f32[8]{0} negate(%param_0.2), metadata={op_name="jit(f)/attn/neg"}
  ROOT %copy.9 = f32[8]{0} copy(%negate.1)
}

ENTRY %main.7 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %copy.2 = f32[8]{0} copy(%fusion.2)
  ROOT %fusion.3 = f32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/ce/own"}
}
'''


def test_scope_map_gives_a_fusion_its_roots_scope():
    scopes = scope_map(FUSED_TEXT)
    assert scopes["fusion.1"] == "jit(f)/head/add"      # its root's
    assert scopes["fusion.2"] == "jit(f)/attn/neg"      # root has none
    assert scopes["fusion.3"] == "jit(f)/ce/own"        # its own wins
    assert scopes["multiply.3"] == "jit(f)/mlp/mul"     # inside a fusion
    assert "copy.2" not in scopes and "param_0.1" not in scopes


def test_scope_map_reads_a_compiled_program():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x, w):
        with jax.named_scope("mlp"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("head"):
            return (h * 2.0).sum()

    compiled = f.lower(jnp.ones((8, 8)), jnp.ones((8, 8))).compile()
    scopes = scope_map(compiled)
    assert scopes == scope_map(compiled.as_text())
    words = set()
    for path in scopes.values():
        words |= scopes_in(path)
    assert {"mlp", "head"} <= words


# ------------------------------------------------------ the data iterator
@pytest.mark.parametrize("prefetch", [2, 0])
def test_iterator_counters_rise_by_one_a_batch(ray_start_regular, prefetch):
    import ray_tpu.data

    ds = ray_tpu.data.from_numpy(
        [np.arange(40).reshape(10, 4) for _ in range(5)], column="tokens")
    assert ds.iter_stats.snapshot() == {}
    batches = ds.iter_batches(batch_size=8, prefetch_batches=prefetch)
    next(batches)
    first = ds.iter_stats.snapshot()
    assert first["data.stage_batch"]["count"] >= 1
    n = 1 + sum(1 for _ in batches)
    assert n == 7                                   # 50 rows: 6 x 8 + 2
    snap = ds.iter_stats.snapshot()
    # the probe that finds the blocks exhausted is not a batch
    assert snap["data.stage_batch"]["count"] == n
    if prefetch:
        assert snap["data.next_batch"]["count"] == n
        assert first["data.next_batch"]["count"] == 1
    else:
        assert "data.next_batch" not in snap
    assert snap["data.stage_batch"]["seconds"] > 0.0


# ----------------------------------------------------------- the server
@pytest.fixture
def llm_server():
    from ray_tpu.llm.serve import LLMServer

    server = LLMServer(LLMConfig(
        model_config=tiny_cfg(), max_batch_size=2, max_seq_len=64))
    yield server
    server.shutdown()


@pytest.mark.parametrize("sampled", [False, True])
def test_generate_sends_a_span_only_under_a_sampled_trace(
        llm_server, monkeypatch, sampled):
    """No profiler session, tracing disabled: generate() sends nothing
    to the hub. Under a trace context (serve.execute sets one for a
    sampled request) it sends one llm.request record."""
    sent = []
    monkeypatch.setattr(tracing, "_emit", sent.append)
    assert not tracing.is_enabled()
    if sampled:
        with tracing.context(("trace0", "parent0")):
            tokens = llm_server.generate(list(range(1, 20)), max_tokens=3)
    else:
        tokens = llm_server.generate(list(range(1, 20)), max_tokens=3)
    assert len(tokens) == 3
    if not sampled:
        assert sent == []
        return
    [record] = sent
    assert record["name"] == "llm.request"
    assert (record["trace_id"], record["parent_id"]) == ("trace0", "parent0")
    attrs = record["attrs"]
    assert attrs["stage"] == "llm.request" and attrs["prefill_chunks"] == "1"
    phases = [float(attrs[f"{p}_s"]) for p in REQUEST_PHASES]
    assert all(p >= 0.0 for p in phases)
    assert sum(phases) == pytest.approx(
        record["end"] - record["start"], abs=1e-4)
    assert float(attrs["first_token_handoff_s"]) > 0.0


def test_engine_stats_call_returns_the_snapshot(llm_server):
    before = llm_server.engine_stats()
    assert before["engine"]["requests_finished"] == 0
    llm_server.generate(list(range(1, 40)), max_tokens=4)
    after = llm_server.engine_stats()
    # what it returned before this PR is still there
    assert {"active", "peak_active", "free_slots", "max_batch", "shards",
            "platform", "pid"} <= set(after)
    # the chunk that served the run, derived or given
    assert after["prefill_chunk"] == llm_server.engine.prefill_chunk
    eng = after["engine"]
    assert eng["requests_finished"] == 1 and eng["tokens_emitted"] == 4
    assert eng["prefill_tokens"] == 39
    [(submitted, *phases, chunks, ingress, accept)] = eng["requests"]
    assert submitted > 0 and len(phases) == 4 and chunks == 1
    # called in-process: no handle routed it, no replica received it
    assert (ingress, accept) == (0.0, 0.0)
    loop = after["loop_phases"]
    assert loop["llm.admit"]["count"] == 1
    assert loop["llm.emit"]["count"] >= 3
    # the request's own thread folded its time outside the engine in
    assert loop["serve.ingress"] == {"seconds": 0.0, "count": 1}
    assert loop["llm.accept"] == {"seconds": 0.0, "count": 1}
    assert loop["llm.first_token_handoff"]["count"] == 1
    assert loop["llm.token_handoff"]["count"] == 4
    assert (loop["llm.token_handoff"]["seconds"]
            >= loop["llm.first_token_handoff"]["seconds"] > 0.0)


def test_a_slow_consumer_reads_as_backlog_not_as_wake(llm_server):
    """A consumer that sleeps between tokens holds the request's thread
    inside ``yield``: the next tokens lie in the queue until it comes
    back (``llm.token_backlog``), and taking one that is there is no
    wait (``llm.token_wake``). The two make up ``llm.token_handoff``."""
    import time

    tokens, nap = 6, 0.05
    before = llm_server.engine_stats()["loop_phases"]
    for _ in llm_server.generate_stream(list(range(1, 20)), tokens):
        time.sleep(nap)
    loop = llm_server.engine_stats()["loop_phases"]
    assert "llm.token_backlog" not in before
    names = ("llm.token_handoff", "llm.token_backlog", "llm.token_wake",
             "llm.token_yield")
    handoff, backlog, wake, yielded = (loop[n]["seconds"] for n in names)
    assert [loop[n]["count"] for n in names] == [tokens] * 4
    assert backlog >= 0.0 and wake > 0.0
    assert backlog + wake == pytest.approx(handoff, rel=1e-9)
    # the engine is a few naps ahead of the reader from the second
    # token on: every nap behind that is a token's wait in the queue
    assert backlog > 2 * nap and wake < backlog / 2
    # the reader's naps pass inside the generator's yields
    assert yielded >= tokens * nap * 0.9


# ------------------------------------- the serve stack around the engine
class _StampServer:
    """Mixed into ``LLMServer`` in the replica: keeps every request's
    stamps as its thread folds them, for the test to read."""

    def _fold_request(self, req):
        super()._fold_request(req)
        if not hasattr(self, "folded"):
            self.folded = []
        self.folded.append({
            "stamps": [req.routed, req.received, req.submitted, req.admitted,
                       req.prefill_started, req.first_token,
                       req.first_yielded, req.finished],
            "phases": req.phases(), "tokens": len(req.generated),
            "handoff": (req.handoff_s, req.handoff_n),
            "backlog_s": req.backlog_s,
            "yield": (req.yield_s, req.yield_n)})

    def folded_requests(self):
        return list(getattr(self, "folded", []))


def _serve_tiny_llm(name):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app
    from ray_tpu.llm.serve import LLMServer

    ray_tpu.init(num_cpus=4, max_workers=4, ignore_reinit_error=True)
    server_cls = type("StampLLMServer", (_StampServer, LLMServer), {})
    app = build_llm_app(
        LLMConfig(model_config=tiny_cfg(), max_batch_size=2, max_seq_len=64,
                  accelerator_type=""),
        name=name, server_cls=server_cls)
    return serve.run(app, name=name)


def _served_routes(name):
    """{requests, latency count} of the deployment's direct-handle route,
    once the hub's registry has them."""
    from ray_tpu.util import state as state_api

    dep = state_api.summarize_serve()["deployments"].get(name) or {}
    route = dep.get("routes", {}).get("", {})
    return {"requests": route.get("requests", 0),
            "latency": (route.get("latency_s") or {}).get("count", 0)}


def _wait_for(read, want, timeout_s=15.0):
    import time

    deadline = time.monotonic() + timeout_s
    while (got := read()) != want and time.monotonic() < deadline:
        time.sleep(0.1)
    return got


STREAMS, TOKENS = 3, 5
SLOW_NAP_S = 0.2


@pytest.fixture(scope="module")
def streamed():
    """A tiny LLM behind ``serve.run``: STREAMS requests of TOKENS tokens
    through a streaming handle, then one unary call and one old-style
    call of the replica's streaming method with no ``request_meta``."""
    import time

    import ray_tpu
    from ray_tpu import serve

    handle = _serve_tiny_llm("llm-stamps")
    stream = handle.options(method_name="generate_stream", stream=True)
    list(stream.remote(list(range(1, 12)), 2))            # warm the path
    stats0 = stream.stream_stats()
    engine0 = handle.engine_stats.remote().result()
    folded0 = len(handle.folded_requests.remote().result())
    # the warming stream and the two unary calls above
    routes0 = _wait_for(lambda: _served_routes("llm-stamps"),
                        {"requests": 3, "latency": 3})
    durations, answers = [], []
    for i in range(STREAMS):
        t0 = time.monotonic()
        answers.append(list(stream.remote(list(range(1, 20 + i)), TOKENS)))
        durations.append(time.monotonic() - t0)
    routes1 = _wait_for(lambda: _served_routes("llm-stamps"),
                        {"requests": 3 + STREAMS, "latency": 3 + STREAMS})
    out = {
        "answers": answers, "durations": durations,
        "routes": (routes0, routes1),
        "stream_stats": (stats0, stream.stream_stats()),
        # an options() view shares the handle's
        "stream_stats_of_handle": handle.stream_stats(),
        "engine": (engine0, handle.engine_stats.remote().result()),
        "folded": handle.folded_requests.remote().result()[folded0:],
    }
    # a unary call is stamped too
    unary = handle.remote({"prompt_ids": list(range(1, 20)),
                           "max_tokens": TOKENS}).result()
    out["unary"] = (unary, handle.folded_requests.remote().result()[-1])
    # a caller from before request_meta: four positional arguments
    handle._refresh(force=True)
    [replica] = handle._replicas
    old_style = replica.handle_request_streaming.options(
        num_returns="streaming").remote(
            "generate_stream", (list(range(1, 20)), TOKENS), {}, "")
    out["old_style"] = ([ray_tpu.get(ref) for ref in old_style],
                        handle.folded_requests.remote().result()[-1])
    # a consumer that sleeps between tokens
    slow0 = (stream.stream_stats(), handle.engine_stats.remote().result())
    for _ in stream.remote(list(range(1, 20)), TOKENS):
        time.sleep(SLOW_NAP_S)
    out["slow"] = (slow0, (stream.stream_stats(),
                           handle.engine_stats.remote().result()))
    yield out
    serve.shutdown()
    ray_tpu.shutdown()


def _stats_delta(before, after, name):
    was = before.get(name, {"seconds": 0.0, "count": 0})
    return {k: after[name][k] - was[k] for k in ("seconds", "count")}


def _loop_delta(streamed, name):
    before, after = (e["loop_phases"] for e in streamed["engine"])
    return _stats_delta(before, after, name)


def _stamps_in_order(streamed):
    assert len(streamed["folded"]) == STREAMS
    for req in streamed["folded"]:
        assert all(s > 0 for s in req["stamps"])
        assert req["stamps"] == sorted(req["stamps"])
        routed, received, submitted = req["stamps"][:3]
        assert routed < received < submitted      # another process sent it


def _phases_sum_to_finished_minus_routed(streamed):
    for req in streamed["folded"]:
        assert tuple(req["phases"]) == REQUEST_PHASES
        assert all(v >= 0.0 for v in req["phases"].values())
        assert sum(req["phases"].values()) == pytest.approx(
            req["stamps"][-1] - req["stamps"][0], abs=1e-9)


def _loop_phases_count_a_request_and_a_token(streamed):
    for name in ("serve.ingress", "llm.accept", "llm.first_token_handoff"):
        assert _loop_delta(streamed, name)["count"] == STREAMS, name
    assert _loop_delta(streamed, "llm.token_handoff")["count"] == (
        STREAMS * TOKENS)
    folded = streamed["folded"]
    assert [r["handoff"][1] for r in folded] == [TOKENS] * STREAMS
    assert _loop_delta(streamed, "llm.token_handoff")["seconds"] == (
        pytest.approx(sum(r["handoff"][0] for r in folded)))
    assert _loop_delta(streamed, "serve.ingress")["seconds"] == (
        pytest.approx(sum(r["phases"]["ingress"] for r in folded)))
    assert _loop_delta(streamed, "llm.first_token_handoff")["seconds"] == (
        pytest.approx(sum(r["stamps"][6] - r["stamps"][5] for r in folded)))
    # the loop's own parts are still there beside them
    assert {"llm.admit", "llm.emit"} <= set(streamed["engine"][1][
        "loop_phases"])


def _ring_rows_keep_their_six_fields_and_gain_two(streamed):
    rows = streamed["engine"][1]["engine"]["requests"][-STREAMS:]
    for row, req in zip(rows, streamed["folded"]):
        p = req["phases"]
        assert row[0] == req["stamps"][2]                  # submitted
        assert tuple(row[1:5]) == tuple(p[n] for n in REQUEST_PHASES[2:])
        assert row[5] >= 1                                 # prefill chunks
        assert tuple(row[6:]) == (p["ingress"], p["accept"])


def _stream_stats_count_a_token(streamed):
    before, after = streamed["stream_stats"]
    assert after == streamed["stream_stats_of_handle"]
    all_items = (after["serve.stream_transit"]["count"]
                 - before["serve.stream_transit"]["count"])
    firsts = (after["serve.stream_first_transit"]["count"]
              - before["serve.stream_first_transit"]["count"])
    assert (all_items, firsts) == (STREAMS * TOKENS, STREAMS)
    assert [len(a) for a in streamed["answers"]] == [TOKENS] * STREAMS
    seconds = (after["serve.stream_transit"]["seconds"]
               - before["serve.stream_transit"]["seconds"])
    first_seconds = (after["serve.stream_first_transit"]["seconds"]
                     - before["serve.stream_first_transit"]["seconds"])
    # every reading is >= 0, so the first items' sum is under the sum,
    # and a token's transit lies inside its request
    assert 0.0 <= first_seconds <= seconds
    assert seconds / all_items < min(streamed["durations"])
    assert seconds < sum(streamed["durations"])


HUB_STRETCHES = ("serve.stream_to_hub", "serve.stream_in_hub",
                 "serve.stream_from_hub")


def _the_hubs_stretches_sum_to_the_transit(streamed):
    before, after = streamed["stream_stats"]
    transit = _stats_delta(before, after, "serve.stream_transit")
    parts = [_stats_delta(before, after, n) for n in HUB_STRETCHES]
    assert [p["count"] for p in parts] == [transit["count"]] * 3
    assert all(p["seconds"] >= 0.0 for p in parts)
    assert sum(p["seconds"] for p in parts) == pytest.approx(
        transit["seconds"], rel=1e-6)
    # a reply carried an item or more, and every stream asked once
    replies = _stats_delta(before, after, "serve.stream_next_wait")
    assert STREAMS <= replies["count"] <= transit["count"]
    assert replies["seconds"] > 0.0


def _the_handoff_is_its_backlog_and_its_wake(streamed):
    handoff, backlog, wake, yielded = (
        _loop_delta(streamed, name) for name in (
            "llm.token_handoff", "llm.token_backlog", "llm.token_wake",
            "llm.token_yield"))
    assert (backlog["count"], wake["count"], yielded["count"]) == (
        handoff["count"],) * 3
    assert backlog["seconds"] + wake["seconds"] == pytest.approx(
        handoff["seconds"], rel=1e-9)
    folded = streamed["folded"]
    for req in folded:                    # request by request
        assert 0.0 <= req["backlog_s"] <= req["handoff"][0]
        assert req["yield"][1] == TOKENS and req["yield"][0] > 0.0
    assert backlog["seconds"] == pytest.approx(
        sum(r["backlog_s"] for r in folded))
    assert yielded["seconds"] == pytest.approx(
        sum(r["yield"][0] for r in folded))


def _a_slow_consumer_shows_in_the_hub_not_in_the_wake(streamed):
    """The stream is not bounded: the replica's worker hands every token
    to the hub as it comes, and there they wait for the reader's next
    ask. The request's thread never waits long for the interpreter."""
    (stats0, engine0), (stats1, engine1) = streamed["slow"]
    in_hub = _stats_delta(stats0, stats1, "serve.stream_in_hub")
    to_hub = _stats_delta(stats0, stats1, "serve.stream_to_hub")
    assert in_hub["count"] == TOKENS
    # the tokens behind the first were made during the reader's first
    # nap and waited out the rest of it
    assert in_hub["seconds"] > SLOW_NAP_S
    assert to_hub["seconds"] < in_hub["seconds"] / 2
    wake = _stats_delta(engine0["loop_phases"], engine1["loop_phases"],
                        "llm.token_wake")
    assert wake["count"] == TOKENS
    assert wake["seconds"] < in_hub["seconds"] / 2


def _a_streamed_request_is_counted_and_timed(streamed):
    before, after = streamed["routes"]
    assert after["requests"] - before["requests"] == STREAMS
    assert after["latency"] - before["latency"] == STREAMS


def _a_unary_call_is_stamped_too(streamed):
    answer, req = streamed["unary"]
    assert answer["num_generated"] == TOKENS == req["tokens"]
    routed, received, submitted = req["stamps"][:3]
    assert 0 < routed < received < submitted
    assert answer["token_ids"] == streamed["answers"][0]   # the same prompt


def _an_old_style_call_still_streams(streamed):
    tokens, req = streamed["old_style"]
    assert tokens == streamed["answers"][0]
    # nobody stamped its route: ingress reads 0.0, accept is the replica's
    assert req["phases"]["ingress"] == 0.0 and req["phases"]["accept"] > 0.0


@pytest.mark.parametrize("check", [
    _stamps_in_order,
    _phases_sum_to_finished_minus_routed,
    _loop_phases_count_a_request_and_a_token,
    _ring_rows_keep_their_six_fields_and_gain_two,
    _stream_stats_count_a_token,
    _the_hubs_stretches_sum_to_the_transit,
    _the_handoff_is_its_backlog_and_its_wake,
    _a_slow_consumer_shows_in_the_hub_not_in_the_wake,
    _a_streamed_request_is_counted_and_timed,
    _a_unary_call_is_stamped_too,
    _an_old_style_call_still_streams,
], ids=lambda f: f.__name__.strip("_"))
def test_a_served_requests_time_outside_the_engine(streamed, check):
    check(streamed)


@pytest.mark.parametrize("stamped", [True, False])
def test_an_item_without_the_hubs_stamps_reads_as_before(stamped):
    """An item whose reply came from a hub that stamps nothing adds its
    transit and no stretch; one with the hub's two stamps adds all
    three, which sum to the transit whatever the clocks' jitter."""
    import time

    from ray_tpu.serve.handle import (DeploymentHandle,
                                      DeploymentResponseGenerator)

    now = tracing.wall_at(time.monotonic())

    class RefGen:
        last_yield_wall = now - 0.5
        # the hub's clock a hair behind the worker's, the reply stamped
        # ahead of this process's own reading
        last_hub_wall = now - 0.6 if stamped else None
        last_reply_wall = now + 60.0 if stamped else None
        last_next_wait_s = 0.25

    handle = DeploymentHandle("nobody")
    items = DeploymentResponseGenerator(RefGen(), handle)
    items._note_item()
    items._note_item()
    assert handle.stream_stats() == {}         # folded as the stream ends
    with items._recorded():
        pass
    stats = handle.stream_stats()
    assert stats["serve.stream_transit"]["count"] == 2
    assert stats["serve.stream_first_transit"]["count"] == 1
    assert stats["serve.stream_next_wait"] == {"seconds": 0.5, "count": 2}
    transit = stats["serve.stream_transit"]["seconds"]
    assert 1.0 <= transit < 1.5
    if not stamped:
        assert not set(HUB_STRETCHES) & set(stats)
        return
    to_hub, in_hub, from_hub = (stats[n]["seconds"] for n in HUB_STRETCHES)
    assert (to_hub, from_hub) == (0.0, 0.0)        # held, not negative
    assert in_hub == pytest.approx(transit, rel=1e-9)


@pytest.mark.parametrize("shards", [1, 2])
def test_a_streamed_item_carries_its_yield_stamp(monkeypatch, shards):
    """STREAM_YIELD's ``t_wall`` comes back on the STREAM_NEXT reply,
    whether the consumer asked before the item was there (the yield's
    waiters) or after (the index lookup), single hub and sharded."""
    import time

    import ray_tpu

    monkeypatch.setenv("RAY_TPU_HUB_SHARDS", str(shards))
    ray_tpu.init(num_cpus=2, max_workers=2, ignore_reinit_error=True)
    try:
        @ray_tpu.remote(num_returns="streaming")
        def slow_then_fast():
            time.sleep(0.3)      # the consumer is waiting by then
            yield 0
            yield 1
            yield 2

        gen = slow_then_fast.remote()
        assert gen.last_yield_wall is None
        t0 = tracing.wall_at(time.monotonic())
        stamps = []
        for i, ref in enumerate(gen):
            if i == 1:
                time.sleep(0.3)  # items 1 and 2 are there before the ask
            stamps.append(gen.last_yield_wall)
            assert ray_tpu.get(ref) == i
        now = tracing.wall_at(time.monotonic())
        assert len(stamps) == 3 and stamps == sorted(stamps)
        assert t0 < stamps[0] and stamps[-1] < now
    finally:
        ray_tpu.shutdown()


def test_a_sampled_stream_parents_under_serve_execute(monkeypatch):
    """A streamed request under a sampled trace leaves serve.route,
    serve.queue_wait and serve.execute as a unary call does, and its
    llm.request span parents under serve.execute."""
    import time

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import worker

    monkeypatch.setenv("RAY_TPU_TRACING", "1")
    handle = _serve_tiny_llm("llm-sampled")
    try:
        stream = handle.options(method_name="generate_stream", stream=True)
        assert len(list(stream.remote(list(range(1, 20)), TOKENS))) == TOKENS
        client = worker.get_client()
        want = {"serve.route", "serve.queue_wait", "serve.execute",
                "llm.request"}
        deadline, spans = time.monotonic() + 20, []
        while time.monotonic() < deadline:
            for row in client.list_state("traces"):
                spans = client.list_state("traces", trace_id=row["trace_id"])
                if want <= {s["name"] for s in spans}:
                    break
            else:
                time.sleep(0.1)
                continue
            break
        by_name = {s["name"]: s for s in spans}
        assert want <= set(by_name), sorted(by_name)
        request, execute = by_name["llm.request"], by_name["serve.execute"]
        assert request["parent_id"] == execute["span_id"]
        assert by_name["serve.queue_wait"]["parent_id"] == execute["parent_id"]
        attrs = request["attrs"]
        for phase_name in (*REQUEST_PHASES, "first_token_handoff"):
            assert float(attrs[f"{phase_name}_s"]) >= 0.0
        assert float(attrs["ingress_s"]) > 0.0
        # the streamed body lies inside serve.execute
        assert execute["start"] <= request["end"] <= execute["end"] + 1e-3
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
