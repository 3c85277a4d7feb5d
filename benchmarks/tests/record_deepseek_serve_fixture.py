"""How ``trace_deepseek.serve.xplane.pb.gz`` and ``trace_deepseek.serve.json.gz``
beside this file were made (on one chip: a CPU trace names no program, so
the readers by program would have nothing to join):

    python benchmarks/tests/record_deepseek_serve_fixture.py

The cell ``deepseek-v3.2-exp.serve-longctx`` at its rehearsal sizes (the
recording is for the readers, not for a number) is built the way
``LLMServer`` builds it
(family, configuration, the cell's ``serve`` block: engine, ``warm_up``),
a few prompts are served under a profiler session with each
``engine.step()`` inside a ``bench.engine_step`` span as the benchmark's
server wraps it, and the trace goes beside this file (and to
``chiprun_out/``) with a sidecar: the scope maps of the engine's
programs, the shape of every leaf of a cache shard, the two
``engine_stats()`` snapshots around the traced steps, the number of
steps, and the cell's declared metrics this recording cannot hold, each
with its reader's reason (``reads_nothing``, found by evaluating them).
``tests/tree/record_toy_moe_serve_fixture.py`` is the shape it follows;
the recording is found by the family's name (tests/test_doors.py:
``serving_ctx``).
"""

import gzip
import json
import os
import shutil
import sys
import tempfile
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.abspath(os.path.join(HERE, *[os.pardir] * 2)))
CELL = "deepseek-v3.2-exp.serve-longctx"
PROMPTS = (200, 40, 100, 150)   # 256 rows a lane, chunks of 64: past
# the toy index_topk (16)


def main():
    import jax

    from benchmarks import holder, spec, trace_reduce
    from benchmarks.server import BenchLLMServer
    from ray_tpu._private.jax_utils import scope_map
    from ray_tpu.llm import GenRequest, LlamaEngine

    # at the toy preset a chunk of 64 rows would go through every held
    # expert, as a decode call does; the recording is to hold both kinds
    # of call, as the cell does at its own sizes (a chunk's grouped
    # matmul, a decode call's batched one), so the chunks are sent the
    # grouped way
    from ray_tpu.ops import moe
    moe.EVERY_EXPERT_ROWS = 8

    cell = spec.load_cell(CELL, rehearse=True)
    # the toy preset states float16 for the CPU's sake (the configuration's
    # rehearsal_why); Mosaic compiles no float16 kernel, and the chip's own
    # type is the published one
    if jax.devices()[0].platform == "tpu":
        cell["hp"] = {**cell["hp"], "compute_dtype": "bfloat16"}
    hp, sv = cell["hp"], cell["serve"]
    family = spec.family_of(hp)
    cfg = family.model_config(hp)
    params = jax.jit(partial(family.init_params, cfg=cfg))(spec.prng_key(0))
    eng = LlamaEngine(cfg, params, max_batch=sv["max_batch_size"],
                      max_seq=sv["max_seq_len"], **sv.get("engine_kwargs", {}))
    eng.warm_up()
    for i, n in enumerate(PROMPTS):
        assert eng.add_request(GenRequest(
            f"r{i}", [1 + (j * 7 + i) % 500 for j in range(n)], max_tokens=6))

    def stats():
        return {"engine": eng.stats.snapshot(), "loop_phases": {}}

    log_dir = tempfile.mkdtemp(prefix="fixture_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    before = stats()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    steps = 0
    while eng.num_active():
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            eng.step()
        steps += 1
    jax.profiler.stop_trace()
    after = stats()

    path = trace_reduce.find_xplane(log_dir)
    trace = trace_reduce.load(path)
    server = type("Replica", (), {"engine": eng})()
    sidecar = {
        "kind": "serve", "chips": 1, "steps": steps,
        "platform": jax.devices()[0].platform,
        "scopes": {k: scope_map(c)
                   for k, c in eng.compiled_programs().items()},
        "cache_shapes": BenchLLMServer._cache_shapes(server),
        "before": before, "after": after}
    # what this recording cannot hold: the cell's declared metrics whose
    # readers find nothing in it, each with the reader's reason
    samples = {"engine_steps": steps, "engine_tokens": 2 * steps,
               "engine_step_s": 0.5, "prefill_s": 0.1,
               "engine_step_ms": [5.0] * steps, "prefill_chunks": 5,
               **holder.engine_deltas(before, after)}
    _, sidecar["reads_nothing"] = spec.evaluate(
        spec.cell_metrics(CELL, traced=True),
        {"cell": cell, "chips": 1, "samples": samples, "trace": trace,
         "peak": {}, "scopes": sidecar["scopes"],
         "cache_shapes": sidecar["cache_shapes"]})

    stem = os.path.join(HERE, "trace_deepseek.serve")
    with open(path, "rb") as f, gzip.GzipFile(
            stem + ".xplane.pb.gz", "wb", compresslevel=9, mtime=0) as out:
        shutil.copyfileobj(f, out)
    with gzip.GzipFile(stem + ".json.gz", "wb", compresslevel=9,
                       mtime=0) as out:
        out.write(json.dumps(sidecar).encode())
    os.makedirs("chiprun_out", exist_ok=True)
    for end in (".xplane.pb.gz", ".json.gz"):
        shutil.copy(stem + end, "chiprun_out")
    # test_doors.py hands the readers no peaks: the roofline shares are
    # read here once with the chip's own row, for the recorder's log
    from benchmarks.readers import indexed_attention_roofline

    ctx = {"cell": cell, "samples": samples, "trace": trace,
           "scopes": sidecar["scopes"],
           "peak": spec.peak_for(jax.devices()[0].device_kind, True)}
    for name in ("index_score", "attn_selected_prefill",
                 "attn_selected_decode"):
        args = spec.load_json(
            "metrics", f"{name}_roofline.json")["args"]
        print(f"{name}_roofline on this recording:",
              indexed_attention_roofline.read(ctx, args))
    print(jax.devices(), "steps", steps, os.path.getsize(
        stem + ".xplane.pb.gz"), "bytes;", len(trace.chips),
        "device plane(s), busy", trace_reduce.busy_seconds(trace), "s;",
        "reads nothing:", sidecar["reads_nothing"])
    shutil.rmtree(log_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
