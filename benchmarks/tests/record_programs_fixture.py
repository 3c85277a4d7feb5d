"""How ``trace_programs.xplane.pb.gz`` and ``trace_programs.scopes.json``
beside this file were made (on one chip):

    python benchmarks/tests/record_programs_fixture.py

A toy engine (2 layers, 256 wide, two 128-wide heads, 4 slots of 256)
serves three prompts under a profiler session: two programs
(``jit_prefill`` in two buckets, ``jit_decode``) that share instruction
names, each op scoped, each ``engine.step()`` inside a ``bench.*`` span
as the benchmark's server wraps it. The trace and the programs' scope
maps go to ``chiprun_out/``, a listing to stdout. This process holds the
chip itself.
"""

import collections
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    import jax
    import jax.numpy as jnp

    from benchmarks import trace_programs, trace_reduce
    from ray_tpu._private.jax_utils import scope_map
    from ray_tpu.llm import GenRequest, LlamaEngine
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=512, dim=256, n_layers=2, n_heads=2, n_kv_heads=1,
        ffn_dim=512, max_seq_len=256, param_dtype=jnp.bfloat16, remat=False)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = LlamaEngine(cfg, params, max_batch=4, max_seq=256, prefill_chunk=64)
    eng.generate(list(range(1, 71)), max_tokens=3)     # buckets 64 and 16
    log_dir = tempfile.mkdtemp(prefix="fixture_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    for i, n in enumerate((70, 40, 100)):
        assert eng.add_request(GenRequest(
            f"r{i}", [1 + (j * 7 + i) % 500 for j in range(n)], max_tokens=6))
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    steps = 0
    while eng.num_active():
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            eng.step()
        steps += 1
    jax.profiler.stop_trace()

    os.makedirs("chiprun_out", exist_ok=True)
    out = "chiprun_out/trace_programs.xplane.pb"
    shutil.copy(trace_reduce.find_xplane(log_dir), out)
    maps = {k: scope_map(c) for k, c in eng.compiled_programs().items()}
    cache = eng.shards[0].cache["k"]
    with open("chiprun_out/trace_programs.scopes.json", "w") as f:
        json.dump({"scopes": maps, "steps": steps, "cache_shapes": [
            f"bf16[{','.join(str(d) for d in cache.shape)}]"]}, f)
    trace = trace_programs.load(out)
    print(jax.devices(), "steps", steps)
    if not trace.chips:
        print("no device plane: this was not a chip")
        return
    print("modules", collections.Counter(trace.chips[0].modules))
    print("spans", collections.Counter(s[0] for s in trace.spans))
    print(json.dumps(trace_programs.summary(
        trace, maps, [f"bf16[{','.join(str(d) for d in cache.shape)}]"]),
        indent=1))


if __name__ == "__main__":
    main()
