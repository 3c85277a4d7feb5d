"""How the recorded traces beside this file were made (on the chip):

    python benchmarks/tests/record_fixture.py        # one or four chips

A toy decoder (2 layers, 256 wide, two 128-wide heads, flash attention,
remat, fsdp over every local chip) trains two traced steps under
``bench.*`` host spans; the trace goes to
``chiprun_out/trace_<n>chip.xplane.pb`` and a listing of its planes,
lines and first events to stdout. This process holds the chips itself.
"""

import os
import shutil
import sys
import tempfile
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    import jax
    import jax.numpy as jnp

    from benchmarks import trace_reduce
    from ray_tpu import parallel
    from ray_tpu.models import llama

    devices = jax.devices()
    n = len(devices)
    cfg = llama.LlamaConfig(
        vocab_size=512, dim=256, n_layers=2, n_heads=2, n_kv_heads=1,
        ffn_dim=512, max_seq_len=256, param_dtype=jnp.bfloat16, remat=True,
        attention_impl="flash")
    mesh = parallel.make_mesh(devices=devices)
    opt = parallel.default_optimizer(1e-4, warmup_steps=2, total_steps=100)
    state, state_sh = parallel.create_train_state(
        mesh, jax.random.PRNGKey(0), lambda r: llama.init_params(r, cfg),
        opt, llama.param_specs(cfg))
    step = parallel.make_train_step(
        partial(llama.loss_fn, config=cfg), opt, mesh, state_sh)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (2 * n, 257), 0, 512,
                           dtype=jnp.int32),
        parallel.batch_sharding(mesh))
    batch = {"tokens": tokens}
    for _ in range(2):
        state, m = step(state, batch)
        float(m["loss"])
    log_dir = tempfile.mkdtemp(prefix="fixture_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    t0 = time.perf_counter()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    t1 = time.perf_counter()
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.step"):
            state, m = step(state, batch)
            float(m["loss"])
        with jax.profiler.TraceAnnotation("bench.input_wait"):
            time.sleep(0.002)
    t2 = time.perf_counter()
    jax.profiler.stop_trace()
    t3 = time.perf_counter()
    print(f"start_trace {t1 - t0:.3f}s traced {t2 - t1:.4f}s "
          f"stop_trace {t3 - t2:.3f}s")
    path = trace_reduce.find_xplane(log_dir)
    os.makedirs("chiprun_out", exist_ok=True)
    out = f"chiprun_out/trace_{n}chip.xplane.pb"
    shutil.copy(path, out)
    print(out, os.path.getsize(out), "bytes")

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for ev in events[:6]:
                print("      ", repr(ev.name), ev.start_ns, ev.duration_ns,
                      [(k, str(v)[:80]) for k, v in ev.stats][:6])
    trace = trace_reduce.load(path)
    print("chips", [c.name for c in trace.chips],
          "busy", trace_reduce.busy_seconds(trace),
          "span", trace_reduce.span_seconds(trace), "host window", t2 - t1)
    print("host spans", trace.host_spans[:6])
    names = sorted({nm for c in trace.chips for nm in c.names})
    print("op names", len(names), [nm for nm in names if not nm.startswith("fusion")][:80])
    print("breakdown", trace_reduce.breakdown(trace))
    shutil.rmtree(log_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
