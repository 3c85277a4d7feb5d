"""How ``trace_toy_moe.train.xplane.pb.gz`` and ``trace_toy_moe.train.json.gz``
beside this file were made (on the CPU: the toy family runs no kernel):

    JAX_PLATFORMS=cpu python benchmarks/tests/tree/record_toy_moe_fixture.py

The cell ``toy-moe.train`` is built the way ``train_loop.train_loop``
builds it (family, configuration, step compiled with its scopes), two
steps are traced under ``bench.*`` spans, and the trace goes beside this
file with a sidecar: the scope map of the step, the sizes it ran at and
the metrics this recording cannot hold, each with the reason
(``reads_nothing``). It is the shape of the recording a PR that adds a
family brings: ``tests/trace_<family>.<kind>.xplane.pb.gz`` and
``.json.gz``, found by the family's name (tests/test_doors.py).
"""

import gzip
import json
import os
import shutil
import sys
import tempfile
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.abspath(os.path.join(HERE, *[os.pardir] * 3)))
STEPS = 2


def main():
    import jax
    import numpy as np

    from benchmarks import spec, trace_reduce, traffic
    from ray_tpu import parallel
    from ray_tpu._private.jax_utils import compile_with_scopes, scope_map

    cell = spec.load_cell("toy-moe.train", rehearse=True)
    hp, tf, opts = cell["hp"], cell["traffic"], cell["train"]
    family = spec.family_of(hp)
    cfg = family.model_config(hp, opts)
    mesh = parallel.make_mesh(devices=jax.devices()[:1])
    opt = parallel.default_optimizer(
        opts["learning_rate"], warmup_steps=opts["warmup_steps"],
        total_steps=opts["total_steps"])
    state, state_sh = parallel.create_train_state(
        mesh, jax.random.PRNGKey(0), partial(family.init_params, cfg=cfg),
        opt, family.param_specs(cfg))
    step = parallel.make_train_step(
        partial(family.loss_fn, config=cfg), opt, mesh, state_sh)
    probe = traffic.probe_sequence(3, tf["seq"] + 1, hp["vocab_size"])
    batch = {"tokens": jax.device_put(np.ascontiguousarray(np.broadcast_to(
        probe, (tf["seqs_per_chip"], tf["seq"] + 1))),
        parallel.batch_sharding(mesh))}
    compiled = compile_with_scopes(step.lower(state, batch))
    state, _ = compiled(state, batch)
    log_dir = tempfile.mkdtemp(prefix="fixture_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=options)
    for _ in range(STEPS):
        with jax.profiler.TraceAnnotation("bench.step"):
            state, metrics = compiled(state, batch)
            float(metrics["loss"])
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(log_dir)
    stem = os.path.join(HERE, "trace_toy_moe.train")
    with open(path, "rb") as f, gzip.GzipFile(
            stem + ".xplane.pb.gz", "wb", compresslevel=9, mtime=0) as out:
        shutil.copyfileobj(f, out)
    with gzip.GzipFile(stem + ".json.gz", "wb", compresslevel=9,
                       mtime=0) as out:
        out.write(json.dumps({
            "kind": "train", "chips": 1, "seq": tf["seq"],
            "seqs_per_step": tf["seqs_per_chip"], "traced_steps": STEPS,
            "reads_nothing": {"flash_attention_roofline":
                              "attention_impl xla: the toy's step holds no "
                              "kernel, on any device"},
            "scopes": {"step": scope_map(compiled)}}).encode())
    trace = trace_reduce.load(path)
    print(os.path.getsize(stem + ".xplane.pb.gz"), "bytes;",
          len(trace.chips), "device plane(s), busy",
          trace_reduce.busy_seconds(trace), "s")
    shutil.rmtree(log_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
