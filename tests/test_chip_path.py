"""The boundary between the runtime and the device, as far as a machine
without a chip can check it: which process may open a chip, that the
Pallas kernels are mapped over a mesh by hand, that the device programs
compile for a v5e, and that chip_smoke.py still walks its phases. The
proof on the chip is ``python chip_smoke.py`` (README "Multi-chip /
multi-host")."""

import dataclasses
import json
import os
import subprocess
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu import parallel
from ray_tpu.models import llama

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def aot_compile():
    """Started before the other tests of this file and collected by the
    last one: the compiler uses the cores the others leave idle."""
    proc = subprocess.Popen(
        [sys.executable, os.path.join(_REPO, "tests", "aot_compile_check.py"),
         "--quick"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    yield proc
    if proc.poll() is None:
        proc.kill()


def test_flash_is_mapped_over_the_mesh_by_hand(aot_compile):
    """attention_impl="flash" under an 8-device mesh: the (interpreted)
    kernel runs inside shard_map, batch over fsdp and heads over model,
    and matches the einsum attention in output and gradients. Without
    the mapping the same program fails to lower on a TPU ("Mosaic
    kernels cannot be automatically partitioned")."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
    mesh = parallel.make_mesh(fsdp=4, model=2)
    B, S, H, hd = 4, 128, 2, 128
    q, k, v = (
        jax.random.normal(key, (B, S, H, hd))
        for key in jax.random.split(jax.random.PRNGKey(0), 3)
    )
    sharding = NamedSharding(mesh, P(("data", "fsdp"), None, "model", None))

    def run(impl):
        c = dataclasses.replace(cfg, attention_impl=impl)

        def loss(q, k, v):
            o = llama._attention(q, k, v, c)
            return jnp.sum(o * jnp.cos(o)), o

        def f(q, k, v):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                (_, o), grads = jax.value_and_grad(
                    loss, argnums=(0, 1, 2), has_aux=True
                )(q, k, v)
            return o, grads

        jitted = jax.jit(f, in_shardings=(sharding,) * 3)
        return jitted.lower(q, k, v).as_text(), jitted(q, k, v)

    text, (o, grads) = run("flash")
    assert "shard_map" in text or "manual" in text.lower()
    _, (o_ref, grads_ref) = run("xla")
    assert o.sharding.spec == sharding.spec
    # float32 throughout: the two differ by summation order only
    np.testing.assert_allclose(o, o_ref, atol=1e-5)
    for g, g_ref in zip(grads, grads_ref):
        np.testing.assert_allclose(g, g_ref, atol=2e-5)


def test_train_step_supplies_the_mesh_to_ring_attention():
    """make_train_step traces under its mesh, so attention that maps
    itself over the ambient mesh (ring over seq here, heads over model)
    finds it without the caller entering set_mesh; the loss equals the
    einsum attention's on the same fsdp x seq x model mesh."""
    mesh = parallel.make_mesh(fsdp=2, seq=2, model=2)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0, 512)
    losses = {}
    for impl in ("ring", "xla"):
        cfg = dataclasses.replace(llama.LLAMA_TINY, attention_impl=impl)
        opt = parallel.default_optimizer(1e-3, warmup_steps=2, total_steps=10)
        state, state_sh = parallel.create_train_state(
            mesh, jax.random.PRNGKey(0),
            lambda r: llama.init_params(r, cfg), opt, llama.param_specs(cfg),
        )
        step = parallel.make_train_step(
            partial(llama.loss_fn, config=cfg), opt, mesh, state_sh
        )
        _, metrics = step(state, {"tokens": tokens})
        losses[impl] = float(metrics["loss"])
    assert np.isfinite(losses["ring"])
    assert abs(losses["ring"] - losses["xla"]) < 2e-2  # bf16 activations


def test_only_a_worker_given_chips_can_reach_for_one(monkeypatch):
    """A worker dispatched without chips computes on the CPU whatever
    the driver's environment names; one dispatched with chips names the
    TPU as its only platform, sees exactly its chips, and raises when it
    cannot open them (there are none here) instead of retreating to the
    CPU; a worker that has run anything is not given chips afterwards;
    a max_calls=1 task's worker exits and its chip comes back."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # as on the chip machine
    monkeypatch.setenv("TPU_TOPOLOGY", "2x2")
    ray_tpu.init(num_cpus=2, num_tpus=4, max_workers=2)
    try:
        @ray_tpu.remote
        def without_chips():
            import jax

            return os.getpid(), os.environ["JAX_PLATFORMS"], jax.default_backend()

        def with_chips():
            env = {
                k: os.environ.get(k)
                for k in ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS",
                          "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS")
            }
            try:
                import jax

                jax.devices()
                error = None
            except RuntimeError as e:
                error = str(e)
            return os.getpid(), env, error

        cpu_pid, platforms, backend = ray_tpu.get(without_chips.remote())
        assert (platforms, backend) == ("cpu", "cpu")

        one_chip = ray_tpu.remote(num_tpus=1, max_calls=1)(with_chips)
        pid1, env, error = ray_tpu.get(one_chip.remote())
        assert pid1 != cpu_pid  # the used worker was passed over
        assert env == {
            "JAX_PLATFORMS": "tpu", "TPU_VISIBLE_CHIPS": "0",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }
        assert error is not None and "tpu" in error.lower()

        pid2, env, _ = ray_tpu.get(one_chip.remote())
        assert pid2 != pid1  # max_calls=1: a process per call
        assert env["TPU_VISIBLE_CHIPS"] == "0"  # and its chip came back

        two = ray_tpu.remote(num_tpus=2)(with_chips)
        _, env, _ = ray_tpu.get(two.remote())
        # chips 0 and 1 of a 2x2 host are neighbours along x
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,1,1"

        whole = ray_tpu.remote(num_tpus=4)(with_chips)
        with pytest.raises(ray_tpu.exceptions.GetTimeoutError):
            # the idle two-chip worker still holds its chips
            ray_tpu.get(whole.remote(), timeout=1.5)
    finally:
        ray_tpu.shutdown()


def test_chip_task_gets_a_fresh_worker_when_the_pool_is_full():
    """Every pooled worker has run something, and the pool is at its
    cap: one idle worker is retired so that a fresh one fits."""
    ray_tpu.init(num_cpus=2, num_tpus=1, max_workers=2)
    try:
        @ray_tpu.remote
        def pid():
            import time

            time.sleep(0.2)
            return os.getpid()

        used = set(ray_tpu.get([pid.remote() for _ in range(4)]))
        assert len(used) == 2
        fresh = ray_tpu.get(pid.options(num_tpus=1).remote(), timeout=30)
        assert fresh not in used
        after = set(ray_tpu.get([pid.remote() for _ in range(4)]))
        assert len(after & used) == 1  # one was retired, not both
    finally:
        ray_tpu.shutdown()


def test_shutdown_outlasts_every_process_it_started():
    """A killed actor's worker leaves the hub's table before its process
    has gone, and one that holds a chip takes seconds to die (libtpu's
    SIGTERM handler, played here by a sleep): shutdown() returns when
    all of them have gone, and leaves no zombie either."""

    def children():
        found = set()
        for name in filter(str.isdigit, os.listdir("/proc")):
            try:
                with open(f"/proc/{name}/stat") as f:
                    ppid = f.read().rsplit(")", 1)[1].split()[1]
            except OSError:
                continue
            if int(ppid) == os.getpid():
                found.add(int(name))
        return found

    before = children()
    ray_tpu.init(num_cpus=2, max_workers=3)
    try:
        @ray_tpu.remote
        class SlowToDie:
            def arm(self):
                import signal
                import time

                signal.signal(
                    signal.SIGTERM, lambda *_: (time.sleep(1.5), os._exit(0))
                )
                return os.getpid()

        killed, pooled = SlowToDie.remote(), SlowToDie.remote()
        pids = ray_tpu.get([killed.arm.remote(), pooled.arm.remote()])
        ray_tpu.kill(killed)
        assert set(pids) <= children()
    finally:
        ray_tpu.shutdown()
    assert children() - before == set()


def test_claim_chips_rules():
    from ray_tpu._private.accelerators import tpu

    assert tpu._box_bounds([3], 4) == "1,1,1"
    assert tpu._box_bounds([0, 2], 4) == "1,2,1"
    with pytest.raises(RuntimeError, match="do not fill a box"):
        tpu._box_bounds([0, 3], 4)  # a diagonal of the 2x2
    with pytest.raises(RuntimeError, match="topology is unknown"):
        tpu._box_bounds([0, 1], 3)
    # this process imported jax long ago: it can no longer take chips
    with pytest.raises(RuntimeError, match="imported jax before"):
        tpu.claim_chips([0], 1)


def test_chips_are_counted_from_device_files(monkeypatch, tmp_path):
    """Not from the accelerator type (a one-chip machine of a four-chip
    host says v5litepod-4) and never by opening a device."""
    from ray_tpu.util.accelerators import tpu

    monkeypatch.delenv("RAY_TPU_NUM_TPUS", raising=False)
    monkeypatch.delenv("TPU_NUM_DEVICES", raising=False)
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    vfio = tmp_path / "vfio"
    vfio.mkdir()
    for name in ("2", "vfio"):
        (vfio / name).touch()
    real_listdir = os.listdir
    monkeypatch.setattr(
        os, "listdir",
        lambda d: real_listdir(vfio) if d == "/dev/vfio"
        else ["accel0", "null"] if d == "/dev" else real_listdir(d),
    )
    assert tpu.get_num_tpu_chips_on_node() == 2  # vfio/2 and accel0
    monkeypatch.setattr(os, "listdir", lambda d: [])
    assert tpu.get_num_tpu_chips_on_node() == 0


def test_compile_cache_is_placed_once(monkeypatch):
    from ray_tpu._private.jax_utils import ensure_compilation_cache_dir

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert ensure_compilation_cache_dir() == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert ensure_compilation_cache_dir() == os.path.join(_REPO, ".jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == os.path.join(_REPO, ".jax_cache")


def test_smoke_and_bench_refuse_to_run_without_a_chip():
    env = {**os.environ, "RAY_TPU_NUM_TPUS": "0"}
    run = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert run.returncode != 0
    assert run.stdout.strip() == ""  # no result line
    assert "chip" in run.stderr or "accelerator" in run.stderr


def test_smoke_rehearsal_walks_every_phase(tmp_path):
    """So that chip_smoke.py does not rot between chip runs."""
    run = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"),
         "--rehearse-on-cpu"],
        # one CPU device stands in for one chip
        env={**os.environ, "RAY_TPU_LOG_TO_DRIVER": "0", "XLA_FLAGS": ""},
        cwd=tmp_path,
        capture_output=True, text=True, timeout=170,
    )
    lines = run.stdout.strip().splitlines()
    assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-3000:]
    assert lines[0].startswith("REHEARSAL")
    reports = [json.loads(line) for line in lines[1:]]
    assert [r.get("phase") for r in reports[:-1]] == [
        "kernels", "train", "serve", "driver"
    ]
    assert all(r["ok"] for r in reports)
    assert reports[-1] == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }


def test_device_programs_compile_for_a_v5e(aot_compile):
    """Flash attention, the fused cross entropy, prefill chunks of 64,
    128 and 256 rows, the 256-row chunk reading the cache's first half
    and the decode step reading that and every row, of one shard and
    of a pair of shards in one call, none of which
    copies a whole cache leaf, the grouped SwiGLU of a chunk's experts
    at the three routed configurations' published widths (what one call
    takes of their smallest bucket, a slab where a share of the experts
    is held: the kernel there and no ``ragged_dot``), and a one-layer
    train step at LLAMA_BENCH's widths, on one device and on four,
    through Mosaic and the TPU compiler (tests/aot_compile_check.py)."""
    out, _ = aot_compile.communicate(timeout=170)
    if aot_compile.returncode == 77:
        pytest.skip(out.strip())
    assert aot_compile.returncode == 0, out
    assert out.count("\nok  ") + out.startswith("ok  ") == 14, out
    assert out.count("lanes reading") == 3 and out.count("a pair of") == 1
    assert out.count("ok   grouped SwiGLU") == 3, out
