"""Shared test fixtures.

Pattern from the reference's conftest (python/ray/tests/conftest.py:580
ray_start_regular, :497 shutdown_only): tests run against a real
single-node runtime. JAX tests run on a virtual 8-device CPU mesh so
multi-chip sharding logic is exercised without TPU hardware (the
reference's analogue: fake NCCL groups / CPUCommunicator,
python/ray/experimental/channel/cpu_communicator.py).
"""

import os

# Must be set before jax is imported anywhere in the test process.
# Hard-set (not setdefault): on a machine with chips the environment may
# name the TPU, and tests pin this process and the drivers it spawns to
# the virtual 8-device CPU mesh. (Workers need no pinning: they start on
# the CPU backend unless they are given chips.)
os.environ["JAX_PLATFORMS"] = "cpu"
# Persistent compilation cache shared by the test process AND every
# spawned worker process (env inherits): each worker would otherwise
# re-jit identical tiny programs, which dominates suite wall time on
# this 1-core box. The dir is keyed by a host fingerprint: XLA:CPU AOT
# artifacts embed the compile machine's CPU features, and loading a
# cache populated on a different host (e.g. a container snapshot moved
# between machines) spews per-program feature-mismatch errors and
# recompiles — slower than no cache at all.


def _host_cache_dir() -> str:
    import hashlib
    import platform

    try:
        with open("/proc/cpuinfo") as f:
            flags = next(
                (ln for ln in f if ln.startswith("flags")), platform.processor()
            )
    except OSError:
        flags = platform.processor()
    fp = hashlib.sha256(
        (platform.machine() + str(flags)).encode()
    ).hexdigest()[:12]
    return f"/tmp/ray_tpu_jax_test_cache_{fp}"


os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _host_cache_dir())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )
os.environ.setdefault("RAY_TPU_NUM_TPUS", "0")
# XLA:CPU's AOT cache loader logs a full ERROR line per cached program
# whose embedded "machine features" include XLA's own tuning pseudo-
# features (+prefer-no-scatter/+prefer-no-gather) — harmless (it just
# recompiles) but it floods test logs. 3 = fatal-only for TSL/XLA logs.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import signal

import pytest

# Per-test wall-clock cap (reference parity: pytest.ini timeout=180).
# SIGALRM-based so no extra dependency; pytest runs tests in the main
# thread, where the alarm is deliverable.
TEST_TIMEOUT_S = int(os.environ.get("RAY_TPU_TEST_TIMEOUT", "180"))


def _alarm_wrapped(phase):
    @pytest.hookimpl(hookwrapper=True)
    def hook(item):
        def _handler(signum, frame):
            raise TimeoutError(
                f"test {phase} exceeded {TEST_TIMEOUT_S}s timeout "
                f"(RAY_TPU_TEST_TIMEOUT)"
            )

        old = signal.signal(signal.SIGALRM, _handler)
        signal.alarm(TEST_TIMEOUT_S)
        try:
            yield
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)

    return hook


# Cover setup and teardown too — a hang in ray_tpu.init inside a fixture
# must be killed just like a hang in the test body (pytest-timeout parity).
pytest_runtest_setup = _alarm_wrapped("setup")
pytest_runtest_call = _alarm_wrapped("call")
pytest_runtest_teardown = _alarm_wrapped("teardown")


def pytest_configure(config):
    # tier-1 runs with -m 'not slow' under a hard suite-level timeout
    # (ROADMAP.md); "slow" marks long soaks and convergence tests that
    # stay runnable via a plain `pytest tests/` invocation.
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 timed suite"
    )


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    ctx = ray_tpu.init(num_cpus=2, max_workers=2, ignore_reinit_error=True)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_4_cpus():
    import ray_tpu

    ctx = ray_tpu.init(num_cpus=4, max_workers=4, ignore_reinit_error=True)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only():
    import ray_tpu

    yield None
    ray_tpu.shutdown()
