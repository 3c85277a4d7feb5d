"""Runs one cell of the benchmark once and prints its result.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A fresh process per run. It starts the system through its normal entry
points (``JaxTrainer.fit()`` / ``serve.run(build_llm_app(...))``), in
workers that hold the cell's chips; this process never initialises a
JAX backend. No chip, or fewer than the cell asks for, is exit 1 and no
result. The last line of stdout is the result, checked against the
contract (contract.py) before it is printed; earlier lines are notes.

``--rehearse`` walks the same code at the cell's toy preset on the CPU
(four virtual devices for a four-chip cell) to the same validated last
line, with ``"correct": false`` and platform ``cpu``: it debugs the
harness and can never pass for a result.
"""

from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import ctypes  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the checkout, not this directory, leads the path: workers import
# ``benchmarks.<module>`` and ``ray_tpu`` by name
sys.path[:] = [ROOT] + [p for p in sys.path if os.path.abspath(p or ".") != HERE]


def _note(label: str, value) -> None:
    print(json.dumps({label: value}), flush=True)


def _stop_descendants() -> list:
    """Kills and reaps whatever is still below this process and returns
    what it found, with each one's state ("Z" is a zombie: a process
    that has ended and that nobody has waited for yet)."""
    me, found = os.getpid(), []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                state, ppid = f.read().rsplit(")", 1)[1].split()[:2]
            with open(f"/proc/{name}/cmdline") as f:
                cmd = f.read().replace("\0", " ").strip()
        except (OSError, ValueError):
            continue  # gone while we looked
        if int(ppid) == me:
            found.append({"pid": int(name), "state": state, "cmd": cmd[:120]})
    for p in found:
        try:
            os.kill(p["pid"], signal.SIGKILL)
            os.waitpid(p["pid"], 0)
        except OSError:
            pass
    return found + (_stop_descendants() if found else [])


def _backend_initialised() -> bool:
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return bool(xla_bridge.backends_are_initialized())


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--rehearse", action="store_true",
                        help="toy preset on the CPU; never a result")
    args = parser.parse_args()
    args.process_start = PROCESS_START

    from benchmarks import contract, spec

    spec.apply_environment()
    cell = spec.load_cell(args.workload, args.rehearse)
    chips = cell["chips"]
    if args.rehearse:
        print("REHEARSAL on the CPU at a toy preset: this exercises the "
              "harness and says nothing about the chip.", flush=True)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={chips}")
    try:
        import ray_tpu
    except ImportError as e:
        print(f"benchmarks/run.py needs the repo it measures: {e}",
              file=sys.stderr)
        return 1
    # orphans of workers are handed to this process, so it can see them
    ctypes.CDLL(None, use_errno=True).prctl(36, 1, 0, 0, 0)
    run_cell = spec.kind_of(cell).run
    end_to_end = spec.cell_metrics(args.workload, traced=False)
    per_layer = spec.cell_metrics(args.workload, traced=True)

    if args.rehearse:
        ray_tpu.init(num_tpus=0)
    else:
        ray_tpu.init()
        found = int(ray_tpu.cluster_resources().get("TPU", 0))
        if found < chips:
            ray_tpu.shutdown()
            _stop_descendants()
            print(f"benchmarks/run.py: cell {args.workload} needs {chips} "
                  f"TPU chip(s) and this machine has {found}; there is no "
                  "fallback (--rehearse debugs the harness on the CPU)",
                  file=sys.stderr)
            return 1
    try:
        result = run_cell(cell, args, per_layer)
    finally:
        ray_tpu.shutdown()
        left_behind = _stop_descendants()

    checks = dict(result["checks"])
    checks["runner_never_opened_a_device"] = not _backend_initialised()
    # A chip holder that dies of SIGTERM after the hub has gone is handed
    # to this process (the subreaper) as a zombie: it has ended, and the
    # sweep above has reaped it. Only a process still alive was left
    # running. The worker that held four chips ends so (PR 23).
    alive = [p for p in left_behind if p["state"] != "Z"]
    checks["nothing_left_running"] = not alive
    device = result["device"]
    checks["on_the_chips_asked_for"] = (
        device["count"] == chips
        and device["platform"] == ("cpu" if args.rehearse else "tpu"))
    samples = result["samples"]
    samples["setup_s"] = result["window_start_wall"] - PROCESS_START
    # A per-layer metric whose reader found nothing to read (a counter
    # or a scope this build of the program lacks, a kernel a CPU
    # rehearsal cannot trace) is left out of the line and named here,
    # with the reader's reason; an end-to-end metric is never optional.
    if args.trace:
        metrics, declared = result["per_layer"], per_layer
        not_read = result["metrics_not_read"]
    else:
        metrics, not_read = spec.evaluate(end_to_end, {
            "cell": cell, "chips": chips, "samples": samples, "trace": None,
            "peak": spec.peak_for(device["kind"], args.rehearse)})
        declared = end_to_end
    _note("notes", result["notes"])
    _note("checks", checks)
    if not_read:
        _note("metrics_not_read", not_read)
    if left_behind:
        _note("found_below_after_shutdown", left_behind)
    last = {
        "correct": all(checks.values()) and not args.rehearse,
        "attempted": result["attempted"], "failed": result["failed"],
        "metrics": metrics, "device": device,
    }
    if args.trace and result["breakdown"]:
        last["breakdown"] = result["breakdown"]
    # each number `correct` compared, beside its limit: last on the
    # line, and the last lines of standard error
    compared = {
        name: [x if math.isfinite(x) else None for x in pair]   # valid JSON
        for name, pair in result["notes"].get("compared", {}).items()}
    if compared:
        last["compared"] = compared
    problems = contract.check_last_line(
        last, {n: m["unit"] for n, m in declared.items()},
        traced=bool(args.trace), chips=chips,
        optional=set(not_read) if args.trace else frozenset())
    if problems:
        _note("refused_last_line", last)
        for p in problems:
            print(f"benchmarks/run.py: malformed result: {p}", file=sys.stderr)
            _note("malformed", p)
        return 1
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value!r} limit {limit!r}", file=sys.stderr)
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
