"""What only the process that holds the chips can do: say what it runs
on, count compilations, and take and reduce a profiler trace. Used by
the train loop (in the JaxTrainer worker) and by the server subclass
(in the replica); the runner process never imports jax through here.
"""

from __future__ import annotations

import shutil
import tempfile
import time

from . import trace_reduce

# a program being lowered for the backend; fires on a persistent-cache
# hit as well, so it also catches a program first loaded in the window
_COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def device_info(rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        raise RuntimeError(
            f"the benchmark measures a TPU and this process got {devices}; "
            "there is no fallback")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """Largest peak_bytes_in_use over the chips. It follows live buffers,
    not a program's temporaries (PERF.md, open questions). The CPU
    backend of the rehearsal reports none: 1 stands in."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks)) or 1


class CompileCounter:
    """Counts programs lowered in this process from now on."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == _COMPILE_EVENT:
            self.count += 1


class Tracer:
    """One traced part of a run. ``window_s`` is the host-clock length
    between start and stop; the device cannot have been busy longer."""

    def __init__(self):
        self._dir = None
        self._t0 = 0.0
        self.window_s = 0.0

    def start(self) -> None:
        import jax

        self._dir = tempfile.mkdtemp(prefix="bench_trace_")  # under TMPDIR
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # no per-call Python events
        opts.host_tracer_level = 2     # our TraceAnnotations
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()

    def reduce(self) -> trace_reduce.Trace:
        try:
            trace = trace_reduce.load(trace_reduce.find_xplane(self._dir))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        # an op that began before start_trace returned can stretch the
        # device's span past the host's window by a hair
        trace.window_s = max(self.window_s, trace_reduce.span_seconds(trace))
        return trace


def traced_device_block(trace: trace_reduce.Trace) -> dict:
    return {"window_s": trace.window_s,
            "busy_s": trace_reduce.busy_seconds(trace)}
