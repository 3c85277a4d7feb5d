"""Per traced step, the time in which an op matching args["pattern"]
runs on a chip and no other op does (mean over the chips)."""

from benchmarks import trace_reduce


def read(ctx, args):
    trace, steps = ctx.get("trace"), ctx["samples"].get("traced_steps")
    if trace is None or not steps:
        return None
    if not trace_reduce.in_flight_seconds(trace, args["pattern"]):
        return None
    return 1e3 * trace_reduce.exposed_seconds(trace, args["pattern"]) / steps
