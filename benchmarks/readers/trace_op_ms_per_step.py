"""Device time per traced step of the ops whose name matches
args["pattern"], mean over the chips: the core's summed durations, or
with args["in_flight"] the time in which any such op is under way, on
the core or asynchronously beside it (collectives)."""

from benchmarks import trace_reduce


def read(ctx, args):
    trace, steps = ctx.get("trace"), ctx["samples"].get("traced_steps")
    if trace is None or not steps:
        return None
    if args.get("in_flight"):
        seconds = trace_reduce.in_flight_seconds(trace, args["pattern"])
    else:
        seconds, count = trace_reduce.matching_seconds(trace, args["pattern"])
        if not count:
            return None
    return 1e3 * seconds / steps if seconds else None
