"""The flash kernels' share of their roofline: for every traced call,
the least time its FLOPs and bytes allow on this chip (flops.py, peaks
table) over the time it took, summed over the three kernels."""

import re

from benchmarks import flops, trace_reduce


def read(ctx, args):
    trace = ctx.get("trace")
    if trace is None:
        return None
    s = ctx["samples"]
    batch = s["seqs_per_step"] // ctx["chips"]  # what one chip's kernel sees
    least = took = 0.0
    for kernel in flops.FLASH_MATMULS:
        seconds, calls = trace_reduce.matching_seconds(
            trace, re.compile(rf"^{kernel}(\.\d+)?$"))
        if not calls:
            continue
        work = flops.flash_call(kernel, batch, s["seq"], ctx["cell"]["hp"])
        least += calls * flops.least_seconds(work, ctx["peak"])[0]
        took += seconds
    return 100.0 * least / took if took else None
