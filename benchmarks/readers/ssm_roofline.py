"""The selective scan's share of its roofline, one form at a time
(args["form"]: ``chunk``, a prefill call's rows of one sequence;
``step``, a decode call's one row a lane): the least time the chip could
take for the scans that were asked of the traced calls
(families/jamba_flops.py, which says in words where the scope's edge
lies, against the chip's row of peaks.json) over the traced time of the
ops under the scopes args["scopes"], joined by (program, instruction).
Whatever implements the scope (a kernel, a loop over rows in
``jax.numpy``, an associative scan) is read the same: the work is what a
call asks for and not what the implementation does for it.

What was asked is read off the traced calls themselves: the engine's
dispatch spans (args["call_span"]) carry the ``rows`` a call was
somebody's tokens for, a chunk's real tokens or a decode's live lanes,
in every state layer. A padded row and an idle lane are computed too,
and an implementation may leave them out: they are not asked for, so the
count is a floor. Nothing where the program has no such spans or scope
maps, as on a program from before the family."""

from benchmarks import flops, spec, trace_programs
from benchmarks.families import jamba_flops

FORMS = {"chunk": jamba_flops.chunk_call, "step": jamba_flops.step_call}


def read(ctx, args):
    trace, maps = ctx.get("trace"), ctx.get("scopes")
    if trace is None or not trace.chips:
        return None
    if maps is None:
        return spec.NotRead("the engine has no compiled_programs()")
    if "hbm_bytes_per_s" not in (ctx.get("peak") or {}):
        return spec.NotRead("no row of peaks.json for this chip")
    calls = [stats for name, _, _, stats in trace.host_spans
             if name == args["call_span"]]
    if not all("rows" in stats for stats in calls):
        return spec.NotRead("the engine's dispatch spans carry no 'rows'")
    took = trace_programs.scope_seconds(
        trace_programs.of(trace), maps, args["scopes"])
    if not took or not calls:
        return None
    hp, one_call = ctx["cell"]["hp"], FORMS[args["form"]]
    work = {"flops": 0.0, "bytes": 0.0}
    for stats in calls:
        for key, value in one_call(hp, int(float(stats["rows"]))).items():
            work[key] += jamba_flops.state_layers(hp) * value
    least, _ = flops.least_seconds(work, ctx["peak"])
    return 100.0 * least / took
