"""The expert matmuls' share of their roofline: the least time the chip
could take for the work that was asked of them in the traced steps
(families/mellum_flops.py, against the chip's row of peaks.json) over
the traced time of the ops under the scope args["scope"]
(``moe_experts``), joined by (program, instruction), and of the ops whose
own name matches args["ops"]: the TPU compiler lowers
``jax.lax.ragged_dot`` to a grouped-matmul kernel and a metadata op of
its own making (``ragged-dot-none.3``, ``ragged-dot-metadata.1``), which
carry that name for a scope path and not the scope they were traced
under. Whatever implements the matmuls (``ragged_dot``, a batched matmul
over every expert, a grouped kernel of the program's own) is read the
same.

What was asked in the traced steps: the harness snapshots the engine's
counters at the window's two ends and not at the trace's (the trace is
the window's last seconds), so the window's ``stats.moe_assignments`` and
``stats.moe_experts_touched`` are brought down to the traced calls by
what the trace itself says of them. The engine's dispatch spans
(args["call_spans"]) carry the ``rows`` each call was somebody's tokens
for (a chunk's tokens, a decode's live lanes: what the counters count);
the assignments go by the traced calls' share of the window's such rows
(``stats.prefill_tokens`` + ``stats.decode_lanes_active``), exactly, and
the experts touched by the traced calls' share of the window's calls
(a decode call of 16 lanes and a chunk touch 55 and 64 of 64 experts a
layer, so a call is the unit that varies least). A call dispatched in
the trace's last milliseconds runs behind its end and one dispatched
before its start runs inside it: one call in the hundred of three
seconds. Nothing where the program has no such counters, spans or scope
maps."""

import re

from benchmarks import flops, spec, trace_programs
from benchmarks.families import mellum_flops

WINDOW = ("stats.moe_assignments", "stats.moe_experts_touched",
          "stats.prefill_tokens", "stats.decode_lanes_active",
          "stats.prefill_chunks", "stats.decode_calls")


def read(ctx, args):
    trace, maps, s = ctx.get("trace"), ctx.get("scopes"), ctx["samples"]
    if trace is None or not trace.chips:
        return None
    if maps is None:
        return spec.NotRead("the engine has no compiled_programs()")
    missing = [k for k in WINDOW[:2] if not s.get(k)] + [
        k for k in WINDOW[2:] if k not in s]
    if missing:
        return spec.NotRead(f"the run recorded no {missing[0]}")
    if "hbm_bytes_per_s" not in (ctx.get("peak") or {}):
        return spec.NotRead("no row of peaks.json for this chip")
    calls = [stats for name, _, _, stats in trace.host_spans
             if name in args["call_spans"]]
    if not all("rows" in stats for stats in calls):
        return spec.NotRead("the engine's dispatch spans carry no rows")
    by_name = re.compile(args["ops"])
    took = trace_programs.scope_seconds(
        trace_programs.of(trace), maps, [args["scope"]],
        also=lambda c, i: bool(by_name.search(c.names[i])))
    rows = sum(int(float(stats["rows"])) for stats in calls)
    if not took or not rows:
        return None
    window_rows = s["stats.prefill_tokens"] + s["stats.decode_lanes_active"]
    window_calls = s["stats.prefill_chunks"] + s["stats.decode_calls"]
    work = mellum_flops.expert_work(
        ctx["cell"]["hp"],
        s["stats.moe_assignments"] * rows / window_rows,
        s["stats.moe_experts_touched"] * len(calls) / window_calls)
    least, _ = flops.least_seconds(work, ctx["peak"])
    return 100.0 * least / took
