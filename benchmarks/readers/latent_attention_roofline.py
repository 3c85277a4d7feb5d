"""Latent attention's share of its roofline, one form at a time
(args["form"]: ``prefill``, a chunk call's expanded form; ``decode``,
the absorbed form): the least time the chip could take for the
attention that was asked of the traced calls
(families/pangu_flops.py, against the chip's row of peaks.json) over
the traced time of the ops under the scopes args["scopes"], joined by
(program, instruction). Whatever implements the form (a loop over
blocks of cache rows in ``jax.numpy``, a kernel, the other form) is
read the same: the work is what a call asks for, a visible pair, a
latent row attended, and not what the implementation does for it.

What was asked is read off the traced calls themselves: the engine's
dispatch spans (args["call_span"]) carry, beside the ``rows`` a call
was somebody's tokens for, the row a chunk's tokens ``start`` at (its
n live rows then see ``n * start + n (n + 1) / 2`` pairs and attend to
``start + n`` latent rows, in every layer) and the rows a decode call's
live lanes attend to, all together (``attended``, a layer). The
window's counters (``stats.attn_pairs_prefill`` and the like, which
``models/latent_moe.py`` sums on the device) are not brought down to
the trace by the traced rows' share: a chunk at row 7936 sees thirty
times the pairs of one at row 0, and three traced seconds whose chunks
lie early or late in their prompts read 30 % high or low that way
(104 % was read so, PERF.md section 6, PR 48). A call dispatched in the
trace's last milliseconds runs behind its end and one dispatched before
its start runs inside it: one call in the hundred of three seconds.
Nothing where the program has no such spans or scope maps, as on a
program from before the family."""

from benchmarks import flops, spec, trace_programs
from benchmarks.families import pangu_flops


def prefill_work(hp, calls):
    layers = hp["num_hidden_layers"]
    pairs = rows = 0
    for stats in calls:
        n, start = int(float(stats["rows"])), int(float(stats["start"]))
        pairs += n * start + n * (n + 1) // 2
        rows += start + n
    return pangu_flops.prefill_work(hp, layers * pairs, layers * rows)


def decode_work(hp, calls):
    return pangu_flops.decode_work(
        hp, hp["num_hidden_layers"] * sum(
            int(float(stats["attended"])) for stats in calls))


# form: (what its dispatch span has to carry, the traced calls' work)
FORMS = {"prefill": ("start", prefill_work), "decode": ("attended", decode_work)}


def read(ctx, args):
    trace, maps = ctx.get("trace"), ctx.get("scopes")
    if trace is None or not trace.chips:
        return None
    if maps is None:
        return spec.NotRead("the engine has no compiled_programs()")
    if "hbm_bytes_per_s" not in (ctx.get("peak") or {}):
        return spec.NotRead("no row of peaks.json for this chip")
    carried, work_of = FORMS[args["form"]]
    calls = [stats for name, _, _, stats in trace.host_spans
             if name == args["call_span"]]
    if not all(carried in stats and "rows" in stats for stats in calls):
        return spec.NotRead(
            f"the engine's dispatch spans carry no {carried!r}")
    took = trace_programs.scope_seconds(
        trace_programs.of(trace), maps, args["scopes"])
    if not took or not calls:
        return None
    least, _ = flops.least_seconds(work_of(ctx["cell"]["hp"], calls),
                                   ctx["peak"])
    return 100.0 * least / took
