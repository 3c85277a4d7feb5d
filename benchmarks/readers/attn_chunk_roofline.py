"""The chunk-form attention's share of its roofline: the least time the
chip could take for the attention that was asked of the traced chunk
calls (families/cohere_flops.py, against the chip's row of peaks.json)
over the traced time of the ops under the scopes args["scopes"]
(``attn_window``, ``attn_cached``) IN THE PREFILL PROGRAMS, joined by
(program, instruction): the kernel of ``ops/pallas_chunk_attention.py``
and what is made around it (the queries turned head-major, the table of
visible blocks). A decode call's attention carries the same scopes in
its own program and is not read here. Whatever implements the chunk
form is read the same: the work is what a call asks for, a visible
pair, a key row attended.

What was asked is read off the traced calls themselves, as
``latent_attention_roofline`` reads it: the engine's prefill dispatch
spans (args["call_span"]) carry the ``rows`` a call was somebody's
tokens for and the row they ``start`` at. Nothing where the program has
no such spans or scope maps (a program from before the family), or the
configuration is none of this family's."""

from benchmarks import flops, spec, trace_programs
from benchmarks.families import cohere_flops


def read(ctx, args):
    trace, maps = ctx.get("trace"), ctx.get("scopes")
    if trace is None or not trace.chips:
        return None
    if maps is None:
        return spec.NotRead("the engine has no compiled_programs()")
    if "hbm_bytes_per_s" not in (ctx.get("peak") or {}):
        return spec.NotRead("no row of peaks.json for this chip")
    hp = ctx["cell"]["hp"]
    if "sliding_window" not in hp or "layer_types" not in hp:
        return spec.NotRead("the configuration has no window layers")
    calls = [stats for name, _, _, stats in trace.host_spans
             if name == args["call_span"]]
    if not all("start" in stats and "rows" in stats for stats in calls):
        return spec.NotRead("the engine's dispatch spans carry no 'start'")
    chunks = {k: v for k, v in maps.items() if k.startswith("prefill")}
    took = trace_programs.scope_seconds(
        trace_programs.of(trace), chunks, args["scopes"])
    if not took or not calls:
        return None
    work = cohere_flops.chunk_attention_work(
        hp, [(int(float(s["rows"])), int(float(s["start"]))) for s in calls])
    least, _ = flops.least_seconds(work, ctx["peak"])
    return 100.0 * least / took
