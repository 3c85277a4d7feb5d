"""An indexed layer's new pieces' shares of their rooflines, one at a time
(args["form"]): ``index``, the indexer's score in both kinds of call;
``selected_prefill``, a chunk's attention over the rows its rows
selected; ``selected_decode``, a decode lane's over its selected rows.
The least time the chip could take for what the traced calls asked for
(families/deepseek_flops.py, against the chip's row of peaks.json) over the
traced time of the ops under the scopes args["scopes"], joined by
(program, instruction), whatever implements them.

What was asked is read off the traced calls' dispatch spans, as
``latent_attention_roofline`` reads it: a chunk call carries the
``rows`` it was somebody's tokens for and the row they ``start`` at (its
n live rows then score ``n * start + n (n + 1) / 2`` pairs against
``start + n`` index keys, and row t of them attends to ``min(t + 1,
index_topk)`` rows of the ``start + n`` latent rows), a decode call its
live lanes (``rows``) and the rows they see all together (``attended``:
what the indexer scores; of them the lanes attend to ``min(attended,
rows x index_topk)``, which is exact where every live lane is past
``index_topk`` rows, as in a cell whose prompts are), each in every layer. Nothing where the program has no such spans or scope maps."""

from benchmarks import flops, spec, trace_programs
from benchmarks.families import deepseek_flops

PREFILL, DECODE = "ray_tpu.llm.prefill_dispatch", "ray_tpu.llm.decode_dispatch"


def _chunks(calls):
    for stats in calls:
        yield int(float(stats["rows"])), int(float(stats["start"]))


def index(hp, chunks, decodes):
    layers = hp["num_hidden_layers"]
    pairs = sum(n * start + n * (n + 1) // 2 for n, start in _chunks(chunks))
    rows = sum(start + n for n, start in _chunks(chunks))
    seen = sum(int(float(stats["attended"])) for stats in decodes)
    return deepseek_flops.index_work(hp, layers * (pairs + seen),
                                 layers * (rows + seen))


def selected_prefill(hp, chunks, decodes):
    k, layers = hp["index_topk"], hp["num_hidden_layers"]
    pairs = rows = 0
    for n, start in _chunks(chunks):
        # rows at positions start .. start + n - 1, each min(t + 1, k)
        under = max(0, min(k - start, n))       # rows that see under k
        pairs += under * start + under * (under + 1) // 2 + (n - under) * k
        rows += start + n
    return deepseek_flops.selected_prefill_work(hp, layers * pairs, layers * rows)


def selected_decode(hp, chunks, decodes):
    k, layers = hp["index_topk"], hp["num_hidden_layers"]
    rows = sum(min(int(float(s["attended"])), int(float(s["rows"])) * k)
               for s in decodes)
    return deepseek_flops.selected_decode_work(hp, layers * rows)


# form: (the work of the traced calls, which kinds of call it reads)
FORMS = {"index": (index, (PREFILL, DECODE)),
         "selected_prefill": (selected_prefill, (PREFILL,)),
         "selected_decode": (selected_decode, (DECODE,))}
CARRIED = {PREFILL: "start", DECODE: "attended"}


def read(ctx, args):
    trace, maps = ctx.get("trace"), ctx.get("scopes")
    if trace is None or not trace.chips:
        return None
    if maps is None:
        return spec.NotRead("the engine has no compiled_programs()")
    if "hbm_bytes_per_s" not in (ctx.get("peak") or {}):
        return spec.NotRead("no row of peaks.json for this chip")
    hp = ctx["cell"]["hp"]
    if "index_topk" not in hp:
        return spec.NotRead("the configuration has no indexer")
    work_of, spans = FORMS[args["form"]]
    calls = {span: [stats for name, _, _, stats in trace.host_spans
                    if name == span] for span in (PREFILL, DECODE)}
    for span in spans:
        if not all(CARRIED[span] in s and "rows" in s for s in calls[span]):
            return spec.NotRead(
                f"the engine's dispatch spans carry no {CARRIED[span]!r}")
    took = trace_programs.scope_seconds(
        trace_programs.of(trace), maps, args["scopes"])
    if not took or not any(calls[span] for span in spans):
        return None
    least, _ = flops.least_seconds(
        work_of(hp, *(calls[s] if s in spans else [] for s in (PREFILL, DECODE))),
        ctx["peak"])
    return 100.0 * least / took
