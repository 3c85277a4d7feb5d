"""Declare PR 39's serve-stack metrics in a tree's BENCHMARK.json.

    python _scratch/declare_serve_stack.py [<tree>] [--transit]

The metric files are committed under ``benchmarks/metrics/``; their
entries are not, because ``benchmarks/tests/test_doors.py`` holds every
llama serving cell to ``tests/engine_stats_pair.json``, recorded with an
empty ``loop_phases`` (PERF.md section 7). This appends the entries to
``<tree>/BENCHMARK.json`` (default: the tree this file lies in), for a
scratch copy to read the metrics on the chip through ``run.py --trace 1``.
Run twice it adds nothing twice.

``--transit`` also gives the scratch copy what only the consumer's
process can read: it patches ``<tree>/benchmarks/serve_load.py`` to put
the deltas of ``stream.stream_stats()`` between the window's start and
its last answer into ``samples`` (``phase_s.serve.stream_transit`` ...)
and the mean and median of each request phase into the notes
(``request_ms``, ``ttft_ms_mean``),
writes ``stream_transit_ms.*`` / ``stream_first_transit_ms.*`` metric
files and declares them, and patches ``holder.py`` to append the traced
``.xplane.pb``'s size to ``/root/repo/chiprun_out/xplane_sizes.jsonl``
(what the chip tool brings back). On a program from before
``stream_stats()`` the patched files read nothing and do not raise. Never in the committed tree: a ``tracing`` PR
edits no file the benchmark has.
"""

import json
import os
import sys

CHAT, OVER, DOCS = ("internlm2-1.8b.serve-chat",
                    "internlm2-1.8b.serve-chat-over",
                    "mistral-7b-v0.3.serve-docbatch")
CELLS = {"chat": CHAT, "over": OVER, "docs": DOCS}
MOVES = {"chat": "ttft_p95_ms", "over": "serve_tokens_per_s",
         "docs": "serve_tokens_per_s"}
# (metric, suffixes, unit, what it moves on serve-chat)
DECLARED = [
    ("ingress_ms", ("chat", "over", "docs"), "ms", "ttft_p95_ms"),
    ("accept_ms", ("chat",), "ms", "ttft_p95_ms"),
    ("first_token_handoff_ms", ("chat", "over"), "ms", "ttft_p95_ms"),
    ("token_handoff_ms", ("chat", "over"), "ms", "itl_p95_ms"),
    ("idle_sleep_share_pct", ("chat",), "%", "ttft_p95_ms"),
]
TRANSIT = [
    ("stream_transit_ms", ("chat", "over", "docs"), "ms", "itl_p95_ms"),
    ("stream_first_transit_ms", ("chat", "over", "docs"), "ms",
     "ttft_p95_ms"),
]
# serve_load.py, as PR 33 left it: where the window opens, and where
# the client's samples are put together
OPEN = '''        def on_window():
            call("begin_window")
'''
OPEN_PATCHED = '''        def on_window():
            call("begin_window")
            window["stream_stats"] = _stream_stats(stream)
'''
SAMPLES = '''        "ttft_ms": ttft, "itl_ms": itl,
    }
'''
SAMPLES_PATCHED = '''        "ttft_ms": ttft, "itl_ms": itl,
    }
    from .holder import phase_deltas
    samples.update(phase_deltas(window["stream_stats"],
                                _stream_stats(stream)))
    # means beside the medians, for the account of a first token's time
    notes["request_ms"] = {
        k: {"mean": statistics.fmean(v), "median": statistics.median(v)}
        for k, v in samples.items() if k.startswith("req.") and v}
    notes["ttft_ms_mean"] = statistics.fmean(ttft) if ttft else None
    # every span's mean, untraced runs too (a metric is read only traced)
    notes["phase_ms"] = {
        k[len("phase_s."):]: 1e3 * v / samples["phase_n." + k[len("phase_s."):]]
        for k, v in samples.items()
        if k.startswith("phase_s.")
        and samples.get("phase_n." + k[len("phase_s."):])}
    notes["generator_late_ms_mean"] = statistics.fmean(late)
'''
# a handle from before stream_stats() answers ANY name with a method
# caller, so the class is asked, not the handle: {} there, nothing read
HELPER_AT = '''def _sleep_until(t: float) -> None:
'''
HELPER = '''def _stream_stats(stream) -> dict:
    read = getattr(type(stream), "stream_stats", None)
    return read(stream) if read else {}


'''
# holder.py: the traced file's size, before the reduction removes it
REDUCE = '''            trace = trace_reduce.load(trace_reduce.find_xplane(self._dir))
'''
REDUCE_PATCHED = '''            import json, os
            xplane = trace_reduce.find_xplane(self._dir)
            os.makedirs("/root/repo/chiprun_out", exist_ok=True)
            with open("/root/repo/chiprun_out/xplane_sizes.jsonl", "a") as f:
                f.write(json.dumps({"tree": os.getcwd(), "at": time.time(),
                                    "xplane_bytes": os.path.getsize(xplane),
                                    "traced_s": self.window_s}) + "\\n")
            trace = trace_reduce.load(xplane)
'''


# scratch-only too: the suffixes ISSUE 39's table leaves out, so that
# every serving cell's time to first token can be accounted for in full
# (span, metric, suffixes, what it moves on serve-chat)
REST = [
    ("llm.accept", "accept_ms", ("over", "docs"), "ttft_p95_ms"),
    ("llm.first_token_handoff", "first_token_handoff_ms", ("docs",),
     "ttft_p95_ms"),
    ("llm.token_handoff", "token_handoff_ms", ("docs",), "itl_p95_ms"),
]


def entries(table):
    for metric, suffixes, unit, chat_moves in table:
        for sfx in suffixes:
            yield {"name": f"{metric}.{sfx}", "unit": unit,
                   "better": "lower", "source": "program_span",
                   "layer": "serve stack",
                   "moves": chat_moves if sfx == "chat" else MOVES[sfx],
                   "workloads": [CELLS[sfx]]}


def transit_files(tree):
    spans = [(f"serve.{m[:-len('_ms')]}", m, sfxs, moves)
             for m, sfxs, _, moves in TRANSIT] + REST
    for span, metric, suffixes, chat_moves in spans:
        for sfx in suffixes:
            name = f"{metric}.{sfx}"
            with open(os.path.join(tree, "benchmarks", "metrics",
                                   name + ".json"), "w") as f:
                json.dump({
                    "name": name, "reader": "quotient",
                    "args": {"num": f"phase_s.{span}",
                             "den": f"phase_n.{span}",
                             "scale": 1000.0},
                    "layer": "serve stack",
                    "moves": chat_moves if sfx == "chat" else MOVES[sfx],
                    "what": f"mean milliseconds of {span}: seconds / "
                            "count over the window (scratch only)"},
                    f, indent=2)


def main(argv):
    transit = "--transit" in argv
    argv = [a for a in argv if a != "--transit"]
    tree = argv[0] if argv else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    have = {m["name"] for m in bench["per_layer"]}
    table = DECLARED + (TRANSIT + [
        (m, sfxs, "ms", moves) for _, m, sfxs, moves in REST]
        if transit else [])
    added = [e for e in entries(table) if e["name"] not in have]
    bench["per_layer"] += added
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
        f.write("\n")
    if transit:
        transit_files(tree)
        load = os.path.join(tree, "benchmarks", "serve_load.py")
        with open(load) as f:
            text = f.read()
        if OPEN_PATCHED not in text:
            assert OPEN in text and SAMPLES in text, "serve_load.py moved"
            assert HELPER_AT in text, "serve_load.py moved"
            text = text.replace(OPEN, OPEN_PATCHED).replace(
                SAMPLES, SAMPLES_PATCHED).replace(
                HELPER_AT, HELPER + HELPER_AT)
            with open(load, "w") as f:
                f.write(text)
        hold = os.path.join(tree, "benchmarks", "holder.py")
        with open(hold) as f:
            text = f.read()
        if REDUCE_PATCHED not in text:
            assert REDUCE in text, "holder.py moved"
            with open(hold, "w") as f:
                f.write(text.replace(REDUCE, REDUCE_PATCHED))
    print(f"{path}: +{len(added)} entries", [e["name"] for e in added])


if __name__ == "__main__":
    main(sys.argv[1:])
