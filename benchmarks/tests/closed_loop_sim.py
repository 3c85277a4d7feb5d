"""A closed-loop cell's spread of ``serve_tokens_per_s`` over seeds,
simulated before chip time is spent on it: the engine as two costs read
off one traced run (a prefill chunk call and a decode call, seconds),
the cell's own traffic (``traffic.closed_loop``: the pool, its order and
where ``--seed`` enters it) and the window's rule. Since PR 60 that rule
is ``serve_load.tokens_in_window``'s: every token emitted inside the
window counts, and a request's prompt whole with its first token,
whether or not the request ends inside. Until then a request counted,
prompt and answer, if it completed inside; that count is printed beside
the new one (``old rule``), and the readings quoted below are of it.

    python benchmarks/tests/closed_loop_sim.py <cell> <chunk call s> <decode call s> [<prompt_len.max> ...]

A step is, for each shard, one chunk of its oldest pending prompt and
one decode call if a lane is live, as ``LlamaEngine.step()`` runs them;
clients send their next request when the last is answered. It prints,
for the cell's traffic (or with ``prompt_len.max`` lowered to each value
given), the median tokens/s, the requests completed, and the spread
(quartiles over the median) of 32 sets of six seeds: its median, its
worst, and the share of sets under 3.6 % and under 5 %; then the same
under the old rule. PR 46 sized
``mellum2-12b-a2.5b.serve-ide-mix`` with it (PERF.md section 6): a chip
run of the named traffic read 85 requests completed where this reads 87
at 32 ms a chunk and 13.7 ms a decode, and twelve runs at prompts to
4096 read 3523 tokens/s and 1.79 % over seeds where this reads 3479 and
1.98 % at 30.5 ms a chunk (shorter prompts, a shorter read); it knows nothing of the machine's own run-to-run noise, about
1.2 % of the median there, so at prompts to 2560 the chip's sets of six
spread 3.35 and 4.20 % where this reads 1.8 %. Nothing of a run's result
depends on it."""

import copy
import os
import statistics
import sys

import numpy as np

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), *[os.pardir] * 2)))

from benchmarks import spec, traffic  # noqa: E402

WINDOW_S = 50.0


def run(tr: dict, cell: dict, seed: int, t_chunk: float, t_decode: float):
    """-> (tokens/s, requests completed, tokens/s by the rule until
    PR 60) of one simulated window."""
    sv = cell["serve"]
    lanes = sv["max_batch_size"]
    n_shards = sv.get("engine_kwargs", {}).get("max_slots", lanes) // lanes
    chunk = sv.get("engine_kwargs", {}).get("prefill_chunk", 256)
    requests = traffic.closed_loop(tr, seed)
    shards = [{"prefilling": [], "active": [], "free": lanes}
              for _ in range(n_shards)]
    sent = alive = done = done_tokens = arrived = 0
    t = 0.0

    def admit():
        nonlocal sent, alive
        while alive < tr["clients"]:
            shard = next((s for s in shards if s["free"]), None)
            if shard is None:
                break
            r = requests[sent % len(requests)]
            sent, alive = sent + 1, alive + 1
            shard["free"] -= 1
            shard["prefilling"].append(
                {"left": r.prompt_len, "out": r.max_tokens,
                 "prompt": r.prompt_len,
                 "size": r.prompt_len + r.max_tokens})

    admit()
    while t < WINDOW_S:
        dt = 0.0
        for s in shards:
            if s["prefilling"]:
                p = s["prefilling"][0]
                p["left"] -= min(chunk, p["left"])
                dt += t_chunk
                if p["left"] == 0:      # its first token comes with it
                    s["prefilling"].pop(0)
                    p["out"] -= 1
                    p["first"] = True
                    s["active"].append(p)
            if s["active"]:
                dt += t_decode
        t += max(dt, 0.001)
        for s in shards:
            still = []
            for p in s["active"]:
                if t <= WINDOW_S:       # what this step delivered
                    arrived += 1 + (
                        p["prompt"] if p.pop("first", False) else 0)
                if p["out"] <= 0:
                    if t <= WINDOW_S:
                        done, done_tokens = done + 1, done_tokens + p["size"]
                    s["free"] += 1
                    alive -= 1
                else:
                    p["out"] -= 1
                    still.append(p)
            s["active"] = still
        admit()
    return arrived / WINDOW_S, done, done_tokens / WINDOW_S


def main(argv) -> int:
    cell = spec.load_cell(argv[0], False)
    t_chunk, t_decode = float(argv[1]), float(argv[2])
    for most in [int(a) for a in argv[3:]] or [None]:
        tr = copy.deepcopy(cell["traffic"])
        if most is not None:
            tr["prompt_len"]["max"] = most
        seeds = np.random.default_rng(1).integers(2**31, 2**31 + 10**6, 192)
        runs = [run(tr, cell, int(s), t_chunk, t_decode) for s in seeds]
        for rule, column in (("", 0), (" (old rule)", 2)):
            rates = [r[column] for r in runs]
            spreads = []
            for i in range(0, len(rates), 6):
                q = statistics.quantiles(rates[i:i + 6], n=4)
                spreads.append(
                    (q[2] - q[0]) / statistics.median(rates[i:i + 6]))
            print(f"prompt_len.max {tr['prompt_len']['max']}{rule}: tokens/s "
                  f"{statistics.median(rates):.0f}, completed "
                  f"{statistics.median(r[1] for r in runs):.0f}, spread of a "
                  f"set of six: median {100 * statistics.median(spreads):.1f}"
                  f" % worst {100 * max(spreads):.1f} %, sets under 3.6 %: "
                  f"{sum(s < 0.036 for s in spreads)} of {len(spreads)}, "
                  f"under 5 %: {sum(s < 0.05 for s in spreads)} of "
                  f"{len(spreads)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
