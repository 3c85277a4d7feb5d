"""PR 39's chip runs, one plan a call: ``python _scratch/pr39_runs.py <plan>``.

Each run is ``python3 benchmarks/run.py`` in a fresh process from the
root of one of the trees under ``.bench_checkout/`` (``parent``: the
parent commit; ``change``: ``git archive $(git write-tree)``;
``final``: the same after the clean-up;
``parent_decl`` / ``change_decl`` / ``final_decl``: the same with
``_scratch/declare_serve_stack.py --transit`` applied, so the traced line
holds the serve-stack metrics and ``serve.stream_transit``). This
process never imports jax. Every run's last line and notes go to
``chiprun_out/pr39/<plan>.jsonl``; a short table is printed.
"""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAT, OVER, DOCS, TRAIN = ("internlm2-1.8b.serve-chat",
                           "internlm2-1.8b.serve-chat-over",
                           "mistral-7b-v0.3.serve-docbatch",
                           "internlm2-1.8b.train-2k")
SEED0 = 2147495000
# PR39_REHEARSE=1: the same plan at toy presets on the CPU, to try this
# script before a chip call; never a result
REHEARSE = (["--seconds", "6", "--rehearse"]
            if os.environ.get("PR39_REHEARSE") else ["--seconds", "50"])


def pairs(cell, first_seed, n, sides=("parent", "change")):
    """n pairs on seeds of their own, the sides alternating which goes
    first."""
    out = []
    for i in range(n):
        order = sides if i % 2 == 0 else sides[::-1]
        out += [(tree, cell, first_seed + i, 0) for tree in order]
    return out


PLANS = {
    # the table's readings and the traced cost: the parent once a cell,
    # the change on three seeds a cell
    "traced": [
        (tree, cell, SEED0 + 100 * k + max(i - 1, 0), 1)   # a pair, then two
        for k, cell in enumerate((CHAT, OVER, DOCS))
        for i, tree in enumerate(("parent_decl", "change_decl",
                                  "change_decl", "change_decl"))
    ],
    "chat": pairs(CHAT, SEED0 + 1000, 6),
    # from here on the change is ``final``: the committed files alone
    # (``git archive $(git write-tree)``) after the clean-up
    "over": pairs(OVER, SEED0 + 2000, 6, ("parent", "final")),
    # untraced pairs whose notes hold every phase's mean on both sides
    "chat2": pairs(CHAT, SEED0 + 6000, 6, ("parent_decl", "final_decl")),
    # more starts of a docbatch replica (one of `rest`'s warmed up late)
    "docs": pairs(DOCS, SEED0 + 7000, 4, ("parent", "final")),
    "rest": pairs(TRAIN, SEED0 + 3000, 1, ("parent", "final"))
            + pairs(DOCS, SEED0 + 4000, 1, ("parent", "final"))
            + pairs(CHAT, SEED0 + 5000, 2, ("parent", "final"))
            + [("final_decl", CHAT, SEED0 + 5100, 1),
               ("final_decl", OVER, SEED0 + 5200, 1),
               ("final_decl", DOCS, SEED0 + 5300, 1)],
}


def numbers(objs):
    """The run's last line, and the notes before it merged."""
    last, notes = objs[-1], {}
    for o in objs[:-1]:
        notes.update(o.get("notes", o))
    return last, notes


def main(plan):
    out_dir = os.path.join(ROOT, "chiprun_out", "pr39")
    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    # one cache for both sides: the programs are the same, so after the
    # first run of a shape both sides load it
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    with open(os.path.join(out_dir, plan + ".jsonl"), "a") as log:
        for tree, cell, seed, trace in PLANS[plan]:
            t0 = time.time()
            proc = subprocess.run(
                ["python3", "benchmarks/run.py", "--workload", cell,
                 "--seed", str(seed), "--trace", str(trace)] + REHEARSE,
                cwd=os.path.join(ROOT, ".bench_checkout", tree), env=env,
                capture_output=True, text=True)
            objs = []
            for line in proc.stdout.splitlines():
                try:
                    objs.append(json.loads(line))
                except ValueError:
                    pass
            row = {"tree": tree, "cell": cell, "seed": seed, "trace": trace,
                   "rc": proc.returncode, "took_s": round(time.time() - t0, 1)}
            if proc.returncode or not objs:
                row["stderr"] = proc.stderr[-3000:]
                print(json.dumps(row)[:3000], flush=True)
                log.write(json.dumps(row) + "\n")
                continue
            last, notes = numbers(objs)
            row["last"] = last
            row["notes"] = {k: notes.get(k) for k in (
                "ttft_ms_median", "ttft_ms_p95", "itl_ms_median",
                "itl_ms_p95", "generator_late_ms_median",
                "generator_late_ms_max", "engine_step_ms_median",
                "completed_in_window", "requests_in_window",
                "metrics_not_read", "setup_phases_s", "request_ms",
                "phase_ms", "ttft_ms_mean", "generator_late_ms_mean") if k in notes}
            log.write(json.dumps(row) + "\n")
            log.flush()
            m = {k: round(v["value"], 4) for k, v in last["metrics"].items()}
            short = {k: v for k, v in m.items() if k.split(".")[0] in (
                "ttft_p95_ms", "itl_p95_ms", "serve_tokens_per_s", "setup_s",
                "train_tokens_per_s_per_chip", "engine_host_ms",
                "engine_step_ms", "ingress_ms", "accept_ms",
                "first_token_handoff_ms", "token_handoff_ms",
                "idle_sleep_share_pct", "stream_transit_ms",
                "stream_first_transit_ms", "queue_wait_p95_ms",
                "prefill_wait_p95_ms", "prefill_p95_ms")}
            print(tree, cell.split(".")[-1], seed, "trace" if trace else "",
                  "correct" if last["correct"] else "NOT CORRECT",
                  f"failed {last['failed']}/{last['attempted']}",
                  json.dumps(short), "ttft median",
                  row["notes"].get("ttft_ms_median"),
                  f"{row['took_s']}s", flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
