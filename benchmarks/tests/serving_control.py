"""A serving cell's comparison with the reference, read outside a run:
the engine as ``LLMServer`` builds it for the cell, in this process. On
each seed, ``server.reference_readings`` over it (the seeded probe), then
a short stretch of the cell's own traffic through ``engine.step()`` at
the cell's own load (an open loop's requests admitted when they are due,
a closed loop's ``clients`` kept alive), and ``server.served_readings``
over a sample of what it finished, drawn as a run draws it. Then the
control, the same with ``models/llama.py``'s ``mlp_sublayer`` patched
here to run its three matmuls' operands in fp8
(``test_first_forward.mlp_in_fp8``), which has to read over the cell's
limits at *every* position of the probe; then the fault "a token altered
where it is produced" (the decode program's token of ONE lane, not lane
0, moved to the next of the vocabulary), which the probe in lane 0
cannot see and the served tokens' choice gap has to.

    python benchmarks/tests/serving_control.py <cell> <seeds> <control seeds>

(seeds: ``n:first``, n seeds from ``first`` on) at the cell's own sizes
on the chip, which this process holds; the readings go to stdout and to
``chiprun_out/serving_control.<cell>.json``. ``--rehearse`` takes the
toy preset on the CPU. ``tests/test_serving_reference.py`` keeps the
control and the fault at that size. Set-up is long (weights, six
programs), so one process reads the program's dozen seeds and the
control's three.

    python benchmarks/tests/serving_control.py standin <seeds> <reach seeds>

reads ``routed_standin.py`` instead, a routed family's arithmetic with no
engine, at the widths such a family would bring to one chip
(``--rehearse``: at a test's): which positions flip a choice that
involves a held expert between bf16 and the float32 side, how they and
the others read, the float32 side's margin at the flipped ones, what a
margin of 1, 1.3 and 2 x the largest of them marks and leaves, the
served tokens' choice gap at each group, the fp8 control at the unmarked
positions, and how far a flip reaches the rows behind it through
attention (PERF.md section 6, PR 34: what a rule that spares a routed
family's near-ties would have to fit, and does not yet).
"""

import json
import os
import statistics
import sys
import time
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from test_first_forward import mlp_in_fp8  # noqa: E402

from benchmarks import serve_load, server, spec, traffic  # noqa: E402


# seconds of an open loop's arrivals that one reading serves
STRETCH_S = 6.0


class Probe:
    """One cell's engine, its weights swapped from seed to seed (one
    set of weights alive at a time: a 7.5 GB model twice is a chip).
    ``fault``: a change of the token vector every decode returns
    (``broken``)."""

    def __init__(self, cell_name: str, rehearse: bool, fault=None,
                 stretch_s: float = STRETCH_S):
        self.fault, self.stretch_s = fault, stretch_s
        self.clients = None     # the cell's own load
        self.cell = spec.load_cell(cell_name, rehearse)
        self.hp, self.sv = self.cell["hp"], self.cell["serve"]
        self.check = self.sv["reference_check"]
        self.family = spec.family_of(self.hp)
        self.cfg = self.family.model_config(self.hp)
        self.eng = None

    def _params(self, seed):
        import jax

        return jax.jit(partial(self.family.init_params, cfg=self.cfg))(
            spec.prng_key(seed))

    def _engine(self, seed):
        from ray_tpu.llm import LlamaEngine

        if self.eng is None:
            self.eng = LlamaEngine(
                self.cfg, self._params(seed),
                max_batch=self.sv["max_batch_size"],
                max_seq=self.sv["max_seq_len"],
                **self.sv.get("engine_kwargs", {}))
            self.eng.warm_up()
            if self.fault is not None:
                self.eng._decode = broken(self.eng._decode, self.fault)
        else:
            self.eng.params = None
            self.eng.params = self._params(seed)
        return self.eng

    def serve(self, seed: int) -> list:
        """A stretch of the cell's traffic through ``engine.step()``, in
        this thread; returns a record for each request it finished."""
        from ray_tpu.llm import GenRequest

        eng, tr = self._engine(seed), self.cell["traffic"]
        if tr["loop"] == "open":
            # the run's own schedule, from the start of its ramp
            requests = traffic.open_loop(tr, seed, 50.0)
            requests = requests[:round(tr["rate_per_s"] * self.stretch_s)]
            first_due, clients = requests[0].due_s, len(requests)
        else:
            requests = traffic.closed_loop(tr, seed)
            first_due, clients = None, tr["clients"]
        if self.clients:    # a test's load: so many alive, whatever is due
            first_due, clients = None, self.clients
        records = [serve_load._Record(r) for r in requests]
        reqs = [GenRequest(
            request_id=str(r.index), max_tokens=r.max_tokens, temperature=0.0,
            prompt_ids=traffic.prompt_tokens(seed, r, self.hp["vocab_size"]))
            for r in requests]
        t0, sent, peak = time.perf_counter(), 0, 0
        while sent < len(reqs) or eng.num_active():
            while (sent < len(reqs) and eng.num_active() < clients
                   and eng.has_capacity() and (
                       first_due is None or requests[sent].due_s - first_due
                       <= time.perf_counter() - t0)):
                eng.add_request(reqs[sent])
                sent += 1
            peak = max(peak, eng.num_active())
            if eng.num_active():
                eng.step()
            else:
                time.sleep(0.001)
        for record, req in zip(records, reqs):
            record.tokens = list(req.generated)
        self.peak_alive = peak
        self.lanes = [(req.shard, req.slot) for req in reqs]
        return records

    def read(self, seed: int, served: bool = True) -> dict:
        eng = self._engine(seed)
        got = server.reference_readings(
            eng, self.family, seed, self.hp, self.check)
        if served:
            sample = serve_load.served_sample(
                self.serve(seed), seed, self.check.get(
                    "served_requests", serve_load.SERVED_REQUESTS))
            got.update(server.served_readings(
                eng.params, self.family, self.hp, [
                    (traffic.prompt_tokens(seed, r.request,
                                           self.hp["vocab_size"]), r.tokens)
                    for r in sample]))
            got["peak_alive"] = self.peak_alive
        return got


def broken(decode, change):
    """The decode program with a fault planted where its tokens are
    produced: ``change`` of the token vector it returns, which is what
    the engine reads, emits and feeds the next decode. The cache rows
    and the logits behind them stay what the program computed, so only a
    choice gap can see it; in a lane that is not the probe's, only the
    served tokens' choice gap."""
    def wrapped(*args):
        tokens, cache, rng = decode(*args)
        return change(tokens), cache, rng
    return wrapped


def next_token(vocab: int, lane=None):
    """A token altered where it is produced: every lane's (or only
    ``lane``'s) moved to the next of the vocabulary."""
    def change(tokens):
        moved = (tokens + 1) % vocab
        return moved if lane is None else tokens.at[lane].set(moved[lane])
    return change


def crossed(tokens):
    """Every lane given its neighbour's token."""
    import jax.numpy as jnp

    return jnp.roll(tokens, 1)


def seeds_of(text: str) -> list:
    n, first = (int(x) for x in text.split(":"))
    return list(range(first, first + n))


# the three serving cells' rel_rms_tol and choice_gap_tol: what a sound
# position of the stand-in should not read over, and its control has to
STANDIN_LIMIT, STANDIN_GAP_LIMIT = 0.03, 0.2
# the margins are summed up from this row on: a serving cell's first
# compared row lies behind a prompt of 32 tokens or more, and a
# sequence's first rows attend to so few that a flip there moves them
# whole
STANDIN_FROM = 32


def standin(seeds: list, reach_seeds: list, rehearse: bool) -> None:
    """``routed_standin.readings`` over ``seeds`` and ``reach`` over
    ``reach_seeds``, summed up; every position's readings go to
    ``chiprun_out/serving_control.standin.npz``."""
    import numpy as np
    import routed_standin

    sizes = routed_standin.TOY if rehearse else routed_standin.CHIP
    rows = {}
    for seed in seeds:
        t0 = time.time()
        got = routed_standin.readings(seed, sizes)
        for k, v in got.items():
            rows.setdefault(k, []).append(v)
        f = got["flipped"]
        print("standin", seed, "flipped", int(f.sum()), "of", f.size,
              "their bf16", spread(got["bf16_rel_rms"][f]), "margin at most",
              float(got["margin"][f].max()) if f.any() else None, "others",
              spread(got["bf16_rel_rms"][~f]), "fp8",
              spread(got["fp8_rel_rms"]), f"{time.time() - t0:.1f}s",
              flush=True)
    got = {k: np.stack(v) for k, v in rows.items()}      # (seeds, B, S)
    out = {"sizes": sizes, "seeds": seeds, **standin_summary(got),
           "reach": {}}
    for seed in reach_seeds:
        r = routed_standin.reach(seed, {**sizes, "seqs": 2},
                                 sizes["seq"] // 4)
        out["reach"][seed] = {
            "at": [float(x) for x in r["at"]],
            "before_largest": float(r["before"].max()),
            "behind": spread(r["behind"]),
            "behind_choices_held": spread(r["behind"][~r["behind_flipped"]]),
            # each row behind that reads over the limit: rows behind,
            # reading, the sound side's margin, a choice of its own flipped
            "behind_over_limit": [
                [int(j) + 1, float(r["behind"][i, j]),
                 float(r["behind_margin"][i, j]),
                 bool(r["behind_flipped"][i, j])]
                for i, j in zip(*np.nonzero(r["behind"] > STANDIN_LIMIT))],
            "behind_flipped_margin": spread(
                r["behind_margin"][r["behind_flipped"]])}
        print("standin reach", seed, out["reach"][seed], flush=True)
    print("standin over", len(seeds), "seeds:", json.dumps(out), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    np.savez_compressed(
        "chiprun_out/serving_control.standin.npz",
        **{k: v.astype(np.float16) if k.endswith(("rms", "gap")) else v
           for k, v in got.items()})
    with open("chiprun_out/serving_control.standin.json", "w") as fh:
        json.dump(out, fh)


def standin_summary(got: dict) -> dict:
    """Of ``routed_standin.readings`` stacked over seeds (seeds, B, S):
    what PERF.md section 6 (PR 34) quotes."""
    import numpy as np

    f, m = got["flipped"], got["margin"]
    b, c = got["bf16_rel_rms"], got["fp8_rel_rms"]
    g, cg = got["bf16_choice_gap"], got["fp8_choice_gap"]
    behind = np.logical_or.accumulate(f, -1) & ~f        # a flip before it
    over = ~f & (b > STANDIN_LIMIT)
    late = np.s_[..., min(STANDIN_FROM, f.shape[-1] // 4):]
    largest = float(m[late][f[late]].max())
    edges = [0, 0.0025, 0.005, 0.01, 0.015, 0.02, 0.025, 0.03, 0.035, 0.04,
             0.045, 0.05, 0.06, 0.07, 0.08, 0.1, 0.15]
    out = {"positions": int(f.size),
           "limits": [STANDIN_LIMIT, STANDIN_GAP_LIMIT],
           "logit_rms": spread(got["logit_rms"]),
           "flipped_share": float(f.mean()),
           "flipped_share_a_sequence_most": float(f.mean(-1).max()),
           "flipped_rel_rms": spread(b[f]), "other_rel_rms": spread(b[~f]),
           "other_over_limit": int(over.sum()),
           # where in their sequences they lie, and what is left later
           "other_over_limit_rows": sorted(set(np.nonzero(over)[-1].tolist())),
           "other_late_largest": float(b[late][~f[late]].max()),
           # the rows before any flipped one of their sequence
           "other_before_any_flip": spread(b[~behind & ~f]),
           "flipped_choice_gap": spread(g[f]),
           "flipped_choice_differs": float((g[f] > 0).mean()),
           "other_choice_gap": spread(g[~f]),
           "choice_gap_over_limit": [int((g[f] > STANDIN_GAP_LIMIT).sum()),
                                     int((g[~f] > STANDIN_GAP_LIMIT).sum())],
           "fp8_rel_rms": spread(c), "fp8_choice_gap": spread(cg),
           "fp8_choice_gap_over_limit_share": float(
               (cg > STANDIN_GAP_LIMIT).mean()),
           "fp8_choice_gap_a_sequence_least": float(cg.max(-1).min()),
           "flipped_margin": spread(m[f]), "largest_flipped_all_rows": float(
               m[f].max()),
           "late_from_row": late[-1].start, "largest_flipped_late": largest,
           # how the largest grows with the positions read
           "largest_flipped_late_by_seeds": {
               n: float(m[:n][late][f[:n][late]].max())
               for n in (1, 2, 4, 8, 16, 32, 64, 128) if n <= len(f)},
           # [from, to, positions, flipped among them]
           "flips_by_margin": [
               [lo, hi, int(((m >= lo) & (m < hi)).sum()),
                int((f & (m >= lo) & (m < hi)).sum())]
               for lo, hi in zip(edges, edges[1:])],
           "late_by_factor": {}}
    f, m, b, c, g, cg = (x[late] for x in (f, m, b, c, g, cg))
    for factor in (1, 1.3, 2):
        marked = m < factor * largest * (1 + 1e-6)
        out["late_by_factor"][factor] = {
            "tie_margin": factor * largest,
            "marked_share": float(marked.mean()),
            "marked_share_a_sequence_most": float(marked.mean(-1).max()),
            "unmarked_over_limit": int((b[~marked] > STANDIN_LIMIT).sum()),
            "unmarked_largest": float(b[~marked].max()),
            "marked_unflipped_choice_gap": spread(g[marked & ~f]),
            "unmarked_choice_gap": spread(g[~marked]),
            "fp8_unmarked_least": float(c[~marked].min()),
            "fp8_unmarked_at_or_under_limit": int(
                (c[~marked] <= STANDIN_LIMIT).sum())}
    return out


def spread(xs) -> list:
    """least, median, 99th percentile, 99.9th, largest; and how many."""
    import numpy as np

    xs = np.asarray(xs, np.float64).ravel()
    if not xs.size:
        return []
    return [float(q) for q in np.quantile(xs, [0, 0.5, 0.99, 0.999, 1])] \
        + [int(xs.size)]


def main(argv) -> int:
    rehearse = "--rehearse" in argv
    cell_name, seeds, control_seeds = [a for a in argv if a != "--rehearse"]
    if cell_name == "standin":
        standin(seeds_of(seeds), seeds_of(control_seeds), rehearse)
        return 0
    from ray_tpu._private.jax_utils import ensure_compilation_cache_dir

    ensure_compilation_cache_dir()
    from ray_tpu.models import llama

    out = {"cell": cell_name, "program": {}, "control": {}, "altered": {}}
    for side, patch, wanted in (
            ("program", None, seeds_of(seeds)),
            ("control", mlp_in_fp8, seeds_of(control_seeds)),
            ("altered", None, seeds_of(control_seeds))):
        sound = llama.mlp_sublayer
        if patch:
            llama.mlp_sublayer = patch
        try:
            # its programs are traced when its first seed is read; the
            # fault sits in the lane the engine fills second
            cell = spec.load_cell(cell_name, rehearse)
            probe = Probe(cell_name, rehearse, fault=next_token(
                cell["hp"]["vocab_size"], cell["serve"]["max_batch_size"] - 2)
                if side == "altered" else None)
            for seed in wanted:
                t0 = time.time()
                got = probe.read(seed)
                row = serve_load.summary(got, probe.check)
                out[side][seed] = {
                    "summary": row, "readings": got,
                    "correct": serve_load.matches_reference(got, probe.check)}
                gaps = got["served_choice_gap"]
                print(side, seed, "correct", out[side][seed]["correct"],
                      {k: [round(v[s], 5) for s in ("median", "max")]
                       for k, v in row.items()},
                      "least", {k: round(min(got[k]), 5) for k in row},
                      "served", len(got["served_by_request"]), "requests",
                      len(gaps), "tokens", sum(g > 0 for g in gaps), "> 0",
                      sum(g > 0.04 for g in gaps), "> 0.04",
                      sum(g > 0.1 for g in gaps), "> 0.1; by request",
                      [[p, n, round(g, 4)]
                       for p, n, g in got["served_by_request"]],
                      "alive at most", got["peak_alive"],
                      f"{got['engine_s']:.2f}s engine {got['total_s']:.2f}s "
                      f"probe {got['served_s']:.2f}s served reference "
                      f"{time.time() - t0:.1f}s all", flush=True)
            del probe
        finally:
            llama.mlp_sublayer = sound
    for side, rows in out.items():
        if side != "cell" and rows:
            print(side, "over", len(rows), "seeds:", {
                k: [min(min(r["readings"][k]) for r in rows.values()),
                    statistics.median(r["summary"][k]["median"]
                                      for r in rows.values()),
                    min(r["summary"][k]["max"] for r in rows.values()),
                    max(r["summary"][k]["max"] for r in rows.values())]
                for k in serve_load.READINGS},
                "(least, median, a seed's largest at the least, largest)")
    os.makedirs("chiprun_out", exist_ok=True)
    tag = ".rehearsal" if rehearse else ""
    with open(f"chiprun_out/serving_control.{cell_name}{tag}.json", "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
