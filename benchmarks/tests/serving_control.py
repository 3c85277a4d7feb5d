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

reads ``routed_standin.py`` instead, a routed family's arithmetic with
the two programs an engine has of it, at the widths such a family would
bring to one chip (``--grouped``: with groups of experts, a dense
leading layer and a rotary part; ``--rehearse``: at a test's sizes):
on every seed ``server.reference_readings`` through its ``_prefill`` and
``_decode`` at the probe a routed cell would bring, ``served_readings``
over a request a lane, and ``serve_load.matches_reference`` itself under
the two shares such a cell would state, for the sound side, the control
(the shared expert's operands in fp8) and the fault (one lane's token
moved to the next of the vocabulary); then how far a flip made on
purpose reaches the rows behind it (PERF.md section 6, PR 36).
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


# what a routed cell would bring: the probe, the limits of the three
# serving cells, and the two shares its sound engine reads over them:
# the stand-in's own at each set of sizes, the means of 64 seeds on the
# chip rounded up (PERF.md section 6, PR 36; a test's sizes take the
# fixture cell's)
STANDIN_CHECK = {"length": 448, "positions": 96, "decode_steps": 32,
                 "rel_rms_tol": 0.03, "choice_gap_tol": 0.2}
STANDIN_SHARES = {"CHIP": (0.028, 0.009), "CHIP_GROUPED": (0.06, 0.014),
                  "TOY": (0.03, 0.01), "TOY_GROUPED": (0.03, 0.01)}
# its served requests, one a lane: prompts and answers of so many
# tokens, the shortest and the longest answer in every seed
STANDIN_PROMPTS, STANDIN_ANSWERS = (32, 480), (48, 256)


def standin_requests(seed: int, lanes: int, vocab: int, prompts, answers):
    """One request a lane: (prompt ids, answer length) drawn from the
    seed, the answers' lengths spread evenly from the shortest to the
    longest and dealt to the lanes in the seed's order."""
    import numpy as np

    rng = np.random.default_rng([seed, 7])
    lens = np.linspace(answers[0], answers[1], lanes).round().astype(int)
    rng.shuffle(lens)
    return [(traffic.prompt_tokens(seed, traffic.Request(
        i, 0.0, int(rng.integers(prompts[0], prompts[1] + 1)), int(n)),
        vocab), int(n)) for i, n in enumerate(lens)]


def standin_sides(sizes: dict, lanes: int, max_seq: int, buckets):
    """The stand-in's engine three times over one set of weights: sound,
    the control (the shared expert's operands in fp8) and the fault (the
    decode's token of the lane before the last moved to the next of the
    vocabulary)."""
    import routed_standin

    def engine(side):
        return routed_standin.Engine(sizes, None, side, max_batch=lanes,
                                     max_seq=max_seq, buckets=buckets)

    sides = {"sound": engine("bf16"), "control": engine("fp8"),
             "fault": engine("bf16")}
    sides["fault"]._decode = broken(
        sides["fault"]._decode, next_token(sizes["vocab"], lanes - 2))
    return sides


def standin_read(eng, family, seed: int, hp: dict, check: dict,
                 requests) -> dict:
    """The comparison as a run makes it, of one engine on one seed:
    ``reference_readings`` (the probe), ``served_readings`` over what
    ``eng.serve`` answered to ``requests`` ((prompt ids, answer length)
    each), the decision and what it counted."""
    got = server.reference_readings(eng, family, seed, hp, check)
    answers, lanes = eng.serve([p for p, _ in requests],
                               [n for _, n in requests])
    got.update(server.served_readings(
        eng.params, family, hp,
        [(p, a) for (p, _), a in zip(requests, answers)]))
    got["lanes"] = lanes
    got["counted"] = serve_load.counted(got, check)
    got["correct"] = serve_load.matches_reference(got, check)
    return got


def standin(seeds: list, reach_seeds: list, rehearse: bool,
            grouped: bool) -> None:
    """The stand-in's engine through the comparison a routed cell would
    bring, on every seed the sound side, the control and the fault; then
    ``routed_standin.reach`` over ``reach_seeds``. Every reading goes to
    ``chiprun_out/serving_control.standin.<sizes>.<first seed>.json``,
    written anew after every seed."""
    import numpy as np
    import routed_standin

    name = ("TOY" if rehearse else "CHIP") + ("_GROUPED" if grouped else "")
    sizes = getattr(routed_standin, name)
    check = dict(STANDIN_CHECK, **dict(zip(
        serve_load.SHARE_CEILINGS, STANDIN_SHARES[name])))
    lanes, max_seq, buckets = 8, 2048, (16, 256)
    prompts, answers = STANDIN_PROMPTS, STANDIN_ANSWERS
    if rehearse:
        check.update(length=96, positions=32, decode_steps=8)
        lanes, max_seq, buckets = 4, 192, (8, 32)
        prompts, answers = (32, 80), (8, 40)
    hp = {**sizes, "vocab_size": sizes["vocab"]}
    sides = standin_sides(sizes, lanes, max_seq, buckets)
    out = {"sizes": sizes, "name": name, "check": check, "risk":
           serve_load.RISK, "seeds": seeds, "by_seed": {}, "reach": {}}
    os.makedirs("chiprun_out", exist_ok=True)
    path = f"chiprun_out/serving_control.standin.{name}.{seeds[0]}.json"

    def keep():     # after every seed: a call may be cut
        with open(path, "w") as fh:
            json.dump(out, fh)

    for seed in seeds:
        t0 = time.time()
        for eng in sides.values():
            eng.params = None
        params = routed_standin.init_params(
            routed_standin.key_of(seed), sizes=routed_standin.frozen(sizes))
        requests = standin_requests(seed, lanes, sizes["vocab"], prompts,
                                    answers)
        row = out["by_seed"][seed] = {}
        for side, eng in sides.items():
            eng.params = params
            got = row[side] = standin_read(eng, routed_standin, seed, hp,
                                           check, requests)
            rel = got["prefill_rel_rms"] + got["after_decode_rel_rms"]
            over = sorted(x for x in rel if x > check["rel_rms_tol"])
            tol = check["choice_gap_tol"]
            print("standin", name, seed, side, "correct", got["correct"],
                  got["counted"], "rel_rms over",
                  [round(x, 4) for x in over[:3] + over[-2:]],
                  "others at most", round(max(
                      [x for x in rel if x <= check["rel_rms_tol"]] + [0]), 5),
                  "probe gaps over", sum(
                      g > tol for g in got["decode_choice_gap"]),
                  "by request (lane, tokens, over)", [
                      [lane, n, sum(g > tol for g in gaps)]
                      for lane, (_, n, _), gaps in zip(
                          got["lanes"], got["served_by_request"],
                          serve_load.by_request(got)[1:])],
                  f"{time.time() - t0:.1f}s", flush=True)
        del params
        keep()
    for eng in sides.values():
        eng.params = None
    for seed in reach_seeds:
        small = {**sizes, "seqs": 2, "seq": min(sizes["seq"], 1024)}
        r = routed_standin.reach(seed, small, small["seq"] // 4)
        held = ~r["behind_flipped"]
        out["reach"][seed] = {
            "at": [float(x) for x in r["at"]],
            "before_largest": float(r["before"].max()),
            "behind": spread(r["behind"]),
            "behind_choices_held": spread(r["behind"][held]),
            # each row behind that reads over the limit: rows behind,
            # reading, a choice of its own flipped
            "behind_over_limit": [
                [int(j) + 1, float(r["behind"][i, j]),
                 bool(r["behind_flipped"][i, j])]
                for i, j in zip(*np.nonzero(
                    r["behind"] > check["rel_rms_tol"]))]}
        print("standin reach", name, seed, out["reach"][seed], flush=True)
        keep()
    out["summary"] = standin_summary(out["by_seed"], check, lanes - 2)
    print("standin", name, "over", len(seeds), "seeds:",
          json.dumps(out["summary"]), flush=True)
    keep()


def standin_summary(by_seed: dict, check: dict, faulty: int) -> dict:
    """Of each side over the seeds: how many read correct; the rows and
    the tokens over their limit (pooled over the seeds: the share a cell
    would state; a seed's least and most beside their allowance); the
    readings over the limit and the others; of the requests, the most
    tokens over beside that request's allowance, and for the fault the
    requests through lane ``faulty`` apart from the others ([tokens
    over, tokens])."""
    tol, gap_tol = check["rel_rms_tol"], check["choice_gap_tol"]
    out = {}
    for side in next(iter(by_seed.values())):
        rows = [r[side] for r in by_seed.values()]
        flat_rel = [x for r in rows for x in
                    r["prefill_rel_rms"] + r["after_decode_rel_rms"]]
        flat_gap = [g for r in rows for g in
                    r["decode_choice_gap"] + r["served_choice_gap"]]
        requests = [[lane, sum(g > gap_tol for g in gs), len(gs)]
                    for r in rows for lane, gs in zip(
                        [0] + r["lanes"], serve_load.by_request(r))]
        worst = {"over_share_most": max(o / n for _, o, n in requests)}
        if side == "fault":
            worst = {"through_the_lane_least": min(
                         [o, n] for l, o, n in requests if l == faulty),
                     "through_others_most": max(
                         [o, n] for l, o, n in requests if l != faulty)}

        def counts(pool, i=0):      # each seed's count (0) or allowance (1)
            return [r["counted"][pool][i] for r in rows]

        seconds = [r["total_s"] + r["served_s"] for r in rows]
        out[side] = {
            "correct": sum(r["correct"] for r in rows), "seeds": len(rows),
            "rows": len(flat_rel),
            "rows_over_share": sum(x > tol for x in flat_rel) / len(flat_rel),
            "rows_over_a_seed": [min(counts("rel_rms_over")),
                                 max(counts("rel_rms_over")),
                                 counts("rel_rms_over", 1)[0]],
            "rel_rms_over": spread([x for x in flat_rel if x > tol]),
            "rel_rms_others": spread([x for x in flat_rel if x <= tol]),
            "tokens": len(flat_gap),
            "tokens_over_share": sum(g > gap_tol for g in flat_gap)
            / len(flat_gap),
            "tokens_over_a_seed": [
                min(counts("choice_gap_over")), max(counts("choice_gap_over")),
                [min(counts("choice_gap_over", 1)),
                 max(counts("choice_gap_over", 1))]],
            "gap_over": spread([g for g in flat_gap if g > gap_tol]),
            "gap_others": spread([g for g in flat_gap if g <= gap_tol]),
            "request_furthest_over": max(
                (r["counted"]["request_choice_gap_over"] for r in rows),
                key=lambda pair: pair[0] - pair[1]),
            "requests": worst,
            "seconds": [min(seconds), max(seconds)]}
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
    rehearse, grouped = "--rehearse" in argv, "--grouped" in argv
    cell_name, seeds, control_seeds = [
        a for a in argv if a not in ("--rehearse", "--grouped")]
    if cell_name == "standin":
        standin(seeds_of(seeds), seeds_of(control_seeds), rehearse, grouped)
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
