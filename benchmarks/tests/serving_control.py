"""A serving cell's comparison with the reference, read outside a run:
the engine as ``LLMServer`` builds it for the cell, in this process (or,
where the program cannot serve the cell's family and the tests' side of
the family brings an engine of its own, that one: ``controls.py``). On
each seed, ``server.reference_readings`` over it (the seeded probe), then
a short stretch of the cell's own traffic through ``engine.step()`` at
the cell's own load (an open loop's requests admitted when they are due,
a closed loop's ``clients`` kept alive), and ``server.served_readings``
over a sample of what it finished, drawn as a run draws it. Then the
control, the same with the engine built and traced under the ``fp8()``
of the cell's family (``control_<family>.py``, found by the family's
name: one sublayer's matmul operands in fp8 and nothing else), which has
to read over the cell's limits at *every* position of the probe; then
the fault "a token altered where it is produced" (the decode program's
token of ONE lane, not lane 0, moved to the next of the vocabulary),
which the probe in lane 0 cannot see and the served tokens' choice gap
has to.

    python benchmarks/tests/serving_control.py <cell> <seeds> <control seeds>

(seeds: ``n:first``, n seeds from ``first`` on) at the cell's own sizes
on the chip, which this process holds; the readings go to stdout and to
``chiprun_out/serving_control.<cell>.json``. ``--rehearse`` takes the
toy preset on the CPU. ``tests/test_serving_reference.py`` keeps the
control and the fault at that size. Set-up is long (weights, the
engine's programs), so one process reads the program's dozen seeds and
the control's three. For a cell that states the two shares of its sound
engine's readings over the limits (benchmarks/README.md, "A served
family") each seed's line ends in its counts beside their allowances,
and the program's seeds end in the two shares as read over all of them
(each seed's own: mean, least and most): the numbers a family's PR
writes into its cell's file and its ``tolerance_why``. For a cell
compared under the engine's own routing choices (``"routing":
"engine"``) the lists read include ``route_margin``, and where the
family's control file gives ``router_fault()`` a fourth side, the engine
built under it, is read on the control's seeds: ``route_margin_tol`` is
set between the program's largest margin and that side's least run.

    python benchmarks/tests/serving_control.py standin <seeds> <reach seeds>

reads ``routed_standin.py`` instead, a routed family's arithmetic with
the two programs an engine has of it, at the widths such a family would
bring to one chip (``--grouped``: with groups of experts, a dense
leading layer and a rotary part; ``--hybrid``: three state layers to one
of latent attention, a cache that is not rows a position; ``--parents``:
the sound side once more through the probe as it stood until PR 44,
``parents_probe_rows``; ``--rehearse``: at a test's sizes):
on every seed ``server.reference_readings`` through its ``_prefill`` and
``_decode`` at the probe a routed cell would bring, ``served_readings``
over a request a lane, and ``serve_load.matches_reference`` itself under
both decisions such a cell may ask for (``standin_checks``: the two
shares stated, against the reference's own routing choices; and
``"routing": "engine"``, every row and margin held, under the engine's
own), for the sound side and the control (the shared expert's operands
in fp8) under both, and under the engine's for the fault (one lane's
token moved to the next of the vocabulary) and the router fault (an
expert the reference would not have chosen, taken and said); then how far a flip made on
purpose reaches the rows behind it, all of them and by how far behind,
and what the arithmetic alone reads with no engine, no cache and no
probe: ``routed_standin.readings``, bf16 against float32 over whole
sequences, the witness for a share the engine reads (PERF.md section 6,
PRs 36 and 44).
"""

import contextlib
import json
import os
import statistics
import sys
import time
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import controls  # noqa: E402

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
        self.tests_side = controls.of(self.hp)
        self.cfg = self.family.model_config(self.hp)
        self.eng = None

    def _params(self, seed):
        import jax

        return jax.jit(partial(self.family.init_params, cfg=self.cfg))(
            spec.prng_key(seed))

    def _engine(self, seed):
        if self.eng is None:
            own = getattr(self.tests_side, "engine", None)
            if own is not None:     # the program cannot serve the family
                self.eng = own(self.hp, self._params(seed), self.sv)
            else:
                from ray_tpu.llm import LlamaEngine

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
        prompts = [traffic.prompt_tokens(seed, r, self.hp["vocab_size"])
                   for r in requests]
        if not hasattr(eng, "add_request"):
            # an engine of the tests' own has no scheduler: its own loop
            # of decodes, every lane kept full while requests are left
            answers, lanes = eng.serve(
                prompts, [r.max_tokens for r in requests])
            for record, tokens in zip(records, answers):
                record.tokens = list(tokens)
            self.peak_alive = min(eng.max_batch, len(requests))
            self.lanes = [(0, lane) for lane in lanes]
            return records
        reqs = [GenRequest(
            request_id=str(r.index), max_tokens=r.max_tokens, temperature=0.0,
            prompt_ids=prompt) for r, prompt in zip(requests, prompts)]
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
            records = self.serve(seed)
            sample = serve_load.served_sample(
                records, seed, self.check.get(
                    "served_requests", serve_load.SERVED_REQUESTS))
            got.update(server.served_readings(
                eng.params, self.family, self.hp, [
                    (traffic.prompt_tokens(seed, r.request,
                                           self.hp["vocab_size"]), r.tokens)
                    for r in sample]))
            got["peak_alive"] = self.peak_alive
            # the (shard, slot) each request read passed through
            where = {id(r): at for r, at in zip(records, self.lanes)}
            got["lanes"] = [list(where[id(r)]) for r in sample]
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


def parents_probe_rows(eng, seq, positions: int, decode_steps: int):
    """``server.probe_rows`` as it stood until PR 44, kept to pin what it
    does to a cache that is not addressed by position: every call runs
    on the real cache, the last chunk ``positions`` times at the same
    start (a state then holds the chunk before all but the first), and
    the token behind each decode goes in twice, once by the one-token
    prefill that reads its row and once by the next decode. Of an engine
    that can say what it chose the choices are handed back as
    ``server.probe_rows`` hands them (the door is PR 61's; a cell
    compared under them is read under this protocol too)."""
    import numpy as np

    length, shard = len(seq), eng.shards[0]
    starts = range(0, length, eng.prefill_chunk)
    tail = length - starts[-1]
    onehot = np.zeros(eng.max_batch, np.float32)
    onehot[0] = 1.0
    read, said = getattr(eng, "read_choices", None), []

    def say(n):
        if read is not None:
            said.append(np.asarray(read(shard.cache)[:, :n]))

    def prefill(tokens, pos, real=None):
        bucket = next(b for b in eng.buckets if b >= len(tokens))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(tokens)] = tokens
        logits, shard.cache = eng._prefill(
            eng.params, shard.cache, padded, onehot,
            np.asarray([pos], np.int32), real or len(tokens), bucket=bucket)
        say(real or len(tokens))
        return np.asarray(logits, np.float32)

    for pos in starts:
        last = prefill(seq[pos:pos + eng.prefill_chunk], pos)
    got_prefill = np.stack([last] + [
        prefill(seq[starts[-1]:], starts[-1], real=length - starts[-1] - back)
        for back in range(1, positions)])
    chosen, got_after = [int(got_prefill[0].argmax())], []
    lens = np.full(eng.max_batch, eng.max_seq - 1, np.int32)
    for i in range(decode_steps):
        tokens = np.zeros(eng.max_batch, np.int32)
        tokens[0], lens[0] = chosen[-1], length + i
        toks, shard.cache, eng._rng = eng._decode(
            eng.params, shard.cache, tokens, lens,
            np.zeros(eng.max_batch, np.float32), eng._rng)
        say(1)
        chosen.append(int(np.asarray(toks)[0]))
        got_after.append(prefill(chosen[-1:], length + i + 1))
    if read is None:
        return got_prefill, chosen, np.stack(got_after), None
    real, short = said[:len(starts)], said[len(starts):][:positions - 1]
    rest = said[len(starts) + positions - 1:]
    return got_prefill, chosen, np.stack(got_after), server.choices_said(
        real, short, rest[0::2], rest[1::2], tail)


@contextlib.contextmanager
def parents_protocol():
    """``server.reference_readings`` under it reads through
    ``parents_probe_rows``."""
    sound = server.probe_rows
    server.probe_rows = parents_probe_rows
    try:
        yield
    finally:
        server.probe_rows = sound


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


# what a routed cell would bring: the probe, the limits of the llama
# family's serving cells, and the two shares its sound engine reads over
# them:
# the stand-in's own at each set of sizes, the means of 64 seeds on the
# chip rounded up (PERF.md section 6, PR 36; a test's sizes take the
# fixture cell's)
STANDIN_CHECK = {"length": 448, "positions": 96, "decode_steps": 32,
                 "rel_rms_tol": 0.03, "choice_gap_tol": 0.2}
STANDIN_SHARES = {"CHIP": (0.028, 0.009), "CHIP_GROUPED": (0.06, 0.014),
                  "TOY": (0.03, 0.01), "TOY_GROUPED": (0.03, 0.01),
                  # the ceilings, the most a cell may state, so that
                  # the command runs and prints its readings: no run
                  # comes out correct under them, with 64 of 256 experts
                  # held the sound side read 89 % of its rows over
                  # (PERF.md section 6, PR 44)
                  "CHIP_HYBRID": (0.1, 0.03), "TOY_HYBRID": (0.03, 0.01)}
# The same probe under the engine's own routing choices (``"routing":
# "engine"``): no share of rows, every row held to ``rel_rms_tol`` and
# every margin to ``route_margin_tol``, each set between two readings of
# the chip runs of PR 61 (PERF.md section 6, PR 61, has them).
# ``CHIP_HYBRID`` over 8 seeds: rows, the sound engine's largest 0.01502,
# the fp8 control's least 0.0460, the limit 1.8 x over the one and 1.7 x
# under the other; margins, the sound engine's largest 0.0421, the
# router fault's least run (a run's largest margin) 0.2545, the limit
# 2.6 x over the one and 2.3 x under the other. ``CHIP`` over 6 seeds:
# rows 0.01498 and 0.1177; margins 0.0602 and 0.902. ``CHIP_GROUPED``
# over 6: rows 0.01449 and 0.0836; margins 0.1619 (a group's near-tie
# is judged by four slopes) and 0.887. A test's sizes take the fixture
# cell's.
STANDIN_ROUTED = {
    "CHIP": {"rel_rms_tol": 0.03, "route_margin_tol": 0.2},
    "CHIP_GROUPED": {"rel_rms_tol": 0.03, "route_margin_tol": 0.35},
    "CHIP_HYBRID": {"rel_rms_tol": 0.027, "route_margin_tol": 0.11},
    "TOY": {"rel_rms_tol": 0.015, "route_margin_tol": 0.25},
    "TOY_GROUPED": {"rel_rms_tol": 0.015, "route_margin_tol": 0.25},
    "TOY_HYBRID": {"rel_rms_tol": 0.03, "route_margin_tol": 0.08},
}


def standin_checks(name: str, rehearse: bool) -> dict:
    """The two decisions a routed cell may ask for, at one set of the
    stand-in's sizes: ``own`` (the reference's own choices, the two
    shares stated: until PR 61 the only one) and ``engine`` (the
    engine's)."""
    check = dict(STANDIN_CHECK, **dict(zip(
        serve_load.SHARE_CEILINGS, STANDIN_SHARES[name])))
    if rehearse:
        check.update(length=96, positions=32, decode_steps=8)
    engine = {k: v for k, v in check.items() if k != "rel_rms_over_share"}
    return {"own": check, "engine": {**engine, "routing": "engine",
                                     **STANDIN_ROUTED[name]}}


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


def standin_sides(sizes: dict, lanes: int, max_seq: int, buckets,
                  parents: bool = False):
    """The stand-in's engine four times over one set of weights: sound,
    the control (the shared expert's operands in fp8), the fault (the
    decode's token of the lane before the last moved to the next of the
    vocabulary) and the router fault (at one row in 32 the first routed
    layer takes the best held expert it had passed over, and says so);
    with ``parents`` a fifth, sound, which ``standin`` reads under
    ``parents_protocol``."""
    import routed_standin

    def engine(side, **more):
        return routed_standin.Engine(sizes, None, side, max_batch=lanes,
                                     max_seq=max_seq, buckets=buckets, **more)

    sides = {"sound": engine("bf16"), "control": engine("fp8"),
             "fault": engine("bf16"),
             "router": engine("bf16", router_fault=True)}
    sides["fault"]._decode = broken(
        sides["fault"]._decode, next_token(sizes["vocab"], lanes - 2))
    if parents:
        sides["parents"] = engine("bf16")
    return sides


def standin_read(eng, family, seed: int, hp: dict, checks: dict,
                 requests) -> dict:
    """The comparison as a run makes it, of one engine on one seed,
    under each of ``checks`` ({name: a ``reference_check`` block}):
    ``reference_readings`` (the probe, once a check),
    ``served_readings`` over what ``eng.serve`` answered to ``requests``
    ((prompt ids, answer length) each; read once), the decision and what
    it counted."""
    probes = {name: server.reference_readings(eng, family, seed, hp, check)
              for name, check in checks.items()}
    answers, lanes = eng.serve([p for p, _ in requests],
                               [n for _, n in requests])
    served = server.served_readings(
        eng.params, family, hp,
        [(p, a) for (p, _), a in zip(requests, answers)])
    out = {}
    for name, check in checks.items():
        got = out[name] = {**probes[name], **served, "lanes": lanes}
        got["counted"] = serve_load.counted(got, check)
        got["correct"] = serve_load.matches_reference(got, check)
    return out


# rows behind a forced flip, from each of these to the next: how far
# the flip reaches
REACH_FROM = (1, 2, 3, 4, 8, 16, 32, 64, 128, 256, 512)


def arithmetic(seed: int, sizes: dict, tol: float, gap_tol: float) -> dict:
    """``routed_standin.readings`` from row 32 on, summed up: the share
    of positions whose routing flipped between bf16 and float32, what
    they and the others read, the others that lie before any flip of
    their sequence apart, the shares over the two limits, and the fp8
    control."""
    import numpy as np
    import routed_standin

    whole = routed_standin.readings(seed, sizes)
    clean = ~np.logical_or.accumulate(whole["flipped"], axis=1)
    r = {k: v[:, serve_load.SHARES_FROM_ROW:]
         for k, v in dict(whole, clean=clean).items() if k != "logit_rms"}
    flipped, rel, clean = r["flipped"], r["bf16_rel_rms"], r["clean"]
    return {"positions": int(flipped.size),
            "flipped_share": float(flipped.mean()),
            "rows_over_share": float((rel > tol).mean()),
            "unflipped_over_share": float((rel[~flipped] > tol).mean()),
            "tokens_over_share": float((r["bf16_choice_gap"] > gap_tol).mean()),
            "flipped": spread(rel[flipped]), "others": spread(rel[~flipped]),
            "before_any_flip": spread(rel[clean]),
            "control": spread(r["fp8_rel_rms"]),
            "control_tokens_over_share": float(
                (r["fp8_choice_gap"] > gap_tol).mean())}


def standin(seeds: list, reach_seeds: list, rehearse: bool,
            variant: str = "", parents: bool = False) -> None:
    """The stand-in's engine through the comparison a routed cell would
    bring, under both decisions (``standin_checks``): on every seed the
    sound side and the control under the reference's own choices and
    under the engine's, the fault and the router fault under the
    engine's; then ``routed_standin.reach`` over ``reach_seeds``. Every
    reading goes to
    ``chiprun_out/serving_control.standin.<sizes>.<first seed>.json``,
    written anew after every seed."""
    import numpy as np
    import routed_standin

    name = ("TOY" if rehearse else "CHIP") + variant
    sizes = getattr(routed_standin, name)
    checks = standin_checks(name, rehearse)
    check = checks["own"]
    lanes, max_seq, buckets = 8, 2048, (16, 256)
    prompts, answers = STANDIN_PROMPTS, STANDIN_ANSWERS
    if rehearse:
        lanes, max_seq, buckets = 4, 192, (8, 32)
        prompts, answers = (32, 80), (8, 40)
    hp = {**sizes, "vocab_size": sizes["vocab"], "name": name}
    sides = standin_sides(sizes, lanes, max_seq, buckets, parents)
    out = {"sizes": sizes, "name": name, "check": check, "checks": checks,
           "risk": serve_load.RISK, "seeds": seeds, "by_seed": {},
           "engine": {}, "reach": {}, "arithmetic": {}}
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
        routed = out["engine"][seed] = {}
        for side, eng in sides.items():
            eng.params = params
            # the faults are the new decision's to see or the served
            # tokens', which both decisions read alike
            under = {k: v for k, v in checks.items()
                     if side in ("sound", "control")
                     or k == ("own" if side == "parents" else "engine")}
            with parents_protocol() if side == "parents" \
                    else contextlib.nullcontext():
                read = standin_read(eng, routed_standin, seed, hp, under,
                                    requests)
            if "engine" in read:
                got = routed[side] = read["engine"]
                rel = got["prefill_rel_rms"] + got["after_decode_rel_rms"]
                print("standin", name, seed, side, "under the engine's "
                      "choices: correct", got["correct"], got["counted"],
                      "rows least, median, largest",
                      [round(x, 5) for x in (min(rel), statistics.median(
                          rel), max(rel))], "differs", round(
                          got["routing_differs_share"], 4), "read again",
                      got["rows_read_again"], flush=True)
            if "own" not in read:
                continue
            got = row[side] = read["own"]
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
        out["arithmetic"][seed] = arithmetic(
            seed, small, check["rel_rms_tol"], check["choice_gap_tol"])
        print("standin arithmetic", name, seed, out["arithmetic"][seed],
              flush=True)
        r = routed_standin.reach(seed, small, small["seq"] // 4)
        steady = ~r["behind_flipped"]    # the rows whose own choices held
        ends = REACH_FROM[1:] + (r["behind"].shape[1] + 1,)
        out["reach"][seed] = {
            # [rows behind from, to, then ``spread`` of the rows there
            # whose own choices held]
            "behind_by_distance": [
                [lo, hi - 1] + spread(r["behind"][:, lo - 1:hi - 1][
                    steady[:, lo - 1:hi - 1]])
                for lo, hi in zip(REACH_FROM, ends)
                if lo <= r["behind"].shape[1]],
            "at": [float(x) for x in r["at"]],
            "before_largest": float(r["before"].max()),
            "behind": spread(r["behind"]),
            "behind_choices_held": spread(r["behind"][steady]),
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
    out["engine_summary"] = routed_summary(out["engine"])
    print("standin", name, "under the engine's choices, over", len(seeds),
          "seeds:", json.dumps(out["engine_summary"]), flush=True)
    keep()


def routed_summary(by_seed: dict) -> dict:
    """Of each side under the engine's own choices, over the seeds: how
    many read correct; ``spread`` of the compared rows, of a seed's
    largest row, of the margins over 0 and of a seed's largest margin
    (the readings a limit is set between: the sound side's largest, the
    control's least row, the router fault's least run); the share of
    (layer, row) pairs whose sets differ; the rows read again; the
    largest decode gap; and the served tokens' counts."""
    out = {}
    for side in next(iter(by_seed.values())):
        rows = [r[side] for r in by_seed.values()]
        rel = [r["prefill_rel_rms"] + r["after_decode_rel_rms"] for r in rows]
        margins = [r["route_margin"] for r in rows]
        out[side] = {
            "correct": sum(r["correct"] for r in rows), "seeds": len(rows),
            "rows": spread([x for xs in rel for x in xs]),
            "a_seeds_largest_row": spread([max(xs) for xs in rel]),
            "a_seeds_least_row": spread([min(xs) for xs in rel]),
            "margins_over_0": spread([x for xs in margins for x in xs
                                      if x > 0]),
            "a_seeds_largest_margin": spread([max(xs) for xs in margins]),
            "routing_differs_share": spread(
                [r["routing_differs_share"] for r in rows]),
            "rows_read_again": [r["rows_read_again"] for r in rows],
            "decode_gap_largest": max(
                max(r["decode_choice_gap"]) for r in rows),
            "served_over": [r["counted"]["served_choice_gap_over"]
                            for r in rows],
            "request_over": [r["counted"]["request_choice_gap_over"]
                             for r in rows],
            "seconds": [min(r["total_s"] for r in rows),
                        max(r["total_s"] for r in rows)]}
    return out


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


def shares_read(rows: list, check: dict) -> dict:
    """What a cell that states its shares would state, from the sound
    program's readings on many seeds (``rows``: each seed's): of each
    limit kind the share of the readings over the limit, all seeds
    pooled (``mean``: the number to state, rounded up), and the least
    and the most that one seed read (its spread), beside the share the
    cell states now."""
    out = {}
    for limit, share in serve_load.OVER_SHARES.items():
        by_seed = [[not x <= check[limit]
                    for name, of in serve_load.READINGS.items()
                    if of == limit for x in got[name]] for got in rows]
        each = [sum(over) / len(over) for over in by_seed]
        out[share] = {"mean": sum(map(sum, by_seed)) / sum(map(len, by_seed)),
                      "a_seed_least": min(each), "a_seed_most": max(each),
                      "readings_a_seed": statistics.median(map(len, by_seed)),
                      "stated": check.get(share, 0.0)}
    return out


def main(argv) -> int:
    flags = ("--rehearse", "--grouped", "--hybrid", "--parents")
    rehearse = "--rehearse" in argv
    cell_name, seeds, control_seeds = [a for a in argv if a not in flags]
    if cell_name == "standin":
        standin(seeds_of(seeds), seeds_of(control_seeds), rehearse,
                "_GROUPED" if "--grouped" in argv
                else "_HYBRID" if "--hybrid" in argv else "",
                "--parents" in argv)
        return 0
    from ray_tpu._private.jax_utils import ensure_compilation_cache_dir

    ensure_compilation_cache_dir()
    cell = spec.load_cell(cell_name, rehearse)
    check = cell["serve"]["reference_check"]
    routed = serve_load.routed(check)
    stating = serve_load.states_a_share(check) or routed
    # the family's control, found by its name: a family that brings none
    # is an error here, before anything is read
    control = controls.of(cell["hp"]).fp8
    out = {"cell": cell_name, "program": {}, "control": {}, "altered": {}}
    sides = [("program", contextlib.nullcontext, seeds_of(seeds)),
             ("control", control, seeds_of(control_seeds)),
             ("altered", contextlib.nullcontext, seeds_of(control_seeds))]
    # a cell compared under the engine's own routing choices: the fault
    # its route_margin_tol is set under, where the family's file plants one
    router_fault = getattr(controls.of(cell["hp"]), "router_fault", None)
    if routed and router_fault is not None:
        out["router"] = {}
        sides.append(("router", router_fault, seeds_of(control_seeds)))
    for side, under, wanted in sides:
        with under():
            # its programs are traced when its first seed is read; the
            # fault sits in the lane the engine fills second
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
                if stating:     # what decides such a cell: [count, most]
                    out[side][seed]["counted"] = serve_load.counted(
                        got, probe.check)
                    print(side, seed, "counted beside allowed",
                          out[side][seed]["counted"], flush=True)
            del probe
    for side, rows in out.items():
        if side != "cell" and rows:
            print(side, "over", len(rows), "seeds:", {
                k: [min(min(r["readings"][k]) for r in rows.values()),
                    statistics.median(r["summary"][k]["median"]
                                      for r in rows.values()),
                    min(r["summary"][k]["max"] for r in rows.values()),
                    max(r["summary"][k]["max"] for r in rows.values())]
                for k in serve_load.readings_of(check)},
                "(least, median, a seed's largest at the least, largest)")
    if serve_load.states_a_share(check) and out["program"]:
        out["shares_read"] = shares_read(
            [r["readings"] for r in out["program"].values()], check)
        print("program over", len(out["program"]), "seeds, shares of the "
              "readings over their limit:", json.dumps(out["shares_read"]),
              flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    tag = ".rehearsal" if rehearse else ""
    with open(f"chiprun_out/serving_control.{cell_name}{tag}.json", "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
