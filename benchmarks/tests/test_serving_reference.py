"""The serving comparison with the reference: the decision rule on
made-up readings; ``routed_standin.py``, a routed family's arithmetic at
a test's size, with no engine (what the decision makes of a sound routed
stack where the cell states no share: every position decides); and, on
every serve cell of ``BENCHMARK.json`` and of the tests' tree alike, the
engine in this process (``serving_control.Probe``: the engine
``LLMServer`` would build at the cell's rehearsal sizes, or the one the
tests' side of the cell's family brings where the program cannot serve
it, ``controls.py``): ``server.reference_readings`` (the seeded probe,
read at many positions) and ``server.served_readings`` over what a
stretch of the cell's traffic finished, on the sound program; under the
control of PERF.md section 4 at a size a test can hold (``fp8()`` of the
cell's family, found by name: one sublayer's matmul operands in fp8,
patched in the test's own process and never in the program); and with
the faults a serving cell can have planted under the timed path where
the decode program's tokens are produced.

No test here knows how many serve cells there are, what family they are
of or a probe's sizes. What is asserted of a cell follows from three
things: whether its ``reference_check`` states the two shares of its
sound engine's readings over the limits (benchmarks/README.md, "A served
family"), whether it asks for its rows to be compared under the
engine's own routing choices (``"routing": "engine"``), and what its
family gives. A cell that states none is held at every reading (each row
at or under 0.6 x ``rel_rms_tol``, each gap under half of
``choice_gap_tol``, the control over at every row); a cell that states
them by its counts beside their allowances, with every row that is not
over the limit under it with room; a cell under the engine's choices at
every row, gap and margin of its probe, and by the counts of its served
tokens alone. ``serving_control.py``
beside this file reads the same on the chip at the cells' own sizes
(PERF.md sections 4 and 6 have the chip's readings)."""

import functools
import math

import controls
import numpy as np
import pytest
import routed_standin
import serving_control
from test_contract import stated_shares
from test_doors import cells_of_kind, tree_cells

from benchmarks import serve_load, server, spec, traffic

# every serve cell: those BENCHMARK.json declares, then the tests' tree's
SERVE = cells_of_kind("serve")
TREE_SERVE = cells_of_kind("serve", tree_cells())
BENCH_SERVE = [c for c in SERVE if c not in TREE_SERVE]
LIMITS = {"rel_rms_tol": 0.03, "choice_gap_tol": 0.1}


@functools.lru_cache(maxsize=None)
def states_shares(cell: str) -> bool:
    """Whether the cell's ``reference_check`` states a share of its
    sound engine's readings over the limits: the one property of a cell
    that the assertions below turn on."""
    return serve_load.states_a_share(
        spec.load_cell(cell, True)["serve"]["reference_check"])


@functools.lru_cache(maxsize=None)
def routed(cell: str) -> bool:
    """Whether the cell's rows are compared under the engine's own
    routing choices: it then states no share of its rows, whatever it
    states of its served tokens, and every row decides."""
    return serve_load.routed(
        spec.load_cell(cell, True)["serve"]["reference_check"])


def rows_by_count(cell: str) -> bool:
    """Whether a count of rows over the limit decides the cell."""
    return states_shares(cell) and not routed(cell)


def served_counts(cell: str) -> tuple:
    """The names ``serve_load.counted`` gives the served tokens' counts,
    all together and by request."""
    return ("served_choice_gap_over" if routed(cell) else "choice_gap_over",
            "request_choice_gap_over")


def readings(rel_rms, gaps=(0.0,) * 16, finite=True, served=(0.0,) * 300):
    return {"prefill_rel_rms": list(rel_rms),
            "after_decode_rel_rms": [0.011] * 16,
            "decode_choice_gap": list(gaps),
            "served_choice_gap": list(served), "finite": finite}


# --------------------------------------------------------------- the rule
@pytest.mark.parametrize("rel_rms,passes", [
    # one position in 32 over the limit, wherever it falls: with no share
    # stated no position is spared (a routed family's flipped choice
    # reads so, which is why its cell states its shares)
    ([0.011] * 31 + [0.13], False),
    ([0.13] + [0.011] * 31, False),
    ([0.011] * 26 + [0.13] * 6, False),
    # an fp8 sublayer moves every position
    ([0.12] * 32, False),
    # at the limit is under it
    ([0.03] * 32, True),
    ([0.011] * 32, True),
    ([0.011] * 31 + [0.0301], False),
])
def test_every_reading_has_to_lie_at_or_under_its_limit(rel_rms, passes):
    ref = readings(rel_rms)
    assert serve_load.matches_reference(ref, LIMITS) is passes
    of = serve_load.summary(ref, LIMITS)["prefill_rel_rms"]
    assert of["n"] == 32 and of["max"] == max(rel_rms)
    assert of["outlier_share"] == sum(x > 0.03 for x in rel_rms) / 32
    assert (of["max"] <= of["limit"]) is passes


@pytest.mark.parametrize("bad", [math.nan, math.inf, -math.inf])
@pytest.mark.parametrize("name", list(serve_load.READINGS))
def test_a_reading_that_is_not_finite_fails_in_every_list(bad, name):
    sound = readings([0.011] * 32)
    assert serve_load.matches_reference(sound, LIMITS)
    assert not serve_load.matches_reference(
        dict(sound, **{name: sound[name][:-1] + [bad]}), LIMITS)
    # logits that were not finite, whatever the readings made of them
    assert not serve_load.matches_reference(dict(sound, finite=False), LIMITS)


@pytest.mark.parametrize("name", list(serve_load.READINGS))
def test_each_list_is_held_to_its_own_limit_and_has_to_be_read(name):
    sound = readings([0.011] * 32)
    limit = LIMITS[serve_load.READINGS[name]]
    at = dict(sound, **{name: sound[name][:-1] + [limit]})
    over = dict(sound, **{name: sound[name][:-1] + [1.01 * limit]})
    assert serve_load.matches_reference(at, LIMITS)
    assert not serve_load.matches_reference(over, LIMITS)
    assert serve_load.summary(over, LIMITS)[name]["outlier_share"] \
        == 1 / len(sound[name])
    # a gap that passes a relative RMS's limit does not pass its own
    if name.endswith("choice_gap"):
        assert not serve_load.matches_reference(
            dict(sound, **{name: [0.2]}), {**LIMITS, "rel_rms_tol": 0.3})
    # nothing read (no request finished, no position asked for) fails
    nothing = dict(sound, **{name: []})
    assert not serve_load.matches_reference(nothing, LIMITS)
    of = serve_load.summary(nothing, LIMITS)[name]
    assert of["n"] == 0 and of["max"] is None


# ---------------- a routed family's arithmetic, no engine (routed_standin.py)
# read as a serving cell reads a model: from row 16 on (a cell's first
# compared row lies behind a prompt), a relative RMS held to STANDIN_TOL.
# STANDIN_MARGIN, in units of the router's logits, is twice the largest
# margin at which these three seeds flip at this size. That factor fits
# a toy and nothing else: at the widths a chip would hold, 1.3 x the
# largest flipped margin marks 35 % of 131 072 positions and 43 % of a
# million, because the largest grows with the positions read (PERF.md
# section 6, PR 34). These tests keep the arithmetic, not a rule.
STANDIN_SEEDS = (2**31 + 77, 2**31 + 2, 4)
STANDIN_FROM, STANDIN_TOL, STANDIN_MARGIN = 16, 0.015, 0.016
STANDIN_CHECK = {"rel_rms_tol": STANDIN_TOL, "choice_gap_tol": 0.2}


@pytest.fixture(scope="module")
def standin():
    return {seed: {k: v[:, STANDIN_FROM:] for k, v in routed_standin.readings(
        seed, routed_standin.TOY).items() if k != "logit_rms"}
        for seed in STANDIN_SEEDS}


@pytest.mark.parametrize("seed", STANDIN_SEEDS)
def test_the_stand_ins_outliers_are_the_positions_whose_routing_flips(
        seed, standin):
    """bf16 against float32 on the same weights: the positions where a
    choice that involves a held expert flips read far over the limit and
    nothing else does; each of them has a small margin on the float32
    side alone; and the control, the shared expert's operands in fp8,
    reads over the limit at every position, near a tie or not."""
    got = standin[seed]
    flipped, rel_rms = got["flipped"], got["bf16_rel_rms"]
    assert 1 <= flipped.sum() <= 0.03 * flipped.size
    assert rel_rms[flipped].min() > 4 * STANDIN_TOL
    assert rel_rms[~flipped].max() < 0.75 * STANDIN_TOL
    assert 0 < got["margin"].min()
    assert got["margin"][flipped].max() < 0.6 * STANDIN_MARGIN
    near = got["margin"] < STANDIN_MARGIN
    assert flipped.mean() < near.mean() < 0.15      # most near-ties hold
    assert got["fp8_rel_rms"].min() > 1.4 * STANDIN_TOL


def test_a_flip_moves_the_gap_of_the_token_chosen_from_its_row_too(standin):
    """The served tokens' reading at the stand-in's positions: where no
    choice flips the bf16 side puts first the float32 side's best token
    or one within a near-tie of it; of the few flipped positions a toy
    has, some read a gap ten times that (on the chip 27 % of them read
    over ``choice_gap_tol``, up to 4.3: PERF.md section 6, PR 34); the
    fp8 control
    reads gaps over the limit where bf16 reads none."""
    flipped = np.stack([standin[s]["flipped"] for s in STANDIN_SEEDS])
    gap = np.stack([standin[s]["bf16_choice_gap"] for s in STANDIN_SEEDS])
    control = np.stack([standin[s]["fp8_choice_gap"] for s in STANDIN_SEEDS])
    tol = STANDIN_CHECK["choice_gap_tol"]
    assert (gap >= 0).all() and (gap[~flipped] == 0).mean() > 0.9
    assert gap[~flipped].max() < 0.1 * tol
    assert gap[flipped].max() > 5 * gap[~flipped].max()
    assert not (gap > tol).any() and (control > tol).sum() >= 5


def parents_decision(ref: dict, check: dict) -> bool:
    """``serve_load.matches_reference`` as it stood until PR 35, kept
    here to hold the decision of a cell that states no share to."""
    return bool(ref["finite"]) and all(
        ref[name] and all(math.isfinite(x) and x <= check[limit]
                          for x in ref[name])
        for name, limit in serve_load.READINGS.items())


def _with(base=None, **lists):
    return dict(base or readings([0.011] * 32), **lists)


GRID = {
    "sound": _with(),
    "one row over": _with(prefill_rel_rms=[0.011] * 31 + [0.13]),
    "a row behind a decode over": _with(
        after_decode_rel_rms=[0.011] * 15 + [0.031]),
    "every row at the limit": _with(prefill_rel_rms=[0.03] * 32,
                                    after_decode_rel_rms=[0.03] * 16),
    "every row over": _with(prefill_rel_rms=[0.12] * 32),
    "a decode's gap over": _with(decode_choice_gap=[0.0] * 15 + [0.11]),
    "a served gap over": _with(served_choice_gap=[0.0] * 299 + [4.2]),
    "gaps at the limit": _with(served_choice_gap=[0.1] * 300,
                               decode_choice_gap=[0.1] * 16),
    "a request's tokens over, by request": _with(
        served_choice_gap=[0.0] * 280 + [5.0] * 20,
        served_by_request=[[40, 280, 0.0], [33, 20, 5.0]]),
    "sound, by request": _with(
        served_by_request=[[40, 100, 0.0], [33, 200, 0.0]]),
    "not a number": _with(prefill_rel_rms=[0.011] * 31 + [math.nan]),
    "a gap of minus infinity": _with(
        served_choice_gap=[0.0] * 299 + [-math.inf]),
    "infinite": _with(after_decode_rel_rms=[math.inf] * 16),
    "no position read": _with(prefill_rel_rms=[]),
    "no request finished": _with(served_choice_gap=[]),
    "logits not finite": _with(finite=False),
}


@pytest.mark.parametrize("case", list(GRID) + ["routed bf16", "routed fp8"])
def test_with_no_share_stated_the_decision_is_the_parents(case, standin):
    """The llama family's serving cells state no share, and their decision
    is then "every list read, every reading finite and at or under its
    limit", to the letter: on a grid of lists, and on the sequences of a
    sound routed stack, correct where no choice flipped and not correct
    where one did (which is why a routed family's cell states a share),
    and of its fp8 control, correct in none. A share stated as 0 is no
    share. ``compared`` is each list's largest beside its limit."""
    if case.startswith("routed"):
        side = case.split()[1]
        seed = STANDIN_SEEDS[0]
        flips = standin[seed]["flipped"]
        assert 2 <= flips.any(-1).sum() < len(flips)
        refs = [(readings([float(x) for x in rel_rms]), STANDIN_CHECK,
                 side == "bf16" and not flips[sequence].any())
                for sequence, rel_rms in enumerate(
                    standin[seed][f"{side}_rel_rms"])]
    else:
        refs = [(GRID[case], LIMITS, parents_decision(GRID[case], LIMITS))]
    for ref, check, expected in refs:
        assert parents_decision(ref, check) is expected
        assert serve_load.matches_reference(ref, check) is expected
        zero = {**check, "rel_rms_over_share": 0.0,
                "choice_gap_over_share": 0}
        assert serve_load.matches_reference(ref, zero) is expected
        assert serve_load.allowed(len(ref["served_choice_gap"]), 0.0) == 0
        got = serve_load.compared(ref, check)
        assert got == serve_load.compared(ref, zero)
        assert list(got) == list(serve_load.READINGS)
        for name, (largest, limit) in got.items():
            assert limit == check[serve_load.READINGS[name]]
            assert largest == (max(ref[name]) if ref[name] else math.inf) \
                or math.isnan(largest)


# ------------------------------------- a cell that states the two shares
SHARES = {**LIMITS, "rel_rms_over_share": 0.03,
          "choice_gap_over_share": 0.01}


def binomial_tail(n: int, p: float, k: int) -> float:
    """P[Binomial(n, p) > k], summed term by term."""
    return sum(math.comb(n, j) * p ** j * (1 - p) ** (n - j)
               for j in range(k + 1, n + 1))


@pytest.mark.parametrize("n,share", [
    (48, 0.028), (128, 0.03), (128, 0.04), (1000, 0.0077), (48, 0.0077),
    (32, 0.01), (8, 0.03), (1, 0.1), (600, 0.1), (300, 0.000001)])
def test_allowed_is_the_least_count_whose_binomial_tail_is_under_the_risk(
        n, share):
    k = serve_load.allowed(n, share)
    assert 0 <= k <= n
    assert binomial_tail(n, share, k) <= serve_load.RISK
    assert k == 0 or binomial_tail(n, share, k - 1) > serve_load.RISK
    # no share, no allowance; and nothing to allow among no readings
    assert serve_load.allowed(n, 0) == serve_load.allowed(n, 0.0) == 0
    assert serve_load.allowed(0, share) == 0


def test_allowed_against_tails_counted_by_hand():
    """P[X > 0] of one reading at 0.1 is 0.1, over any risk: one may be
    over; of two readings at 0.0005, P[X > 0] = 0.00099975 and P[X > 1] =
    2.5e-7: one may be over at a risk of 1e-6, not two."""
    assert serve_load.RISK == 1e-6
    assert serve_load.allowed(1, 0.1) == 1
    assert serve_load.allowed(2, 0.0005) == 1
    assert serve_load.allowed(2, 0.002) == 2     # P[X > 1] = 4e-6
    # a routed cell's pools (PERF.md section 6, PR 36)
    assert serve_load.allowed(128, 0.06) == 23
    assert serve_load.allowed(48, 0.014) == 7


def test_the_pools_do_not_mix():
    """Nine of ten relative-RMS readings over their limit and not one
    gap over its own: not correct, though of all the readings together
    (48 + 1316) they are 3 %. The count of each kind is held to its own
    allowance."""
    ref = readings([0.13] * 29 + [0.011] * 3, served=[0.0] * 1300)
    ref["after_decode_rel_rms"] = [0.13] * 14 + [0.011] * 2
    assert not serve_load.matches_reference(ref, SHARES)
    got = serve_load.counted(ref, SHARES)
    assert got["rel_rms_over"] == [43, serve_load.allowed(48, 0.03)]
    assert got["choice_gap_over"] == [0, serve_load.allowed(1316, 0.01)]
    assert got["request_choice_gap_over"][0] == 0
    # and a few rows over, as a sound routed engine reads, are correct
    few = readings([0.13] * 3 + [0.011] * 29, served=[0.0] * 1300)
    assert serve_load.matches_reference(few, SHARES)
    assert not serve_load.matches_reference(few, LIMITS)


def test_a_request_is_held_on_its_own():
    """One short request with every token but its first over the limit
    (what a lane's fault reads), beside a thousand sound tokens: the
    pooled count is under the pool's allowance, the request's is over
    its own, and the run is not correct. The same tokens over, spread
    over the requests as a sound engine's are, is correct."""
    short = [0.0] + [5.0] * 15
    by_request = [[100, 500, 0.0], [40, 16, 5.0], [64, 500, 0.0]]
    ref = readings([0.011] * 32,
                   served=[0.0] * 500 + short + [0.0] * 500)
    ref["served_by_request"] = by_request
    got = serve_load.counted(ref, SHARES)
    assert got["choice_gap_over"] == [15, serve_load.allowed(1032, 0.01)]
    assert got["choice_gap_over"][0] <= got["choice_gap_over"][1]
    assert got["request_choice_gap_over"] == [15, serve_load.allowed(16, 0.01)]
    assert not serve_load.matches_reference(ref, SHARES)
    spread = [0.0] * 1016
    for i in range(15):
        spread[13 + 67 * i] = 5.0
    sound = dict(ref, served_choice_gap=spread)
    assert serve_load.counted(sound, SHARES)["choice_gap_over"][0] == 15
    assert serve_load.matches_reference(sound, SHARES)
    # the probe's decodes are a request's tokens too
    probe = dict(sound, decode_choice_gap=[0.0] + [5.0] * 15)
    assert not serve_load.matches_reference(probe, SHARES)
    # without the split the served tokens are one request
    assert serve_load.by_request(readings([0.011] * 32))[1] == [0.0] * 300


def test_a_cell_that_states_a_share_compares_its_counts():
    """``compared``: each pool's count over its limit beside its
    allowance, and the request furthest over its own; numbers that pass
    the last line's contract."""
    from benchmarks import contract

    ref = readings([0.13] * 2 + [0.011] * 30,
                   served=[0.0] * 100 + [0.9] + [0.0] * 199)
    ref["served_by_request"] = [[50, 101, 0.9], [60, 199, 0.0]]
    got = serve_load.compared(ref, SHARES)
    assert got == serve_load.counted(ref, SHARES) == {
        "unread_or_not_finite": [0, 0],
        "rel_rms_over": [2, serve_load.allowed(48, 0.03)],
        "choice_gap_over": [1, serve_load.allowed(316, 0.01)],
        # least under its own allowance: the probe's 16 decodes
        "request_choice_gap_over": [0, serve_load.allowed(16, 0.01)]}
    assert 1 - serve_load.allowed(101, 0.01) < 0 - serve_load.allowed(16, 0.01)
    assert serve_load.matches_reference(ref, SHARES)
    line = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1}, "compared": got}
    assert contract.check_last_line(line, {}, traced=False, chips=1) == []
    # a list not read or a reading that is not finite shows there too
    broken = dict(ref, after_decode_rel_rms=[], prefill_rel_rms=[
        math.nan] + ref["prefill_rel_rms"][1:])
    assert serve_load.counted(broken, SHARES)["unread_or_not_finite"] == [2, 0]
    assert not serve_load.matches_reference(broken, SHARES)
    # one share stated is enough for the counts to be what is compared
    assert list(serve_load.compared(ref, {
        **LIMITS, "choice_gap_over_share": 0.01})) == list(got)


ROUTED_CELL = "routed-standin.serve"


@pytest.mark.parametrize("change,why", [
    ({"rel_rms_over_share": 0.11}, "ceiling 0.1"),
    ({"choice_gap_over_share": 0.031}, "ceiling 0.03"),
    ({"rel_rms_over_share": -0.01}, "from 0 to the ceiling"),
    ({"choice_gap_over_share": "0.01"}, "from 0 to the ceiling"),
    ({"rel_rms_over_share": True}, "from 0 to the ceiling"),
    ({"length": 90}, "first compared row .* is 26"),
    ({"positions": None}, "states its 'positions' too"),
    ({"prompt_len.min": 31}, "least prompt .* is 31"),
])
def test_a_share_over_its_ceiling_or_a_row_before_row_32_is_refused(
        change, why, monkeypatch):
    """Held where a cell is loaded, so before any run: a stated share is
    a number from 0 to its ceiling, and a cell that states one compares
    no row before row 32 (its probe and its traffic's least prompt)."""
    real = spec.load_json
    assert spec.load_cell(ROUTED_CELL, False)["serve"]["reference_check"][
        "rel_rms_over_share"] > 0

    def fake(*parts):
        out = real(*parts)
        if parts == ("workloads", f"{ROUTED_CELL}.json"):
            check = out["serve"]["reference_check"]
            for key, value in change.items():
                if key == "prompt_len.min":
                    out["traffic"]["prompt_len"]["min"] = value
                elif value is None:
                    del check[key]
                else:
                    check[key] = value
        return out

    monkeypatch.setattr(spec, "load_json", fake)
    with pytest.raises(ValueError, match=why):
        spec.load_cell(ROUTED_CELL, False)


def test_a_cell_that_states_no_share_may_compare_early_rows():
    """The rule about row 32 is the share's: a cell whose every reading
    decides may read from any row, as the rehearsals of the llama
    family's serving cells do."""
    cell = spec.load_cell(ROUTED_CELL, False)
    check = cell["serve"]["reference_check"]
    for share in serve_load.SHARE_CEILINGS:
        check[share] = 0
    check["length"] = 80
    cell["traffic"]["prompt_len"]["min"] = 8
    serve_load.check_cell(cell)
    early = [name for name in SERVE if not states_shares(name)
             and spec.load_cell(name, True)["traffic"]["prompt_len"]["min"]
             < serve_load.SHARES_FROM_ROW]
    assert early


def test_a_flip_made_on_purpose_reaches_no_row_before_it():
    at = 24
    by = routed_standin.reach(5, {**routed_standin.TOY, "seqs": 2}, at)
    assert by["before"].max() == 0.0            # causal
    assert by["at"].min() > 4 * STANDIN_TOL     # a held expert more or less
    # behind it, through attention: little, where no choice of the row's
    # own flipped with its moved input
    held = ~by["behind_flipped"]
    assert by["behind"][held].max() < 0.5 * STANDIN_TOL
    assert by["behind_margin"].shape == by["behind"].shape


class _Done:
    def __init__(self, index, prompt_len, tokens, error=None):
        self.request = traffic.Request(index, 0.0, prompt_len, len(tokens))
        self.tokens, self.error = tokens, error


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_the_sample_of_the_window_holds_its_longest_request(seed):
    done = [_Done(i, 10 + 7 * i % 90, [1] * (3 + i % 11)) for i in range(40)]
    done[13] = _Done(13, 95, [1] * 40)                  # the longest
    done[5] = _Done(5, 500, [], error="refused")        # never answered
    done[6] = _Done(6, 500, [])
    got = serve_load.served_sample(done, seed, 8)
    assert len(got) == len({r.request.index for r in got}) == 8
    assert got[0] is done[13]
    assert not {5, 6} & {r.request.index for r in got}
    again = serve_load.served_sample(done, seed, 8)
    assert [r.request.index for r in again] == [r.request.index for r in got]
    other = serve_load.served_sample(done, seed + 1, 8)
    assert [r.request.index for r in other] != [r.request.index for r in got]
    # fewer finished than asked for: all of them; none: nothing
    assert len(serve_load.served_sample(done[:4], seed, 8)) == 4
    assert serve_load.served_sample([done[5], done[6]], seed, 8) == []


def whole_chunk(cell: dict) -> int:
    """The rows of a whole prefill chunk of the engine that serves this
    cell at the sizes it states: what the tests' side of its family
    brings where it brings an engine, else the program's own rule (the
    chunk the cell's ``engine_kwargs`` state, or the one the engine
    derives for bf16 weights on the chips of ``peaks.json``)."""
    sv = cell["serve"]
    own = getattr(controls.of(cell["hp"]), "engine", None)
    if own is not None:
        return own(cell["hp"], None, sv).prefill_chunk
    from ray_tpu.llm._internal.engine import derived_prefill_chunk

    return sv.get("engine_kwargs", {}).get("prefill_chunk") or max(
        derived_prefill_chunk(kind, 2.0, sv["max_seq_len"])
        for kind in spec.load_json("peaks.json"))


def a_count_can_bite(check: dict) -> bool:
    """The allowance of the probe's compared rows at the share the cell
    states is under a quarter of them: the control, over at every row,
    is then four allowances over, and a fault has to touch a quarter of
    the rows at the most to pass."""
    rows = check["positions"] + check["decode_steps"]
    return serve_load.allowed(rows, check["rel_rms_over_share"]) < rows / 4


@pytest.mark.parametrize("positions,decode_steps,share,allowed,bites", [
    (96, 32, 0.06, 23, True),       # the stand-in's probe on the chip
    (32, 16, 0.06, 13, False),      # that share at a probe of 48 rows
    (32, 16, 0.028, 9, True),
    (64, 16, 0.03, 12, True),       # the fixture cell's
])
def test_a_probe_in_which_a_count_can_bite(positions, decode_steps, share,
                                           allowed, bites):
    check = {"positions": positions, "decode_steps": decode_steps,
             "rel_rms_over_share": share}
    assert serve_load.allowed(positions + decode_steps, share) == allowed
    assert a_count_can_bite(check) is bites


@pytest.mark.parametrize("cell", BENCH_SERVE)
def test_a_cell_of_the_benchmark_reads_enough_for_its_decision(cell):
    """Whatever the benchmark's serve cells are and however many: each
    decides on 32 positions or more of a probe that lies behind a whole
    chunk, on 16 decodes or more, and on every served token of a sample
    of 8 or more of the window's requests, and says in ``tolerance_why``
    what its limits were set from. One that states a share states both,
    under the ceilings, compares no row before row 32
    (``test_contract.stated_shares``) and brings a probe in which a count
    can bite; one compared under the engine's own routing choices states
    no share of its rows, so no count of them has to."""
    loaded = spec.load_cell(cell, False)
    check = loaded["serve"]["reference_check"]
    assert "quantile" not in check
    positions = check.get("positions", server.POSITIONS)
    assert positions >= 32
    assert check.get("decode_steps", server.DECODE_STEPS) >= 16
    assert check.get("served_requests", serve_load.SERVED_REQUESTS) >= 8
    assert len(check["tolerance_why"]) > 100
    assert check["length"] - positions >= whole_chunk(loaded)
    if serve_load.routed(check):
        assert "rel_rms_over_share" not in check
        assert "route_margin" in check["tolerance_why"]
    elif stated_shares(loaded):
        assert a_count_can_bite(check), (
            f"{cell}: a probe of {positions} positions and "
            f"{check['decode_steps']} decodes is too short for the share "
            f"{check['rel_rms_over_share']}")


# ------------------------------------------- the engine's own programs
STRETCH_S = 3.0     # of the rehearsal's arrivals, a reading
# the seeds each sort of cell is read on: a cell that states no share on
# the seeds its assertions have held on since PR 33; one that states its
# shares on more of them, since a count has to hold seed after seed
SEEDS = {
    False: {"sound": (3, 2**31 + 5), "control": (3, 2**31 + 5, 2**31 + 77),
            "fault": (2**31 + 5,)},
    True: {"sound": (3, 7, 2**31 + 5, 2**31 + 77, 11, 13),
           "control": (3, 7, 2**31 + 5), "fault": (3, 7)},
}
# how far under ``rel_rms_tol`` a sound engine's rows lie: every row of
# a cell that states no share, and of one that does every row that is
# not over the limit (the fixture's sound rows read 0.0096-0.0108 under
# its limit of 0.015, the stand-in on the chip 0.0109-0.0151 under 0.03)
ROOM = {False: 0.6, True: 0.8}


def by_seed(what: str) -> list:
    return [pytest.param(cell, seed, id=f"{cell}-{seed}") for cell in SERVE
            for seed in SEEDS[states_shares(cell)][what]]


def probe_of(cell, fault=None, every_lane=False):
    probe = serving_control.Probe(cell, rehearse=True, fault=fault,
                                  stretch_s=STRETCH_S)
    # every finished request is read, so what a test sees is no draw
    probe.check = {**probe.check, "served_requests": 10**6}
    if every_lane:      # and no clock in what a test sees
        probe.clients = probe.sv["max_batch_size"]
    return probe


@pytest.fixture(scope="module")
def probes():
    return {name: probe_of(name, every_lane=True) for name in SERVE}


def rows_of(got: dict) -> list:
    return got["prefill_rel_rms"] + got["after_decode_rel_rms"]


def within(counted: dict, *names) -> bool:
    return all(counted[n][0] <= counted[n][1] for n in names or counted)


@pytest.mark.parametrize("cell,seed", by_seed("sound"))
def test_the_sound_program_is_correct_on_every_seed(cell, seed, probes):
    """``correct`` by ``serve_load.matches_reference``, the probe and
    what a stretch of the cell's traffic served together. A cell that
    states no share: every compared row and every gap of the probe under
    its limit with room. A cell that states its shares: its counts at or
    under their allowances, the rows over the limit as many as counted,
    and every other row under it with room."""
    probe = probes[cell]
    check, stating = probe.check, states_shares(cell)
    got = probe.read(seed)
    assert len(got["prefill_rel_rms"]) == check["positions"]
    assert len(got["after_decode_rel_rms"]) == check["decode_steps"]
    assert len(got["decode_choice_gap"]) == check["decode_steps"]
    counted = serve_load.counted(got, check)
    assert serve_load.matches_reference(got, check), (counted, got)
    rows, tol = rows_of(got), check["rel_rms_tol"]
    under = [x for x in rows if x <= tol]
    assert 0 < min(rows) and max(under) <= ROOM[stating] * tol, got
    if routed(cell):
        # every row, gap and margin of the probe decides; the reference
        # was handed a choice of the engine's at a few pairs in a
        # hundred, each where it was itself near a tie
        assert under == rows and within(counted)
        assert serve_load.compared(got, check) == counted
        assert counted["route_margin"] == [max(got["route_margin"]),
                                           check["route_margin_tol"]]
        assert max(got["route_margin"]) <= 0.6 * check["route_margin_tol"]
        assert len(got["route_margin"]) == check["length"] \
            + check["decode_steps"] + 1 + got["rows_read_again"]
        assert 0 < got["routing_differs_share"] < 0.1
        assert counted["shortened_calls_chose_otherwise"] == [0, 0]
        assert max(got["decode_choice_gap"]) <= 0.5 * check["choice_gap_tol"]
    elif stating:
        assert within(counted)
        assert counted["rel_rms_over"] == [
            len(rows) - len(under),
            serve_load.allowed(len(rows), check["rel_rms_over_share"])]
        assert serve_load.compared(got, check) == counted
    else:
        assert under == rows
        assert max(got["decode_choice_gap"]) <= 0.5 * check["choice_gap_tol"]
    # the compared chunk lies behind a whole one, its rows in the cache
    assert check["length"] - check["positions"] >= probe.eng.prefill_chunk


@pytest.mark.parametrize("cell", [c for c in TREE_SERVE if rows_by_count(c)])
def test_some_toy_seed_flips_and_none_is_correct_without_the_share(
        cell, probes):
    """The allowance is used: on some of the fixture cell's seeds a
    compared row's routing flips, and with no share stated such a run is
    not correct, by that row alone."""
    flipped = 0
    for seed in SEEDS[True]["sound"][:3]:
        got, check = probes[cell].read(seed), probes[cell].check
        over = serve_load.counted(got, check)["rel_rms_over"][0]
        none = {k: v for k, v in check.items()
                if k not in serve_load.SHARE_CEILINGS}
        assert serve_load.matches_reference(got, none) is (
            over == 0 and max(got["served_choice_gap"]
                              + got["decode_choice_gap"])
            <= check["choice_gap_tol"])
        flipped += over
    assert flipped >= 2


@pytest.mark.parametrize("cell", SERVE)
def test_what_the_timed_path_served_is_held_as_the_cell_states(cell, probes):
    """A stretch of the cell's traffic through the engine, every lane
    alive: of a cell that states no share every served token of every
    finished request lies at or near the reference's best, the first
    token (the prefill's) among them; of one that states its shares the
    tokens over the limit are within the allowance, all together and
    request by request."""
    probe = probes[cell]
    got = probe.read(2**31 + 11)
    assert probe.peak_alive == probe.sv["max_batch_size"]
    assert len(set(probe.lanes)) == probe.peak_alive     # lane 0 too
    by_request = got["served_by_request"]
    assert len(by_request) >= 6
    assert sum(n for _, n, _ in by_request) == len(got["served_choice_gap"])
    # prompts of more than one bucket, and on docbatch of two chunks
    assert len({next(b for b in probe.eng.buckets + [10**6] if b >= p)
                for p, _, _ in by_request}) > 1
    if states_shares(cell):
        assert within(serve_load.counted(got, probe.check),
                      *served_counts(cell))
    else:
        assert max(got["served_choice_gap"]) \
            <= 0.5 * probe.check["choice_gap_tol"]
        assert got["served_agree_share"] > 0.9
    assert serve_load.matches_reference(got, probe.check)


def test_served_readings_find_the_rows_behind_the_padding():
    """The reference's pass is padded behind the tokens and its head
    taken over whole blocks of rows: the gap read for each served token
    is the one a plain pass over prompt and answer gives."""
    import numpy as np

    class Family:
        @staticmethod
        def reference_logits(params, tokens, hp, last=0):
            # a "model" whose row p prefers token (7 p + sum of the
            # tokens so far) % vocab by 1.0 over the one behind it by 0.25
            tokens = np.asarray(tokens)
            out = np.zeros((len(tokens), hp["vocab_size"]), np.float32)
            for p in range(len(tokens)):
                best = (7 * p + int(tokens[:p + 1].sum())) % hp["vocab_size"]
                out[p, best] = 1.0
                out[p, (best + 1) % hp["vocab_size"]] = 0.75
            return out[-last:] if last else out

    hp = {"vocab_size": 97}
    served = []
    for plen, n in ((5, 3), (511, 2), (512, 9), (700, 130), (1, 1)):
        seq = [(3 * i) % 97 for i in range(plen)]
        answer = []
        for _ in range(n):
            answer.append((7 * (len(seq) - 1) + sum(seq)) % 97)
            seq.append(answer[-1])
        served.append((seq[:plen], answer))
    got = server.served_readings(None, Family, hp, served)
    assert got["served_choice_gap"] == [0.0] * sum(
        len(a) for _, a in served)
    assert got["served_agree_share"] == 1.0
    # the second best everywhere, and one token that is neither
    off = [(p, [(t + 1) % 97 for t in a[:1]]) for p, a in served]
    assert server.served_readings(None, Family, hp, off)[
        "served_choice_gap"] == [0.25] * len(served)
    wrong = [(served[3][0], served[3][1][:50] + [(served[3][1][50] + 2) % 97])]
    gaps = server.served_readings(None, Family, hp, wrong)["served_choice_gap"]
    assert gaps[:50] == [0.0] * 50 and gaps[50] == 1.0
    assert got["served_by_request"][3] == [700, 130, 0.0]


@pytest.mark.parametrize("cell,seed", by_seed("control"))
def test_the_control_moves_every_position_over_the_limit(cell, seed):
    """One sublayer's operands in fp8, nothing else changed (the
    ``fp8()`` of the cell's family, the engine built and traced under
    it): every one of the readings through the prefill and behind the
    decodes is over the cell's limit, and ``correct`` is false; where
    the cell states a share, by four allowances or more."""
    with controls.of(spec.load_cell(cell, True)["hp"]).fp8():
        probe = probe_of(cell)
        got = probe.read(seed)
    tol = probe.check["rel_rms_tol"]
    assert min(got["prefill_rel_rms"]) > tol, got
    assert min(got["after_decode_rel_rms"]) > tol, got
    assert not serve_load.matches_reference(got, probe.check)
    of = serve_load.summary(got, probe.check)
    assert of["prefill_rel_rms"]["outlier_share"] == 1.0
    assert of["after_decode_rel_rms"]["outlier_share"] == 1.0
    if routed(cell):
        # its own choices handed over, so its flips go too: what is left
        # is the lower precision, at every row
        assert serve_load.counted(got, probe.check)["prefill_rel_rms"][0] > tol
    elif states_shares(cell):
        count, most = serve_load.counted(got, probe.check)["rel_rms_over"]
        assert count == len(rows_of(got)) > 4 * most


@pytest.mark.parametrize("cell", SERVE)
def test_a_token_altered_where_it_is_produced_fails_the_choice_gap(cell):
    """The decode program's tokens each moved to the next of the
    vocabulary: the rows it wrote and the logits behind them are still
    that token's, so the two lists of relative RMS read as a sound
    engine's (under their limit, or within the allowance of a cell that
    states one) and the choice gaps alone are over their own, the
    probe's at every step."""
    vocab = spec.load_cell(cell, True)["hp"]["vocab_size"]
    probe = probe_of(cell, fault=serving_control.next_token(vocab))
    got = probe.read(2**31 + 5)
    check = probe.check
    assert not serve_load.matches_reference(got, check)
    assert min(got["decode_choice_gap"]) > check["choice_gap_tol"], got
    if rows_by_count(cell):
        assert within(serve_load.counted(got, check), "rel_rms_over")
    else:
        assert max(rows_of(got)) <= check["rel_rms_tol"]
    sound = dict(got, decode_choice_gap=[0.0] * check["decode_steps"],
                 served_choice_gap=[0.0], served_by_request=None)
    assert serve_load.matches_reference(sound, check)


def by_fault() -> list:
    return [pytest.param(cell, fault, seed, id=f"{cell}-{fault}-{seed}")
            for cell in SERVE for fault in ("one_lane", "crossed")
            for seed in SEEDS[states_shares(cell)]["fault"]]


@pytest.mark.parametrize("cell,fault,seed", by_fault())
def test_a_fault_outside_the_probes_lane_fails_the_served_tokens(
        cell, fault, seed, probes):
    """The timed path broken underneath, the rest of the comparison as a
    run drives it. A token altered where it is produced in ONE lane that
    is not lane 0: the seeded probe (lane 0, the others idle) reads as
    on a sound engine, and the served tokens of the requests that passed
    through that lane read whole logits under the reference's best.
    Lanes given each other's tokens: the probe's decode gaps may see it,
    the served tokens do, in every lane that decoded. A cell that states
    its shares sees either by the request furthest over its own
    allowance (``request_choice_gap_over``): every decoded token of a
    request through the faulty lane, where a sound engine reads a few in
    a hundred."""
    loaded = spec.load_cell(cell, True)
    lane = loaded["serve"]["max_batch_size"] - 2      # the second filled
    change = (serving_control.next_token(loaded["hp"]["vocab_size"], lane)
              if fault == "one_lane" else serving_control.crossed)
    probe = probe_of(cell, fault=change, every_lane=True)
    got = probe.read(seed)
    check, stating = probe.check, states_shares(cell)
    tol = check["choice_gap_tol"]
    assert not serve_load.matches_reference(got, check)
    if stating:
        counted = serve_load.counted(got, check)
        assert not within(counted, "request_choice_gap_over")
        if routed(cell):
            assert max(rows_of(got)) <= check["rel_rms_tol"]
            assert within(counted, "route_margin")
        else:
            assert within(counted, "rel_rms_over")
    else:
        assert max(got["served_choice_gap"]) > 4 * tol, got
        assert max(rows_of(got)) <= check["rel_rms_tol"]
    if fault != "one_lane":
        return
    assert lane != 0 and any(at == lane for _, at in probe.lanes)
    # but for the served tokens the run would have read correct
    assert serve_load.matches_reference(
        dict(got, served_choice_gap=[0.0], served_by_request=None), check)
    if not stating:
        assert max(got["decode_choice_gap"]) <= tol
        # the requests of the other lanes read as on a sound engine
        assert sum(g > tol for _, _, g in got["served_by_request"]) \
            < len(got["served_by_request"])
        return
    # the probe is blind to it: its lists are the sound side's
    sound = probes[cell].read(seed, served=False)
    for name in ("prefill_rel_rms", "after_decode_rel_rms",
                 "decode_choice_gap"):
        assert got[name] == sound[name]
    # every decoded token (all but the prefill's) of a request through
    # the lane; the others as on a sound engine
    share = check["choice_gap_over_share"]
    for (_, at), gaps in zip(got["lanes"], serve_load.by_request(got)[1:]):
        over = sum(g > tol for g in gaps)
        if at == lane:
            assert over >= len(gaps) - 2 > serve_load.allowed(len(gaps), share)
        else:
            assert over <= serve_load.allowed(len(gaps), share)


PROBE_LISTS = ("prefill_rel_rms", "after_decode_rel_rms",
               "decode_choice_gap")


@pytest.fixture(scope="module")
def under_both_protocols(probes):
    """cell -> (its sound engine's probe readings as ``server.probe_rows``
    reads them, and as ``serving_control.parents_probe_rows`` did until
    PR 44), on one seed and the same weights; read once a cell."""
    @functools.lru_cache(maxsize=None)
    def read(cell):
        probe = probes[cell]
        seed = SEEDS[states_shares(cell)]["sound"][0]
        sound = probe.read(seed, served=False)
        with serving_control.parents_protocol():
            return sound, probe.read(seed, served=False)
    return read


@pytest.mark.parametrize("cell", SERVE)
def test_no_call_is_repeated_on_a_cache_that_holds_its_result(
        cell, probes, under_both_protocols):
    """``server.probe_rows`` runs every call that only reads a row on a
    copy of the cache. Against the protocol it replaced (the last chunk
    dispatched ``positions`` times into the real cache, the token behind
    each decode fed twice), on the same engine and seed, one of two
    things is observed, and which is not asked of the cell, its family's
    sizes or its cache's leaves. Either a call made twice leaves the
    cache as a call made once (rows a position: keys and values, latent
    rows): the three lists are then equal to the last digit. Or it does
    not (a recurrent state, a convolution's tail): then only the first
    call, a request's own, reads as under the new protocol, every other
    compared row reads over the limit and well over what the new
    protocol reads, and the parent's protocol would have had a sound
    engine not correct on every run. Nothing between the two passes."""
    check = probes[cell].check
    sound, parents = under_both_protocols(cell)
    served = {"served_choice_gap": [0.0], "served_by_request": None}
    assert serve_load.matches_reference({**sound, **served}, check)
    if all(parents[name] == sound[name] for name in PROBE_LISTS):
        return
    assert parents["prefill_rel_rms"][0] == sound["prefill_rel_rms"][0]
    twice = rows_of(parents)[1:]
    assert min(twice) > check["rel_rms_tol"], parents
    assert min(twice) > 2 * max(x for x in rows_of(sound)
                                if x <= check["rel_rms_tol"])
    assert not serve_load.matches_reference({**parents, **served}, check)


def test_the_trees_own_cells_keep_both_sides_of_that_door(
        under_both_protocols):
    """The keeper, by the fixture cells' names (the tests' tree is the
    tests' own): of its serve cells ``hybrid-standin.serve`` alone has
    an engine whose cache a call made twice alters, so the test above
    takes each of its two ways on some cell whatever BENCHMARK.json
    declares."""
    def differ(cell):
        sound, parents = under_both_protocols(cell)
        return any(parents[name] != sound[name] for name in PROBE_LISTS)

    altered = [cell for cell in TREE_SERVE if differ(cell)]
    assert altered == ["hybrid-standin.serve"]
    assert len(TREE_SERVE) > len(altered)


# ------------------------------ under the engine's own routing choices
ROUTED = [c for c in SERVE if routed(c)]
ROUTED_LIMITS = {**LIMITS, "routing": "engine", "route_margin_tol": 0.05,
                 "choice_gap_over_share": 0.01}


def routed_readings(**lists):
    return {**readings([0.011] * 32), "route_margin": [0.0] * 40 + [0.02],
            "shortened_calls_chose_otherwise": 0,
            "served_by_request": [[40, 100, 0.0], [33, 200, 0.0]], **lists}


@pytest.mark.parametrize("lists,passes", [
    ({}, True),
    # no row is spared: the cell states no share of them
    ({"prefill_rel_rms": [0.011] * 31 + [0.0301]}, False),
    ({"after_decode_rel_rms": [0.011] * 15 + [0.13]}, False),
    ({"prefill_rel_rms": [0.03] * 32}, True),
    # a choice the reference would not have made, far from a tie
    ({"route_margin": [0.0] * 40 + [0.051]}, False),
    ({"route_margin": [0.05] * 41}, True),
    ({"route_margin": []}, False),
    ({"route_margin": [0.0, math.nan]}, False),
    ({"route_margin": [0.0, math.inf]}, False),
    ({"shortened_calls_chose_otherwise": 1}, False),
    # the probe's decodes are read under the engine's choices: every gap
    # decides, and none is pooled with the served tokens
    ({"decode_choice_gap": [0.0] * 15 + [0.11]}, False),
    # the served tokens carry no choices: counted at the stated share,
    # all together and request by request
    ({"served_choice_gap": [0.0] * 50 + [0.9] + [0.0] * 249}, True),
    ({"served_choice_gap": [0.0] * 100 + [5.0] * 20 + [0.0] * 180,
      "served_by_request": [[40, 100, 0.0], [33, 20, 5.0], [9, 180, 0.0]]},
     False),
    ({"served_choice_gap": []}, False),
])
def test_under_the_engines_choices_every_probe_reading_decides(lists, passes):
    """The rule of a cell that says ``"routing": "engine"``, on made-up
    readings: the probe's rows, gaps and margins each at or under their
    limit at every reading, no shortened call that chose otherwise, and
    the served tokens by their counts; ``compared`` is what was held,
    the largest readings beside their limits and the counts beside
    their allowances, and passes the last line's contract."""
    from benchmarks import contract

    ref = routed_readings(**lists)
    assert serve_load.matches_reference(ref, ROUTED_LIMITS) is passes
    got = serve_load.compared(ref, ROUTED_LIMITS)
    assert got == serve_load.counted(ref, ROUTED_LIMITS)
    assert list(got) == [
        "unread_or_not_finite", "shortened_calls_chose_otherwise",
        "prefill_rel_rms", "after_decode_rel_rms", "decode_choice_gap",
        "route_margin", "served_choice_gap_over", "request_choice_gap_over"]
    assert got["route_margin"][1] == 0.05
    assert got["served_choice_gap_over"][1] == serve_load.allowed(
        len(ref["served_choice_gap"]), 0.01)
    assert "route_margin" in serve_load.summary(ref, ROUTED_LIMITS)
    assert "route_margin" not in serve_load.summary(ref, LIMITS)
    # without a stated share of tokens every served token decides too
    none = {k: v for k, v in ROUTED_LIMITS.items()
            if k != "choice_gap_over_share"}
    assert serve_load.matches_reference(ref, none) is (
        passes and max(ref["served_choice_gap"], default=1.0) <= 0.1)
    line = {"correct": passes, "attempted": 1, "failed": 0, "metrics": {},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1},
            "compared": {k: [x if math.isfinite(x) else None for x in v]
                         for k, v in got.items()}}
    assert contract.check_last_line(line, {}, traced=False, chips=1) == []


HYBRID_CELL = "hybrid-standin.serve"


@pytest.mark.parametrize("change,why", [
    ({"routing": "reference"}, "the one thing it may say is"),
    ({"routing": True}, "the one thing it may say is"),
    ({"rel_rms_over_share": 0.03}, "states no share of them"),
    ({"route_margin_tol": None}, "route_margin_tol is None"),
    ({"route_margin_tol": 0}, "a number over 0"),
    ({"route_margin_tol": "0.08"}, "a number over 0"),
    ({"route_margin_tol": True}, "a number over 0"),
    ({"choice_gap_over_share": 0.031}, "ceiling 0.03"),
    ({"length": 90}, "first compared row .* is 26"),
    ({"family": "toy_moe"}, "gives no reference_routed"),
])
def test_a_cell_under_the_engines_choices_is_held_when_it_is_loaded(
        change, why, monkeypatch):
    """``serve_load.check_cell`` of a cell whose ``reference_check`` has
    the key ``routing``: it says ``"engine"``, states no share of its
    rows, states its ``route_margin_tol``, and its family gives
    ``reference_routed``; what it states of its served tokens is held as
    any cell's share is."""
    real = spec.load_json
    assert serve_load.routed(spec.load_cell(HYBRID_CELL, False)["serve"][
        "reference_check"])

    def fake(*parts):
        out = real(*parts)
        if parts[0] == "configs" and "family" in change:
            out["family"] = change["family"]
        if parts == ("workloads", f"{HYBRID_CELL}.json"):
            for key, value in change.items():
                if key == "family":
                    continue
                if value is None:
                    del out["serve"]["reference_check"][key]
                else:
                    out["serve"]["reference_check"][key] = value
        return out

    monkeypatch.setattr(spec, "load_json", fake)
    with pytest.raises(ValueError, match=why):
        spec.load_cell(HYBRID_CELL, False)


def by_router_fault() -> list:
    return [pytest.param(cell, seed, id=f"{cell}-{seed}") for cell in ROUTED
            if hasattr(controls.of(spec.load_cell(cell, True)["hp"]),
                       "router_fault")
            for seed in SEEDS[True]["control"]]


@pytest.mark.parametrize("cell,seed", by_router_fault())
def test_a_router_fault_fails_by_the_margin_alone(cell, seed):
    """The fault only such a cell can have: the engine takes, at one row
    in 32 of its first routed layer, the best held expert it had passed
    over, and says so. The reference follows, so every compared row
    reads as a sound engine's; what it was handed lies far under its own
    k-th best at some faulted row, and the margin alone fails the run."""
    with controls.of(spec.load_cell(cell, True)["hp"]).router_fault():
        probe = probe_of(cell)
        got = probe.read(seed)
    check = probe.check
    counted = serve_load.counted(got, check)
    assert not serve_load.matches_reference(got, check)
    assert counted["route_margin"][0] > 1.5 * check["route_margin_tol"]
    assert max(rows_of(got)) <= ROOM[True] * check["rel_rms_tol"]
    assert within({k: v for k, v in counted.items() if k != "route_margin"})
    assert serve_load.matches_reference(dict(got, route_margin=[0.0]), check)


@pytest.mark.parametrize("cell", [c for c in TREE_SERVE if routed(c)])
def test_without_the_key_the_same_cell_stands_before_the_wall(cell, probes):
    """The same engine, weights and probe against the reference's OWN
    choices, as the cell was compared until PR 61: a flip is carried
    through the state layers behind it into the rows that follow, so on
    every seed more rows read over the limit than any share a cell may
    state allows, and most of them over all the seeds; the control reads
    over at every row either way, so no limit and no count stands
    between the two (PERF.md section 6, PR 44)."""
    probe = probes[cell]
    own = {k: v for k, v in probe.check.items()
           if k not in ("routing", "route_margin_tol")}
    tol, over, rows = own["rel_rms_tol"], 0, 0
    for seed in SEEDS[True]["sound"][:3]:
        got = server.reference_readings(
            probe._engine(seed), probe.family, seed, probe.hp, own)
        assert "route_margin" not in got
        mine = sum(x > tol for x in rows_of(got))
        assert mine > serve_load.allowed(
            len(rows_of(got)), serve_load.SHARE_CEILINGS["rel_rms_over_share"])
        over, rows = over + mine, rows + len(rows_of(got))
    assert over > rows / 2


@pytest.mark.parametrize("cell", TREE_SERVE)
def test_an_engine_that_cannot_say_what_it_chose(cell):
    """Under a cell that asks for the engine's choices it fails by the
    sentence, which names ``read_choices`` (``serve_load.run`` raises the
    same before its window, with the cell's name); under any other cell
    it is read as an engine that can, to the last digit: the door is
    asked for by the cell, not taken because it is there."""
    probe, seed = probe_of(cell), SEEDS[states_shares(cell)]["sound"][0]
    eng = probe._engine(seed)
    if getattr(eng, "read_choices", None) is None:
        assert not routed(cell)
        return
    with_door = None if routed(cell) else probe.read(seed, served=False)
    eng.read_choices = None
    if routed(cell):
        with pytest.raises(RuntimeError, match=r"gives no\s+read_choices"):
            probe.read(seed, served=False)
        assert f"cell {cell}: " in server.no_door(f"cell {cell}")
        return
    without = probe.read(seed, served=False)
    for name in PROBE_LISTS:
        assert without[name] == with_door[name]
    assert "route_margin" not in without


# ---------------------------------------------------- a probe that fits
class _Engine:
    prefill_chunk, buckets, max_seq = 32, [16, 32], 128


@pytest.mark.parametrize("check,why", [
    ({"length": 40, "positions": 16}, "16 positions or more in the last"),
    ({"length": 24, "positions": 8}, "one whole chunk"),
    ({"length": 32, "positions": 8}, "one whole chunk"),
    ({"length": 104, "positions": 8, "decode_steps": 16}, "do not fit"),
])
def test_a_probe_that_does_not_cross_a_chunk_or_fit_is_refused(check, why):
    with pytest.raises(ValueError, match=why):
        server.reference_readings(
            _Engine(), None, 1, {"vocab_size": 64}, check)


def _leaf(device, nbytes):
    import types

    return types.SimpleNamespace(addressable_shards=[types.SimpleNamespace(
        device=device, data=types.SimpleNamespace(nbytes=nbytes))])


@pytest.mark.parametrize("stats,fits", [
    # the probe keeps one copy of the cache alive: the bytes the cache
    # holds on a chip have to be free there once more
    ({"bytes_limit": 1000, "bytes_in_use": 600}, True),
    ({"bytes_limit": 1000, "bytes_in_use": 601}, False),
    # a device that says nothing (the CPU of a rehearsal) is not asked
    ({}, True),
    (None, True),
])
def test_a_cache_with_no_room_for_its_copy_is_refused_by_name(stats, fits):
    class Device:
        def memory_stats(self):
            return stats

    device = Device()
    cache = {"rows": _leaf(device, 300), "more": [_leaf(device, 100)]}
    if fits:
        return server.room_for_a_copy(cache)
    with pytest.raises(RuntimeError, match="copy of the cache, 400 bytes"):
        server.room_for_a_copy(cache)
