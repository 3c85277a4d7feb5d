"""The serving comparison with the reference: the decision rule on
made-up readings; on the cells' rehearsal engines, in this process,
``server.reference_readings`` (the seeded probe, read at many positions)
and ``server.served_readings`` over a sample of what a stretch of the
cell's traffic through ``engine.step()`` finished; the control of PERF.md
section 4 at a size a test can hold (``models/llama.py``'s
``mlp_sublayer`` with its matmuls' operands in fp8, patched here and
never in the program), which has to move *every* position's reading over
the limit where the program as it is stays under it with room; and the
faults a serving cell can have, planted under the timed path where the
decode program's tokens are produced, in a lane that is not the probe's.
``serving_control.py`` beside this file reads the same on the chip at the
cells' own sizes. And ``routed_standin.py``, a routed family's arithmetic
and the two programs an engine has of it, at a test's size: what the
decision makes of a sound routed stack where the cell states no share
(every position decides, as at the parent), and, through the fixture
cell ``routed-standin.serve`` of the tests' tree, which states the two
shares of its own engine's readings over the limits, the real path:
``spec.load_cell``, ``server.reference_readings``,
``server.served_readings`` and ``serve_load.matches_reference`` on the
sound side, the fp8 control and a token altered in one lane (PERF.md
section 6, PR 36, has the chip's readings)."""

import math

import numpy as np
import pytest
import routed_standin
import serving_control
from test_first_forward import mlp_in_fp8

from benchmarks import serve_load, server, spec, traffic

SERVE = [w["name"] for w in spec.benchmark_json()["workloads"]
         if spec.load_cell(w["name"], True)["kind"] == "serve"]
LIMITS = {"rel_rms_tol": 0.03, "choice_gap_tol": 0.1}


def readings(rel_rms, gaps=(0.0,) * 16, finite=True, served=(0.0,) * 300):
    return {"prefill_rel_rms": list(rel_rms),
            "after_decode_rel_rms": [0.011] * 16,
            "decode_choice_gap": list(gaps),
            "served_choice_gap": list(served), "finite": finite}


# --------------------------------------------------------------- the rule
@pytest.mark.parametrize("rel_rms,passes", [
    # one position in 32 over the limit, wherever it falls: no position
    # is spared (a routed family's flipped choice would read so; none is
    # in the benchmark, and a rule for one waits for its readings)
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
    """The three serving cells state no share, and their decision is
    then "every list read, every reading finite and at or under its
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


def test_a_cell_that_states_no_share_may_compare_early_rows(monkeypatch):
    """The rule about row 32 is the share's: a cell whose every reading
    decides may read from any row, as the rehearsals of the three
    serving cells do."""
    cell = spec.load_cell(ROUTED_CELL, False)
    check = cell["serve"]["reference_check"]
    for share in serve_load.SHARE_CEILINGS:
        check[share] = 0
    check["length"] = 80
    cell["traffic"]["prompt_len"]["min"] = 8
    serve_load.check_cell(cell)
    for name in SERVE:
        for rehearse in (False, True):
            loaded = spec.load_cell(name, rehearse)
            assert not any(share in loaded["serve"]["reference_check"]
                           for share in serve_load.SHARE_CEILINGS)


# ---------------- the routed stand-in's two programs, through the real path
ROUTED_SEEDS = (3, 7, 2**31 + 5, 2**31 + 77, 11, 13)
ROUTED_FAULTY_LANE = 2


@pytest.fixture(scope="module")
def routed():
    """The fixture cell as ``spec.load_cell`` gives it, its family found
    by name, and the family's engine three times: sound, the control
    (the shared expert's operands in fp8), and a token altered where it
    is produced in one lane that is not the probe's."""
    cell = spec.load_cell(ROUTED_CELL, True)
    hp, sv = cell["hp"], cell["serve"]
    family = spec.family_of(hp)
    engines = {"sound": family.engine(hp, None, sv),
               "control": family.engine(hp, None, sv, side="fp8"),
               "fault": family.engine(hp, None, sv)}
    engines["fault"]._decode = serving_control.broken(
        engines["fault"]._decode, serving_control.next_token(
            hp["vocab_size"], ROUTED_FAULTY_LANE))
    return cell, family, engines


def routed_read(routed, side, seed):
    """What a run's comparison reads of one engine: the seeded probe,
    and every request of the cell's traffic the engine answered."""
    cell, family, engines = routed
    hp, check = cell["hp"], cell["serve"]["reference_check"]
    eng = engines[side]
    eng.params = family.init_params(spec.prng_key(seed),
                                    family.model_config(hp))
    requests = traffic.closed_loop(cell["traffic"], seed)
    got = serving_control.standin_read(
        eng, family, seed, hp, check,
        [(traffic.prompt_tokens(seed, r, hp["vocab_size"]), r.max_tokens)
         for r in requests])
    assert [n for _, n, _ in got["served_by_request"]] == [
        r.max_tokens for r in requests]
    return got, check, got["lanes"]


@pytest.mark.parametrize("seed", ROUTED_SEEDS)
def test_the_routed_stand_in_is_correct_under_its_stated_shares(seed, routed):
    """Through ``reference_readings``, ``served_readings`` and
    ``matches_reference``: the rows whose routing flips read far over
    the limit and every other row under it with room, and their count
    is inside the allowance of the share the cell states."""
    got, check, _ = routed_read(routed, "sound", seed)
    assert len(got["prefill_rel_rms"]) == check["positions"]
    assert len(got["after_decode_rel_rms"]) == check["decode_steps"]
    assert serve_load.matches_reference(got, check), serve_load.counted(
        got, check)
    tol = check["rel_rms_tol"]
    rows = got["prefill_rel_rms"] + got["after_decode_rel_rms"]
    over = [x for x in rows if x > tol]
    assert len(over) <= 0.05 * len(rows)
    assert all(x > 4 * tol for x in over)
    assert max(x for x in rows if x <= tol) < 0.8 * tol
    counted = serve_load.counted(got, check)
    assert counted["rel_rms_over"] == [len(over), serve_load.allowed(
        len(rows), check["rel_rms_over_share"])]
    assert serve_load.compared(got, check) == counted
    # every request read, each on its own
    assert len(got["served_by_request"]) == routed[0]["traffic"]["pool"]


def test_some_toy_seed_flips_and_none_is_correct_without_the_share(routed):
    """The allowance is used: on some of these seeds a compared row's
    routing flips, and with no share stated such a run is not correct,
    by that row alone."""
    flipped = 0
    for seed in ROUTED_SEEDS[:3]:
        got, check, _ = routed_read(routed, "sound", seed)
        over = serve_load.counted(got, check)["rel_rms_over"][0]
        none = {k: v for k, v in check.items()
                if k not in serve_load.SHARE_CEILINGS}
        assert serve_load.matches_reference(got, none) is (
            over == 0 and max(got["served_choice_gap"]
                              + got["decode_choice_gap"])
            <= check["choice_gap_tol"])
        flipped += over
    assert flipped >= 2


@pytest.mark.parametrize("seed", ROUTED_SEEDS[:3])
def test_the_routed_stand_ins_control_is_not_correct(seed, routed):
    """The shared expert's operands in fp8: every compared row over the
    limit, far over any allowance."""
    got, check, _ = routed_read(routed, "control", seed)
    assert not serve_load.matches_reference(got, check)
    count, most = serve_load.counted(got, check)["rel_rms_over"]
    assert count == check["positions"] + check["decode_steps"] > 5 * most
    assert min(got["prefill_rel_rms"] + got["after_decode_rel_rms"]) \
        > 1.5 * check["rel_rms_tol"]


@pytest.mark.parametrize("seed", ROUTED_SEEDS[:2])
def test_a_token_altered_in_one_lane_of_the_routed_stand_in_is_not_correct(
        seed, routed):
    """The probe (lane 0) reads as on the sound engine; the requests
    through the faulty lane read over the limit at every decoded token
    and are over their own allowance; those through the other lanes are
    not."""
    got, check, lanes = routed_read(routed, "fault", seed)
    sound, _, _ = routed_read(routed, "sound", seed)
    assert not serve_load.matches_reference(got, check)
    for name in ("prefill_rel_rms", "after_decode_rel_rms",
                 "decode_choice_gap"):
        assert got[name] == sound[name]
    tol, share = check["choice_gap_tol"], check["choice_gap_over_share"]
    assert ROUTED_FAULTY_LANE in lanes and ROUTED_FAULTY_LANE != 0
    for lane, gaps in zip(lanes, serve_load.by_request(got)[1:]):
        over = sum(g > tol for g in gaps)
        if lane == ROUTED_FAULTY_LANE:      # all but the prefill's token
            assert over >= len(gaps) - 2 > serve_load.allowed(len(gaps), share)
        else:
            assert over <= serve_load.allowed(len(gaps), share)
    # but for the served tokens the run would have read correct
    assert serve_load.matches_reference(
        dict(got, served_choice_gap=[0.0], served_by_request=None), check)


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


def test_the_cells_of_the_benchmark_read_every_position_and_the_window():
    """The three serving cells decide on every position, 32 + 16 + 16 of
    the probe behind a whole chunk, and on every served token of a
    sample of the window's requests."""
    assert len(SERVE) == 3
    for name in SERVE:
        check = spec.load_cell(name, False)["serve"]["reference_check"]
        assert "quantile" not in check
        assert (check["length"], check["positions"],
                check["decode_steps"]) == (384, 32, 16)
        assert check["served_requests"] >= 8
        assert len(check["tolerance_why"]) > 100


# ------------------------------------------- the engine's own programs
STRETCH_S = 3.0     # of the rehearsal's arrivals, a reading


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


@pytest.mark.parametrize("cell", SERVE)
def test_the_program_reads_under_the_limit_at_every_position(cell, probes):
    probe = probes[cell]
    check = probe.check
    for seed in (3, 2**31 + 5):
        got = probe.read(seed)
        assert len(got["prefill_rel_rms"]) == check["positions"]
        assert len(got["after_decode_rel_rms"]) == check["decode_steps"]
        assert len(got["decode_choice_gap"]) == check["decode_steps"]
        assert serve_load.matches_reference(got, check), got
        both = got["prefill_rel_rms"] + got["after_decode_rel_rms"]
        assert 0 < min(both) and max(both) <= 0.6 * check["rel_rms_tol"], got
        assert max(got["decode_choice_gap"]) <= 0.5 * check["choice_gap_tol"]
    # the compared chunk lies behind a whole one, its rows in the cache
    assert check["length"] - check["positions"] >= probe.eng.prefill_chunk


@pytest.mark.parametrize("cell", SERVE)
def test_what_the_timed_path_served_reads_under_the_limit(cell, probes):
    """A stretch of the cell's traffic through ``engine.step()``, more
    than one lane alive: every served token of every finished request
    lies at or near the reference's best, the first token (the prefill's)
    among them."""
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
    assert max(got["served_choice_gap"]) <= 0.5 * probe.check["choice_gap_tol"]
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


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**31 + 77])
@pytest.mark.parametrize("cell", SERVE)
def test_the_control_moves_every_position_over_the_limit(cell, seed,
                                                         monkeypatch):
    """One sublayer's operands in fp8, nothing else changed: every one
    of the readings through the prefill and behind the decodes is over
    the cell's limit."""
    from ray_tpu.models import llama

    monkeypatch.setattr(llama, "mlp_sublayer", mlp_in_fp8)
    probe = probe_of(cell)                               # traced patched
    got = probe.read(seed)
    tol = probe.check["rel_rms_tol"]
    assert min(got["prefill_rel_rms"]) > tol, got
    assert min(got["after_decode_rel_rms"]) > tol, got
    assert not serve_load.matches_reference(got, probe.check)
    of = serve_load.summary(got, probe.check)
    assert of["prefill_rel_rms"]["outlier_share"] == 1.0
    assert of["after_decode_rel_rms"]["outlier_share"] == 1.0


@pytest.mark.parametrize("cell", SERVE)
def test_a_token_altered_where_it_is_produced_fails_the_choice_gap(cell):
    """The decode program's tokens each moved to the next of the
    vocabulary: the rows it wrote and the logits behind them are still
    that token's, so the two lists of relative RMS stay under their
    limit and the choice gaps alone are over their own, the probe's at
    every step."""
    vocab = spec.load_cell(cell, True)["hp"]["vocab_size"]
    probe = probe_of(cell, fault=serving_control.next_token(vocab))
    got = probe.read(2**31 + 5)
    check = probe.check
    assert not serve_load.matches_reference(got, check)
    assert min(got["decode_choice_gap"]) > check["choice_gap_tol"], got
    assert max(got["prefill_rel_rms"] + got["after_decode_rel_rms"]) \
        <= check["rel_rms_tol"]
    sound = dict(got, decode_choice_gap=[0.0] * check["decode_steps"],
                 served_choice_gap=[0.0])
    assert serve_load.matches_reference(sound, check)


@pytest.mark.parametrize("fault", ["one_lane", "crossed"])
@pytest.mark.parametrize("cell", SERVE)
def test_a_fault_outside_the_probes_lane_fails_the_served_tokens(cell, fault):
    """The timed path broken underneath, the rest of the comparison as a
    run drives it. A token altered where it is produced in ONE lane that
    is not lane 0: the seeded probe (lane 0, the others idle) reads as
    on a sound engine, and the served tokens of the requests that passed
    through that lane read whole logits under the reference's best.
    Lanes given each other's tokens: the probe's decode gaps may see it,
    the served tokens do, in every lane that decoded."""
    loaded = spec.load_cell(cell, True)
    lane = loaded["serve"]["max_batch_size"] - 2      # the second filled
    change = (serving_control.next_token(loaded["hp"]["vocab_size"], lane)
              if fault == "one_lane" else serving_control.crossed)
    probe = probe_of(cell, fault=change, every_lane=True)
    got = probe.read(2**31 + 5)
    check = probe.check
    assert not serve_load.matches_reference(got, check)
    assert max(got["served_choice_gap"]) > 4 * check["choice_gap_tol"], got
    assert max(got["prefill_rel_rms"] + got["after_decode_rel_rms"]) \
        <= check["rel_rms_tol"]
    if fault == "one_lane":
        assert lane != 0 and max(got["decode_choice_gap"]) \
            <= check["choice_gap_tol"]
        # but for the served tokens the run would have read correct
        assert serve_load.matches_reference(
            dict(got, served_choice_gap=[0.0]), check)
        # the requests of the other lanes read as on a sound engine
        assert any(at == lane for _, at in probe.lanes)
        assert sum(g > check["choice_gap_tol"]
                   for _, _, g in got["served_by_request"]) \
            < len(got["served_by_request"])


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
