"""traffic.py: the same seed gives the same schedule; another seed
gives the same work in another order."""

from collections import Counter

import numpy as np
import pytest

from benchmarks import spec, traffic

CHAT = spec.load_cell("internlm2-1.8b.serve-chat", False)["traffic"]
DOCS = spec.load_cell("mistral-7b-v0.3.serve-docbatch", False)["traffic"]
BIG = 2**31 + 12345  # the driver's seeds pass 32 signed bits


def test_open_loop_repeats_from_its_seed():
    a = traffic.open_loop(CHAT, BIG, 40.0)
    assert a == traffic.open_loop(CHAT, BIG, 40.0)
    assert a != traffic.open_loop(CHAT, BIG + 1, 40.0)
    assert (traffic.prompt_tokens(BIG, a[3], 92544)
            == traffic.prompt_tokens(BIG, a[3], 92544))


def test_seed_permutes_the_work_and_never_changes_it():
    a = traffic.open_loop(CHAT, 1, 40.0)
    b = traffic.open_loop(CHAT, BIG, 40.0)
    assert traffic.offered(a) == traffic.offered(b)
    for field in ("prompt_len", "max_tokens"):
        in_window = lambda rs: Counter(  # noqa: E731
            getattr(r, field) for r in rs if r.due_s >= 0)
        assert in_window(a) == in_window(b)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    # one cycle, entered at another place: the same neighbours
    pairs = lambda rs: {  # noqa: E731
        (x.prompt_len, x.max_tokens, y.prompt_len, y.max_tokens)
        for x, y in zip(rs, rs[1:])}
    assert len(pairs(a) & pairs(b)) >= len(a) - 40


def test_open_loop_arrivals():
    rate, ramp = CHAT["rate_per_s"], CHAT["ramp_s"]
    reqs = traffic.open_loop(CHAT, 7, 40.0)
    window = [r for r in reqs if r.due_s >= 0]
    assert len(window) == round(rate * 40.0)
    assert len(reqs) - len(window) == round(rate * ramp)
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues) and dues[-1] < 40.0
    assert -1.5 * ramp < dues[0] < -0.5 * ramp
    gaps = np.diff([r.due_s for r in window])
    assert abs(gaps.mean() - 1 / rate) < 0.01 / rate
    assert 0.8 < gaps.std() / gaps.mean() < 1.1    # exponential: CV near 1
    assert [r.index for r in reqs] == list(range(len(reqs)))


def test_lengths_sit_at_fixed_quantiles_inside_their_limits():
    spec_ = CHAT["prompt_len"]
    lens = traffic.quantile_lengths(spec_, 201)
    assert lens == sorted(lens)
    assert lens[100] == spec_["median"]
    assert min(lens) >= spec_["min"] and max(lens) <= spec_["max"]
    docs = traffic.quantile_lengths(DOCS["prompt_len"], 16)
    assert min(docs) >= 1024 and max(docs) <= 3072
    assert abs(np.mean(docs) - 2048) < 4


def test_closed_loop_pool():
    pool = traffic.closed_loop(DOCS, 3)
    assert len(pool) == DOCS["pool"] and pool == traffic.closed_loop(DOCS, 3)
    assert all(16 <= r.max_tokens <= 32 for r in pool)
    other = traffic.closed_loop(DOCS, BIG)   # the same cycle, rotated
    lens = [r.prompt_len for r in pool]
    k = [r.prompt_len for r in other].index(lens[0])
    assert [r.prompt_len for r in other][k:] + [
        r.prompt_len for r in other][:k] == lens


def test_corpus_and_probe_repeat():
    a = traffic.corpus(BIG, 3, 2, 16, 512)
    b = traffic.corpus(BIG, 3, 2, 16, 512)
    assert all((x == y).all() for x, y in zip(a, b))
    assert a[0].shape == (2, 17) and a[0].dtype == np.int32
    assert not (a[0] == a[1]).all()
    assert (traffic.probe_sequence(5, 64, 512)
            == traffic.probe_sequence(5, 64, 512)).all()


def test_pacer_reports_how_late_it_ran():
    """The open-loop pacer stamps due and sent times; lateness is their
    difference, and a slow send does not delay the next request."""
    import time

    from benchmarks import serve_load

    class SlowStream:
        class generate_stream:  # noqa: N801 - mimics handle.method.remote
            @staticmethod
            def remote(prompt, max_tokens, temperature):
                time.sleep(0.2)
                return iter(range(max_tokens))

    chat = {**CHAT, "rate_per_s": 20.0, "ramp_s": 0.2}
    reqs = traffic.open_loop(chat, 1, 0.5)
    opened = []
    records, t0, t1, pool, futures = serve_load._open_loop(
        SlowStream, reqs, [[1]] * len(reqs), 0.5, 32, lambda: opened.append(1))
    pool.shutdown(wait=True)
    assert opened == [1] and abs((t1 - t0) - 0.5) < 1e-6
    late = [r.sent - r.due for r in records]
    assert all(-1e-4 <= x < 0.1 for x in late), late
    assert all(len(r.tokens) == r.request.max_tokens for r in records)


# ----------------------------------------------------- gamma arrivals
# serve-chat's cycle in clumps: the trial cell of PR 28 (PERF.md section
# 6 says why it is no cell of BENCHMARK.json)
BURST = {**CHAT, "arrivals": {"dist": "gamma", "cv": 3}, "cycle": 200}


def old_open_loop_dues(traffic_, seed, seconds):
    """open_loop's due times as the generator made them before it read
    ``arrivals`` (PR 26's lines, copied): exponential quantiles."""
    rate = float(traffic_["rate_per_s"])
    n, n_ramp = round(rate * seconds), round(rate * traffic_.get("ramp_s", 0))
    cycle = int(traffic_.get("cycle", n))
    order = traffic._rng(traffic_.get("schedule_seed", 0), 1)
    gaps = -np.log1p(-(np.arange(cycle) + 0.5) / cycle)
    gaps *= (cycle / rate if "cycle" in traffic_ else seconds) / gaps.sum()
    order.shuffle(gaps)
    first = int(traffic._rng(seed, 1).integers(cycle))
    ramp, due = [], 0.0
    for j in range(-1, -n_ramp - 1, -1):
        due -= gaps[(first + j) % cycle]
        ramp.append(float(due))
    window, due = [], 0.0
    for j in range(n):
        window.append(float(due))
        due += gaps[(first + j) % cycle]
    return ramp[::-1] + window


def test_without_arrivals_every_schedule_is_bit_for_bit_what_it_was():
    over = spec.load_cell("internlm2-1.8b.serve-chat-over", False)["traffic"]
    for cell in (CHAT, over):
        assert "arrivals" not in cell
        for seed in (1, BIG):
            got = [r.due_s for r in traffic.open_loop(cell, seed, 50.0)]
            assert got == old_open_loop_dues(cell, seed, 50.0)  # no approx
    named = {**CHAT, "arrivals": {"dist": "exponential"}}
    assert traffic.open_loop(named, 3, 50.0) == traffic.open_loop(CHAT, 3, 50.0)


def test_gamma_gaps_have_the_rate_and_the_cv_asked_for():
    for cv, n, low in ((3.0, 200, 2.8), (3.0, 20000, 2.98), (2.0, 200, 1.9),
                       (1.0, 200, 0.95)):
        gaps = traffic.quantile_gaps({"dist": "gamma", "cv": cv}, n)
        assert len(gaps) == n and (gaps >= 0).all()
        assert (np.diff(gaps) >= 0).all()              # at rising quantiles
        # quantiles cut the far tail off: a little under cv, never over
        assert low < gaps.std() / gaps.mean() <= cv
    # shape 1 is the exponential distribution
    np.testing.assert_allclose(
        traffic.quantile_gaps({"dist": "gamma", "cv": 1.0}, 200),
        traffic.quantile_gaps(None, 200), rtol=1e-9)
    # the quantile of a gamma, made a second time: its CDF (the series of
    # the lower incomplete gamma function) at each gap is (i + 0.5) / n
    import math
    shape = 1 / 9
    for i, x in enumerate(traffic.quantile_gaps({"dist": "gamma", "cv": 3.0}, 8)):
        series = sum((-1) ** k * x ** (shape + k) / (math.factorial(k)
                     * (shape + k)) for k in range(80))
        assert series / math.gamma(shape) == pytest.approx((i + 0.5) / 8,
                                                           abs=1e-9)
    with pytest.raises(ValueError, match="unknown arrival"):
        traffic.quantile_gaps({"dist": "weibull"}, 8)


def test_gamma_arrivals_offer_the_same_requests_in_clumps():
    """The same requests at the same mean rate, and nothing but the
    arrival pattern differs: CV near 3, bursts of five requests and more
    inside 50 ms of each other, seconds of quiet."""
    seed, seconds = BIG, 50.0
    chat = [r for r in traffic.open_loop(CHAT, seed, seconds) if r.due_s >= 0]
    burst = [r for r in traffic.open_loop(BURST, seed, seconds)
             if r.due_s >= 0]
    assert len(burst) == len(chat) == 200
    assert [(r.prompt_len, r.max_tokens) for r in burst] == [
        (r.prompt_len, r.max_tokens) for r in chat]
    gaps = np.diff([r.due_s for r in burst] + [seconds])
    assert gaps.sum() == pytest.approx(seconds - burst[0].due_s)
    assert gaps.mean() == pytest.approx(1 / BURST["rate_per_s"], rel=0.01)
    assert 2.7 < gaps.std() / gaps.mean() < 3.0
    runs, run = [], 1
    for g in gaps[:-1]:
        if g < 0.05:
            run += 1
        else:
            runs.append(run)
            run = 1
    assert max(runs) >= 10 and sum(r >= 5 for r in runs) >= 5
    assert gaps.max() > 5.0 and (gaps > 1.0).sum() >= 8
    # every seed offers the same gaps, entered at another place
    other = np.diff([r.due_s for r in traffic.open_loop(
        BURST, seed + 1, seconds) if r.due_s >= 0] + [seconds])
    np.testing.assert_allclose(np.sort(other), np.sort(gaps), atol=1e-9)
