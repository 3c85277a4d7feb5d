"""traffic.py: the same seed gives the same schedule; another seed
gives the same work in another order."""

from collections import Counter

import numpy as np

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
