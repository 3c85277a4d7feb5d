"""What ``serve_tokens_per_s`` counts since PR 60
(``serve_load.tokens_in_window``): the tokens that reached a client
inside the window and, whole, the prompt of every request whose first
token did; on records made by hand, and against the count it replaces
(prompt + answer of the requests that END inside), which jumps with the
side of the close a request ends on."""

import pytest

from benchmarks import serve_load, spec, traffic

T0, T1 = 100.0, 150.0


def record(prompt_len, times, due=None, error=None):
    r = serve_load._Record(traffic.Request(0, 0.0, prompt_len, len(times)))
    if due is None:
        due = times[0] - 0.5 if times else T0
    r.due = r.sent = due
    r.token_times = list(times)
    r.tokens = list(range(len(times)))
    r.error = error
    return r


def ended_inside(records):
    """The count until PR 60, as ``serve_load.run`` still makes it for
    the notes (``tokens_completed_in_window``)."""
    return sum(r.request.prompt_len + len(r.tokens) for r in records
               if r.due >= T0 and not r.error and r.token_times
               and r.token_times[-1] <= T1)


@pytest.mark.parametrize("what,rec,counted", [
    ("a request wholly inside: its prompt and every token",
     record(300, [110.0, 111.0, 112.0]), 303),
    ("one that straddles the close: its prompt and the tokens that arrived",
     record(300, [148.0, 149.0, 150.0, 150.5, 151.0]), 303),
    ("one whose first token came after the close: nothing",
     record(300, [150.001, 151.0], due=149.0), 0),
    ("a ramp request of the open loop: the tokens that arrived inside and "
     "no prompt (its first token came before the window)",
     record(300, [98.0, 99.0, 100.0, 101.0, 102.0], due=97.0), 3),
    ("a ramp request whose first token came inside: its prompt too",
     record(300, [100.5, 101.0], due=99.0), 302),
    ("a failed request: nothing, whatever it had delivered",
     record(300, [110.0, 111.0], error="TimeoutError: x"), 0),
    ("a request that was never answered: nothing", record(300, []), 0),
])
def test_the_count_on_a_record_made_by_hand(what, rec, counted):
    assert serve_load.tokens_in_window([rec], T0, T1) == counted, what


def test_the_ends_are_inside_and_a_prompt_is_never_split():
    on_the_ends = record(7, [T0, T1])
    assert serve_load.tokens_in_window([on_the_ends], T0, T1) == 9
    # a prompt is credited at the first token, once and whole: moving
    # the close across the answer moves the count a token at a time
    times = [120.0 + i for i in range(10)]
    counts = [serve_load.tokens_in_window([record(1000, times)], T0, close)
              for close in (119.9, 120.0, 124.5, 129.0, 200.0)]
    assert counts == [0, 1001, 1005, 1010, 1010]


def test_the_window_is_the_sum_of_its_records_and_of_its_parts():
    records = [record(300, [110.0, 111.0, 112.0]),
               record(50, [148.0, 149.0, 150.5]),
               record(80, [98.0, 101.0], due=97.0),
               record(20, [130.0], error="x")]
    whole = serve_load.tokens_in_window(records, T0, T1)
    assert whole == 303 + 52 + 1
    assert whole == sum(serve_load.tokens_in_window([r], T0, T1)
                        for r in records)
    # two windows laid end to end count what one over both counts,
    # but for a token on the seam, which is in both
    mid = 125.0
    assert (serve_load.tokens_in_window(records, T0, mid)
            + serve_load.tokens_in_window(records, mid, T1)) == whole


def test_a_request_across_the_close_moves_the_old_count_by_itself():
    """Two runs of one closed loop that differ by when one long request
    ends: 5 ms before the close, 5 ms after. The old count differs by
    the whole request, the new one by the one token."""
    steady = [record(200, [101.0 + i * 0.1 for i in range(100)])
              for _ in range(10)]
    times = [120.0 + i * (29.995 / 999) for i in range(1000)]
    before = record(2000, times)
    after = record(2000, [t + 0.01 for t in times])
    assert before.token_times[-1] < T1 < after.token_times[-1]
    old = [ended_inside(steady + [r]) for r in (before, after)]
    new = [serve_load.tokens_in_window(steady + [r], T0, T1)
           for r in (before, after)]
    assert old[0] - old[1] == 3000
    assert new[0] - new[1] == 1


def test_the_metrics_file_reads_that_count_over_the_window():
    on_file = spec.load_json("metrics", "serve_tokens_per_s.json")
    assert on_file["reader"] == "rate"
    assert on_file["args"] == {"key": "tokens_in_window"}
    assert "reached a client inside the window" in on_file["what"]
    out, not_read = spec.evaluate(
        {"serve_tokens_per_s": {**on_file, "unit": "tokens/s"}},
        {"chips": 1, "samples": {
            "window_s": T1 - T0, "tokens_in_window":
                serve_load.tokens_in_window(
                    [record(300, [148.0, 149.0, 150.5])], T0, T1)}})
    assert not_read == {}
    assert out["serve_tokens_per_s"]["value"] == 302 / 50.0
