"""A serving cell: ``serve.run(build_llm_app(cfg, server_cls=...))`` and
a streaming handle, loaded from this one process.

Open loop: a pacer thread sends each request at its due time whatever
the state of earlier ones; every time is taken from when the request
was DUE, so a stall is charged to the requests behind it, and how late
the pacer ran is reported. Closed loop: ``clients`` threads, each
sending its next request when the last answer is complete.
"""

from __future__ import annotations

import math
import statistics
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from . import spec, traffic

CALL_TIMEOUT_S = 900


class _Record:
    __slots__ = ("request", "due", "sent", "token_times", "tokens", "error")

    def __init__(self, request):
        self.request = request
        self.due = self.sent = 0.0
        self.token_times = []
        self.tokens = []
        self.error = None


def _send(stream, record: _Record, prompt) -> None:
    """One request: tokens as they arrive at the client."""
    record.sent = time.perf_counter()
    try:
        for token in stream.generate_stream.remote(
                prompt, max_tokens=record.request.max_tokens,
                temperature=0.0):
            record.token_times.append(time.perf_counter())
            record.tokens.append(int(token))
    except Exception as e:  # a failed request is a result, not a crash
        record.error = f"{type(e).__name__}: {e}"[:300]


def _open_loop(stream, requests, prompts, seconds, max_in_flight, on_window):
    """Returns the records, the window's start and end on perf_counter,
    and the pool and futures still to be waited for."""
    records = [_Record(r) for r in requests]
    ramp_s = -min([r.due_s for r in requests] + [0.0])
    pool = ThreadPoolExecutor(max_workers=max_in_flight)
    origin = time.perf_counter() + ramp_s + 0.05  # the window's start
    window_open = False
    futures = []
    for record, prompt in zip(records, prompts):
        record.due = origin + record.request.due_s
        if record.request.due_s >= 0 and not window_open:
            _sleep_until(origin)
            on_window()
            window_open = True
        _sleep_until(record.due)
        futures.append(pool.submit(_send, stream, record, prompt))
    _sleep_until(origin + seconds)
    return records, origin, origin + seconds, pool, futures


def _sleep_until(t: float) -> None:
    """Sleeps in short naps, and yields without sleeping over the last
    two milliseconds, so that a send is not late by a nap's overshoot."""
    while (left := t - time.perf_counter()) > 0:
        time.sleep(min(left, 0.05) if left > 0.002 else 0)


def _closed_loop(stream, requests, prompts, seconds, clients, on_window):
    """The pool in its seeded order, round again if it runs out."""
    records = []
    lock = threading.Lock()
    state = {"stop": False}

    def client():
        while True:
            with lock:
                if state["stop"]:
                    return
                i = len(records) % len(requests)
                record = _Record(requests[i])
                records.append(record)
            record.due = time.perf_counter()
            _send(stream, record, prompts[i])

    pool = ThreadPoolExecutor(max_workers=clients)
    on_window()
    origin = time.perf_counter()
    futures = [pool.submit(client) for _ in range(clients)]
    _sleep_until(origin + seconds)
    with lock:
        state["stop"] = True
    return records, origin, origin + seconds, pool, futures


def tokens_in_window(records, t0: float, t1: float) -> int:
    """What reached a client inside the window, over every record of the
    run (an open loop's ramp too): one for each token that arrived in
    ``[t0, t1]``, and a request's ``prompt_len`` where its FIRST token
    did, the client's evidence that the prompt was read. A prompt is
    credited once and whole, never pro rata: a count, not an estimate.
    A request in flight at the close counts for what it had delivered,
    so the number does not jump with the side of the close a request
    ends on; a failed request counts for nothing."""
    total = 0
    for r in records:
        if r.error or not r.token_times:
            continue
        total += sum(t0 <= t <= t1 for t in r.token_times)
        if t0 <= r.token_times[0] <= t1:
            total += r.request.prompt_len
    return total


# -- the reference comparison's decision ------------------------------
# the lists of readings the comparison gives (reference_check: the
# seeded probe through the engine's programs; served_check: a sample of
# the requests the window finished), each with the key of its limit in
# the cell's ``reference_check`` block
READINGS = {"prefill_rel_rms": "rel_rms_tol",
            "after_decode_rel_rms": "rel_rms_tol",
            "decode_choice_gap": "choice_gap_tol",
            "served_choice_gap": "choice_gap_tol"}
# how many of the window's finished requests are read where the cell's
# ``reference_check`` block does not say
SERVED_REQUESTS = 8


def served_sample(records, seed: int, count: int) -> list:
    """``count`` of the requests the window finished, drawn from the
    seed, the longest (prompt and answer) among them."""
    done = [r for r in records if not r.error and r.tokens]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (
        done[i].request.prompt_len + len(done[i].tokens), -i))
    rest = [i for i in range(len(done)) if i != longest]
    drawn = traffic.sample(seed, len(rest), min(count - 1, len(rest)))
    return [done[longest]] + [done[rest[i]] for i in drawn]


# A cell whose ``reference_check`` says ``"routing": "engine"`` has its
# probe's rows and decodes compared under the engine's own routing
# choices (``server.routed_rows``), and one list more: at every row of
# the probe the largest ``route_margin`` over its routed layers, how far
# the worst expert the engine chose lies under the reference's own k-th
# best, in units of the router's logits (0 where the two would have
# chosen the same), held to the cell's ``route_margin_tol``: a choice the
# reference would not have made is allowed only where the reference was
# itself near a tie.
ROUTING = "engine"      # the one thing the key ``routing`` may say
ROUTED_READINGS = {**READINGS, "route_margin": "route_margin_tol"}


def routed(check: dict) -> bool:
    return check.get("routing") == ROUTING


def readings_of(check: dict) -> dict:
    return ROUTED_READINGS if routed(check) else READINGS


def summary(ref: dict, check: dict) -> dict:
    """Of each list of readings its median, its largest, and the share
    over the limit; its limit beside them."""
    out = {}
    for name, limit in readings_of(check).items():
        xs, tol = ref[name], check[limit]
        out[name] = {
            "n": len(xs), "median": statistics.median(xs) if xs else None,
            "max": max(xs) if xs else None, "limit": tol,
            "outlier_share": sum(not x <= tol for x in xs) / max(len(xs), 1)}
    return out


# A cell whose sound engine reads over a limit at a few readings in a
# hundred (a family with discrete routing: bf16 picks another of two
# near-tied experts than the float32 reference, computing what it
# should) states how often, in its ``reference_check`` block, as two
# shares it read on its own engine on the chip: of the compared rows,
# those over ``rel_rms_tol``; of its served tokens, those over
# ``choice_gap_tol``. Absent is 0, and then every reading decides.
OVER_SHARES = {"rel_rms_tol": "rel_rms_over_share",
               "choice_gap_tol": "choice_gap_over_share"}
# The most a cell may state. Rows: 0.1, which is 1.7 times what the
# routed stand-in reads on the chip with groups of experts (5.9 % of the
# compared rows) and 3.2 times what it reads without (3.1 %), and a
# tenth of what its fp8 control reads (every row). Tokens: 0.03, which
# is 2.3 and 3.4 times the stand-in's 1.3 % and 0.9 %, and a quarter and
# a sixth of the control's 11 % and 19 % (PERF.md section 6, PR 36).
SHARE_CEILINGS = {"rel_rms_over_share": 0.1, "choice_gap_over_share": 0.03}
# A cell that states a share compares no row before this one: a row
# that attends to one or two rows takes a flip there whole, and every
# sound row read over the limit without a flip of its own (107 of a
# million) lay at rows 1-17 (PERF.md section 6, PR 34).
SHARES_FROM_ROW = 32
# How often a decision may go against a sound engine whose readings are
# over their limit independently at the stated share. A run decides two
# pools and each request read (17 with the probe's decodes at
# ``served_requests`` 16), a check makes 14 runs of a cell: 266
# decisions, so 1e-6 refuses a sound cell in under three checks of ten
# thousand.
RISK = 1e-6


def allowed(n: int, share: float) -> int:
    """The least k with P[Binomial(n, share) > k] <= RISK: how many of
    ``n`` readings may lie over their limit where a sound engine's do at
    ``share`` of them. 0 where the cell states no share."""
    if share <= 0 or n <= 0:
        return 0
    log_p, log_q = math.log(share), math.log1p(-share)
    tail, k = 0.0, n           # tail = P[X > k], summed from the top
    while k > 0:
        pmf = math.exp(math.lgamma(n + 1) - math.lgamma(k + 1)
                       - math.lgamma(n - k + 1) + k * log_p
                       + (n - k) * log_q)
        if tail + pmf > RISK:
            return k
        tail, k = tail + pmf, k - 1
    return 0


def by_request(ref: dict) -> list:
    """The choice gaps request by request: the probe's decodes (one
    request's tokens through lane 0), then each request
    ``served_readings`` read (``served_by_request`` holds how many
    tokens each had; without it the served tokens are one request)."""
    gaps, out, at = ref["served_choice_gap"], [ref["decode_choice_gap"]], 0
    for _, n, _ in ref.get("served_by_request") or [[0, len(gaps), 0.0]]:
        out.append(gaps[at:at + n])
        at += n
    return out


def states_a_share(check: dict) -> bool:
    return any(check.get(share) for share in SHARE_CEILINGS)


def counted(ref: dict, check: dict) -> dict:
    """What decides, each count beside the most it may be: the lists
    not read, their readings that are not finite; by limit kind, the
    readings of the two relative-RMS lists together that lie over
    ``rel_rms_tol`` and those of the two lists of choice gaps together
    over ``choice_gap_tol``, each beside ``allowed`` of its pool's size
    at the cell's stated share (the two kinds are never pooled: a
    thousand tokens would hide 48 rows); and by request, the tokens over
    ``choice_gap_tol`` of the request that is furthest over (or least
    under) its own allowance: a lane's fault reads so at every decoded
    token of a request through it, a sound engine at a few of a hundred,
    and pooled with a thousand tokens a short request would hide.

    A cell that says ``"routing": "engine"`` states no share of rows:
    each of the probe's lists (its rows, its decodes' gaps, the margins)
    is held at every reading, its largest beside its limit; no
    shortened last chunk of the probe may have chosen otherwise than the
    whole one at a row both compute (``server.probe_rows``); the served
    tokens alone, which carry no choices and are read against
    ``reference_logits``, are counted, all together and by request,
    beside ``allowed`` at the ``choice_gap_over_share`` the cell may
    state."""
    out = {"unread_or_not_finite": [
        (not ref["finite"]) + sum(
            (not ref[name]) + sum(not math.isfinite(x) for x in ref[name])
            for name in readings_of(check)), 0]}

    def over(xs, limit):
        return [sum(not x <= check[limit] for x in xs),
                allowed(len(xs), check.get(OVER_SHARES[limit], 0.0))]

    if routed(check):
        out["shortened_calls_chose_otherwise"] = [
            ref["shortened_calls_chose_otherwise"], 0]
        for name, limit in ROUTED_READINGS.items():
            if name != "served_choice_gap":
                out[name] = [max(ref[name], default=math.inf), check[limit]]
        out["served_choice_gap_over"] = over(ref["served_choice_gap"],
                                             "choice_gap_tol")
        out["request_choice_gap_over"] = max(
            (over(xs, "choice_gap_tol") for xs in by_request(ref)[1:]),
            key=lambda pair: (pair[0] - pair[1], pair[0]), default=[0, 0])
        return out
    for limit in OVER_SHARES:
        out[limit[:-len("_tol")] + "_over"] = over(
            [x for name, of in READINGS.items() if of == limit
             for x in ref[name]], limit)
    out["request_choice_gap_over"] = max(
        (over(xs, "choice_gap_tol") for xs in by_request(ref)),
        key=lambda pair: (pair[0] - pair[1], pair[0]))
    return out


def matches_reference(ref: dict, check: dict) -> bool:
    """Every list read and every reading finite; by limit kind and by
    request, no more readings over their limit than ``allowed`` at the
    share the cell states (``counted``). A cell that states none, as the
    three serving cells do, is held at every position and every served
    token read: every reading at or under its limit; so are the probe's
    rows, gaps and margins of a cell compared under the engine's own
    routing choices, whose pairs are then [largest, limit]."""
    return all(count <= most for count, most in counted(ref, check).values())


def compared(ref: dict, check: dict) -> dict:
    """Each number compared beside its limit, for the run's last line:
    each list's largest reading beside its limit where every reading
    decides, the counts beside their allowances where the cell states a
    share; of a cell that says ``"routing": "engine"`` the largest row,
    gap and margin beside their limits and the served counts beside
    their allowances."""
    if states_a_share(check) or routed(check):
        return counted(ref, check)
    return {name: [of["max"] if of["n"] else math.inf, of["limit"]]
            for name, of in summary(ref, check).items()}


def check_cell(cell: dict) -> None:
    """Held when a serve cell is loaded (``spec.load_cell``): a stated
    share is a number from 0 to its ceiling, and a cell that states one
    compares no row before ``SHARES_FROM_ROW``."""
    check, tr = cell["serve"]["reference_check"], cell["traffic"]
    name = cell.get("name")
    if "routing" in check:
        check_routed(cell)
    for share, ceiling in SHARE_CEILINGS.items():
        stated = check.get(share, 0.0)
        if (isinstance(stated, bool) or not isinstance(stated, (int, float))
                or not 0 <= stated <= ceiling):
            raise ValueError(
                f"cell {name}: reference_check.{share} is {stated!r}; it is "
                "the share of the cell's own sound engine's readings over "
                f"the limit, a number from 0 to the ceiling {ceiling} "
                "(serve_load.SHARE_CEILINGS has the arithmetic)")
    if not states_a_share(check):
        return
    if "positions" not in check:
        raise ValueError(f"cell {name}: a reference_check that states a "
                         "share states its 'positions' too")
    first = {"the probe's first compared row (length - positions)":
             check["length"] - check["positions"],
             "the traffic's least prompt (prompt_len.min)":
             tr["prompt_len"]["min"]}
    for what, row in first.items():
        if row < SHARES_FROM_ROW:
            raise ValueError(
                f"cell {name} states a share of readings over the limit and "
                f"{what} is {row}: every compared row has to lie at or "
                f"behind row {SHARES_FROM_ROW}, since a sequence's first "
                "rows take a flip whole (serve_load.SHARES_FROM_ROW)")


def check_routed(cell: dict) -> None:
    """Of a cell whose ``reference_check`` has the key ``routing``: it
    says ``"engine"``; it states no ``rel_rms_over_share``, since under
    the engine's own choices every row decides; it states
    ``route_margin_tol``, a number over 0; and its family gives
    ``reference_routed``."""
    check, name = cell["serve"]["reference_check"], cell.get("name")
    if not routed(check):
        raise ValueError(
            f"cell {name}: reference_check.routing is {check['routing']!r}; "
            f"the one thing it may say is \"{ROUTING}\" (the rows compared under "
            "the engine's own routing choices); a cell compared under the "
            "reference's own leaves the key out")
    if check.get("rel_rms_over_share"):
        raise ValueError(
            f"cell {name} says \"routing\": \"engine\" and states "
            f"rel_rms_over_share {check['rel_rms_over_share']!r}: under the "
            "engine's own choices no row is expected over rel_rms_tol, so "
            "every row decides and the cell states no share of them "
            "(choice_gap_over_share, of the served tokens, it may)")
    tol = check.get("route_margin_tol")
    if (isinstance(tol, bool) or not isinstance(tol, (int, float))
            or not 0 < tol < math.inf):
        raise ValueError(
            f"cell {name} says \"routing\": \"engine\" and its "
            f"reference_check.route_margin_tol is {tol!r}: a number over 0, "
            "in units of the router's logits, set between what the cell's "
            "own sound engine and a router fault read")
    if not hasattr(spec.family_of(cell["hp"]), "reference_routed"):
        raise ValueError(
            f"cell {name} says \"routing\": \"engine\" and the family of its "
            f"configuration {cell['hp'].get('name')!r} gives no "
            "reference_routed(params, tokens, hp, choices, last=0) -> "
            "(logits, margin) (benchmarks/README.md, 'A served family')")


def run(cell: dict, args, per_layer: dict) -> dict:
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app

    from .server import BenchLLMServer, SeededLLMConfig, no_door

    hp, tr, sv = cell["hp"], cell["traffic"], cell["serve"]
    phases = {"runner_ready": time.time()}  # where set-up goes, wall clock
    llm_config = SeededLLMConfig(
        model_config=spec.family_of(hp).model_config(hp),
        hp=hp,
        max_batch_size=sv["max_batch_size"], max_seq_len=sv["max_seq_len"],
        accelerator_type="" if args.rehearse else "TPU",
        engine_kwargs=sv.get("engine_kwargs", {}),
        seed=args.seed, rehearse=args.rehearse)
    app = build_llm_app(llm_config, server_cls=BenchLLMServer)
    # more request threads than slots plus the deepest queue, so that a
    # health ping never waits behind the generations
    app = app.deployment.options(
        num_replicas=1, max_ongoing_requests=sv["max_ongoing_requests"],
    ).bind(*app.args)
    handle = serve.run(app).options(request_timeout_s=CALL_TIMEOUT_S)
    stream = handle.options(stream=True)
    phases["replica_up"] = time.time()  # chip open, weights, engine

    def call(method, *a):
        return getattr(handle, method).remote(*a).result(
            timeout_s=CALL_TIMEOUT_S)

    notes = {}
    try:
        # -- set-up: the traffic, the programs, the checks -------------
        open_loop = tr["loop"] == "open"
        requests = (traffic.open_loop(tr, args.seed, args.seconds)
                    if open_loop else traffic.closed_loop(tr, args.seed))
        prompts = [traffic.prompt_tokens(args.seed, r, hp["vocab_size"])
                   for r in requests]
        notes["offered"] = traffic.offered(requests)
        warm = call("warm_up", sv["warm_up_prompt_lens"])
        phases["warmed_up"] = time.time()
        check = sv["reference_check"]
        if routed(check) and not call("reads_choices"):
            raise RuntimeError(no_door(f"cell {cell['name']}"))
        probe = traffic.prompt_tokens(
            args.seed, traffic.Request(10**6, 0.0, tr["probe_prompt_len"], 8),
            hp["vocab_size"])
        probes = [list(stream.generate_stream.remote(
            probe, max_tokens=8, temperature=0.0)) for _ in range(2)]

        phases["probes_done"] = time.time()

        # -- the window -------------------------------------------------
        window = {}

        def on_window():
            call("begin_window")
            window["wall"] = phases["window"] = time.time()

        tracing = threading.Event()
        if args.trace:
            def trace_part():
                # the last seconds of the window, still under load
                lead = min(sv["trace_seconds"] + 1.0, args.seconds * 0.6)
                while "wall" not in window:
                    time.sleep(0.01)
                time.sleep(max(args.seconds - lead, 0.0))
                call("trace_start")
                time.sleep(min(sv["trace_seconds"], lead * 0.8))
                window["traced_s"] = call("trace_stop")
                tracing.set()

            threading.Thread(target=trace_part, daemon=True).start()

        if open_loop:
            records, t0, t1, pool, futures = _open_loop(
                stream, requests, prompts, args.seconds,
                sv["max_ongoing_requests"], on_window)
        else:
            records, t0, t1, pool, futures = _closed_loop(
                stream, requests, prompts, args.seconds, tr["clients"],
                on_window)
        at_end = call("end_window")
        # every request that was sent is waited for: a tail is the tail
        # of all requests of the window
        pool.shutdown(wait=True)
        for f in futures:
            f.result()
        if args.trace:
            tracing.wait(timeout=120)
    except BaseException:
        serve.shutdown()
        raise
    in_window = [r for r in records if r.due >= t0 - 1e-9]

    # -- samples, as the client saw them --------------------------------
    ttft, itl, late = [], [], []
    done_tokens = done = failed = 0
    lengths_ok = vocab_ok = True
    for r in in_window:
        late.append(1e3 * (r.sent - r.due))
        if r.error or not r.token_times:
            failed += 1
            continue
        lengths_ok &= len(r.tokens) == r.request.max_tokens
        vocab_ok &= all(0 <= t < hp["vocab_size"] for t in r.tokens)
        ttft.append(1e3 * (r.token_times[0] - r.due))
        itl.extend(1e3 * (b - a) for a, b in
                   zip(r.token_times, r.token_times[1:]))
        if r.token_times[-1] <= t1:
            done += 1
            done_tokens += r.request.prompt_len + len(r.tokens)
    half = (t0 + t1) / 2
    samples = {
        **at_end["samples"],
        "window_s": t1 - t0,
        "tokens_in_window": tokens_in_window(records, t0, t1),
        "ttft_ms": ttft, "itl_ms": itl,
    }
    result = {
        "attempted": len(in_window), "failed": failed, "samples": samples,
        "window_start_wall": window["wall"], "device": at_end["device"],
        "per_layer": {}, "metrics_not_read": {}, "breakdown": None,
    }
    if args.trace:
        slim = {k: v for k, v in samples.items()
                if k not in ("ttft_ms", "itl_ms")}
        slim["traced_s"] = window.get("traced_s")
        report = call("trace_report", per_layer, cell, slim)
        result["device"] = {**result["device"], **report["device"]}
        result["per_layer"] = report["per_layer"]
        result["metrics_not_read"] = report["metrics_not_read"]
        result["breakdown"] = report["breakdown"]

    # -- the comparison with the reference ------------------------------
    # once the window has closed, every request is answered (the engine
    # is idle) and memory_peak_bytes has been read, so none of it is in
    # setup_s: the seeded probe through the engine's programs, then a
    # sample of what the window itself served, the longest request in it
    try:
        ref = call("reference_check", args.seed, hp, check)
        sample = served_sample(
            in_window, args.seed, check.get("served_requests",
                                            SERVED_REQUESTS))
        ref.update(call("served_check", hp, [
            (traffic.prompt_tokens(args.seed, r.request, hp["vocab_size"]),
             r.tokens) for r in sample]))
    finally:
        serve.shutdown()
    notes["reference"] = {**ref, "summary": summary(ref, check)}
    # each number compared, beside its limit
    notes["compared"] = compared(ref, check)

    result["checks"] = {
        "every_request_answered": failed == 0 and len(in_window) > 0,
        "every_answer_full_length": lengths_ok,
        "tokens_in_vocabulary": vocab_ok,
        "equal_prompts_equal_tokens":
            probes[0] == probes[1] and len(probes[0]) == 8,
        "nothing_compiled_in_window": at_end["compiled_in_window"] == 0,
        "engine_matches_reference": matches_reference(ref, check),
        "on_one_chip": warm["count"] == cell["chips"],
    }

    def med(xs):
        return statistics.median(xs) if xs else None

    def p95(xs):
        return statistics.quantiles(xs, n=20)[-1] if len(xs) > 1 else None

    notes.update({
        "requests_in_window": len(in_window), "completed_in_window": done,
        # the count until PR 60 (prompt + answer of the requests that
        # ended inside), beside the one serve_tokens_per_s reads
        "tokens_completed_in_window": done_tokens,
        "tokens_in_window": samples["tokens_in_window"],
        "arrived_second_half": sum(r.due >= half for r in in_window),
        "completed_second_half": sum(
            bool(r.token_times) and half <= r.token_times[-1] <= t1
            for r in records),
        "ttft_ms_median": med(ttft), "ttft_samples": len(ttft),
        "itl_ms_median": med(itl), "itl_samples": len(itl),
        # the tails too: a cell above its knee is not judged on them
        "ttft_ms_p95": p95(ttft), "itl_ms_p95": p95(itl),
        "generator_late_ms_median": med(late),
        "generator_late_ms_max": max(late) if late else None,
        "engine_step_ms_median": med(at_end["samples"]["engine_step_ms"]),
        "engine_steps": at_end["samples"]["engine_steps"],
        "shards": at_end["shards"], "peak_active": at_end["peak_active"],
        "compiled_in_window": at_end["compiled_in_window"],
    })
    # seconds from the runner's start to the end of each phase
    notes["setup_phases_s"] = {k: round(v - args.process_start, 3)
                               for k, v in phases.items()}
    result["notes"] = notes
    return result
