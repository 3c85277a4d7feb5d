"""The one traffic generator. A cell's file gives the parameters; the
seed gives the order and the arrival times, never the amount of work.

Lengths are taken at fixed quantiles of their distribution, so every
run of a cell offers the same multiset of prompt and output lengths
(hence the same tokens). Open-loop arrivals are Poisson in the same
way: the n = round(rate x seconds) gaps between arrivals sit at the
quantiles of the exponential distribution, scaled to fill the window
exactly. A cell whose clients come in clumps gives ``arrivals``:
``{"dist": "gamma", "cv": c}`` takes the gaps at the same quantiles of a
gamma distribution of shape 1 / c^2 (c = 1 is the exponential), scaled
to the same rate: most gaps a few milliseconds, a few of seconds.
Gaps and lengths are put into ONE order, fixed by the cell's
``schedule_seed``, and read as a cycle; ``--seed`` decides where in the
cycle the window starts (the ramp is the stretch of the cycle before
it), besides the weights and the prompts' tokens. Every run therefore
meets the same bursts and the same long prompts behind the same short
ones. The cycle is the window's n requests, or where the cell gives
``cycle`` that many (a cell at another rate then offers the lengths, the
order and the shape of the bursts of the cell it is to be compared
with, faster), read round and round. Measured on the chip (PR 23):
with the order itself drawn from ``--seed``, the 95th percentile of time to first token spread by 40-50 %
of its median from seed to seed while two runs of one seed agreed to
0.3 %: the seed was changing the work, not sampling the system.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # relative to the start of the window; <0 = ramp
    prompt_len: int
    max_tokens: int


def quantile_lengths(spec: dict, n: int) -> List[int]:
    """n lengths at the quantiles (i + 0.5) / n of ``spec``'s
    distribution, clipped to [min, max]."""
    qs = [(i + 0.5) / n for i in range(n)]
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        mu, sigma = math.log(spec["median"]), float(spec["sigma"])
        z = NormalDist()
        vals = [math.exp(mu + sigma * z.inv_cdf(q)) for q in qs]
    elif spec["dist"] == "uniform":
        vals = [lo + q * (hi - lo) for q in qs]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(min(max(round(v), lo), hi)) for v in vals]


def quantile_gaps(arrivals: dict, n: int) -> np.ndarray:
    """n arrival gaps at the quantiles (i + 0.5) / n of the cell's
    ``arrivals`` distribution (absent: exponential), before scaling to
    the rate."""
    qs = (np.arange(n) + 0.5) / n
    dist = (arrivals or {}).get("dist", "exponential")
    if dist == "exponential":
        return -np.log1p(-qs)
    if dist == "gamma":
        from scipy.special import gammaincinv  # jax itself needs scipy

        return gammaincinv(1.0 / float(arrivals["cv"]) ** 2, qs)
    raise ValueError(f"unknown arrival distribution {dist!r}")


def _rng(seed: int, stream: int) -> np.random.Generator:
    # seeds run past 2**31; SeedSequence takes any non-negative integer
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _shuffled_lengths(traffic: dict, n: int, rng):
    prompts = quantile_lengths(traffic["prompt_len"], n)
    outputs = quantile_lengths(traffic["output_len"], n)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    return prompts, outputs


def open_loop(traffic: dict, seed: int, seconds: float) -> List[Request]:
    """Ramp requests (due < 0) then the window's, in order of due time:
    the cell's fixed cycle, entered where ``seed`` says."""
    rate = float(traffic["rate_per_s"])
    n = round(rate * seconds)
    n_ramp = round(rate * traffic.get("ramp_s", 0))
    cycle = int(traffic.get("cycle", n))
    order = _rng(traffic.get("schedule_seed", 0), 1)
    gaps = quantile_gaps(traffic.get("arrivals"), cycle)
    # the window's n requests fill it exactly; a given cycle keeps the rate
    gaps *= (cycle / rate if "cycle" in traffic else seconds) / gaps.sum()
    order.shuffle(gaps)
    prompts, outputs = _shuffled_lengths(traffic, cycle, order)
    first = int(_rng(seed, 1).integers(cycle))
    out, due = [], 0.0
    for j in range(-1, -n_ramp - 1, -1):   # the ramp, backwards from 0
        i = (first + j) % cycle
        due -= gaps[i]
        out.append(Request(n_ramp + j, float(due), prompts[i], outputs[i]))
    out.reverse()
    due = 0.0
    for j in range(n):                     # the window, round the cycle
        i = (first + j) % cycle
        out.append(Request(n_ramp + j, float(due), prompts[i], outputs[i]))
        due += gaps[i]
    return out


def closed_loop(traffic: dict, seed: int) -> List[Request]:
    """The pool the clients draw from, round and round: the cell's fixed
    cycle of ``pool`` requests, entered where ``seed`` says. A short
    pool makes every stretch of the run the same mix of lengths. due_s
    is unused (a client sends when its last answer is complete)."""
    n = int(traffic["pool"])
    prompts, outputs = _shuffled_lengths(
        traffic, n, _rng(traffic.get("schedule_seed", 0), 1))
    first = int(_rng(seed, 1).integers(n))
    return [Request(j, 0.0, prompts[(first + j) % n], outputs[(first + j) % n])
            for j in range(n)]


def prompt_tokens(seed: int, request: Request, vocab: int) -> List[int]:
    rng = _rng(seed, 1000 + request.index)
    return rng.integers(0, vocab, request.prompt_len).tolist()


def sample(seed: int, n: int, count: int) -> List[int]:
    """``count`` of range(n), drawn from the seed without replacement:
    which of the window's finished requests the reference reads."""
    return sorted(_rng(seed, 4).choice(
        n, size=max(count, 0), replace=False).tolist())


def offered(requests: List[Request]) -> dict:
    window = [r for r in requests if r.due_s >= 0]
    return {
        "requests": len(window),
        "prompt_tokens": sum(r.prompt_len for r in window),
        "output_tokens": sum(r.max_tokens for r in window),
    }


def corpus(seed: int, distinct: int, batch: int, seq: int, vocab: int):
    """``distinct`` seeded token batches (batch, seq + 1) int32: the
    training corpus, cycled so that the loss can fall."""
    rng = _rng(seed, 2)
    return [
        rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
        for _ in range(distinct)
    ]


def probe_sequence(seed: int, length: int, vocab: int) -> np.ndarray:
    """The one seeded sequence the float32 reference is compared on."""
    return _rng(seed, 3).integers(0, vocab, length, dtype=np.int32)
