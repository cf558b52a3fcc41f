"""The one traffic generator.  A traffic mix is a data file,
`bench/traffic/<mix>.json`, of parameters that this module reads:

  loop        "closed": a queue kept `waiting` requests deep beyond the
              slots, drawn in order from a pool of `pool` requests;
              "open": arrivals on a schedule, `rate_per_s` on average,
              the rate multiplied by `bursts.factors` in turn, each for
              `bursts.phase_s` seconds; `lead_in_s` of arrivals come before
              the measured window.
  pattern_seed
              (open loop) the seed of the arrival pattern: see below.
  prompt, output
              length distributions: {"dist": "uniform", "min", "max"} or
              {"dist": "lognormal", "median", "sigma", "min", "max"}.
  sampling    {"temperature", "top_k", "greedy_every"}: every
              `greedy_every`-th request decodes greedily (temperature 0),
              the ones the correctness check reads.

The run's seed chooses the order and the token ids, not the work.  The
lengths of a closed pool are the distribution's quantiles at (i + 1/2)/n,
shuffled by the seed.  An open loop's tail latency hangs on where the few
longest prompts land among the bursts, so there the work is one pattern,
drawn from the mix's own `pattern_seed`: as many whole burst cycles as
cover the window, each phase holding rate * factor * phase_s arrivals
(rounded on the running total) at times drawn uniformly inside it (so the
gaps are exponential, as in a Poisson process), with lengths at the
quantiles, shuffled.  The run's seed picks the cycle the window starts at
(a rotation of the pattern, which repeats before and after the window) and
draws every prompt's token ids and sampling seed.  Every seed then offers
the window the same arrivals with the same lengths, in another order, and
runs with different seeds spread little more than runs of one seed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Tuple

import numpy as np

TAIL_S = 16.0


@dataclasses.dataclass
class Req:
    prompt: np.ndarray      # int32 token ids
    max_tokens: int
    temperature: float
    top_k: int
    seed: int               # the request's own sampling seed
    greedy: bool


def quantile(dist: dict, q: float) -> int:
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        return min(hi, lo + int(math.floor(q * (hi - lo + 1))))
    if dist["dist"] == "lognormal":
        z = NormalDist().inv_cdf(q)
        v = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return int(min(hi, max(lo, round(v))))
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n lengths at the distribution's quantiles (i + 1/2)/n, shuffled."""
    vals = np.array([quantile(dist, (i + 0.5) / n) for i in range(n)],
                    np.int64)
    return rng.permutation(vals)


def longest(params: dict) -> int:
    """The most tokens one request can hold: prompt plus output."""
    return int(params["prompt"]["max"]) + int(params["output"]["max"])


def _requests(params: dict, rng, vocab: int, n: int) -> List[Req]:
    s = params["sampling"]
    every = int(s.get("greedy_every", 0))
    plen = lengths(params["prompt"], n, rng)
    olen = lengths(params["output"], n, rng)
    out = []
    for i in range(n):
        greedy = every > 0 and i % every == every - 1
        out.append(Req(
            prompt=rng.integers(0, vocab, size=int(plen[i])).astype(np.int32),
            max_tokens=int(olen[i]),
            temperature=0.0 if greedy else float(s["temperature"]),
            top_k=0 if greedy else int(s["top_k"]),
            seed=int(rng.integers(0, 2**31 - 1)), greedy=greedy))
    return out


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def closed_pool(params: dict, seed: int, vocab: int) -> List[Req]:
    """The closed loop's requests, in the order they are submitted."""
    return _requests(params, _rng(seed, 1), vocab, int(params["pool"]))


def preroll(params: dict, seed: int, vocab: int, slots: int) -> List[Req]:
    """One request per slot before the window, prompts at the prompt
    median and outputs staggered up to the output median, so that the
    slots retire at different times and admissions are spread as in steady
    state once each has retired."""
    rng = _rng(seed, 2)
    p = quantile(params["prompt"], 0.5)
    o = quantile(params["output"], 0.5)
    s = params["sampling"]
    return [Req(prompt=rng.integers(0, vocab, size=p).astype(np.int32),
                max_tokens=max(1, -(-o * (j + 1) // slots)),
                temperature=float(s["temperature"]), top_k=int(s["top_k"]),
                seed=int(rng.integers(0, 2**31 - 1)), greedy=False)
            for j in range(slots)]


def open_schedule(params: dict, seed: int, vocab: int,
                  seconds: float) -> List[Tuple[float, Req]]:
    """(due time, request) for an open loop, due times in seconds from the
    window's start, from -lead_in_s to `TAIL_S` past the end of the window
    (arrivals after it keep the slots busy while the check reads them)."""
    b = params["bursts"]
    phase, factors = float(b["phase_s"]), [float(f) for f in b["factors"]]
    cycles = max(1, int(math.ceil(seconds / (phase * len(factors)) - 1e-9)))
    period = cycles * phase * len(factors)
    pat = _rng(int(params["pattern_seed"]), 3)
    times, expected, placed = [], 0.0, 0
    for k in range(cycles * len(factors)):
        # rounding the running total keeps every stretch of whole phases
        # within one arrival of rate * length
        expected += params["rate_per_s"] * factors[k % len(factors)] * phase
        n = int(round(expected)) - placed
        placed += n
        times.extend(k * phase + np.sort(pat.uniform(0.0, phase, size=n)))
    shape = _requests(params, pat, vocab, len(times))
    rng = _rng(seed, 3)
    shift = period * int(rng.integers(0, cycles)) / cycles
    lo, hi = -float(params["lead_in_s"]), seconds + TAIL_S
    out = []
    for rep in range(int(math.floor((lo + shift) / period)),
                     int(math.ceil((hi + shift) / period)) + 1):
        for t, r in zip(times, shape):
            due = float(t) + rep * period - shift
            if lo <= due < hi:
                out.append((due, dataclasses.replace(
                    r, prompt=rng.integers(0, vocab, size=r.prompt.size)
                    .astype(np.int32),
                    seed=int(rng.integers(0, 2**31 - 1)))))
    return out
