"""The one traffic generator: a mix file of parameters in, a schedule out.

A mix (`traffic/<name>.json`) is data only:

    loop              "open" (arrivals on a schedule) or "closed"
                      (`clients` callers, each sending its next request
                      when the previous one is done, no think time)
    rate_share_of_knee  open loop: offered rate as a share of the cell's
                      knee; the cell file (`cells/<cell>.json`) fixes the
                      rate itself in `rate_rps`
    clients           closed loop: number of callers
    arrivals          open loop: "poisson"
    answer_tokens     {"dist": "lognormal", "median": m, "sigma": s,
                       "min": a, "max": b}: per-request max_new
    questions         "corpus": questions drawn uniformly, with
                      replacement, from the corpus's own question set
    pool              closed loop: size of the length pool clients draw from
    block             closed loop: the pool is made of blocks of this many
                      requests, each holding the same lengths (default:
                      one block, the whole pool)

Every seed gets the same multiset of sizes and arrival gaps, in another
order: lengths and gaps are stratified quantiles of their distributions
(one per request, at (i + 0.5) / n), shuffled by the seed. So two seeds
do the same work, and the seed changes only which question goes where and
in what order the lengths and gaps come. A closed loop consumes an
unknown part of its pool, so there each block of `block` requests holds
the same lengths: any window sees the same lengths but for the part of
one block.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Sequence

import numpy as np


@dataclass(frozen=True)
class Request:
    """One request of a schedule. `due_s` is seconds after the window
    opens (open loop), or None when a closed-loop client sends it."""
    question: str
    max_new: int
    due_s: float | None = None


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose): the same seed always
    gives the same questions, arrivals and lengths, and no stream shifts
    when another draws more."""
    tag = [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence([int(seed), *tag]))


def answer_lengths(spec: dict, n: int) -> np.ndarray:
    """n stratified quantiles of the answer-length distribution, clipped
    and rounded, in ascending order."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown answer_tokens dist {spec['dist']!r}")
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    qs = [(i + 0.5) / n for i in range(n)]
    vals = np.exp([mu + sigma * nd.inv_cdf(q) for q in qs])
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def poisson_gaps(rate_rps: float, seconds: float) -> np.ndarray:
    """Inter-arrival gaps of a Poisson process at `rate_rps` over
    `seconds`: round(rate * seconds) stratified exponential quantiles,
    rescaled so the arrivals span the window exactly."""
    n = max(1, int(round(rate_rps * seconds)))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return gaps * (seconds / gaps.sum())


def open_loop(mix: dict, rate_rps: float, seconds: float,
              questions: Sequence[str], seed: int) -> List[Request]:
    """The window's arrivals in due order: due times from shuffled gaps
    (the first request is due when the window opens), a shuffled length
    per request, and a question drawn per request."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    gaps = poisson_gaps(rate_rps, seconds)
    n = len(gaps)
    gaps = gaps[rng_for(seed, "gaps").permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    lens = answer_lengths(mix["answer_tokens"], n)
    lens = lens[rng_for(seed, "lengths").permutation(n)]
    qi = rng_for(seed, "questions").integers(0, len(questions), n)
    return [Request(questions[q], int(m), float(t))
            for q, m, t in zip(qi, lens, due)]


def closed_loop_pool(mix: dict, questions: Sequence[str],
                     seed: int) -> List[Request]:
    """The closed loop's request pool, taken in order by whichever client
    is free (and cycled if the window outlasts it)."""
    n = int(mix["pool"])
    block = int(mix.get("block", n))
    if n % block:
        raise ValueError(f"pool {n} is not a whole number of blocks of "
                         f"{block}")
    rng = rng_for(seed, "lengths")
    one = answer_lengths(mix["answer_tokens"], block)
    lens = np.concatenate([one[rng.permutation(block)]
                           for _ in range(n // block)])
    qi = rng_for(seed, "questions").integers(0, len(questions), n)
    return [Request(questions[q], int(m)) for q, m in zip(qi, lens)]


def warmup_requests(mix: dict, questions: Sequence[str], seed: int,
                    n: int) -> List[Request]:
    """n requests for set-up, drawn apart from the window's, each at the
    shortest answer the mix asks for: warm-up has to run every program
    the window runs (retrieval, SCR, chunk prefill, page copy, decode,
    sampling), and decode's shape does not depend on the answer length."""
    low = int(mix["answer_tokens"]["min"])
    qi = rng_for(seed, "warmup").integers(0, len(questions), n)
    return [Request(questions[q], low) for q in qi]
