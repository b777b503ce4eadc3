"""One general generator for every traffic mix (``traffic/<name>.json``).

A mix is data:

    loop           "open": arrivals on a schedule, Poisson at ``rate_rps``
    task           the encode task each request asks for ("classify")
    prompt_len     {"dist": "lognormal", "median", "sigma", "min", "max"}
    ramp_s         seconds of arrivals before the measured window opens

Steadiness: a seed must change the order of the work, not its amount. So
the sizes and the Poisson gaps are fixed sets, the quantiles
``(i + 0.5) / n`` of their distributions, and the seed only shuffles them
and draws the token ids. Two seeds then offer the same lengths and gaps in
another order.

The arrival process follows ``serving/loadgen.make_arrivals`` (exponential
gaps at a fixed rate); the lengths are heavy-tailed here, where that
generator draws them uniform.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Arrival:
    index: int
    t: float                     # offset from the first arrival, seconds
    tokens: np.ndarray


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a length distribution, as ints."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = int(spec["min"]), int(spec["max"])
    q = (np.arange(n) + 0.5) / n
    mu, sigma = math.log(float(spec["median"])), float(spec["sigma"])
    nd = NormalDist()
    flo = nd.cdf((math.log(lo) - mu) / sigma)
    fhi = nd.cdf((math.log(hi + 1) - mu) / sigma)
    z = [nd.inv_cdf(flo + p * (fhi - flo)) for p in q]
    vals = np.exp(mu + sigma * np.asarray(z))
    return np.clip(np.floor(vals), lo, hi).astype(np.int64)


def poisson_gaps(rate: float, n: int) -> np.ndarray:
    """Mid-quantiles of the exponential inter-arrival law at ``rate``."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def request_count(traffic: dict, seconds: float) -> int:
    """How many requests a run draws: every arrival of the ramp and the
    window."""
    span = float(traffic.get("ramp_s", 0.0)) + seconds
    return int(math.ceil(traffic["rate_rps"] * span)) + 1


def make_arrivals(traffic: dict, seed: int, vocab: int, n: int) -> list:
    if traffic["loop"] != "open":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    rng = np.random.default_rng(seed)
    plens = rng.permutation(quantiles(traffic["prompt_len"], n))
    t = np.cumsum(rng.permutation(poisson_gaps(traffic["rate_rps"], n)))
    return [Arrival(index=i, t=float(t[i]),
                    tokens=rng.integers(1, vocab, int(plens[i]))
                    .astype(np.int32))
            for i in range(n)]
