"""The one traffic generator: turns a traffic file of parameters into the
requests of one run, from the run's seed.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps (stratified quantiles of the stated distributions), in an
order of its own, and token ids of its own. So runs with different seeds do
the same amount of work and differ only in order and content.

Open loop (``"loop": "open"``): ``round(rate_per_s * seconds)`` requests due
at fixed offsets inside ``[0, seconds)``, Poisson-like gaps (exponential
quantiles) scaled to fill the window exactly. Closed loop
(``"loop": "closed"``): a pool of ``pool`` requests with no due time,
cycled in a fresh order per pass; the driver keeps ``waiting`` of them
queued.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np


@dataclass
class Req:
    prompt: np.ndarray           # int32 token ids
    max_new: int
    due: Optional[float]         # seconds after the window opens (open loop)


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths: the stratified quantiles of ``spec``'s distribution,
    clipped to its bounds, in the order ``rng`` draws."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(p) for p in _strata(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    vals = np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(vals)


def open_loop(traffic: dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    rng = np.random.default_rng(seed)
    gaps = rng.permutation(-np.log1p(-_strata(n)))
    gaps *= seconds / gaps.sum()
    dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plens = lengths(traffic["prompt_len"], n, rng)
    outs = lengths(traffic["output_len"], n, rng)
    return [Req(rng.integers(0, vocab, int(plens[i])).astype(np.int32),
                int(outs[i]), float(dues[i])) for i in range(n)]


def closed_loop(traffic: dict, seed: int, vocab: int) -> Iterator[Req]:
    """Endless stream: passes over the same pool of sizes, each pass in an
    order of its own."""
    rng = np.random.default_rng(seed)
    n = traffic["pool"]
    while True:
        plens = lengths(traffic["prompt_len"], n, rng)
        outs = lengths(traffic["output_len"], n, rng)
        for p, o in zip(plens, outs):
            yield Req(rng.integers(0, vocab, int(p)).astype(np.int32),
                      int(o), None)


def prompt_bounds(traffic: dict) -> tuple:
    return traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
