"""One generator for every traffic mix: a mix is a data file of parameters.

Lengths and inter-arrival gaps are drawn by stratified quantiles and then
shuffled by the seed, so every seed serves the same multiset of sizes and
gaps in another order: the seed changes which request is which, not how
much work a run holds. Token ids are uniform over the vocabulary.

File keys (``bench/traffic/<name>.json``):
  loop            "open" (arrivals on a schedule) or "closed" (a fixed
                  number of outstanding requests, a new one on each finish)
  rate_per_s      open loop: mean arrival rate (Poisson)
  concurrency     closed loop: outstanding requests
  prompt, output  {"dist": "lognormal", "median", "sigma", "min", "max"}
                  or {"dist": "uniform", "min", "max"} (inclusive)
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

#: purposes of the streams derived from one ``--seed``
WEIGHTS, TRAFFIC, SAMPLE = 0, 1, 2


def rng(seed: int, purpose: int) -> np.random.Generator:
    return np.random.default_rng([purpose, int(seed) % (1 << 64)])


def seed32(seed: int, purpose: int) -> int:
    return int(np.random.SeedSequence(
        [purpose, int(seed) % (1 << 64)]).generate_state(1)[0])


@dataclasses.dataclass
class Req:
    idx: int
    prompt: np.ndarray      # int32 token ids
    max_new: int
    due: float = 0.0        # seconds from the start of the load


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int, g: np.random.Generator) -> np.ndarray:
    q = _quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        v = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    elif dist["dist"] == "uniform":
        v = lo + np.floor(q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return g.permutation(np.clip(v, lo, hi).astype(np.int64))


def gaps(rate: float, n: int, g: np.random.Generator) -> np.ndarray:
    """Poisson inter-arrival gaps in seconds."""
    return g.permutation(-np.log1p(-_quantiles(n)) / rate)


def count(traffic: dict, horizon_s: float) -> int:
    """Requests the mix needs to cover ``horizon_s`` seconds of load."""
    if traffic["loop"] == "open":
        return int(math.ceil(traffic["rate_per_s"] * horizon_s)) + 1
    # closed loop: more than a run can finish; only the order is used
    return int(traffic["pool"])


def generate(traffic: dict, seed: int, vocab: int,
             horizon_s: float) -> List[Req]:
    n = count(traffic, horizon_s)
    g = rng(seed, TRAFFIC)
    p_len = lengths(traffic["prompt"], n, g)
    o_len = lengths(traffic["output"], n, g)
    due = (np.cumsum(gaps(traffic["rate_per_s"], n, g))
           if traffic["loop"] == "open" else np.zeros(n))
    toks = g.integers(0, vocab, size=int(p_len.sum()), dtype=np.int32)
    cut = np.concatenate([[0], np.cumsum(p_len)])
    return [Req(i, toks[cut[i]:cut[i + 1]], int(o_len[i]), float(due[i]))
            for i in range(n)]
