"""The one traffic generator: turns a mix's parameters and a seed into
requests or training rows.

Every seed gets the same multiset of sizes and gaps, in another order:
sizes are the distribution's quantiles at evenly spaced points, and the
seed permutes them.  So two seeds ask the same work of the system, and
runs with different seeds differ by order, not by load.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np
from scipy.special import ndtri


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of a (possibly > 32-bit) seed."""
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` integer sizes at the quantiles (i + 0.5) / n of ``dist``."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"])
        x = np.floor(x)
    elif kind == "lognormal":
        x = np.round(dist["median"] * np.exp(dist["sigma"] * ndtri(u)))
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


@dataclasses.dataclass
class Req:
    uid: int
    arrival: float            # seconds after the window opens (open loop)
    prompt: np.ndarray        # int32 tokens
    max_new: int


def _prompts(seed: int, lens: np.ndarray, vocab: int) -> List[np.ndarray]:
    rng = rng_for(seed, 3)
    flat = rng.integers(0, vocab, int(lens.sum()), dtype=np.int32)
    return np.split(flat, np.cumsum(lens)[:-1])


def max_new_limit(mix: dict) -> int:
    """The most new tokens any request of ``mix`` asks for."""
    mn = mix["max_new"]
    if mn["dist"] == "budget":
        return int(mn["total"] - mix["prompt_len"]["min"])
    return int(mn["max"])


def requests(mix: dict, seed: int, n: int, vocab: int,
             uid_base: int = 0) -> List[Req]:
    """``n`` requests of ``mix``: sizes from its quantiles in seeded order,
    arrivals (open loop) from the quantiles of the exponential gap; uids
    from ``uid_base``.  A ``max_new`` of dist ``budget`` gives each request
    what is left of ``total`` tokens after its prompt."""
    lens = rng_for(seed, 1).permutation(quantiles(mix["prompt_len"], n))
    mn = mix["max_new"]
    if mn["dist"] == "budget":
        new = mn["total"] - lens
    else:
        new = rng_for(seed, 2).permutation(quantiles(mn, n))
    arrivals = np.zeros(n)
    if mix["kind"] == "open_loop":
        arrivals = open_loop_arrivals(mix, seed, n)
    return [Req(uid_base + i, float(arrivals[i]), p, int(new[i]))
            for i, p in enumerate(_prompts(seed, lens, vocab))]


def open_loop_arrivals(mix: dict, seed: int, n: int) -> np.ndarray:
    """Arrival times of ``n`` requests at the mix's Poisson rate: gaps at
    the exponential's quantiles, permuted by the seed, scaled so that the
    ``n`` gaps add up to exactly n / rate.  The first arrives at 0."""
    rate = mix["arrivals"]["rate_per_s"]
    u = (np.arange(n) + 0.5) / n
    gaps = rng_for(seed, 4).permutation(-np.log1p(-u))
    gaps *= (n / rate) / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def open_loop_count(mix: dict, seconds: float) -> int:
    """Requests that arrive in a window of ``seconds``."""
    return max(1, int(math.floor(mix["arrivals"]["rate_per_s"] * seconds)))


class TrainRows:
    """Training rows from the seed: a fresh [batch, seq + 1] block of
    uniform token ids on every call, next-token labels."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.b, self.s = mix["batch"], mix["seq"]
        self.vocab = vocab
        self.rng = rng_for(seed, 5)

    def next(self):
        toks = self.rng.integers(0, self.vocab, (self.b, self.s + 1),
                                 dtype=np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
