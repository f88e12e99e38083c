"""Seeded sizes for traffic generators.

Every seed gets the same set of sizes and gaps, in another order: the set
comes from evenly spaced quantiles of the mix's distribution, and the seed
only permutes it (and draws the token ids). So two seeds do the same work,
and a difference between their runs is noise, not a different workload.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def quantiles(n: int) -> np.ndarray:
    """n evenly spaced probabilities, the midpoints of n equal strata."""
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n integer lengths from ``spec``, permuted by ``rng``.

    ``spec``: ``{"dist": "lognormal", "median", "sigma", "min", "max"}``
    or ``{"dist": "uniform", "min", "max"}`` (both ends included).
    """
    u = quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(p) for p in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        x = lo + u * (hi + 1 - lo) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    out = np.clip(np.round(x), lo, hi).astype(np.int64)
    return rng.permutation(out)


def exponential_gaps(rate: float, n: int, rng: np.random.Generator
                     ) -> np.ndarray:
    """n inter-arrival gaps of a Poisson process at ``rate`` per second."""
    gaps = -np.log1p(-quantiles(n)) / rate
    return rng.permutation(gaps)


def token_ids(seed: int, rid: int, n: int, vocab: int) -> np.ndarray:
    """The prompt of request ``rid``: n ids drawn from the run's seed."""
    rng = np.random.default_rng([seed, rid])
    return rng.integers(0, vocab, size=n).astype(np.int32)


def count(rate: float, seconds: float) -> int:
    return max(1, int(math.ceil(rate * seconds)))
