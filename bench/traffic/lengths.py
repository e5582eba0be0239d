"""Length and arrival multisets shared by the request generators.

Every seed gets the same multiset of sizes and gaps, taken at evenly spaced
quantiles of the stated distribution; the seed only shuffles their order and
draws the token ids.  So two seeds do the same work in a different order,
and the spread between runs is the system's, not the sample's.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the (i + 0.5) / n quantiles of ``spec``
    (``lognormal`` with ``median`` and ``sigma``, or ``uniform``), clipped
    to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + (spec["max"] - spec["min"]) * u
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles of an exponential of
    ``rate`` per second: a Poisson process's gaps, as a fixed multiset."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def lengths(spec: dict, n: int, rng: np.random.Generator):
    """(prompt, output) length pairs, shuffled independently by ``rng``;
    the output is cut so that prompt + output <= max_total."""
    p = rng.permutation(quantiles(spec["prompt"], n))
    o = rng.permutation(quantiles(spec["output"], n))
    o = np.minimum(o, spec["max_total"] - p)
    if np.any(o < 1):
        raise ValueError("max_total leaves no room for an output")
    return p, o
