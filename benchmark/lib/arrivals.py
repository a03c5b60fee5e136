"""Traffic draws, seeded from `--seed`.

The distributions are `bench_load.py`'s (Poisson arrivals, which are exponential
gaps; lognormal or uniform token counts, clipped). What differs is how a seed uses
them: a seed may not change the amount of work, or runs with different seeds spread
by the difference in work and not by the system's noise. So each draw here is the
distribution's own quantiles at n evenly spaced levels (the same multiset for every
seed), shuffled once by the traffic file's `order_seed` into one fixed cycle. `--seed`
chooses where in the cycle a run starts (`rotation`) and draws the token ids: every
seed replays the same cycle of sizes and gaps from another phase, so a request meets
the same neighbours in every run, and a tail is the tail of the same traffic.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _levels(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def exponential_gaps(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """n inter-arrival gaps of a Poisson process at `rate` per second: the quantiles of
    Exp(rate), permuted. Their sum is close to n / rate for every seed."""
    gaps = -np.log1p(-_levels(n)) / rate
    return rng.permutation(gaps)


def lognormal_lengths(median: float, sigma: float, lo: int, hi: int, n: int,
                      rng: np.random.Generator) -> np.ndarray:
    """n token counts: quantiles of a lognormal with this median and sigma, clipped to
    [lo, hi], permuted."""
    nd = NormalDist()
    raw = [median * math.exp(sigma * nd.inv_cdf(float(p))) for p in _levels(n)]
    vals = np.array([int(min(hi, max(lo, round(x)))) for x in raw], dtype=np.int64)
    return rng.permutation(vals)


def uniform_lengths(lo: int, hi: int, n: int, rng: np.random.Generator) -> np.ndarray:
    """n token counts evenly spread over [lo, hi], permuted."""
    vals = np.rint(lo + _levels(n) * (hi - lo)).astype(np.int64)
    return rng.permutation(vals)


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Dispatch on a traffic file's length spec: {"dist": "uniform", "lo", "hi"} or
    {"dist": "lognormal", "median", "sigma", "lo", "hi"}."""
    if spec["dist"] == "uniform":
        return uniform_lengths(spec["lo"], spec["hi"], n, rng)
    if spec["dist"] == "lognormal":
        return lognormal_lengths(spec["median"], spec["sigma"], spec["lo"], spec["hi"], n, rng)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def token_ids(n: int, vocab: int, rng: np.random.Generator) -> list:
    """A prompt of n ids drawn uniformly: unique with near certainty, so no two
    prompts share a cache block."""
    return rng.integers(0, vocab, size=int(n)).tolist()


def rotation(seed: int, n: int) -> int:
    """Where in a cycle of n this seed starts."""
    return int(rng_for(seed, 11).integers(n))


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """Seeds are any whole number up to a little over 2**31; numpy takes them whole."""
    return np.random.default_rng([int(seed), int(stream)])
