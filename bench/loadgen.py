"""Seeded generators of the benchmark's traffic.

The same seed gives the same sequence; the loops draw from these and hand
the program only the generated inputs.

* ``member_order``: simulation members in a fresh permutation per cycle
  (certification cells).
* ``poisson_gaps`` and ``exact_mix``: open-loop arrivals and rollout lengths
  for serving cells.  The arrival process is the program's
  ``serving/loadgen.py`` Poisson stream (there drawn afresh, with lengths
  drawn uniformly); here every seed gets the same multiset of gaps (the
  exponential distribution's quantiles) and of lengths (the mix's weights
  times the query count), in an order drawn from the seed.  So the work of a
  run does not depend on its seed, only its order does, and runs on
  different seeds spread no more than runs on one.
"""
from __future__ import annotations

from typing import Iterator, Sequence

import numpy as np


def member_order(pool: int, rng: np.random.Generator) -> Iterator[int]:
    """Endless member indices: a fresh permutation of the pool per cycle."""
    while True:
        yield from (int(m) for m in rng.permutation(pool))


def poisson_gaps(n: int, rate_per_s: float,
                 rng: np.random.Generator) -> np.ndarray:
    """(n,) inter-arrival gaps of a Poisson process at ``rate_per_s``: the
    quantiles ``(i + 1/2) / n`` of the exponential distribution, permuted."""
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-q) / rate_per_s)


def exact_mix(n: int, values: Sequence[int], weights: Sequence[float],
              rng: np.random.Generator) -> np.ndarray:
    """(n,) draws of ``values`` in the proportions ``weights`` (counts
    rounded by largest remainder), permuted."""
    p = np.asarray(weights, np.float64)
    share = n * p / p.sum()
    counts = np.floor(share).astype(int)
    short = n - counts.sum()
    counts[np.argsort(counts - share)[:short]] += 1
    return rng.permutation(np.repeat(np.asarray(values), counts))
