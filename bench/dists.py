"""Sizes and arrival gaps drawn by quantile, so that every seed gets the
same set of values and only their order changes.

A seed that changed the work itself would make runs of different seeds
differ by more than runs of one seed; stratified quantiles at
``(i + 0.5) / n`` keep the set fixed while `--seed` permutes it.  Where a
window sees only the first few values of a long sequence (the refills of
a closed loop), no permutation keeps the few it sees alike from seed to
seed; `spread_order` is then one fixed order whose every prefix of 2**k
values is stratified.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List

import numpy as np


def quantile(dist: Dict, q: float) -> float:
    kind = dist["dist"]
    if kind == "uniform":
        return dist["min"] + q * (dist["max"] - dist["min"])
    if kind == "lognormal":
        z = statistics.NormalDist().inv_cdf(q)
        v = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return min(max(v, dist["min"]), dist["max"])
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: Dict, n: int) -> List[int]:
    """n whole sizes at the quantiles (i + 0.5) / n, ascending."""
    return [int(round(quantile(dist, (i + 0.5) / n))) for i in range(n)]


def sizes(dist: Dict, n: int, rng: np.random.Generator) -> List[int]:
    """n whole sizes at stratified quantiles, in an order drawn from rng."""
    vals = stratified(dist, n)
    return [vals[i] for i in rng.permutation(n)]


def spread_order(n: int) -> List[int]:
    """A permutation of range(n) whose every prefix of 2**k indices holds
    one index in each of 2**k equal strata: the bit-reversed counter
    (indices past n, where n is not a power of two, are skipped)."""
    bits = max(1, (n - 1).bit_length())
    rev = (int(format(j, f"0{bits}b")[::-1], 2) for j in range(1 << bits))
    return [i for i in rev if i < n]


def residuals(dist: Dict, n: int, grid: int = 4096) -> List[int]:
    """n whole remaining lengths at stratified quantiles of the steady
    state of slots that each serve one request after another with lengths
    from `dist`: a slot holds a request with probability in proportion to
    its length, at a position uniform within it."""
    lengths = np.asarray(stratified(dist, grid), np.float64)
    r = np.arange(1, int(lengths.max()) + 1)
    cdf = np.minimum(r[:, None], lengths[None, :]).sum(1) / lengths.sum()
    return [int(r[min(np.searchsorted(cdf, (i + 0.5) / n), len(r) - 1)])
            for i in range(n)]


def poisson_gaps(rate: float, n: int, rng: np.random.Generator
                 ) -> List[float]:
    """n exponential inter-arrival gaps (mean 1/rate) at stratified
    quantiles, in an order drawn from rng."""
    vals = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    return [vals[i] for i in rng.permutation(n)]


def prompt_tokens(lengths: List[int], vocab: int,
                  rng: np.random.Generator) -> List[List[int]]:
    return [rng.integers(1, vocab, size=n).tolist() for n in lengths]
