"""Stratified lengths: the ``(i + 1/2) / N`` quantiles of a stated
distribution, so that every run of a cell holds the SAME multiset of
lengths and a seed only decides which request gets which."""

from __future__ import annotations

import math
from statistics import NormalDist


def quantile(dist: dict, u: float) -> int:
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        x = math.exp(lo + u * (hi - lo))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "fixed":
        x = dist["value"]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    x = int(round(x))
    return max(dist.get("min", x), min(dist.get("max", x), x))


def stratified(dist: dict, n: int) -> list:
    """``n`` lengths at the (i + 1/2)/n quantiles, ascending."""
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def fixed_shuffle(items: list, salt: int) -> list:
    """A permutation that does NOT depend on the run's seed (a fixed
    pairing of strata, e.g. prompt with output length): multiplicative
    stride over the index, coprime with the count."""
    n = len(items)
    if n < 2:
        return list(items)
    stride = max(1, int(n * 0.6180339887) + salt)
    while math.gcd(stride, n) != 1:
        stride += 1
    return [items[(i * stride + salt) % n] for i in range(n)]
