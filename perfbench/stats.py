"""Percentiles, spreads and seeded schedules.  No JAX, no clock."""

from __future__ import annotations

import math
import random
import statistics
from typing import Any, Dict, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks, as numpy's default does."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def quantile_set(dist: Dict[str, Any], n: int) -> List[int]:
    """``n`` sizes at the fixed quantiles (i + 0.5) / n of ``dist``: every
    seed gets the same sizes and only their order differs."""
    kind = dist["dist"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    lo, hi = dist["low"], dist["high"]
    qs = [(i + 0.5) / n for i in range(n)]
    if kind == "uniform":
        return [int(round(lo + (hi - lo) * q)) for q in qs]
    if kind == "loguniform":
        return [int(round(math.exp(
            math.log(lo) + (math.log(hi) - math.log(lo)) * q))) for q in qs]
    raise ValueError(f"unknown distribution {kind!r}")


def sizes(dist: Dict[str, Any], n: int, rng: random.Random) -> List[int]:
    out = quantile_set(dist, n)
    rng.shuffle(out)
    return out


def poisson_arrivals(rate: float, seconds: float, rng: random.Random
                     ) -> List[float]:
    """Arrival times in [0, seconds) of a Poisson process of ``rate``: the
    n = rate * seconds gaps are the exponential's fixed quantiles
    (i + 0.5) / n, scaled to fill the window and shuffled by the seed.
    Same gaps for every seed, another order."""
    n = max(1, int(round(rate * seconds)))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    scale = seconds / sum(gaps) * n / (n + 0.5)   # last arrival inside
    rng.shuffle(gaps)
    t, out = 0.0, []
    for g in gaps:
        t += g * scale
        out.append(t)
    return out


def prompt(rng: random.Random, n: int, vocab: int) -> List[int]:
    return [rng.randrange(vocab) for _ in range(n)]
