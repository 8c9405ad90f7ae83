"""Percentile, window and spread arithmetic on raw samples.

No histogram and no bucket: every quantile here is computed from the
samples themselves, so a bound of a few percent can rest on it."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) of ``samples`` by linear
    interpolation between the two closest ranks (numpy's default rule).
    Raises on an empty sample: a percentile of nothing is not 0."""
    if not samples:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    data = sorted(samples)
    pos = (len(data) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(data) - 1)
    return data[lo] + (data[hi] - data[lo]) * (pos - lo)


def in_window(stamps: Iterable[float], start: float, end: float) -> int:
    """How many stamps fall in the half-open window ``[start, end)``."""
    return sum(1 for t in stamps if start <= t < end)


def rate_in_window(stamps: Iterable[float], start: float,
                   end: float) -> float:
    """Events per second over ALL of ``[start, end)``: every event of the
    window over the whole length of the window, ramp and lull included."""
    if end <= start:
        raise ValueError(f"empty window [{start}, {end})")
    return in_window(stamps, start, end) / (end - start)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def iqr_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` — the spread the
    driver reads when it judges a bound."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    if med == 0:
        raise ValueError("spread of a sample whose median is 0")
    return (q3 - q1) / abs(med)
