"""Percentile and spread arithmetic, kept with the benchmark."""

from __future__ import annotations

import statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), on a copy sorted here."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * (q / 100.0)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def spread(values) -> float:
    """Distance between the first and third quartile as
    ``statistics.quantiles(values, n=4)`` gives them, as a share of the
    median: the measure the bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def spread_leaving_one_out(values) -> float:
    """The driver's measure for tightness: the spread of the set without
    the run farthest from its median, where that narrows it."""
    xs = list(values)
    if len(xs) < 4:
        return spread(xs)
    med = statistics.median(xs)
    far = max(range(len(xs)), key=lambda i: abs(xs[i] - med))
    rest = xs[:far] + xs[far + 1:]
    return min(spread(xs), spread(rest))
