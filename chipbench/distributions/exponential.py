"""Exponential with the given mean."""

import math


def quantile(u: float, *, mean: float) -> float:
    return -mean * math.log1p(-u)
