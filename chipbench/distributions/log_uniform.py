"""Log-uniform on [low, high]: ``quantile(u) = low * (high/low) ** u``."""


def quantile(u: float, *, low: float, high: float) -> float:
    return low * (high / low) ** u


def mean(*, low: float, high: float) -> float:
    import math

    return (high - low) / math.log(high / low)
