"""A percentile of a client-side sample (``Observations.sample``)."""

from chipbench.stats import percentile


def read(obs, *, sample: str, q: float):
    xs = obs.sample(sample)
    return percentile(xs, q) if xs else None
