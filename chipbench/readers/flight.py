"""A statistic over the window's flight-recorder steps of the sum of the
named fields: ``stat`` is ``mean`` or a percentile ``p50``, ``p95``..."""

from chipbench.stats import percentile


def read(obs, *, fields, stat: str):
    xs = [
        sum(rec.get(f, 0) for f in fields)
        for rec in obs.flight
        if "dispatch_ms" in rec
    ]
    if not xs:
        return None
    if stat == "mean":
        return sum(xs) / len(xs)
    return percentile(xs, float(stat[1:]))
