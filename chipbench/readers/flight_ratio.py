"""A ratio of two sums over the window's flight-recorder steps:
``scale * sum(over fields) / sum(under fields)``. A program whose records
lack the fields (or a window that fed none) reads nothing."""


def read(obs, *, over, under, scale: float = 1.0):
    steps = [rec for rec in obs.flight if "dispatch_ms" in rec]
    below = sum(rec.get(f, 0) for rec in steps for f in under)
    if not below:
        return None
    return scale * sum(rec.get(f, 0) for rec in steps for f in over) / below
