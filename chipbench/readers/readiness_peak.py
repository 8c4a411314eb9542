"""The peak over the window of a polled ``readiness()`` gauge."""


def read(obs, *, key: str, scale: float = 1.0):
    xs = [snap[key] for snap in obs.readiness if key in snap]
    return scale * max(xs) if xs else None
