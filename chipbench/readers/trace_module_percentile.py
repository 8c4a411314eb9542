"""A percentile of the device durations of one jitted program's module
events in the traced part of the window (chip 0)."""

from chipbench.stats import percentile


def read(obs, *, module: str, q: float):
    if obs.trace is None:
        return None
    xs = [
        1000.0 * dur
        for name, dur in obs.trace["module_events"]
        if module in name
    ]
    return percentile(xs, q) if xs else None
