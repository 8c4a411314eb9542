"""A kernel's share of its roofline: the least time the chip could take
for the traced dispatches' own shapes — the larger of operations over peak
FLOP/s and bytes over peak bytes/s, by the cost function the metric's file
names (``chipbench/costs/<cost>.py``) — over the kernel's time in the trace.

The dispatches counted are those the harness saw the runner make while the
trace ran; the kernel's time is all of its events in the trace. The two
ends of the traced part can differ by a dispatch in about 150."""

from chipbench import registry
from chipbench.peaks import peaks_for


def read(obs, *, kernel: str, cost: str):
    if obs.trace is None:
        return None
    secs = sum(
        s for name, s in obs.trace["op_seconds"].items()
        if name.startswith(kernel)
    )
    t0, t1 = obs.trace["host_window"]
    steps = [lanes for t, lanes in obs.dispatches if t0 <= t < t1]
    if not secs or not steps:
        return None
    peaks = peaks_for(obs.device_kind)
    fn = registry.load("costs", cost).cost
    least = 0.0
    for lanes in steps:
        flops, nbytes = fn(lanes, model=obs.model, engine=obs.engine)
        least += max(
            flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"]
        )
    return 100.0 * least / secs
