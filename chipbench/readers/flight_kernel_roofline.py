"""A kernel's share of its roofline where the least time depends on what
the program itself counted for each dispatch: as ``kernel_roofline``, but
the dispatches are the flight recorder's records inside the traced part of
the window, and the cost function (``chipbench/costs/<cost>.py``) is given
each record's rows and its ``counted`` field under the keyword the
metric's file names. A program whose records lack the field reads nothing.

A record is stamped when the program notes it (a block-diffusion dispatch
at its retire), so the two ends of the traced part can differ by a
dispatch in about a hundred."""

from chipbench import registry
from chipbench.peaks import peaks_for


def read(obs, *, kernel: str, cost: str, counted: str, keyword: str):
    if obs.trace is None:
        return None
    secs = sum(
        s for name, s in obs.trace["op_seconds"].items()
        if name.startswith(kernel)
    )
    t0, t1 = (t + obs.unix_minus_mono for t in obs.trace["host_window"])
    steps = [
        rec for rec in obs.flight
        if "dispatch_ms" in rec and t0 <= rec["t_unix"] < t1
        and rec.get(counted)
    ]
    if not secs or not steps:
        return None
    peaks = peaks_for(obs.device_kind)
    fn = registry.load("costs", cost).cost
    least = 0.0
    for rec in steps:
        rows = rec.get("decode_tokens", 0) + rec.get("prefill_tokens", 0)
        flops, nbytes = fn(
            [(0, rows)], model=obs.model, engine=obs.engine,
            **{keyword: rec[counted]},
        )
        least += max(
            flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"]
        )
    return 100.0 * least / secs
