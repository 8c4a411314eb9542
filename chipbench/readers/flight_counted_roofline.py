"""A kernel's share of its roofline where the least time depends on SEVERAL
numbers the program itself counted for each dispatch: as
``flight_kernel_roofline``, with ``counted`` a mapping from a flight
record's field to the keyword the cost function
(``chipbench/costs/<cost>.py``) takes it under. A record that lacks one of
the fields, or reads 0 in all of them, is left out; a program whose records
lack them reads nothing."""

from chipbench import registry
from chipbench.peaks import peaks_for


def read(obs, *, kernel: str, cost: str, counted: dict):
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
        and all(field in rec for field in counted)
        and any(rec[field] for field in counted)
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
            **{keyword: rec[field] for field, keyword in counted.items()},
        )
        least += max(
            flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"]
        )
    return 100.0 * least / secs
