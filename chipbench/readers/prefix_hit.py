"""Prompt blocks served from the device's prefix cache, as a share of the
prompt blocks of the requests sent in the window: the difference of
``kv_reused_device_blocks_total`` across the window over prompt tokens
(as the server counted them, template included) / block size."""


def read(obs):
    if obs.readiness_edges is None:
        return None
    first, last = obs.readiness_edges
    key = "kv_reused_device_blocks_total"
    if key not in first or key not in last:
        return None
    bs = obs.engine["block_size"]
    blocks = sum(
        r["usage_prompt"] // bs
        for r in obs.records
        if obs.in_window(r["sent"]) and "usage_prompt" in r
    )
    if not blocks:
        return None
    return 100.0 * (last[key] - first[key]) / blocks
