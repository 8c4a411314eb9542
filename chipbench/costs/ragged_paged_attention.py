"""The ragged paged attention kernel over a ``(k, v)`` cache
(``ops/pallas/ragged_attention.py``)."""

from __future__ import annotations


def cost(lanes, *, model: dict, engine: dict):
    """Causal attention of each span's ``n`` new rows over its
    ``prefix + n`` cached positions (fewer under a sliding window).
    FLOPs: QK^T and PV, 2 each per (query, key, head, dim). Bytes: every
    K and V row the span may see read once from the paged cache at the
    cache's (lane-padded) head width, q read and the output written."""
    tp = engine.get("tp", 1)
    heads = model["num_heads"] // tp
    kv_heads = max(model["num_kv_heads"] // tp, 1)
    d = model["head_dim"]
    dc = engine["cache_head_dim"]
    itemsize = engine["dtype_bytes"]
    kv_itemsize = engine.get("kv_dtype_bytes", itemsize)
    window = model.get("sliding_window", 0)
    flops = 0
    nbytes = 0
    for prefix, n in lanes:
        if n <= 0:
            continue
        # keys seen by the row at position p: p + 1, or the window
        pairs = 0
        for p in (prefix, prefix + n - 1):
            pairs += min(p + 1, window) if window else p + 1
        pairs = pairs * n / 2.0  # arithmetic series (exact without a window)
        kv_rows = prefix + n
        if window:
            kv_rows = min(kv_rows, window + n - 1)
        flops += 4 * pairs * heads * d
        nbytes += 2 * kv_rows * kv_heads * dc * kv_itemsize
        nbytes += 2 * n * heads * d * itemsize
    layers = model["num_layers"]
    return flops * layers, nbytes * layers
