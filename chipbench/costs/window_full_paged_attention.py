"""The ragged paged attention kernel (``ops/pallas/ragged_attention.py``)
over a ``(k, v)`` cache whose layers are of two kinds: every
``window_pattern``-th layer attends over the whole context, the others
over a sliding window. Each layer is reckoned by ITS window."""

from __future__ import annotations


def layer_windows(model: dict) -> list[int]:
    """Each layer's window (0 = the whole context), from the scalar fields
    ``sliding_window``, ``window_pattern`` and ``num_layers``: layer ``l``
    is full iff ``(l + 1) % window_pattern == 0``; without a pattern every
    layer has the window."""
    window = model.get("sliding_window", 0)
    period = model.get("window_pattern", 0)
    return [
        0 if period and (li + 1) % period == 0 else window
        for li in range(model["num_layers"])
    ]


def one_layer(lanes, window: int, *, heads, kv_heads, d, dc, itemsize,
              kv_itemsize):
    """(flops, bytes) of one layer under ``window``. FLOPs: QK^T and PV, 2
    each per (query, visible key, head, dim). Bytes: every K and V row the
    span may see read once from the paged cache at the cache's
    (lane-padded) head width, q read and the output written."""
    flops = 0
    nbytes = 0
    for prefix, n in lanes:
        if n <= 0:
            continue
        if window:
            # the row at position p sees min(p + 1, window) keys: exact
            # sum over the span's rows
            ramp = max(min(window - 1 - prefix, n), 0)   # rows still under it
            pairs = ramp * (2 * prefix + ramp + 1) / 2.0 + (n - ramp) * window
            kv_rows = min(prefix + n, window + n - 1)
        else:
            pairs = n * (2 * prefix + n + 1) / 2.0
            kv_rows = prefix + n
        flops += 4 * pairs * heads * d
        nbytes += 2 * kv_rows * kv_heads * dc * kv_itemsize
        nbytes += 2 * n * heads * d * itemsize
    return flops, nbytes


def cost(lanes, *, model: dict, engine: dict):
    tp = engine.get("tp", 1)
    shape = dict(
        heads=model["num_heads"] // tp,
        kv_heads=max(model["num_kv_heads"] // tp, 1),
        d=model["head_dim"], dc=engine["cache_head_dim"],
        itemsize=engine["dtype_bytes"],
        kv_itemsize=engine.get("kv_dtype_bytes", engine["dtype_bytes"]),
    )
    by_window = {}
    flops = nbytes = 0
    for window in layer_windows(model):
        if window not in by_window:
            by_window[window] = one_layer(lanes, window, **shape)
        flops += by_window[window][0]
        nbytes += by_window[window][1]
    return flops, nbytes
