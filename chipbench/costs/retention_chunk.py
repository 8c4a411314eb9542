"""Power retention over the spans of more rows (``ops/pallas/
retention.py`` ``retention_chunk``): prefill quanta in the chunkwise form."""

from __future__ import annotations

from chipbench.costs.retention_recurrent import retention_layers, state_width

#: chunk lengths the least is taken over (and the whole span as one chunk)
CHUNKS = (16, 32, 64, 128, 256, 512, 1024)


def span_flops(prefix: int, n: int, *, h: int, kvh: int, d: int, D: int):
    """The fewest FLOPs a layer of the chunkwise form needs for a span of
    ``n`` rows behind ``prefix`` positions, over its chunk length ``C``, so
    that the count does not depend on the kernel's own chunk.

    Whatever ``C``: the rows' keys and values into the state, ``2 D (d +
    1)`` a row a cached head (``S`` and ``z``). By ``C``: within a chunk the
    attention form under the causal mask, ``QK^T`` and ``PV`` at 2 each a
    (query, key <= query, query head, dim); and ``phi(Q) [S | z]``, ``2 D
    (d + 1)`` a row a query head, for every row that has a state behind its
    chunk: all of them behind a prefix, those past the first chunk where
    the span starts the sequence."""
    update = n * kvh * 2 * D * (d + 1)

    def by_chunk(c: int) -> int:
        whole, rest = divmod(n, c)
        pairs = whole * c * (c + 1) // 2 + rest * (rest + 1) // 2
        reading = n if prefix else max(n - c, 0)
        return 4 * pairs * h * d + reading * h * 2 * D * (d + 1)

    return update + min(by_chunk(c) for c in CHUNKS + (n,))


def cost(lanes, *, model: dict, engine: dict):
    """Exactly the spans the ``retention_chunk`` kernel serves: those of
    more than one new row. FLOPs: ``span_flops``. Bytes: the state once a
    span (``kvH x D x (d + 1)`` float32 written, and read where the span
    has a prefix behind it), the span's q, k, v read and its output written
    in the served dtype. Lanes of one row go through
    ``retention_recurrent`` and are not counted here."""
    layers = retention_layers(model)
    spans = [(prefix, n) for prefix, n in lanes if n > 1]
    if not spans or not layers:
        return 0, 0
    h, kvh, d = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    D = state_width(model)
    state = kvh * D * (d + 1) * 4
    flops = nbytes = 0
    for prefix, n in spans:
        flops += span_flops(prefix, n, h=h, kvh=kvh, d=d, D=D)
        nbytes += (2 if prefix else 1) * state
        nbytes += n * (2 * h + 2 * kvh) * d * engine["dtype_bytes"]
    return flops * layers, nbytes * layers
