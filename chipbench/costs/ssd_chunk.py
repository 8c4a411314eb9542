"""The Mamba-2 recurrence over the spans of more rows (``ops/pallas/
ssd.py`` ``ssd_chunk``): prefill quanta in the masked form of the
state-space duality, in tiles of 128 rows."""

from __future__ import annotations

from chipbench.costs.ssd_recurrent import (
    row_elements,
    ssd_layers,
    state_elements,
)

#: rows of a tile (the family's ``chunk_size``)
TILE = 128


def span_flops(prefix: int, n: int, *, h: int, p: int, g: int, s: int) -> int:
    """The FLOPs a layer of the masked form needs for a span of ``n`` rows
    behind ``prefix`` positions, in tiles of ``TILE`` rows, a multiply-add
    2. A tile of ``r`` rows: ``C B^T`` a group and ``((C B^T) .* L) (dt
    x)`` a head over its causal pairs ``r (r + 1) / 2``, each counted ONCE
    (the kernel's masked products spend a full ``r x r``: 6.55 MFLOP a row
    at full tiles where this counts 5.4); the rows into the state,
    ``(dt x)^T B``, ``P x N`` a row a head; and ``C S_0`` likewise for
    every row that has a state behind its tile: all of them behind a
    prefix, those past the first tile where the span starts the
    sequence."""
    flops = 0
    for off in range(0, n, TILE):
        r = min(TILE, n - off)
        pairs = r * (r + 1) // 2
        flops += 2 * pairs * (g * s + h * p)
        flops += 2 * r * h * p * s * (2 if prefix or off else 1)
    return flops


def cost(lanes, *, model: dict, engine: dict):
    """Exactly the spans the ``ssd_chunk`` kernel serves: those of more
    than one new row. FLOPs: ``span_flops``. Bytes: the state once a span
    (``H x P x N`` float32 written, and read where the span has a prefix
    behind it) and each row's inputs and output in float32
    (``ssd_recurrent.row_elements``). Lanes of one row go through
    ``ssd_recurrent`` and are not counted here."""
    del engine
    layers = ssd_layers(model)
    spans = [(prefix, n) for prefix, n in lanes if n > 1]
    if not spans or not layers:
        return 0, 0
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, s = model["mamba_n_groups"], model["ssm_state_size"]
    state = state_elements(model) * 4
    flops = nbytes = 0
    for prefix, n in spans:
        flops += span_flops(prefix, n, h=h, p=p, g=g, s=s)
        nbytes += (2 if prefix else 1) * state + n * row_elements(model) * 4
    return flops * layers, nbytes * layers
