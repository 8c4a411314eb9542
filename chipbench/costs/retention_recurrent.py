"""Power retention's state update for the lanes of one row
(``ops/pallas/retention.py`` ``retention_recurrent``): decode lanes, the
state read and written once, in place."""

from __future__ import annotations


def retention_layers(model: dict) -> int:
    """Layers that keep a retention state: all of them where the model has
    a ``retention_degree``, else none."""
    return model["num_layers"] if model.get("retention_degree") else 0


def state_width(model: dict) -> int:
    """The mathematical ``D`` of the symmetric square of a key: ``d (d + 1)
    / 2``, 8,256 at ``d`` 128, whatever a kernel pads it to."""
    d = model["head_dim"]
    return d * (d + 1) // 2


def cost(lanes, *, model: dict, engine: dict):
    """Exactly the lanes the ``retention_recurrent`` kernel serves: spans
    of one new row (a decode lane, or a prompt of one token), whatever lies
    behind them. A lane and layer, per cached head: bytes, ``S`` of ``D x
    d`` and ``z`` of ``D`` in float32 read and written (2 x kvH x 8,256 x
    129 x 4 B at these widths), the row's q, k, v read and its output
    written in the served dtype. FLOPs: the gate (1) and the rank-one
    update (2) an element of the state, and ``phi(q)^T [S | z]`` (2) an
    element a query head of the group. Spans of more rows go through
    ``retention_chunk`` and are not counted here."""
    one_row = sum(1 for _prefix, n in lanes if n == 1)
    layers = retention_layers(model)
    if not one_row or not layers:
        return 0, 0
    h, kvh, d = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    state = kvh * state_width(model) * (d + 1)
    flops = one_row * (3 * state + 2 * (h // kvh) * state)
    nbytes = one_row * (
        2 * state * 4 + (2 * h + 2 * kvh) * d * engine["dtype_bytes"]
    )
    return flops * layers, nbytes * layers
