"""The delta-rule state update of a linear-attention (KDA) layer for the
lanes of one row (``ops/pallas/kda.py`` ``kda_recurrent``): decode lanes,
the state read and written once, in place."""

from __future__ import annotations


def kda_layers(model: dict) -> int:
    """Layers that keep a recurrent state: every layer but each group's
    last (``layer_group_size``; 0 = the model has none)."""
    group = model.get("layer_group_size", 0)
    if not group:
        return 0
    return sum((li + 1) % group != 0 for li in range(model["num_layers"]))


def cost(lanes, *, model: dict, engine: dict):
    """Exactly the lanes the ``kda_recurrent`` kernel serves: spans of one
    new row (a decode lane, or a prompt of one token), whatever lies behind
    them. A lane and layer: bytes, the state of ``H x d x d`` float32 read
    and written, the row's decay, k, beta*k and q (4 x H x d) and beta*v
    (H x d) read and the output (H x d) written, all float32. FLOPs: the
    decay (1), ``k^T S`` (2), the rank-one update (2) and ``q^T S`` (2)
    an element of the state, and one more for the correction: 8 x H x d
    x d. Spans of more rows go through ``kda_chunk`` and are not counted
    here."""
    del engine
    one_row = sum(1 for _prefix, n in lanes if n == 1)
    layers = kda_layers(model)
    if not one_row or not layers:
        return 0, 0
    h, d = model["num_heads"], model["head_dim"]
    state = h * d * d
    flops = one_row * 8 * state
    nbytes = one_row * (2 * state + 6 * h * d) * 4
    return flops * layers, nbytes * layers
