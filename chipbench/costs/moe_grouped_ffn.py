"""The grouped expert path of a model of many routed experts
(``models/moe.py`` ``_moe_mlp_grouped``): the routed rows sorted by expert
through three grouped matrix products a layer (gate, up, down)."""

from __future__ import annotations


def cost(lanes, *, model: dict, engine: dict, experts_hit=None):
    """A dispatch's ``rows`` (its spans' new tokens) each go to ``k``
    experts. FLOPs: three products of [1, D] x [D, I] a routed row, 2 a
    multiply-add. Bytes: the three matrices of every expert that has a
    row, read once; each routed row read (D wide) and its result written
    in float32. Under tensor parallelism a chip holds ``I / tp`` of every
    expert's width.

    Which experts have a row is the routing's to say, and with seeded
    weights it is far from even (every masked row of a block-diffusion
    dispatch embeds the same id): ``experts_hit`` is the program's own
    count for the dispatch, summed over its expert layers (the flight
    record's ``moe_experts_hit``). Without it the count is the most the
    rows can touch, ``min(E, rows * k)`` a layer: an upper end, not a
    least."""
    rows = sum(n for _prefix, n in lanes if n > 0)
    if not rows or not model.get("num_experts"):
        return 0, 0
    tp = engine.get("tp", 1)
    d = model["hidden_size"]
    i = (model.get("moe_intermediate_size") or model["intermediate_size"]) // tp
    k = model["num_experts_per_tok"]
    itemsize = engine["dtype_bytes"]
    routed = rows * k
    flops = routed * 3 * 2 * d * i
    layers = model["num_layers"] - model.get("first_k_dense_replace", 0)
    if experts_hit is None:
        experts_hit = min(model["num_experts"], routed) * layers
    nbytes = experts_hit * 3 * d * i * itemsize
    nbytes += routed * d * (itemsize + 4) * layers
    return flops * layers, nbytes
