"""The grouped expert path of an expert SHARE (``models/moe.py``
``_moe_mlp_grouped`` told which experts it holds): only the routed rows
that landed on an expert held here go through the three grouped matrix
products a layer (gate, up, down)."""

from __future__ import annotations


def cost(lanes, *, model: dict, engine: dict, rows_held=None,
         experts_hit=None):
    """``rows_held`` is the program's own count for the dispatch of the
    routed (row, expert) pairs that landed on an expert held here, summed
    over its expert layers (the flight record's ``moe_rows_held``);
    ``experts_hit`` the experts held here that had a row, summed likewise
    (``moe_experts_hit``). FLOPs: three products of [1, D] x [D, I] a
    landed pair, 2 a multiply-add. Bytes: the three matrices of every
    expert that had a row, read once; each landed row read (D wide) and
    its result written in float32. Without the counts: an even spread of
    the dispatch's ``rows x k`` pairs over the source's experts, and every
    held expert the landed rows can touch (an upper end, not a least)."""
    rows = sum(n for _prefix, n in lanes if n > 0)
    experts = model.get("num_experts", 0)
    if not rows or not experts:
        return 0, 0
    held = model.get("num_experts_held") or experts
    d = model["hidden_size"]
    i = model.get("moe_intermediate_size") or model["intermediate_size"]
    itemsize = engine["dtype_bytes"]
    layers = model["num_layers"] - model.get("first_k_dense_replace", 0)
    if rows_held is None:
        rows_held = rows * model["num_experts_per_tok"] * held // experts * layers
    if experts_hit is None:
        experts_hit = min(held, rows_held // layers) * layers
    flops = rows_held * 3 * 2 * d * i
    nbytes = experts_hit * 3 * d * i * itemsize
    nbytes += rows_held * d * (itemsize + 4)
    return flops, nbytes
