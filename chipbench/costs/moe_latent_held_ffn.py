"""The grouped expert path of an expert SHARE whose experts are non-gated
and live in a LATENT (``models/moe.py`` ``_moe_mlp_grouped`` with ``act``
"relu2" and an ``expert_input_size``): only the routed rows that landed on
an expert held here go through the TWO grouped matrix products a layer
(``relu(l W1)^2 W2``), each ``latent x width``, not ``hidden x width``."""

from __future__ import annotations


def expert_layers(model: dict) -> int:
    """Layers with routed experts: the ``E`` letters of the model's layer
    pattern within its depth; without a pattern, every layer behind the
    leading dense ones."""
    pattern = model.get("layer_pattern") or ""
    if pattern:
        return pattern[: model["num_layers"]].count("E")
    return model["num_layers"] - model.get("first_k_dense_replace", 0)


def cost(lanes, *, model: dict, engine: dict, rows_held=None,
         experts_hit=None):
    """``rows_held`` is the program's own count for the dispatch of the
    routed (row, expert) pairs that landed on an expert held here, summed
    over its expert layers (the flight record's ``moe_rows_held``);
    ``experts_hit`` the experts held here that had a row, summed likewise
    (``moe_experts_hit``). FLOPs: two products of [1, Z] x [Z, I] a landed
    pair, 2 a multiply-add (``Z`` the latent's width, the model's where it
    has no latent). Bytes: the two matrices of every expert that had a
    row, read once; each landed pair read ``Z`` wide and its result
    written ``Z`` wide in float32. Without the counts: an even spread of
    the dispatch's ``rows x k`` pairs over the source's experts, and every
    held expert the landed rows can touch (an upper end, not a least)."""
    rows = sum(n for _prefix, n in lanes if n > 0)
    experts = model.get("num_experts", 0)
    layers = expert_layers(model)
    if not rows or not experts or not layers:
        return 0, 0
    held = model.get("num_experts_held") or experts
    z = model.get("moe_latent_size") or model["hidden_size"]
    i = model.get("moe_intermediate_size") or model["intermediate_size"]
    itemsize = engine["dtype_bytes"]
    if rows_held is None:
        rows_held = rows * model["num_experts_per_tok"] * held // experts * layers
    if experts_hit is None:
        experts_hit = min(held, rows_held // layers) * layers
    flops = rows_held * 2 * 2 * z * i
    nbytes = experts_hit * 2 * z * i * itemsize
    nbytes += rows_held * z * (itemsize + 4)
    return flops, nbytes
