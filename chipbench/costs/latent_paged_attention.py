"""The ragged paged attention kernel (``ops/pallas/ragged_attention.py``)
over a latent-attention cache: every query head reads ONE cached head of
``kv_lora_rank + qk_rope_head_dim``, lane-padded, and only the softmax
layers of a hybrid model have it."""

from __future__ import annotations

LANE = 128


def softmax_layers(model: dict) -> int:
    """Layers that read keys and values: each group's last
    (``layer_group_size``); every layer where there is no group."""
    group = model.get("layer_group_size", 0)
    if not group:
        return model["num_layers"]
    return sum((li + 1) % group == 0 for li in range(model["num_layers"]))


def cost(lanes, *, model: dict, engine: dict):
    """Causal attention of each span's ``n`` new rows over its ``prefix +
    n`` cached positions, all ``num_heads`` query heads over the one cached
    head. The width is the program's own: ``kv_lora_rank +
    qk_rope_head_dim`` padded to whole lanes of 128 (from the model's
    fields, not ``engine["cache_head_dim"]``). FLOPs: QK^T and PV, 2 each
    per (query, key, head, dim) at that width (the program multiplies the
    padding too, but the algorithm needs only the unpadded: counted
    unpadded). Bytes: both arrays the program stores (K and the
    zero-padded V) read once a span at the padded width, q read and the
    output written."""
    rank, rope = model["kv_lora_rank"], model["qk_rope_head_dim"]
    logical = rank + rope
    padded = -(-logical // LANE) * LANE
    heads = model["num_heads"]
    itemsize = engine["dtype_bytes"]
    kv_itemsize = engine.get("kv_dtype_bytes", itemsize)
    flops = 0
    nbytes = 0
    for prefix, n in lanes:
        if n <= 0:
            continue
        pairs = ((prefix + 1) + (prefix + n)) * n / 2.0
        flops += 2 * pairs * heads * (logical + rank)
        nbytes += 2 * (prefix + n) * padded * kv_itemsize
        nbytes += 2 * n * heads * padded * itemsize
    layers = softmax_layers(model)
    return flops * layers, nbytes * layers
