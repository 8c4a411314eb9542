"""The state update of a Mamba-2 (state-space) layer for the lanes of one
row (``ops/pallas/ssd.py`` ``ssd_recurrent``): decode lanes, the state read
and written once, in place."""

from __future__ import annotations


def ssd_layers(model: dict) -> int:
    """Layers that keep a state-space state: the ``M`` letters of the
    model's layer pattern within its depth (no pattern: none)."""
    pattern = model.get("layer_pattern") or ""
    return pattern[: model["num_layers"]].count("M")


def state_elements(model: dict) -> int:
    """Elements of one sequence's state in one layer: ``H x P x N``."""
    return (model["mamba_num_heads"] * model["mamba_head_dim"]
            * model["ssm_state_size"])


def row_elements(model: dict) -> int:
    """Elements a row hands a layer's recurrence and takes back: x and y
    (``H x P`` each), the step and the decay (``H`` each), B and C (``G x
    N`` each)."""
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    return 2 * h * p + 2 * h + 2 * model["mamba_n_groups"] * model[
        "ssm_state_size"]


def cost(lanes, *, model: dict, engine: dict):
    """Exactly the lanes the ``ssd_recurrent`` kernel serves: spans of one
    new row (a decode lane, or a prompt of one token), whatever lies behind
    them. A lane and layer: bytes, the state of ``H x P x N`` float32 read
    and written and the row's inputs and output (``row_elements``), all
    float32. FLOPs: the decay (1), the outer product's term and its sum
    (2) and ``S C`` (2) an element of the state: 5 x H x P x N. Spans of
    more rows go through ``ssd_chunk`` and are not counted here."""
    del engine
    one_row = sum(1 for _prefix, n in lanes if n == 1)
    layers = ssd_layers(model)
    if not one_row or not layers:
        return 0, 0
    state = state_elements(model)
    flops = one_row * 5 * state
    nbytes = one_row * (2 * state + row_elements(model)) * 4
    return flops * layers, nbytes * layers
