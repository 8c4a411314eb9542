"""From a configuration file to the program's ``ModelConfig``.

``chipbench/configs/<name>.json`` holds the ``preset`` of the program that
implements the family, the model's ``published`` sizes under the key names
of the source's ``config.json``, ``reduced`` (the keys cut from the
source), ``assumed``, the deployment it stands for and the arguments it is
served with. A cut configuration needs no change to the program: the
preset is scaled (``ModelConfig.scaled``) and registered under the
configuration's own name, in this process, and served as
``preset:<name>``.

**Which field a published key sets.** ``published`` may hold any key of
the source. A key resolves to a ``ModelConfig`` field by, in order:

1. the file's own optional ``fields`` block, ``{"<hf key>": "<field>"}``;
   ``null`` for a key that the reference reads and the program carries
   otherwise (``topk_method``, a ``rope_scaling`` object), which is then
   held to nothing here;
2. ``FIELDS``, the names the accepted files rest on;
3. the key's own name, where ``ModelConfig`` has a field of that name.

A key that resolves to nothing, or to a field the program does not have,
raises and names it: nothing is ignored silently. A value that is an
object or a list cannot be held to the preset and must be ``null`` in
``fields``. Every other resolved key that is not in ``reduced`` must equal
the preset's value (``null`` reads as 0): a width can never differ.

**What may be cut** (``model-configs`` section 4): the depth
(``num_hidden_layers``), the vocabulary (``vocab_size``) and a key that
counts the experts or heads held here (``REDUCIBLE`` is the closed list);
never a width. For every reduced key but the depth the file states the
source's value under ``source_values`` and a ``share`` block: over how many
chips a layer is divided (``chips_sharing_a_layer``), which of them this is
(``index``) and ``how``. The cut is held to the guide's floors and raises
below them: held <= source; the chips that share a layer hold it between
them; at least 8 routed experts; at least an eighth of the vocabulary;
after the leading dense layers (``first_k_dense_replace`` of ``published``,
0 if absent) at least four layers and one whole period (``layer_period`` in
the file, 1 if absent). The source's value must equal the preset's own:
the router keeps its published width. For a reduced key a ``fields`` entry
may give two names, ``{"source": <field whose preset value must equal the
source's>, "held": <field the held value sets on the scaled preset>}``;
one name means both are one field, as for the depth and the vocabulary.
Which field holds the experts held is the file's to say: the PR that adds
such a configuration gives the program the field and names it there.
"""

from __future__ import annotations

import dataclasses

#: HF key -> ModelConfig field
FIELDS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "max_position_embeddings": "max_position",
    "sliding_window": "sliding_window",
    "num_local_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "tie_word_embeddings": "tie_word_embeddings",
    "hidden_act": "hidden_act",
}
#: keys that count the routed experts, and the heads, held here
EXPERT_COUNTS = ("n_routed_experts", "num_local_experts", "num_experts")
COUNTS = EXPERT_COUNTS + ("num_attention_heads", "num_key_value_heads")
#: the keys a configuration may cut: a closed list that holds no width (a
#: hidden, intermediate, latent, state or projection size, a head size,
#: an expansion factor, the experts a token)
REDUCIBLE = ("num_hidden_layers", "vocab_size") + COUNTS
MIN_EXPERTS = 8
MIN_VOCAB_SHARE = 8   # at least an eighth of the vocabulary
MIN_LAYERS = 4        # after the leading dense layers


def _resolve(data: dict, key: str, have: set[str]):
    """``(source field, held field)`` of a published key, or ``None`` where
    the file's ``fields`` block says the program has no field for it."""
    name = data["name"]
    fields = data.get("fields", {})
    if key in fields:
        entry = fields[key]
        if entry is None:
            return None
        if isinstance(entry, dict):
            if sorted(entry) != ["held", "source"] or key not in data.get(
                    "reduced", []):
                raise ValueError(
                    f"{name}: fields[{key}] gives two names, 'source' and "
                    "'held', only for a reduced key"
                )
            pair = (entry["source"], entry["held"])
        else:
            pair = (entry, entry)
    elif key in FIELDS:
        pair = (FIELDS[key], FIELDS[key])
    elif key in have:
        pair = (key, key)
    else:
        raise ValueError(
            f"{name}: published key {key} resolves to no ModelConfig field; "
            "name its field, or null, in the file's 'fields' block"
        )
    for field in pair:
        if field not in have:
            raise ValueError(
                f"{name}: {key} names the field {field}, which the "
                "program's ModelConfig does not have"
            )
    return pair


def _held_to_floors(data: dict, key: str, held, source) -> None:
    """Raise where the cut of ``key`` from ``source`` to ``held`` is below
    the floors of ``model-configs`` section 4."""
    name = data["name"]
    if held > source:
        raise ValueError(f"{name}: {key} grown, not cut ({held} > {source})")
    if key == "num_hidden_layers":
        dense = data["published"].get("first_k_dense_replace") or 0
        least = max(MIN_LAYERS, int(data.get("layer_period", 1)))
        if held - dense < least:
            raise ValueError(
                f"{name}: {held - dense} layers after the {dense} leading "
                f"dense one(s); a cut keeps at least {MIN_LAYERS} and one "
                f"whole period ({least})"
            )
        return
    share = data.get("share")
    if not isinstance(share, dict) or not (
        isinstance(share.get("chips_sharing_a_layer"), int)
        and isinstance(share.get("index"), int)
        and 0 <= share["index"] < share["chips_sharing_a_layer"]
        and share.get("how")
    ):
        raise ValueError(
            f"{name}: {key} is reduced, so the file states a 'share' block: "
            "chips_sharing_a_layer, index (which of them this is) and how"
        )
    if held < 1 or held * share["chips_sharing_a_layer"] < source:
        raise ValueError(
            f"{name}: {share['chips_sharing_a_layer']} chips with {held} of "
            f"{key} each do not hold the source's {source} between them"
        )
    if key in EXPERT_COUNTS and held < MIN_EXPERTS:
        raise ValueError(
            f"{name}: {key}={held}; a share holds at least {MIN_EXPERTS} "
            "routed experts"
        )
    if key == "vocab_size" and held * MIN_VOCAB_SHARE < source:
        raise ValueError(
            f"{name}: vocab_size={held} is under an eighth of the "
            f"source's {source}"
        )


def model_config(data: dict):
    """The served ``ModelConfig`` of a configuration file, held to its
    preset on every key but the reduced ones, and to the floors on those."""
    from dynamo_tpu.models.config import PRESETS

    name = data["name"]
    preset = PRESETS[data["preset"]]()
    have = {f.name for f in dataclasses.fields(preset)}
    published = data["published"]
    reduced = data.get("reduced", [])
    source_values = data.get("source_values", {})
    for key in reduced:
        if key not in REDUCIBLE:
            raise ValueError(f"{name}: {key} may not be reduced")
        if key not in published:
            raise ValueError(f"{name}: {key} is reduced but not published")
        if key != "num_hidden_layers" and key not in source_values:
            raise ValueError(
                f"{name}: {key} is reduced, so the file states the "
                "source's value under 'source_values'"
            )
    changes = {}
    for key, value in published.items():
        pair = _resolve(data, key, have)
        if pair is None:
            if key in reduced:
                raise ValueError(
                    f"{name}: {key} is reduced, so it names the field that "
                    "the held value sets, not null"
                )
            continue
        if isinstance(value, (dict, list)):
            raise ValueError(
                f"{name}: published {key} is no scalar and cannot be held "
                "to the preset; map it to null in the file's 'fields' block"
            )
        source_field, held_field = pair
        have_value = getattr(preset, source_field)
        want = value if value is not None else 0
        if key in reduced:
            source = source_values.get(key, have_value)
            if source != have_value:
                raise ValueError(
                    f"{name}: the source's {key}={source} but the program's "
                    f"preset {data['preset']} has {have_value}"
                )
            _held_to_floors(data, key, want, source)
            changes[held_field] = want
        elif want != have_value:
            raise ValueError(
                f"{name}: published {key}={value} but the "
                f"program's preset {data['preset']} has {have_value}"
            )
    return preset.scaled(name=name, **changes)


def register(data: dict):
    """Make ``preset:<name>`` resolve to this configuration in this
    process (``LocalModel.prepare`` reads ``PRESETS``)."""
    from dynamo_tpu.models.config import PRESETS

    cfg = model_config(data)
    PRESETS[data["name"]] = lambda: cfg
    return cfg
