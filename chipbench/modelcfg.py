"""From a configuration file to the program's ``ModelConfig``.

``chipbench/configs/<name>.json`` holds the model's ``published`` sizes
under their HF key names, the ``preset`` of the program that implements
the family, ``reduced`` (the keys cut from the source), ``assumed``, the
deployment it stands for and the arguments it is served with. A cut
configuration needs no change to the program: the preset is scaled
(``ModelConfig.scaled``) and registered under the configuration's own
name, in this process, and served as ``preset:<name>``. Every published
key that is not in ``reduced`` must equal the preset's value: a width can
never differ.
"""

from __future__ import annotations

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
#: the only key a configuration may cut
REDUCIBLE = ("num_hidden_layers",)


def model_config(data: dict):
    """The served ``ModelConfig`` of a configuration file, held to its
    preset on every key but the reduced ones."""
    from dynamo_tpu.models.config import PRESETS

    preset = PRESETS[data["preset"]]()
    published = data["published"]
    reduced = data.get("reduced", [])
    for key in reduced:
        if key not in REDUCIBLE:
            raise ValueError(f"{data['name']}: {key} may not be reduced")
    changes = {}
    for key, value in published.items():
        field = FIELDS[key]
        have = getattr(preset, field)
        want = value if value is not None else 0
        if key in reduced:
            if want > have:
                raise ValueError(f"{data['name']}: {key} grown, not cut")
            changes[field] = want
        elif want != have:
            raise ValueError(
                f"{data['name']}: published {key}={value} but the "
                f"program's preset {data['preset']} has {have}"
            )
    return preset.scaled(name=data["name"], **changes)


def register(data: dict):
    """Make ``preset:<name>`` resolve to this configuration in this
    process (``LocalModel.prepare`` reads ``PRESETS``)."""
    from dynamo_tpu.models.config import PRESETS

    cfg = model_config(data)
    PRESETS[data["name"]] = lambda: cfg
    return cfg
