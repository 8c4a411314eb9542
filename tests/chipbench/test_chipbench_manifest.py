"""BENCHMARK.json and the data files under chipbench/ name each other
consistently, within the contract's alphabet and limits."""

import copy
import dataclasses
import glob
import json
import os
import re

import pytest

from chipbench import manifest, modelcfg, registry, traffic

BENCH = manifest.benchmark_json()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CONFIG_FILES = sorted(
    os.path.basename(p)[:-len(".json")]
    for p in glob.glob(os.path.join(manifest.ROOT, "configs", "*.json"))
)
#: what no configuration may cut: a hidden, intermediate, latent, state or
#: projection size, a head size, an expansion factor, the experts a token
WIDTH = re.compile(
    r"(_dim$|_rank$|_per_tok$|_factor$|hidden_size$|intermediate_size$)"
)


def test_manifest_is_consistent():
    assert manifest.check() == []


def test_top_level_keys_and_limits():
    assert sorted(BENCH) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    ])
    assert BENCH["command"] == ["python3", "-m", "chipbench"]
    assert BENCH["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60 s, 24 x 180 s, 1200 s spare
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    size = os.path.getsize(os.path.join(manifest.CHECKOUT, "BENCHMARK.json"))
    assert size <= 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell(cell):
    w = next(x for x in BENCH["workloads"] if x["name"] == cell)
    assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    data = manifest.workload(cell)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    moves = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
    assert "setup_s" in data["end_to_end"] and len(data["end_to_end"]) >= 2
    assert set(data["end_to_end"]) <= e2e
    for m in data["per_layer"]:
        assert moves[m] in data["end_to_end"], (cell, m)
    spec = traffic.load(w["traffic"])
    assert spec["clients"] >= 1 and spec["ramp_s"] >= 0


@pytest.mark.parametrize("name", METRICS)
def test_metric(name):
    m = next(
        x for x in BENCH["end_to_end"] + BENCH["per_layer"]
        if x["name"] == name
    )
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in manifest.SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if "bound" in m:
        assert 0.01 <= m["bound"] <= 0.1
        allowed |= {"bound"}
    else:
        allowed |= {"layer", "moves"}
        assert m["layer"] and "\n" not in m["layer"]
    assert set(m) <= allowed
    data = manifest.metric(name)
    reader = registry.load("readers", data["reader"])
    assert callable(reader.read)
    if name.endswith("_roofline"):
        assert m["unit"] == "%"


def test_every_metric_file_is_declared():
    # a metric taken out of BENCHMARK.json takes its file with it
    files = {
        os.path.basename(p)[:-len(".json")]
        for p in glob.glob(os.path.join(manifest.ROOT, "metrics", "*.json"))
    }
    assert files == set(METRICS)


@pytest.mark.parametrize("config", CONFIG_FILES)
def test_config_widths_are_the_published_ones(config):
    data = manifest.config(config)
    assert set(data["reduced"]) <= set(modelcfg.REDUCIBLE)
    assert not any(WIDTH.search(k) for k in data["reduced"])
    cfg = modelcfg.model_config(data)
    from dynamo_tpu.models.config import PRESETS

    preset = PRESETS[data["preset"]]()
    for key, field in modelcfg.FIELDS.items():
        if key not in data["published"]:
            continue
        if key in data["reduced"]:
            assert getattr(cfg, field) == data["published"][key], key
        else:
            assert getattr(cfg, field) == getattr(preset, field), key
    assert cfg.name == config
    assert 0 < data["check"]["limit"]
    driver = registry.load("steps", data["check"].get("step", "span"))
    assert callable(driver.drive) and callable(driver.sample_len)
    if config in CONFIGS:
        entry = next(c for c in BENCH["configs"] if c["name"] == config)
        assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
        assert len(entry["reduced"]) <= 16


def test_every_declared_configuration_has_its_file():
    assert set(CONFIGS) <= set(CONFIG_FILES)
    assert not any(WIDTH.search(k) for k in modelcfg.REDUCIBLE)
    assert WIDTH.search("vocab_size") is None


@pytest.mark.parametrize(
    "config",
    ["mistral-7b-l16", "mixtral-8x7b-l4", "mistral-7b-tp4", "tiny-rehearsal"],
)
def test_accepted_files_resolve_to_the_preset_cut_in_depth_alone(config):
    """Field for field what they resolved to before any key could join:
    the preset under the configuration's name with its depth."""
    from dynamo_tpu.models.config import PRESETS

    data = manifest.config(config)
    assert "fields" not in data and "share" not in data
    want = dataclasses.replace(
        PRESETS[data["preset"]](), name=config,
        num_layers=data["published"]["num_hidden_layers"],
    )
    assert modelcfg.model_config(data) == want


def test_a_changed_width_is_refused():
    data = json.loads(json.dumps(manifest.config("mistral-7b-l16")))
    data["published"]["hidden_size"] = 2048
    with pytest.raises(ValueError, match="hidden_size"):
        modelcfg.model_config(data)
    data = json.loads(json.dumps(manifest.config("mistral-7b-l16")))
    data["reduced"] = ["intermediate_size"]
    with pytest.raises(ValueError, match="may not be reduced"):
        modelcfg.model_config(data)


# A configuration of a family the benchmark has not seen, cut to one
# chip's share of a deployment: latent attention and sparse experts at a
# published size, against a stand-in preset that has a field for the
# experts held (the program has none yet).

SHARE_CUT = {
    "name": "stand-in-share", "preset": "stand-in", "reference": "stub",
    "published": {
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "num_attention_heads": 64,
        "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64,
        "qk_nope_head_dim": 128, "v_head_dim": 128,
        "n_routed_experts": 12, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "routed_scaling_factor": 2.827,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "rope_scaling": {"type": "yarn", "factor": 64.0},
        "vocab_size": 20480, "num_hidden_layers": 8,
        "first_k_dense_replace": 1,
    },
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "source_values": {"num_hidden_layers": 61, "n_routed_experts": 384,
                      "vocab_size": 163840},
    "share": {"chips_sharing_a_layer": 32, "index": 0,
              "how": "experts 0-11 of 384 here; attention and the shared "
                     "expert whole on every chip; vocabulary rows 0-20479"},
    "fields": {
        "n_routed_experts": {"source": "num_experts",
                             "held": "num_experts_held"},
        "scoring_func": "gating", "topk_method": None, "rope_scaling": None,
    },
}


@pytest.fixture
def stand_in_preset():
    from dynamo_tpu.models.config import PRESETS, ModelConfig

    @dataclasses.dataclass(frozen=True)
    class StandIn(ModelConfig):
        num_experts_held: int = 0

    preset = StandIn(
        name="stand-in", vocab_size=163840, hidden_size=7168,
        intermediate_size=18432, moe_intermediate_size=2048, num_layers=61,
        num_heads=64, num_kv_heads=64, kv_lora_rank=512, q_lora_rank=1536,
        num_experts=384, num_experts_per_tok=8, n_shared_experts=1,
        first_k_dense_replace=1, gating="sigmoid",
        routed_scaling_factor=2.827,
    )
    PRESETS["stand-in"] = lambda: preset
    try:
        yield preset
    finally:
        del PRESETS["stand-in"]


def test_a_sound_share_cut_resolves(stand_in_preset):
    cfg = modelcfg.model_config(copy.deepcopy(SHARE_CUT))
    assert cfg.num_experts == 384          # the router's published width
    assert cfg.num_experts_held == 12      # the experts held here
    assert (cfg.vocab_size, cfg.num_layers) == (20480, 8)
    want = dataclasses.replace(
        stand_in_preset, name="stand-in-share", num_experts_held=12,
        vocab_size=20480, num_layers=8,
    )
    assert cfg == want                     # and every width as published


GONE = object()


def _set(path, value):
    def change(data):
        *parents, last = path
        for key in parents:
            data = data[key]
        if value is GONE:
            del data[last]
        else:
            data[last] = value
    return change


REFUSED = {
    "an unknown published key is named":
        (_set(("published", "attn_logit_softcap"), 30.0),
         "attn_logit_softcap"),
    "a field the program lacks is named":
        (_set(("fields", "n_routed_experts", "held"), "experts_on_this_chip"),
         "experts_on_this_chip"),
    "7 experts": (_set(("published", "n_routed_experts"), 7), "at least 8"),
    "a ninth of the vocabulary":
        (_set(("published", "vocab_size"), 163840 // 9), "eighth"),
    "three layers after a dense one":
        (_set(("published", "num_hidden_layers"), 4), "layers after"),
    "less than a period": (_set(("layer_period",), 8), "whole period"),
    "held above the source":
        (_set(("published", "n_routed_experts"), 400), "grown"),
    "a source value that is not the preset's":
        (_set(("source_values", "n_routed_experts"), 256), "preset"),
    "a reduced count without source_values":
        (_set(("source_values", "n_routed_experts"), GONE), "source_values"),
    "a reduced count without share": (_set(("share",), GONE), "'share'"),
    "a share that names no chip of its own":
        (_set(("share", "index"), 32), "'share'"),
    "chips that do not hold the layer between them":
        (_set(("share", "chips_sharing_a_layer"), 16), "between them"),
    "a latent width in reduced":
        (_set(("reduced",), ["num_hidden_layers", "kv_lora_rank"]),
         "kv_lora_rank may not be reduced"),
    "the experts a token in reduced":
        (_set(("reduced",), ["num_experts_per_tok"]), "may not be reduced"),
    "a changed latent width":
        (_set(("published", "kv_lora_rank"), 256), "kv_lora_rank"),
    "an object that no fields entry sets aside":
        (_set(("fields", "rope_scaling"), "rope_scaling"), "no scalar"),
    "two names for a key that is not reduced":
        (_set(("fields", "hidden_size"),
              {"source": "hidden_size", "held": "hidden_size"}),
         "two names"),
    "a reduced key set aside as null":
        (_set(("fields", "n_routed_experts"), None), "not null"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_share_cut_below_the_rules_is_refused(case, stand_in_preset):
    change, match = REFUSED[case]
    data = copy.deepcopy(SHARE_CUT)
    if case == "7 experts":  # over chips enough to hold the layer so
        data["share"]["chips_sharing_a_layer"] = 64
    change(data)
    with pytest.raises(ValueError, match=match):
        modelcfg.model_config(data)


def test_every_file_under_paths_is_named_from_the_alphabet():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top in BENCH["paths"]:
        for root, dirs, files in os.walk(os.path.join(manifest.CHECKOUT, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), manifest.CHECKOUT)
                assert ok.match(rel), rel


def test_registry_refuses_what_is_not_there():
    with pytest.raises(KeyError):
        registry.load("readers", "no_such_reader")
    with pytest.raises(KeyError):
        registry.load("kernels", "x")
    with pytest.raises(KeyError, match="costs/no_such_kernel.py"):
        registry.load("costs", "no_such_kernel")
    assert callable(registry.load("costs", "ragged_paged_attention").cost)
    with pytest.raises(KeyError, match="steps/no_such_step.py"):
        registry.load("steps", "no_such_step")
    assert callable(registry.load("steps", "span").drive)
