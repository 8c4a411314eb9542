"""BENCHMARK.json and the data files under chipbench/ name each other
consistently, within the contract's alphabet and limits."""

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
WIDTH = re.compile(r"(_dim$|_rank$|_size$|head_dim|experts_per_tok)")


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


@pytest.mark.parametrize("config", CONFIGS + ["tiny-rehearsal"])
def test_config_widths_are_the_published_ones(config):
    data = manifest.config(config)
    assert set(data["reduced"]) <= {"num_hidden_layers"}
    assert not any(WIDTH.search(k) for k in data["reduced"])
    cfg = modelcfg.model_config(data)
    from dynamo_tpu.models.config import PRESETS

    preset = PRESETS[data["preset"]]()
    for key, field in modelcfg.FIELDS.items():
        if key in data["published"] and key not in data["reduced"]:
            assert getattr(cfg, field) == getattr(preset, field), key
    assert cfg.num_layers == data["published"]["num_hidden_layers"]
    assert cfg.name == config
    assert 0 < data["check"]["limit"]
    if config in CONFIGS:
        entry = next(c for c in BENCH["configs"] if c["name"] == config)
        assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
        assert len(entry["reduced"]) <= 16


def test_a_changed_width_is_refused():
    data = json.loads(json.dumps(manifest.config("mistral-7b-l16")))
    data["published"]["hidden_size"] = 2048
    with pytest.raises(ValueError, match="hidden_size"):
        modelcfg.model_config(data)
    data = json.loads(json.dumps(manifest.config("mistral-7b-l16")))
    data["reduced"] = ["intermediate_size"]
    with pytest.raises(ValueError, match="may not be reduced"):
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
