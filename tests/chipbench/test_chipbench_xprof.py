"""The reduction from the profiler's trace to numbers, on a hand-made
trace whose answers are known and on a small recorded one."""

import gzip
import json
import math
import os

import pytest

from chipbench import xprof
from chipbench.observe import Observations
from chipbench import registry

HERE = os.path.dirname(os.path.abspath(__file__))


def _ms(x):
    return x * 1e6  # ms -> ns


def made_trace():
    """Two executions of jit_unified_fn on chip 0, 10 ms each, 4 ms apart;
    inside each a 6 ms fusion and a 3 ms kernel with a 1 ms hole; chip 1
    busy half as long. The host sits in shard_args between programs."""
    ops0, mods0 = [], []
    for start in (0.0, 14.0):
        mods0.append(["jit_unified_fn(123)", _ms(start), _ms(10)])
        ops0.append(["fusion.7", _ms(start), _ms(6)])
        ops0.append(["ragged_paged_attention_pallas.3", _ms(start + 7), _ms(3)])
    ops1 = [["fusion.7", _ms(0), _ms(5)], ["all-reduce.1", _ms(14), _ms(5)]]
    host = [
        ["PjitFunction(unified_fn)", _ms(9.5), _ms(5)],
        ["shard_args", _ms(11), _ms(2)],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods0},
            {"name": "XLA Ops", "events": ops0},
        ]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": ops1},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
    ]}


def test_reduce_on_a_made_trace():
    r = xprof.reduce(made_trace(), chips=2)
    assert math.isclose(r["window_s"], 0.024)
    assert math.isclose(r["busy_s_chip0"], 0.018)
    assert math.isclose(r["busy_s"], (0.018 + 0.010) / 2)
    assert math.isclose(r["module_s"], 0.020)
    assert [n for n, _ in r["module_events"]] == ["jit_unified_fn(123)"] * 2
    assert math.isclose(r["op_seconds"]["fusion"], 0.012)
    assert math.isclose(r["op_seconds"]["ragged_paged_attention_pallas"], 0.006)
    assert r["device_ops"][0][0] == "fusion"
    gaps = dict(r["idle_gaps"])
    assert math.isclose(gaps["inside_jit_unified_fn_123_"], 0.002)
    assert math.isclose(gaps["between_programs__host_in_shard_args"], 0.004)
    # one chip asked for: chip 1 is left out
    assert xprof.reduce(made_trace(), chips=1)["chips_traced"] == 1


def test_readers_take_their_numbers_from_the_reduction():
    r = dict(xprof.reduce(made_trace(), chips=1), host_window=(100.0, 103.0))
    obs = Observations(
        window=(90.0, 140.0), chips=1, setup_s=1.0, records=[], trace=r,
        dispatches=[(101.0, [(99, 1)]), (102.0, [(99, 1)]), (150.0, [(0, 9)])],
        model={"num_layers": 2, "num_heads": 8, "num_kv_heads": 2,
               "head_dim": 128, "sliding_window": 0},
        engine={"tp": 1, "cache_head_dim": 128, "dtype_bytes": 2},
        device_kind="TPU v5 lite",
    )
    read = lambda name, **kw: registry.load("readers", name).read(obs, **kw)
    assert math.isclose(read("trace_idle"), 100 * (1 - 0.018 / 0.024))
    assert math.isclose(
        read("trace_op_share", prefixes=["ragged_paged_attention_pallas"]), 30.0
    )
    assert read("trace_op_share", prefixes=["all-reduce"]) == 0.0
    assert math.isclose(
        read("trace_module_percentile", module="unified_fn", q=50), 10.0
    )
    # two traced decode lanes at context 100: bytes bind (0.21 MB each)
    nbytes = 2 * (2 * 100 * 2 * 128 * 2 + 2 * 8 * 128 * 2)
    want = 100 * (2 * nbytes / 819e9) / 0.006
    assert math.isclose(
        read("kernel_roofline", kernel="ragged_paged_attention_pallas",
             cost="ragged_paged_attention"), want
    )
    obs.device_kind = "TPU v9"
    with pytest.raises(KeyError):
        read("kernel_roofline", kernel="ragged_paged_attention_pallas",
             cost="ragged_paged_attention")
    obs.trace = None
    assert read("trace_idle") is None


def test_no_device_plane_is_an_error():
    trace = {"planes": [p for p in made_trace()["planes"]
                        if p["name"].startswith("/host")]}
    with pytest.raises(ValueError, match="TPU:0"):
        xprof.reduce(trace)


def test_reduce_on_the_recorded_trace():
    """A few steps of mistral-7b-l16.chat-c64 recorded on a TPU v5e
    (PR 25), cut to chip 0's device lanes and the host's events."""
    with gzip.open(os.path.join(HERE, "data", "recorded_trace.json.gz"), "rt") as f:
        doc = json.load(f)
    r = xprof.reduce(doc["trace"], chips=1)
    for key, want in doc["expected"].items():
        assert math.isclose(r[key], want, rel_tol=1e-9), key
    assert r["busy_s_chip0"] <= r["window_s"]
    assert r["op_seconds"]["ragged_paged_attention_pallas"] > 0
    assert any("unified_fn" in n for n, _ in r["module_events"])
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
