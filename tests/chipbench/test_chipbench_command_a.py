"""The family that keeps its cache by layer group in the benchmark: the
manifest holds the new entries by name, the configuration file holds the
catalog's widths and the share cut, the reference draws the program's
weights, the two new cost files agree with hand counts, the new reader
passes both counted fields, and the whole harness path runs on the CPU with
the share cut and the step driver that allocates by group."""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import manifest, modelcfg, registry
from chipbench.observe import Observations
from chipbench.reference import cohere2_moe as ref
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from test_chipbench_run import _last_lines, _run

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = "command-a-plus-ep8-l4"
TINY = "tiny-command-a-rehearsal"
CELL = f"{CONFIG}.longmix-c48"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["kernel.window_full_attn_roofline", "kernel.moe_held_roofline",
       "kv.full_pool_used_peak_pct", "kv.window_pool_used_peak_pct",
       "kv.cache_bytes_per_ctx_token", "moe.held_rows_pct"]
JOINED = ["frontend.itl_p95_ms", "scheduler.tokens_per_dispatch",
          "kv.pool_used_peak_pct", "runner.dispatch_p50_ms",
          "runner.compiles_in_window", "model.device_step_p50_ms",
          "kernel.ragged_attn_step_pct", "kernel.moe_grouped_step_pct",
          "device.idle_pct",
          # the first-token wait and the queue, read per layer: 16k
          # prompts prefilled beside decode lanes, two pools at admission
          "frontend.pre_engine_p50_ms", "frontend.ttft_p50_ms",
          "frontend.ttft_p90_ms", "frontend.ttft_p95_ms",
          "scheduler.queue_wait_p95_ms"]


def test_manifest_holds_the_new_entries_by_name():
    """By NAME and not by position: a later PR appends behind these."""
    assert manifest.check() == []
    bench = manifest.benchmark_json()
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longmix-c48", 1)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"][0] == CELL, name
        assert per_layer[name]["moves"] == "out_tok_s_chip"
    for name in JOINED:
        assert CELL in per_layer[name]["workloads"], name
    # whose cost files apply one window to every layer, or rows x 8
    for name in ("kernel.ragged_attn_roofline", "kernel.moe_grouped_roofline"):
        assert CELL not in per_layer[name]["workloads"], name
    assert sorted(manifest.workload(CELL)["per_layer"]) == sorted(NEW + JOINED)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 1 and len(four) <= max(1, len(bench["workloads"]) // 4)


def test_the_traffic_is_what_the_issue_gives():
    from chipbench import traffic

    spec = traffic.load("longmix-c48")
    assert (spec["loop"], spec["clients"], spec["block"], spec["ramp_s"]) == (
        "closed", 48, 48, 24)
    assert spec["prompt_tokens"] == {
        "distribution": "log_uniform", "low": 512, "high": 16384}
    assert spec["output_tokens"] == {
        "distribution": "log_uniform", "low": 128, "high": 512}
    assert spec["think_time_s"] == {"distribution": "constant", "value": 0.0}
    block = traffic.requests(spec, 5, 48)
    prompts = np.array([r["prompt_tokens"] for r in block])
    assert 4300 < prompts.mean() < 4900
    assert 0.35 < (prompts > 4096).mean() < 0.45
    assert 0.72 < prompts[prompts > 4096].sum() / prompts.sum() < 0.82
    # the longest request fits the served context with its template
    args = manifest.config(CONFIG)["serve_args"]
    longest = prompts.max() + max(r["output_tokens"] for r in block)
    assert longest + 64 < int(args[args.index("--max-model-len") + 1])


def test_published_widths_are_the_catalogs_and_the_share_is_stated():
    data = manifest.config(CONFIG)
    pub = data["published"]
    # the driver reads the source's keys at the file's top level
    assert {k: data[k] for k in pub} == pub
    held = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 32768}
    assert sorted(data["reduced"]) == sorted(held)
    assert data["source_values"] == {"num_experts": 128, "vocab_size": 262144}
    assert data["share"]["chips_sharing_a_layer"] == 8
    assert data["share"]["index"] == 0 and data["layer_period"] == 4
    assert len(data["assumed"]) >= 5 and data["deployment"]
    assert set(data["serve_args_why"]) >= {
        a for a in data["serve_args"] if a.startswith("--")}
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r.get("name") == "command-a-plus-05-2026")
        assert data["source"] == row["source_url"]
        assert pub == {**row["config"], **held,
                       "layer_types": row["config"]["layer_types"][:4]}
    served = modelcfg.model_config(data)
    assert served == ModelConfig.command_a_plus_ep8_l4()
    assert served.num_experts == 128 and served.experts_here == 16
    assert [served.layer_window(li) for li in range(4)] == [4096] * 3 + [0]
    assert served.cache_groups == (0, 4096)
    for key, value in (("intermediate_size", 2048), ("head_dim", 64),
                       ("sliding_window", 1024), ("num_shared_experts", 2),
                       ("num_key_value_heads", 4)):
        bad = json.loads(json.dumps(data))
        bad["published"][key] = value
        with pytest.raises(ValueError, match=key):
            modelcfg.model_config(bad)
    # the floors: under a whole period, or a share that does not hold the
    # source between its chips
    for key, value, why in (("num_hidden_layers", 3, "whole period"),
                            ("num_experts", 8, "do not hold"),
                            ("vocab_size", 16384, "do not hold")):
        bad = json.loads(json.dumps(data))
        bad["published"][key] = value
        with pytest.raises(ValueError, match=why):
            modelcfg.model_config(bad)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("held", [0, 8])
def test_reference_draws_the_programs_weights(dtype, held):
    cfg = ModelConfig.tiny_command_a_test(held=held)
    data = manifest.config(TINY)
    pub = dict(data["published"], num_experts=held or 16)
    s = ref.sizes(pub, {"num_experts": 16}, {"index": 0})
    assert (s["E"], s["held"], s["first"]) == (16, held or 16, 0)
    assert s["full"] == (False, False, False, True) * 2
    seed = 7654321
    params = llama.init_params(
        jax.random.PRNGKey(seed), cfg, dtype=jnp.dtype(dtype))
    layer_keys, ek = ref.model_keys(seed, cfg.num_layers)
    for li in range(cfg.num_layers):
        mine = ref.layer_weights(layer_keys[li], s, jnp.dtype(dtype))
        theirs = params["layers"][li]
        inert = {k for k in theirs if k.startswith("ln_")} | {"router_bias"}
        assert sorted(mine) == sorted(set(theirs) - inert), li
        assert list(inert & set(theirs)) and "ln_mlp" not in theirs
        assert bool(jnp.all(theirs["ln_attn"] == 1))
        assert bool(jnp.all(theirs["router_bias"] == 0))
        for name in mine:
            np.testing.assert_array_equal(
                np.asarray(mine[name], np.float32),
                np.asarray(theirs[name], np.float32), err_msg=f"{li} {name}")
    table = ref._draw(ek, (384, 64), 1, jnp.dtype(dtype))
    np.testing.assert_array_equal(
        np.asarray(table, np.float32), np.asarray(params["embed"], np.float32))


MODEL = dict(num_layers=4, sliding_window=4096, window_pattern=4,
             num_heads=128, num_kv_heads=8, head_dim=128, hidden_size=4096,
             intermediate_size=4096, moe_intermediate_size=4096,
             num_experts=128, num_experts_held=16, num_experts_per_tok=8,
             first_k_dense_replace=0)
ENGINE = dict(dtype_bytes=2, kv_dtype_bytes=2, cache_head_dim=128)


def test_the_attention_cost_reckons_each_layer_by_its_window():
    mod = registry.load("costs", "window_full_paged_attention")
    assert mod.layer_windows(MODEL) == [4096, 4096, 4096, 0]
    assert mod.layer_windows(dict(MODEL, window_pattern=0)) == [4096] * 4
    shape = dict(heads=128, kv_heads=8, d=128, dc=128, itemsize=2,
                 kv_itemsize=2)
    # a decode row at a 10k prefix: the full layer sees 10,001 keys, a
    # window layer 4,096
    row = [(10000, 1)]
    q_o = 2 * 1 * 128 * 128 * 2
    assert mod.one_layer(row, 0, **shape) == (
        4 * 10001 * 128 * 128, 2 * 10001 * 8 * 128 * 2 + q_o)
    assert mod.one_layer(row, 4096, **shape) == (
        4 * 4096 * 128 * 128, 2 * 4096 * 8 * 128 * 2 + q_o)
    flops, nbytes = mod.cost(row, model=MODEL, engine=ENGINE)
    assert flops == 4 * 128 * 128 * (10001 + 3 * 4096)
    assert nbytes == 2 * 8 * 128 * 2 * (10001 + 3 * 4096) + 4 * q_o
    # a quantum of 1,024 rows at the same prefix: the full layer's pairs
    # are an arithmetic series, a window layer's a constant; the window
    # layers read a window and the quantum, not the prefix
    quantum = [(10000, 1024)]
    f0, b0 = mod.one_layer(quantum, 0, **shape)
    fw, bw = mod.one_layer(quantum, 4096, **shape)
    assert f0 == 4 * 128 * 128 * (1024 * (10001 + 11024) / 2)
    assert fw == 4 * 128 * 128 * 1024 * 4096
    assert b0 - bw == 2 * 8 * 128 * 2 * (11024 - (4096 + 1023))
    # under the window a layer is a full layer: a span that starts at 0
    # and ends inside it
    assert mod.one_layer([(0, 1000)], 4096, **shape) == mod.one_layer(
        [(0, 1000)], 0, **shape)
    # a span that crosses it: 96 rows still ramp, 904 see a whole window
    f, _ = mod.one_layer([(4000, 1000)], 4096, **shape)
    assert f == 4 * 128 * 128 * (
        sum(range(4001, 4096)) + 905 * 4096)
    # against the accepted cost file, which applies the window to the full
    # layer too (why the cell does not report that metric)
    one_window = registry.load("costs", "ragged_paged_attention").cost(
        row, model=MODEL, engine=ENGINE)
    assert one_window[0] == 4 * 4 * 4096 * 128 * 128 < flops
    assert mod.cost([(5, 0)], model=MODEL, engine=ENGINE) == (0, 0)


def test_the_expert_cost_counts_what_landed_here():
    cost = registry.load("costs", "moe_held_ffn").cost
    lanes = [(0, 1024)]
    expert = 3 * 4096 * 4096
    # an eighth of 1,024 x 8 routed rows lands on each of four layers'
    # held experts, and all sixteen have a row
    flops, nbytes = cost(lanes, model=MODEL, engine=ENGINE,
                         rows_held=4 * 1024, experts_hit=4 * 16)
    assert flops == 4 * 1024 * 2 * expert
    assert nbytes == 4 * 16 * expert * 2 + 4 * 1024 * 4096 * 6
    # the matrices bound it: 6.4 GB at 819 GB/s against 0.41 TFLOP
    assert nbytes / 819e9 > 3 * flops / 197e12
    # without the program's counts: an even spread, every held expert
    assert cost(lanes, model=MODEL, engine=ENGINE) == (flops, nbytes)
    # the accepted cost file reckons rows x 8 routed rows: eight times
    whole = registry.load("costs", "moe_grouped_ffn").cost(
        lanes, model=MODEL, engine=ENGINE, experts_hit=4 * 16)
    assert whole[0] == 8 * flops
    # a decode dispatch of 48 rows, six experts with a row a layer
    flops, nbytes = cost([(900, 1)] * 48, model=MODEL, engine=ENGINE,
                         rows_held=4 * 48, experts_hit=4 * 6)
    assert flops == 4 * 48 * 2 * expert
    assert nbytes == 4 * 6 * expert * 2 + 4 * 48 * 4096 * 6
    assert cost([], model=MODEL, engine=ENGINE) == (0, 0)
    assert cost(lanes, model=dict(MODEL, num_experts=0), engine=ENGINE) == (0, 0)


def _observed(flight, op_seconds):
    return Observations(
        window=(0.0, 10.0), chips=1, setup_s=1.0, records=[],
        unix_minus_mono=1000.0, flight=flight, model=MODEL, engine=ENGINE,
        device_kind="TPU v5 lite",
        trace={"op_seconds": op_seconds, "host_window": (5.0, 8.0)},
    )


def test_the_new_reader_passes_both_counted_fields():
    read = registry.load("readers", "flight_counted_roofline").read
    params = manifest.metric("kernel.moe_held_roofline")["params"]
    step = {"dispatch_ms": 1.0, "t_unix": 1006.0, "decode_tokens": 48,
            "prefill_tokens": 976, "moe_rows_held": 4096,
            "moe_experts_hit": 64}
    outside = dict(step, t_unix=1009.0)
    least = 64 * 3 * 4096 * 4096 * 2 + 4096 * 4096 * 6
    obs = _observed([step, outside], {"gmm.3": 0.004, "gmm.7": 0.006,
                                      "fusion": 1.0})
    assert read(obs, **params) == pytest.approx(
        100.0 * (least / 819e9) / 0.010)
    # a program without the fields (the parent), or no trace: nothing
    bare = {k: v for k, v in step.items() if not k.startswith("moe_")}
    assert read(_observed([bare], {"gmm": 0.01}), **params) is None
    assert read(_observed([dict(step, moe_rows_held=0, moe_experts_hit=0)],
                          {"gmm": 0.01}), **params) is None
    assert read(_observed([step], {"fusion": 1.0}), **params) is None
    obs.trace = None
    assert read(obs, **params) is None


def test_the_other_new_metrics_read_the_gauges_and_the_flight_record():
    flight = [
        {"dispatch_ms": 1.0, "t_unix": 1006.0, "decode_tokens": 40,
         "prefill_tokens": 984, "moe_rows_held": 4000,
         "kv_bytes_live": 3_000_000_000, "context_tokens_live": 300_000},
        {"dispatch_ms": 1.0, "t_unix": 1007.0, "decode_tokens": 48,
         "prefill_tokens": 0, "moe_rows_held": 200,
         "kv_bytes_live": 3_200_000_000, "context_tokens_live": 320_000},
    ]
    obs = _observed(flight, {})
    obs.readiness = [
        {"t": 1.0, "kv_full_usage_perc": 0.41, "kv_window_usage_perc": 0.52},
        {"t": 3.0, "kv_full_usage_perc": 0.63, "kv_window_usage_perc": 0.50},
    ]

    def read(name):
        m = manifest.metric(name)
        return registry.load("readers", m["reader"]).read(obs, **m["params"])

    assert read("kv.full_pool_used_peak_pct") == pytest.approx(63.0)
    assert read("kv.window_pool_used_peak_pct") == pytest.approx(52.0)
    assert read("kv.cache_bytes_per_ctx_token") == pytest.approx(10_000.0)
    assert read("moe.held_rows_pct") == pytest.approx(
        100.0 * 4200 / (1072 * 32))
    # a program that has none of it (the parent): nothing, and no error
    obs.readiness = [{"t": 1.0, "gpu_cache_usage_perc": 0.3}]
    obs.flight = [{"dispatch_ms": 1.0, "t_unix": 1006.0}]
    for name in NEW[2:]:
        assert read(name) is None, name


def test_costs_stay_under_the_traced_kernel_times():
    """Dispatches, flight records and kernel times recorded from a traced
    run of the cell on a v5e (my chip run, PR 45): each new roofline share
    reads what the harness read there (28.28 and 68.41 %), above 0 and
    under 100 %; the accepted cost files on the same dispatches would not
    (one window on every layer undercounts the full layer; rows x 8 counts
    eight times what landed)."""
    path = os.path.join(HERE, "data", "command_a_traced_dispatches.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    obs = Observations(
        window=(0.0, 10.0), chips=1, setup_s=1.0, records=[],
        unix_minus_mono=0.0, flight=rec["flight"], model=rec["model"],
        engine=rec["engine"], device_kind=rec["device_kind"],
        dispatches=[(1.0, lanes) for lanes in rec["dispatches"]],
        trace={"op_seconds": rec["op_seconds"], "host_window": (0.0, 2e9)},
    )

    def read(name, **change):
        m = manifest.metric(name)
        params = dict(m["params"], **change)
        return registry.load("readers", m["reader"]).read(obs, **params)

    assert read("kernel.window_full_attn_roofline") == pytest.approx(28.277, abs=0.01)
    assert read("kernel.moe_held_roofline") == pytest.approx(68.409, abs=0.01)
    assert read("kernel.window_full_attn_roofline",
                cost="ragged_paged_attention") < 25
    grouped = manifest.metric("kernel.moe_grouped_roofline")
    assert registry.load("readers", grouped["reader"]).read(
        obs, **grouped["params"]) > 100
    # the cache by group costs a live token well under one table's 16 KiB
    assert 9000 < read("kv.cache_bytes_per_ctx_token") < 12500
    assert sum(r["kv_window_released"] for r in rec["flight"]) > 0


def test_whole_run_of_the_new_family_on_the_cpu():
    """The harness path end to end with the share cut, two pools behind
    the served engine and the step driver that allocates by group: one SSE
    chunk a token, nothing compiles in the window, the served step is the
    reference's."""
    proc = _run(
        "chipbench", "--workload", f"{TINY}.rehearsal", "--seed",
        str(2**31 + 4545), "--seconds", "2", "--trace", "0", "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result, _said, _errors = _last_lines(proc)
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compared"]["rel_err_p100"]["value"] < 1e-3
    assert set(result["metrics"]) == {"out_tok_s_chip", "setup_s"}


def test_the_int8_weights_control_of_the_new_family_comes_out_not_correct():
    """The program's own int8 weights against the tiny configuration's
    float32 limit, through ``chipbench.control`` (at the published widths
    the cell's limits refuse it on the chip: PERF.md section 6, PR 45)."""
    proc = _run(
        "chipbench.control", "--config", TINY, "--seeds", "1",
        "--control-seeds", "1", "--controls", "int8_weights", "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    limit = last["limits_in_file"]["limit"]
    assert last["sound_max"]["rel_err"] < limit / 3
    assert last["sound_not_correct"] == 0
    assert last["control_min"]["int8_weights"]["rel_err"] > 3 * limit, last
    assert last["control_correct"]["int8_weights"] == 0, last
