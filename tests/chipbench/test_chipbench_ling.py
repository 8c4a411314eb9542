"""The hybrid linear-attention family in the benchmark: the configuration
file holds the catalog's widths and the share cut, the reference draws the
program's weights, the new cost files count their own layers only and stay
under a traced kernel time, and the whole harness path runs on the CPU with
the share cut and the step driver that keeps a state."""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import manifest, modelcfg, registry
from chipbench.peaks import peaks_for
from chipbench.reference import ling as ref
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from test_chipbench_run import _last_lines, _run

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = "ling-3.0-flash-ep4-l8"
TINY = "tiny-ling-rehearsal"
CELL = f"{CONFIG}.chat-c128"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["kernel.kda_step_pct", "kernel.kda_roofline",
       "kernel.latent_attn_roofline", "moe.share_rows_pct"]


# The cells that came with a family of their own, each with the metrics
# it brought, in BENCHMARK.json's order. Found by NAME: a later PR appends
# its cell after these and joins their metrics' ``workloads`` lists.
BROUGHT = {
    "sdar-30b-a3b-l7.chat-c64": ("chat-c64", [
        "diffusion.tokens_per_lane_pass", "diffusion.commit_pass_pct",
        "kernel.moe_grouped_step_pct", "kernel.moe_grouped_roofline"]),
    CELL: ("chat-c128", NEW),
}


@pytest.mark.parametrize("name", list(BROUGHT))
def test_manifest_holds_the_cell_under_its_name(name):
    traffic, brought = BROUGHT[name]
    assert manifest.check() == []
    bench = manifest.benchmark_json()
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    assert cell["config"] in [c["name"] for c in bench["configs"]]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        name.rsplit(".", 1)[0], traffic, 1)
    new = [m for m in bench["per_layer"]
           if m.get("workloads", [None])[0] == name]
    assert [m["name"] for m in new] == brought
    assert all(m["moves"] == "out_tok_s_chip" for m in new)
    assert set(brought) <= set(manifest.workload(name)["per_layer"])


def test_the_new_cell_reports_what_the_issue_lists():
    bench = manifest.benchmark_json()
    reported = manifest.workload(CELL)["per_layer"]
    assert "kernel.ragged_attn_roofline" not in reported
    assert {"kernel.moe_grouped_roofline", "model.device_step_p50_ms",
            "device.idle_pct", *NEW} <= set(reported)
    # one cell in four may ask for four chips: this one asks for one
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 1 and len(four) <= max(1, len(bench["workloads"]) // 4)


def test_published_widths_are_the_catalogs_and_the_share_is_stated():
    data = manifest.config(CONFIG)
    pub = data["published"]
    # the driver reads the source's keys at the file's top level
    assert {k: data[k] for k in pub} == pub
    held = {"num_hidden_layers": 8, "num_experts": 128, "vocab_size": 39296}
    assert sorted(data["reduced"]) == sorted(held)
    assert data["source_values"] == {"num_experts": 512, "vocab_size": 157184}
    assert data["share"]["chips_sharing_a_layer"] == 4
    assert data["share"]["index"] == 0 and data["layer_period"] == 6
    assert data["assumed"] and data["deployment"]
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r.get("name") == "Ling-3.0-flash")
        assert data["source"] == row["source_url"]
        assert pub == {**row["config"], **held}
    served = modelcfg.model_config(data)
    assert served == ModelConfig.ling_30_flash().scaled(
        name=CONFIG, num_layers=8, num_experts_held=128, vocab_size=39296)
    assert served == ModelConfig.ling_30_flash_ep4_l8()
    assert served.num_experts == 512 and served.experts_here == 128
    assert [served.layer_kind(li) for li in range(8)] == (
        ["kda"] * 5 + ["attn"] + ["kda"] * 2)
    for key, value in (("moe_intermediate_size", 512), ("head_dim", 64),
                       ("short_conv_kernel_size", 2)):
        bad = json.loads(json.dumps(data))
        bad["published"][key] = value
        with pytest.raises(ValueError, match=key):
            modelcfg.model_config(bad)
    # the floors: fewer than a whole group behind the dense layers, or a
    # share that does not hold the source between its chips
    for key, value, why in (("num_hidden_layers", 7, "whole period"),
                            ("num_experts", 64, "do not hold"),
                            ("vocab_size", 1000, "do not hold")):
        bad = json.loads(json.dumps(data))
        bad["published"][key] = value
        with pytest.raises(ValueError, match=why):
            modelcfg.model_config(bad)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("held", [0, 8])
def test_reference_draws_the_programs_weights(dtype, held):
    cfg = ModelConfig.tiny_ling_test(held=held)
    data = manifest.config(TINY)
    pub = dict(data["published"], num_experts=held or 32)
    s = ref.sizes(pub, {"num_experts": 32}, {"index": 0})
    assert (s["E"], s["held"], s["first"]) == (32, held or 32, 0)
    seed = 7654321
    params = llama.init_params(
        jax.random.PRNGKey(seed), cfg, dtype=jnp.dtype(dtype))
    layer_keys, _ek, _hk = ref.model_keys(seed, cfg.num_layers)
    for li in range(cfg.num_layers):
        mine = ref.layer_weights(layer_keys[li], s, li, jnp.dtype(dtype))
        theirs = params["layers"][li]
        norms = {k for k in theirs if k.startswith("ln_")}
        assert sorted(mine) == sorted(set(theirs) - norms), li
        assert all(bool(jnp.all(theirs[k] == 1)) for k in norms)
        for name in mine:
            np.testing.assert_array_equal(
                np.asarray(mine[name], np.float32),
                np.asarray(theirs[name], np.float32), err_msg=f"{li} {name}")


MODEL = dict(num_layers=8, layer_group_size=6, num_heads=32, head_dim=128,
             kv_lora_rank=512, qk_rope_head_dim=64, num_kv_heads=32)
ENGINE = dict(dtype_bytes=2, kv_dtype_bytes=2, cache_head_dim=640)


def test_cost_files_count_their_own_layers_only():
    kda = registry.load("costs", "kda_recurrent").cost
    latent = registry.load("costs", "latent_paged_attention").cost
    lanes = [(100, 1), (0, 1), (64, 64), (0, 0)]
    flops, nbytes = kda(lanes, model=MODEL, engine=ENGINE)
    # two lanes of one row in 7 recurrent layers; the quantum of 64 rows
    # is kda_chunk's
    state = 32 * 128 * 128
    assert flops == 2 * 7 * 8 * state
    assert nbytes == 2 * 7 * (2 * state + 6 * 32 * 128) * 4
    assert kda([(64, 64)], model=MODEL, engine=ENGINE) == (0, 0)
    assert kda(lanes, model=dict(MODEL, layer_group_size=0),
               engine=ENGINE) == (0, 0)
    f1, b1 = latent(lanes, model=MODEL, engine=ENGINE)
    assert f1 > 0 and b1 > 0
    # one softmax layer in these eight: a sixteen-layer cut has two
    f2, b2 = latent(lanes, model=dict(MODEL, num_layers=16), engine=ENGINE)
    assert (f2, b2) == (2 * f1, 2 * b1)
    # one cached head of 640 in two arrays, whatever the engine says
    rows = 101 + 1 + 128
    assert b1 == 2 * rows * 640 * 2 + 2 * 66 * 32 * 640 * 2
    assert latent(lanes, model=MODEL,
                  engine=dict(ENGINE, cache_head_dim=128))[1] == b1
    # against the accepted cost at this model's shape: eight layers of 32
    # cached heads, which is why the cell does not report that metric
    whole = registry.load("costs", "ragged_paged_attention").cost(
        lanes, model=dict(MODEL, sliding_window=0), engine=ENGINE)[1]
    assert whole > 8 * b1


def test_costs_stay_under_the_traced_kernel_times():
    """Dispatches and kernel times recorded from a traced run of the cell
    on a v5e (my chip run, PR 41): each new roofline share is above 0 and
    under 100 %."""
    path = os.path.join(HERE, "data", "ling_traced_dispatches.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    peaks = peaks_for(rec["device_kind"])
    for metric in ("kernel.kda_roofline", "kernel.latent_attn_roofline"):
        params = manifest.metric(metric)["params"]
        cost = registry.load("costs", params["cost"]).cost
        secs = sum(s for name, s in rec["op_seconds"].items()
                   if name.startswith(params["kernel"]))
        least = 0.0
        for lanes in rec["dispatches"]:
            flops, nbytes = cost(lanes, model=rec["model"], engine=rec["engine"])
            least += max(flops / peaks["flops_bf16"],
                         nbytes / peaks["hbm_bytes_per_s"])
        assert 0.0 < least / secs < 1.0, (metric, least, secs)


def test_whole_run_of_the_new_family_on_the_cpu():
    """The harness path end to end with the share cut and the step driver
    that keeps a state: one SSE chunk a token, nothing compiles in the
    window, the served step is the reference's."""
    proc = _run(
        "chipbench", "--workload", f"{TINY}.rehearsal", "--seed",
        str(2**31 + 4321), "--seconds", "2", "--trace", "0", "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result, _said, _errors = _last_lines(proc)
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compared"]["rel_err_p100"]["value"] < 1e-3
    assert set(result["metrics"]) == {"out_tok_s_chip", "setup_s"}


def test_the_state_control_of_the_new_family_comes_out_not_correct():
    """The state held in bfloat16 against the tiny configuration's float32
    limit, through ``chipbench.control_state`` (at the published widths in
    bfloat16 no limit can refuse it: PERF.md section 6, PR 41)."""
    proc = _run(
        "chipbench.control_state", "--config", TINY, "--seeds", "1",
        "--control-seeds", "1", "--controls", "bf16_state", "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    limit = last["limits_in_file"]["limit"]
    assert last["sound_max"]["rel_err"] < limit / 3
    assert last["sound_not_correct"] == 0
    assert last["control_min"]["bf16_state"]["rel_err"] > 3 * limit, last
    assert last["control_correct"]["bf16_state"] == 0, last


def test_quantized_init_draws_each_layer_kind():
    """``init_params_int8`` (what the int8-weights control serves) draws a
    layer by its kind: the latent-attention layer among the KDA ones, dense
    and expert MLPs, each the quantized form of ``init_params``' weights."""
    from dynamo_tpu.ops.quant import dequantize_weight, init_params_int8

    cfg = ModelConfig.tiny_ling_test(held=8)
    key = jax.random.PRNGKey(5)
    plain = llama.init_params(key, cfg, jnp.float32)
    quant = init_params_int8(key, cfg, jnp.float32)
    for li, (a, b) in enumerate(zip(plain["layers"], quant["layers"])):
        assert sorted(a) == sorted(b), li
        assert ("w_dkv" in b) == (cfg.layer_kind(li) == "attn")
        assert ("w_router" in b) == cfg.moe_layer(li)
        for name in ("wq", "wo", "w_gate"):
            got = dequantize_weight(b[name])
            assert got.shape == a[name].shape
            np.testing.assert_allclose(got, a[name], atol=0.02)
