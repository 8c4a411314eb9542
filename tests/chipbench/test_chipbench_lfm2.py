"""The family whose mixers are gated short convolutions in the benchmark:
the configuration file holds the catalog's widths and the cut in depth
alone, the reference imports nothing of the program and draws its weights,
the cell and the five metrics it brought are held by NAME, the four host
metrics are the definitions ``test_chipbench_host_phases.py`` proposed, and
the whole harness path runs on the CPU with the step driver that keeps a
state (a tail alone) and both controls are refused."""

import ast
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, control_lowered, manifest, modelcfg, registry
from chipbench.observe import Observations
from chipbench.reference import lfm2_moe as ref
from dynamo_tpu.engine.flight_recorder import FlightRecorder
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import LFM2_24B_LAYER_TYPES, ModelConfig
from test_chipbench_host_phases import HOST, PROPOSED, observations, records
from test_chipbench_run import _last_lines, _run

CONFIG = "lfm2-24b-a2b-l10"
TINY = "tiny-lfm2-rehearsal"
CELL = f"{CONFIG}.chat-c128"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "LFM2-24B-A2B"
HOST_METRICS = ["scheduler.host_step_p50_ms", "scheduler.device_wait_pct",
                "scheduler.side_channels_pct", "frontend.handoff_wait_pct"]
NEW = ["kv.cache_lane_pad_pct"] + HOST_METRICS
JOINED = ["frontend.itl_p95_ms", "scheduler.tokens_per_dispatch",
          "kv.pool_used_peak_pct", "kv.cache_bytes_per_ctx_token",
          "state.slots_used_peak_pct", "runner.dispatch_p50_ms",
          "runner.compiles_in_window", "model.device_step_p50_ms",
          "kernel.ragged_attn_step_pct", "kernel.moe_grouped_step_pct",
          "kernel.moe_grouped_roofline", "device.idle_pct"]


def test_manifest_holds_the_cell_by_name_and_what_it_brought():
    """By NAME, never by position or count: the next cell does not outlive
    this test."""
    assert manifest.check() == []
    bench = manifest.benchmark_json()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "chat-c128", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"][0] == CELL and m["moves"] == "out_tok_s_chip"
        assert m["source"] == "program_counter"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert all(CELL in by_name[m]["workloads"] for m in JOINED)
    reported = manifest.workload(CELL)
    assert reported["per_layer"] == JOINED + NEW
    assert reported["end_to_end"] == ["out_tok_s_chip", "setup_s"]
    # the accepted attention cost multiplies the rows by the STORED head
    # width: half of what it would count here is padding
    assert "kernel.ragged_attn_roofline" not in reported["per_layer"]
    assert CELL not in by_name["kernel.ragged_attn_roofline"]["workloads"]
    # nothing of this cell exists only across chips
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "mistral-7b-tp4.chat-c128"]


#: The cells before this one by NAME, each with the metrics it brought: what
#: ``test_chipbench_nemotron_h.py`` holds of them, without pinning a list
#: that a later cell may join to its length (its Brumby case pins
#: ``state.slots_used_peak_pct`` to two cells and is outlived since this
#: cell reports it too: ``tests/conftest.py`` ``_OUTLIVED``).
BEFORE = {
    "nemotron-3-super-ep4-l11.reason-c128": {
        "config": ("nemotron-3-super-ep4-l11", "reason-c128", 1),
        "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
        "brought": ["kernel.ssd_step_pct", "kernel.ssd_roofline",
                    "kernel.ssd_chunk_roofline",
                    "kernel.moe_latent_held_roofline",
                    "moe.share_rows_22x5_pct"],
    },
    "brumby-14b-l8.longdoc-c20": {
        "config": ("brumby-14b-l8", "longdoc-c20", 1),
        "reduced": ["num_hidden_layers"],
        "brought": ["kernel.retention_step_pct", "kernel.retention_roofline",
                    "kernel.retention_chunk_roofline",
                    "state.slots_used_peak_pct"],
        "joined": [
            "frontend.pre_engine_p50_ms", "frontend.ttft_p50_ms",
            "frontend.ttft_p90_ms", "frontend.ttft_p95_ms",
            "frontend.itl_p95_ms", "scheduler.queue_wait_p95_ms",
            "scheduler.tokens_per_dispatch", "runner.dispatch_p50_ms",
            "runner.compiles_in_window", "model.device_step_p50_ms",
            "device.idle_pct"],
    },
}


@pytest.mark.parametrize("cell", sorted(BEFORE))
def test_the_cells_before_still_hold_what_they_brought(cell):
    held = BEFORE[cell]
    bench = manifest.benchmark_json()
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == held["config"]
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert config["reduced"] == held["reduced"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    brought = [m["name"] for m in bench["per_layer"]
               if m.get("workloads", [None])[0] == cell]
    assert brought == held["brought"]
    names = [w["name"] for w in bench["workloads"]]
    for name in brought:
        # what it brought it leads; whoever joined stands behind it, in the
        # order the cells were added
        cells = by_name[name]["workloads"]
        assert cells[0] == cell
        assert cells == sorted(cells, key=names.index), name
    reported = manifest.workload(cell)["per_layer"]
    assert reported[-len(brought):] == held["brought"]
    for name in held.get("joined", ()):
        cells = by_name[name]["workloads"]
        assert cell in cells and name in reported
        assert CELL not in cells or cells.index(cell) < cells.index(CELL)
    if cell.startswith("brumby"):
        assert reported == held["joined"] + held["brought"]
        assert not [m for m in reported
                    if m.startswith(("kv.", "kernel.ragged"))]
        assert by_name["state.slots_used_peak_pct"]["layer"] == "scheduler"
        assert by_name["state.slots_used_peak_pct"]["workloads"][:3] == [
            cell, "nemotron-3-super-ep4-l11.reason-c128", CELL]


def test_the_traffic_is_the_accepted_one_untouched():
    spec = manifest._load("traffic", "chat-c128")
    assert {k: spec[k] for k in ("loop", "clients", "block", "ramp_s")} == {
        "loop": "closed", "clients": 128, "block": 128, "ramp_s": 12}
    assert spec["prompt_tokens"] == {
        "distribution": "log_uniform", "low": 64, "high": 1024}
    assert spec["output_tokens"] == {
        "distribution": "log_uniform", "low": 128, "high": 512}
    assert spec["think_time_s"] == {"distribution": "constant", "value": 0.0}
    # the same traffic as two accepted cells: what differs is the model
    bench = manifest.benchmark_json()
    assert {w["config"] for w in bench["workloads"]
            if w["traffic"] == "chat-c128"} >= {
        CONFIG, "ling-3.0-flash-ep4-l8", "mistral-7b-tp4"}


def test_published_widths_are_the_catalogs_and_only_the_depth_is_cut():
    data = manifest.config(CONFIG)
    pub = data["published"]
    # the driver reads the source's keys at the file's top level
    assert {k: data[k] for k in pub} == pub
    assert data["reduced"] == ["num_hidden_layers"]
    assert "share" not in data and "source_values" not in data
    assert data["layer_period"] == 4 and data["chips"] == 1
    assert len(pub["layer_types"]) == 40            # never cut in the file
    assert "head_dim" not in pub and "tie_word_embeddings" not in pub
    assumed = " ".join(data["assumed"])
    for what in ("head_dim 64", "tied", "DRAWN", "1e-6", "served dtype"):
        assert what in assumed, what
    assert data["deployment"].startswith("the first of four pipeline stages")
    assert data["check"]["step"] == "recurrent_span"
    assert data["check"]["decode_steps"] >= 6
    assert data["check"]["token_mismatch_limit"] < 16 * 7
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r.get("name") == ROW)
        assert data["source"] == row["source_url"]
        assert pub == {**row["config"], "num_hidden_layers": 10}
    served = modelcfg.model_config(data)
    assert served == ModelConfig.lfm2_24b_a2b_l10()
    assert served == ModelConfig.lfm2_24b_a2b().scaled(
        name=CONFIG, num_layers=10)
    assert served.layer_types == LFM2_24B_LAYER_TYPES == tuple(
        pub["layer_types"])
    assert (served.num_experts, served.experts_here, served.vocab_size) == (
        64, 64, 65536)
    assert (served.head_dim, served.first_k_dense_replace) == (64, 2)
    # a width can never differ
    for key, value in (("conv_L_cache", 4), ("moe_intermediate_size", 768),
                       ("intermediate_size", 8192), ("hidden_size", 1024),
                       ("num_experts_per_tok", 2), ("num_dense_layers", 1),
                       ("use_expert_bias", False), ("norm_eps", 1e-6)):
        bad = json.loads(json.dumps(data))
        bad["published"][key] = value
        with pytest.raises(ValueError, match=key):
            modelcfg.model_config(bad)
    # the floor: four layers and one whole period of four
    bad = json.loads(json.dumps(data))
    bad["published"]["num_hidden_layers"] = 3
    with pytest.raises(ValueError, match="whole period"):
        modelcfg.model_config(bad)
    # the serving arguments: a lane a client, every lane to its full length
    args = dict(zip(data["serve_args"][::2], data["serve_args"][1::2]))
    assert set(args) == set(data["serve_args_why"])
    assert int(args["--num-blocks"]) * 16 == int(args["--max-num-seqs"]) * int(
        args["--max-model-len"])


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(open(ref.__file__).read())
    names = [
        n.module if isinstance(n, ast.ImportFrom) else a.name
        for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom))
        for a in n.names
    ]
    assert names and not [n for n in names if n.split(".")[0] not in (
        "__future__", "math", "functools", "jax", "numpy")], names
    # the convolution is written out, not the program's causal_conv
    assert "causal_conv" not in open(ref.__file__).read().split('"""', 2)[2]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_reference_draws_the_programs_weights(dtype):
    cfg = ModelConfig.tiny_lfm2_test()
    s = ref.sizes(manifest.config(TINY)["published"])
    assert (s["hd"], s["E"], s["k"], s["K"], s["dense"]) == (16, 16, 4, 3, 2)
    seed = 7654321
    params = llama.init_params(
        jax.random.PRNGKey(seed), cfg, dtype=jnp.dtype(dtype))
    layer_keys, ek = ref.model_keys(seed, cfg.num_layers)
    names = {"taps": "conv_w", "w1": "w_gate", "w3": "w_up", "w2": "w_down"}
    for li in range(cfg.num_layers):
        kind = "conv" if s["types"][li] == "conv" else "attn"
        mine = ref.layer_weights(
            layer_keys[li], s, kind, li >= s["dense"], jnp.dtype(dtype))
        theirs = params["layers"][li]
        ones = {k for k in theirs if k.startswith("ln_")}
        named = {names.get(k, k): v for k, v in mine.items()}
        assert sorted(named) == sorted(set(theirs) - ones), li
        assert all(bool(jnp.all(theirs[k] == 1)) for k in ones)
        for name, value in named.items():
            np.testing.assert_array_equal(
                np.asarray(value, np.float32),
                np.asarray(theirs[name], np.float32), err_msg=f"{li} {name}")
    items = tuple(sorted({**s, "lowered": None}.items()))
    with jax.disable_jit():     # the program draws outside any jit
        table = ref._table(ek, items, dtype)
    np.testing.assert_array_equal(
        np.asarray(table, np.float32),
        np.asarray(params["embed"], np.float32))
    assert "lm_head" not in params              # tied


def test_the_convolution_written_out_is_the_sum_over_three_shifted_copies():
    rng = np.random.default_rng(0)
    a = rng.normal(size=(2, 9, 5)).astype(np.float32)
    taps = rng.normal(size=(3, 5)).astype(np.float32)
    want = np.zeros_like(a)
    for t in range(9):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += taps[j] * a[:, t - 2 + j]
    np.testing.assert_allclose(ref.short_conv(a, taps), want, rtol=1e-5, atol=1e-6)
    # the control: a row reads zeros for a row of an earlier span
    rows = np.asarray([[3, 4, 8], [8, 8, 8]], np.int32)
    span = ref.spans_of(rows, 9)
    assert span[0].tolist() == [0, 0, 0, 0, 1, 2, 2, 2, 2]
    cut = np.asarray(ref.short_conv(jnp.asarray(a), taps, jnp.asarray(span)))
    np.testing.assert_allclose(cut[1], want[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(cut[0, :4], want[0, :4], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(cut[0, 4], taps[2] * a[0, 4], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        cut[0, 6], taps[2] * a[0, 6] + taps[1] * a[0, 5], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(cut[0, 7], want[0, 7], rtol=1e-5, atol=1e-6)


# -- the five metrics ----------------------------------------------------

@pytest.mark.parametrize("name", HOST_METRICS)
def test_a_host_metric_is_the_definition_that_was_proposed(name):
    """Each file is exactly ``PROPOSED[name]`` of
    ``test_chipbench_host_phases.py`` plus name, kind, source and a what;
    the accepted readers; a field the program writes. (That file's own
    test of these four names asserts that they are NOT in the benchmark
    yet: outlived since they are, ``tests/conftest.py`` ``_OUTLIVED``.)"""
    data = manifest.metric(name)
    assert {k: data[k] for k in PROPOSED[name]} == PROPOSED[name]
    assert set(data) == set(PROPOSED[name]) | {
        "name", "kind", "source", "what"}
    assert (data["name"], data["kind"], data["source"]) == (
        name, "per_layer", "program_counter")
    assert data["reader"] in ("flight", "flight_ratio")
    bench = manifest.benchmark_json()
    assert data["moves"] in {e["name"] for e in bench["end_to_end"]}
    assert data["layer"] in {e["layer"] for e in bench["per_layer"]
                             if e["name"] not in NEW}
    assert manifest.NAME.match(name) and manifest.UNIT.match(data["unit"])
    rec = FlightRecorder(capacity=8)
    rec.note_step("unified")
    params = data["params"]
    fields = params.get("fields") or params["over"] + params["under"]
    assert set(fields) <= set(rec.snapshot()[0])


def read(names, obs):
    from chipbench.harness import read_metrics

    return {k: v["value"] for k, v in read_metrics(names, obs).items()}


def test_the_five_read_what_the_program_writes_and_nothing_of_the_parent():
    snap = {"kv_cache_lane_pad_perc": 0.5, "t": 110.0}
    got = read(NEW, observations(records([HOST] * 3), [snap]))
    assert got["kv.cache_lane_pad_pct"] == 50.0
    assert got["scheduler.host_step_p50_ms"] == pytest.approx(13.0)
    assert got["scheduler.device_wait_pct"] == pytest.approx(20.0)
    assert got["scheduler.side_channels_pct"] == pytest.approx(10.0)
    assert got["frontend.handoff_wait_pct"] == pytest.approx(10.0)
    # the parent: no such gauge; a program before PR 58: no phases (the
    # accepted flight reader sums absent fields as 0)
    old = read(NEW, observations(records([None] * 3), [{"t": 110.0}]))
    assert old == {"scheduler.host_step_p50_ms": 0.0}
    assert read(NEW, observations([])) == {}


def test_the_grouped_expert_cost_counts_this_models_eight_expert_layers():
    """The accepted ``costs/moe_grouped_ffn.py`` on this model's fields:
    ``num_dense_layers`` is ``first_k_dense_replace``, so two of the ten
    layers are left out; every expert hit reads its three matrices once."""
    from chipbench.harness import scalar_fields

    model = scalar_fields(ModelConfig.lfm2_24b_a2b_l10())
    assert model["first_k_dense_replace"] == 2 and model["num_layers"] == 10
    assert "layer_types" not in model           # a tuple is no scalar
    fn = registry.load("costs", "moe_grouped_ffn").cost
    engine = {"dtype_bytes": 2, "tp": 1}
    flops, nbytes = fn([(0, 300)], model=model, engine=engine,
                       experts_hit=8 * 64)
    assert flops == 300 * 4 * 3 * 2 * 2048 * 1536 * 8
    weights = 8 * 64 * 3 * 2048 * 1536 * 2
    assert weights == 9_663_676_416
    assert nbytes == weights + 300 * 4 * 2048 * (2 + 4) * 8


# -- the whole path on the CPU --------------------------------------------

def test_whole_run_of_the_new_family_on_the_cpu():
    """The harness path end to end with the step driver that keeps a state:
    one SSE chunk a token, nothing compiles in the window, the served step
    is the reference's."""
    proc = _run(
        "chipbench", "--workload", f"{TINY}.rehearsal", "--seed",
        str(2**31 + 6161), "--seconds", "2", "--trace", "0", "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result, _said, _errors = _last_lines(proc)
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compared"]["rel_err_p100"]["value"] < 1e-4
    assert set(result["metrics"]) == {"out_tok_s_chip", "setup_s"}


@pytest.mark.parametrize("lowered", ref.LOWERED)
def test_a_control_in_the_programs_place_is_not_correct(lowered):
    """``chipbench.control_lowered``: the reference with int8's weights, and
    with the tail dropped between the sample's dispatches, put in the
    program's place, are refused by the tiny configuration's own limit
    through ``check.judge``."""
    data = manifest.config(TINY)
    budget = int(data["serve_args"][
        data["serve_args"].index("--unified-token-budget") + 1])
    out = control_lowered.lowered_one(data, budget, 2**31 + 77, lowered)
    assert out["rows"] > 0 and out["rel_err_by_phase"]["decode"] is not None
    assert out["rel_err"] > 3 * data["check"]["limit"], out
    assert out["not_correct"], out
    with pytest.raises(ValueError, match="lowered"):
        ref.logits(data["published"], 1, np.zeros((1, 4), np.int32),
                   np.zeros((1, 1), np.int32), "float32", lowered="fp4")


def test_the_tiny_sample_cuts_a_sequence_one_and_two_rows_in():
    """The sample's dispatches at the tiny budget: a first span of ONE row
    and one of TWO (a tail of one row over a zero, then of both), and a
    later span behind a longer one."""
    from chipbench.steps.span import plan_steps

    block = manifest.config(TINY)["check"]
    spans = plan_steps(block["prompt_lens"], block["decode_steps"], 32)
    first = {}
    for dispatch in spans:
        for b, prefix, n in dispatch:
            if prefix == 0:
                first[b] = n
    assert first[2] == 1 and first[3] == 2
    assert first[4] < block["prompt_lens"][4]
    # and the cell's own sample at its own budget
    data = manifest.config(CONFIG)
    budget = int(data["serve_args"][
        data["serve_args"].index("--unified-token-budget") + 1])
    lens = data["check"]["prompt_lens"]
    first = {}
    for dispatch in plan_steps(lens, data["check"]["decode_steps"], budget):
        for b, prefix, n in dispatch:
            if prefix == 0:
                first[b] = n
    cut = sorted(first[b] for b in first if first[b] < lens[b])
    assert cut[:2] == [1, 2] and len(cut) >= 3, cut
