"""The family whose layers are one part each in the benchmark: the
configuration file holds the catalog's widths and the share cut, the
reference imports nothing of the program and draws its weights, the new
cost files count their own layers only (and the bytes the one-row kernel
must move), and the whole harness path runs on the CPU with the share cut
and the step driver that keeps a state."""

import ast
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import manifest, modelcfg, registry
from chipbench.reference import nemotron_h as ref
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from test_chipbench_run import _last_lines, _run

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = "nemotron-3-super-ep4-l11"
TINY = "tiny-nemotron-h-rehearsal"
CELL = f"{CONFIG}.reason-c128"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
NEW = ["kernel.ssd_step_pct", "kernel.ssd_roofline",
       "kernel.ssd_chunk_roofline", "kernel.moe_latent_held_roofline",
       "moe.share_rows_22x5_pct"]
JOINED = ["frontend.itl_p95_ms", "scheduler.tokens_per_dispatch",
          "kv.pool_used_peak_pct", "kv.cache_bytes_per_ctx_token",
          "runner.dispatch_p50_ms", "runner.compiles_in_window",
          "model.device_step_p50_ms", "kernel.ragged_attn_step_pct",
          "kernel.moe_grouped_step_pct", "device.idle_pct",
          # after review: the frontend, the queue and the state's slots run
          # here as in the cells that list them
          "frontend.pre_engine_p50_ms", "frontend.ttft_p50_ms",
          "scheduler.queue_wait_p95_ms", "state.slots_used_peak_pct"]
HELD = {"num_hidden_layers": 11, "n_routed_experts": 128, "vocab_size": 32768}


def test_manifest_holds_the_cell_and_what_it_brought():
    assert manifest.check() == []
    bench = manifest.benchmark_json()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reason-c128", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(HELD)
    brought = [m for m in bench["per_layer"]
               if m.get("workloads", [None])[0] == CELL]
    assert [m["name"] for m in brought] == NEW
    assert all(m["moves"] == "out_tok_s_chip" and m["workloads"] == [CELL]
               for m in brought)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert all(CELL in by_name[m]["workloads"] for m in JOINED)
    reported = manifest.workload(CELL)
    assert reported["per_layer"] == JOINED + NEW
    assert reported["end_to_end"] == ["out_tok_s_chip", "setup_s"]
    # the accepted expert costs count three matrices as wide as the model:
    # over 100 % here; and no attention roofline over one layer in eleven
    assert not {"kernel.moe_held_roofline", "kernel.moe_grouped_roofline",
                "kernel.ragged_attn_roofline"} & set(reported["per_layer"])
    # one cell in four may ask for four chips: this one asks for one
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 1 and len(bench["workloads"]) >= 10


#: The cells before this one by NAME, each with the metrics it brought and
#: the lists it joined: what ``test_chipbench_deepseek_v2.py`` asserts of its
#: own cell while it is the newest and of Brumby's behind it (that second
#: test pins ``state.slots_used_peak_pct`` to Brumby's cell alone and is
#: outlived since this cell reports it: ``tests/conftest.py`` ``_OUTLIVED``).
BEFORE = {
    "deepseek-v2-ep4-l5.docqa-c48": {
        "config": ("deepseek-v2-ep4-l5", "docqa-c48", 1),
        "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
        "brought": ["kernel.latent_once_attn_roofline",
                    "moe.share_rows_6x4_pct"],
        "joined_later": [],
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
        "joined_later": ["state.slots_used_peak_pct"],
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
    brought = [m for m in bench["per_layer"]
               if m.get("workloads", [None])[0] == cell]
    assert [m["name"] for m in brought] == held["brought"]
    # what it brought is its own, but for a list a later cell joined BEHIND it
    for m in brought:
        later = [CELL] if m["name"] in held["joined_later"] else []
        assert m["workloads"] == [cell] + later, m["name"]
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


def test_the_traffic_is_what_the_issue_gives():
    spec = manifest._load("traffic", "reason-c128")
    assert {k: spec[k] for k in ("loop", "clients", "block", "ramp_s")} == {
        "loop": "closed", "clients": 128, "block": 128, "ramp_s": 24}
    assert spec["prompt_tokens"] == {
        "distribution": "log_uniform", "low": 128, "high": 2048}
    assert spec["output_tokens"] == {
        "distribution": "log_uniform", "low": 512, "high": 4096}
    assert spec["think_time_s"] == {"distribution": "constant", "value": 0.0}
    assert "shared_prefix" not in spec and "prefix" not in spec


def test_published_widths_are_the_catalogs_and_the_share_is_stated():
    data = manifest.config(CONFIG)
    pub = data["published"]
    # the driver reads the source's keys at the file's top level
    assert {k: data[k] for k in pub} == pub
    assert sorted(data["reduced"]) == sorted(HELD)
    assert data["source_values"] == {
        "n_routed_experts": 512, "vocab_size": 131072}
    assert data["share"]["chips_sharing_a_layer"] == 4
    assert data["share"]["index"] == 0 and data["layer_period"] == 11
    assert data["assumed"] and data["deployment"]
    assert data["check"]["step"] == "recurrent_span"
    assert data["check"]["decode_steps"] >= 6
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r.get("name") == ROW)
        assert data["source"] == row["source_url"]
        assert pub == {**row["config"], **HELD}
    served = modelcfg.model_config(data)
    assert served == ModelConfig.nemotron_3_super_ep4_l11()
    assert served == ModelConfig.nemotron_3_super().scaled(
        name=CONFIG, num_layers=11, num_experts_held=128, vocab_size=32768)
    assert served.num_experts == 512 and served.experts_here == 128
    assert (served.n_group, served.mamba_n_groups) == (1, 8)
    assert len(served.layer_pattern) == 88      # never cut in the file
    assert [served.layer_kind(li) for li in range(11)] == [
        {"M": "ssd", "*": "attn", "E": "none"}[c] for c in "MEMEMEM*EME"]
    # a width can never differ
    for key, value in (("moe_latent_size", 512), ("mamba_head_dim", 32),
                       ("n_groups", 4), ("ssm_state_size", 64),
                       ("moe_shared_expert_intermediate_size", 2688),
                       ("num_experts_per_tok", 8), ("conv_kernel", 2)):
        bad = json.loads(json.dumps(data))
        bad["published"][key] = value
        with pytest.raises(ValueError, match=key):
            modelcfg.model_config(bad)
    # the floors: less than the whole period of eleven, or a share that
    # does not hold the source between its chips
    for key, value, why in (("num_hidden_layers", 10, "whole period"),
                            ("n_routed_experts", 64, "do not hold"),
                            ("vocab_size", 1000, "do not hold")):
        bad = json.loads(json.dumps(data))
        bad["published"][key] = value
        with pytest.raises(ValueError, match=why):
            modelcfg.model_config(bad)


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(open(ref.__file__).read())
    names = [
        n.module if isinstance(n, ast.ImportFrom) else a.name
        for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom))
        for a in n.names
    ]
    assert names and not [n for n in names if n.split(".")[0] not in (
        "__future__", "math", "functools", "jax")], names


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("held", [0, 16])
def test_reference_draws_the_programs_weights(dtype, held):
    cfg = ModelConfig.tiny_nemotron_h_test(held=held)
    data = manifest.config(TINY)
    pub = dict(data["published"], n_routed_experts=held or 32)
    s = ref.sizes(pub, {"n_routed_experts": 32}, {"index": 0})
    assert (s["E"], s["held"], s["first"]) == (32, held or 32, 0)
    seed = 7654321
    params = llama.init_params(
        jax.random.PRNGKey(seed), cfg, dtype=jnp.dtype(dtype))
    layer_keys, _ek, _hk = ref.model_keys(seed, cfg.num_layers)
    mine_to_theirs = {"w_dn": "w_latent_down", "w_up": "w_latent_up",
                      "w1": "w_up", "w2": "w_down", "u1": "w_shared_up",
                      "u2": "w_shared_down"}
    for li in range(cfg.num_layers):
        mine = ref.layer_weights(layer_keys[li], s, li, jnp.dtype(dtype))
        theirs = params["layers"][li]
        ones = {k for k in theirs if k.startswith("ln_")} | {"D"} & set(theirs)
        named = {mine_to_theirs.get(k, k) if "w_router" in theirs else k: v
                 for k, v in mine.items()}
        assert sorted(named) == sorted(set(theirs) - ones), li
        assert all(bool(jnp.all(theirs[k] == 1)) for k in ones)
        for name, value in named.items():
            np.testing.assert_array_equal(
                np.asarray(value, np.float32),
                np.asarray(theirs[name], np.float32), err_msg=f"{li} {name}")


MODEL = dict(
    num_layers=11, layer_pattern="MEMEMEM*EMEMEMEM*", hidden_size=4096,
    mamba_num_heads=128, mamba_head_dim=64, mamba_n_groups=8,
    ssm_state_size=128, num_experts=512, num_experts_held=128,
    num_experts_per_tok=22, moe_intermediate_size=2688, moe_latent_size=1024,
    intermediate_size=2688,
)
ENGINE = dict(dtype_bytes=2, kv_dtype_bytes=2, cache_head_dim=128)


def test_cost_files_count_their_own_layers_only():
    lanes_cost = registry.load("costs", "ssd_recurrent").cost
    chunk_cost = registry.load("costs", "ssd_chunk").cost
    lanes = [(100, 1), (0, 1), (64, 130), (0, 50), (0, 0)]
    state = 128 * 64 * 128
    row = 2 * 128 * 64 + 2 * 128 + 2 * 8 * 128
    # two lanes of one row in the 5 mixers of the first 11 letters
    flops, nbytes = lanes_cost(lanes, model=MODEL, engine=ENGINE)
    assert flops == 2 * 5 * 5 * state
    assert nbytes == 2 * 5 * (2 * state + row) * 4
    assert lanes_cost([(64, 64)], model=MODEL, engine=ENGINE) == (0, 0)
    assert lanes_cost(lanes, model=dict(MODEL, layer_pattern=""),
                      engine=ENGINE) == (0, 0)
    # a deeper cut counts the letters it reaches
    assert lanes_cost(lanes, model=dict(MODEL, num_layers=16),
                      engine=ENGINE)[0] == 2 * 8 * 5 * state
    # against the bytes the kernel must move: its state block in and out
    # and its operands, by the kernel's own layout
    from dynamo_tpu.ops.pallas.ssd import layout
    lay = layout(128, 64, 8, 128)
    block = 8 * lay["pg"] * lay["PW"] * 128 * 4      # a lane's state
    assert block == state * 4 == 4 * 1024 * 1024
    assert 2 * block <= nbytes / 10 <= 2 * block * 1.01
    # the chunk kernel: the span of 130 rows behind a prefix (a tile of
    # 128 and one of 2, the state read and written) and the fresh one of 50
    f, b = chunk_cost(lanes, model=MODEL, engine=ENGINE)
    pairs = lambda r: r * (r + 1) // 2
    per_pair = 2 * (8 * 128 + 128 * 64)
    per_row = 2 * 128 * 64 * 128
    assert f == 5 * (
        (pairs(128) + pairs(2) + pairs(50)) * per_pair
        + (2 * 130 + 1 * 50) * per_row)
    assert b == 5 * (3 * state * 4 + 180 * row * 4)
    # 5.4 MFLOP a row a layer at a full tile behind a state
    full = chunk_cost([(128, 128)], model=MODEL, engine=ENGINE)[0] / 5 / 128
    assert 5.3e6 < full < 5.5e6
    assert chunk_cost([(5, 1)], model=MODEL, engine=ENGINE) == (0, 0)


def test_the_latent_expert_cost_counts_two_matrices_in_the_latent():
    cost = registry.load("costs", "moe_latent_held_ffn").cost
    lanes = [(0, 178)]
    f, b = cost(lanes, model=MODEL, engine=ENGINE, rows_held=4700,
                experts_hit=640)
    assert f == 4700 * 2 * 2 * 1024 * 2688
    assert b == 640 * 2 * 1024 * 2688 * 2 + 4700 * 1024 * (2 + 4)
    # a third of what the accepted share cost counts for the same counts
    # (three matrices as wide as the model): why the cell lists this one
    old = registry.load("costs", "moe_held_ffn").cost(
        lanes, model=MODEL, engine=ENGINE, rows_held=4700, experts_hit=640)
    assert old[1] > 5 * b and old[0] == 6 * f
    # without the counts: an even spread over the four shares
    f0, b0 = cost(lanes, model=MODEL, engine=ENGINE)
    assert f0 == (178 * 22 // 4) * 5 * 2 * 2 * 1024 * 2688
    # a model without a latent or a pattern counts its own width and depth
    plain = dict(MODEL, layer_pattern="", moe_latent_size=0,
                 first_k_dense_replace=1)
    f1, _ = cost(lanes, model=plain, engine=ENGINE, rows_held=10,
                 experts_hit=10)
    assert f1 == 10 * 2 * 2 * 4096 * 2688
    assert cost([(0, 0)], model=MODEL, engine=ENGINE) == (0, 0)


def test_costs_stay_under_the_traced_kernel_times():
    """Dispatches, flight records and kernel times recorded from a traced
    run of the cell on a v5e (my chip run, PR 56): each new roofline share
    is above 0 and under 100 %, by the readers' own arithmetic."""
    import gzip

    from chipbench.peaks import peaks_for

    path = os.path.join(HERE, "data", "nemotron_traced_dispatches.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    peaks = peaks_for(rec["device_kind"])
    least_of = lambda flops, nbytes: max(
        flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    shares = {}
    for metric in NEW[1:4]:
        params = manifest.metric(metric)["params"]
        cost = registry.load("costs", params["cost"]).cost
        secs = sum(s for name, s in rec["op_seconds"].items()
                   if name.startswith(params["kernel"]))
        if "counted" in params:
            least = sum(
                least_of(*cost(
                    [(0, r["decode_tokens"] + r["prefill_tokens"])],
                    model=rec["model"], engine=rec["engine"],
                    **{kw: r[field] for field, kw in params["counted"].items()}))
                for r in rec["flight"])
        else:
            least = sum(
                least_of(*cost(lanes, model=rec["model"], engine=rec["engine"]))
                for lanes in rec["dispatches"])
        shares[metric] = least / secs
        assert 0.0 < least / secs < 1.0, (metric, least, secs)
    # the one-row kernel is the larger part and bytes-bound: past a half
    assert shares["kernel.ssd_roofline"] > 0.5, shares
    # the accepted share cost would read over 100 % on these counts
    old = registry.load("costs", "moe_held_ffn").cost
    gmm = sum(s for n, s in rec["op_seconds"].items() if n.startswith("gmm"))
    over = sum(
        least_of(*old([(0, r["decode_tokens"] + r["prefill_tokens"])],
                      model=rec["model"], engine=rec["engine"],
                      rows_held=r["moe_rows_held"],
                      experts_hit=r["moe_experts_hit"]))
        for r in rec["flight"])
    assert over / gmm > 1.0


def test_whole_run_of_the_new_family_on_the_cpu():
    """The harness path end to end with the share cut and the step driver
    that keeps a state: one SSE chunk a token, nothing compiles in the
    window, the served step is the reference's."""
    proc = _run(
        "chipbench", "--workload", f"{TINY}.rehearsal", "--seed",
        str(2**31 + 5678), "--seconds", "2", "--trace", "0", "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result, _said, _errors = _last_lines(proc)
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compared"]["rel_err_p100"]["value"] < 1e-3
    assert set(result["metrics"]) == {"out_tok_s_chip", "setup_s"}


@pytest.mark.parametrize("lowered", ref.LOWERED)
def test_the_reference_below_the_stated_precision_is_not_correct(lowered):
    """``chipbench.control_lowered``: the reference computed in a precision
    below the tiny configuration's float32 (weights at int8's precision;
    the state rounded to bfloat16 after every token), put in the program's
    place, is refused by the configuration's own limit through
    ``check.judge``; the plain pass in that place reads 0."""
    from chipbench import control_lowered

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


def test_the_state_control_of_the_new_family_comes_out_not_correct():
    """The state held in bfloat16 against the tiny configuration's float32
    limit, through ``chipbench.control_state``."""
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
