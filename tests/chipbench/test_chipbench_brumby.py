"""The power-retention family in the benchmark: the manifest holds the new
cell and its four metrics, the configuration file holds the catalog's
widths with depth the only cut, the reference draws the program's weights
and agrees with the program at the tiny size, both cost functions against
hand counts, the traffic file's moments, and the whole harness path on the
CPU for a model with no paged cache."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, manifest, modelcfg, registry, traffic
from chipbench.reference import brumby as ref
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from test_chipbench_run import _last_lines, _run

CONFIG = "brumby-14b-l8"
TINY = "tiny-brumby-rehearsal"
CELL = f"{CONFIG}.longdoc-c20"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["kernel.retention_step_pct", "kernel.retention_roofline",
       "kernel.retention_chunk_roofline", "state.slots_used_peak_pct"]
JOINED = ["frontend.pre_engine_p50_ms", "frontend.ttft_p50_ms",
          "frontend.ttft_p90_ms", "frontend.ttft_p95_ms",
          "frontend.itl_p95_ms", "scheduler.queue_wait_p95_ms",
          "scheduler.tokens_per_dispatch", "runner.dispatch_p50_ms",
          "runner.compiles_in_window", "model.device_step_p50_ms",
          "device.idle_pct"]


def test_manifest_holds_the_cell_and_what_it_brought():
    assert manifest.check() == []
    bench = manifest.benchmark_json()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc-c20", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    brought = [m for m in bench["per_layer"]
               if m.get("workloads", [None])[0] == CELL]
    assert [m["name"] for m in brought] == NEW
    assert all(m["moves"] == "out_tok_s_chip" and m["workloads"] == [CELL]
               for m in brought)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert all(by_name[m]["workloads"][-1] == CELL for m in JOINED)
    # none that reads a pool or the ragged kernel
    reported = manifest.workload(CELL)["per_layer"]
    assert reported == JOINED + NEW
    assert not [m for m in reported if m.startswith(("kv.", "kernel.ragged"))]
    assert by_name["state.slots_used_peak_pct"]["layer"] == "scheduler"
    # one cell in four may ask for four chips: this one asks for one
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(bench["workloads"]) == 8 and len(four) == 1


def test_published_widths_are_the_catalogs_and_depth_is_the_only_cut():
    data = manifest.config(CONFIG)
    pub = data["published"]
    # the driver reads the source's keys at the file's top level
    assert {k: data[k] for k in pub} == pub
    assert data["reduced"] == ["num_hidden_layers"] and "share" not in data
    assert len(data["assumed"]) >= 8 and data["deployment"]
    if os.path.exists(CATALOG):
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r.get("name") == "Brumby-14B-Base")
        assert data["source"] == row["source_url"]
        assert pub == {**row["config"], "num_hidden_layers": 8}
    served = modelcfg.model_config(data)
    assert served == ModelConfig.brumby_14b().scaled(name=CONFIG, num_layers=8)
    assert {served.layer_kind(li) for li in range(8)} == {"retention"}
    assert not served.has_pool
    for key, value in (("intermediate_size", 8704), ("head_dim", 64),
                       ("num_key_value_heads", 4), ("vocab_size", 75968)):
        bad = json.loads(json.dumps(data))
        bad["published"][key] = value
        with pytest.raises(ValueError, match=key):
            modelcfg.model_config(bad)
    bad = json.loads(json.dumps(data))
    bad["published"]["num_hidden_layers"] = 3
    with pytest.raises(ValueError, match="whole period"):
        modelcfg.model_config(bad)
    # the served arguments: lanes are a memory decision; the state and the
    # weights are what a deployment's stage would hold
    args = dict(zip(data["serve_args"][::2], data["serve_args"][1::2]))
    assert args["--max-num-seqs"] == "20" and args["--max-model-len"] == "32768"
    state = served.recurrent_state_bytes(21, "bfloat16")
    assert 5.7e9 < state < 5.8e9
    check.compare_kwargs(data)
    lens, steps = data["check"]["prompt_lens"], data["check"]["decode_steps"]
    assert max(lens) + steps > 8192        # a decode row at least 8k deep
    assert max(lens) > 3 * 1024            # a prompt of several quanta


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_reference_draws_the_programs_weights(dtype):
    cfg = ModelConfig.tiny_brumby_test()
    pub = manifest.config(TINY)["published"]
    seed = 7654321
    params = llama.init_params(
        jax.random.PRNGKey(seed), cfg, dtype=jnp.dtype(dtype))
    layer_keys, _ek, _hk = ref.model_keys(seed, cfg.num_layers)
    names = {"wg": "w_gate_r", "bg": "b_gate_r"}
    for li in range(cfg.num_layers):
        mine = ref.layer_weights(layer_keys[li], pub, jnp.dtype(dtype))
        theirs = params["layers"][li]
        norms = {k for k in theirs if k.startswith("ln_")}
        assert sorted(names.get(k, k) for k in mine) == sorted(
            set(theirs) - norms), li
        assert all(bool(jnp.all(theirs[k] == 1)) for k in norms)
        for name in mine:
            np.testing.assert_array_equal(
                np.asarray(mine[name], np.float32),
                np.asarray(theirs[names.get(name, name)], np.float32),
                err_msg=f"{li} {name}")


def test_reference_against_the_program_at_the_tiny_size():
    """The reference's attention form in blocks of rows against the
    program's no-cache oracle, and against its own one-block pass."""
    cfg = ModelConfig.tiny_brumby_test()
    pub = manifest.config(TINY)["published"]
    seed = 99
    params = llama.init_params(jax.random.PRNGKey(seed), cfg, jnp.float32)
    tokens = check.sample_tokens(4, 384, [70, 33], 70)
    rows = np.stack([np.arange(70), np.minimum(np.arange(70), 32)]).astype(
        np.int32)
    want = np.asarray(ref.logits(pub, seed, tokens, rows, "float32"))
    for b, n in enumerate((70, 33)):
        got = np.asarray(llama.reference_forward(
            cfg, params, jnp.asarray(tokens[b])))[rows[b]]
        assert check.row_errors(got, want[b]).max() < 1e-4, b
    block = ref.ROW_BLOCK
    try:
        ref.ROW_BLOCK = 16          # several blocks, a ragged last one
        ref._layer.clear_cache()
        cut = np.asarray(ref.logits(pub, seed, tokens, rows, "float32"))
    finally:
        ref.ROW_BLOCK = block
        ref._layer.clear_cache()
    assert check.row_errors(cut, want).max() < 1e-5


MODEL = dict(num_layers=8, retention_degree=2, num_heads=40, num_kv_heads=8,
             head_dim=128)
ENGINE = dict(dtype_bytes=2)


def test_both_cost_functions_against_hand_counts():
    lanes_cost = registry.load("costs", "retention_recurrent").cost
    chunk = registry.load("costs", "retention_chunk")
    D = 128 * 129 // 2
    assert D == 8256
    state = 8 * D * 129                      # S and z, elements a layer
    lanes = [(9000, 1)] * 20 + [(4096, 1024)]
    flops, nbytes = lanes_cost(lanes, model=MODEL, engine=ENGINE)
    # 2 x 8 x 8,256 x 129 x 4 B a lane a layer, and the row's own vectors
    assert nbytes == 20 * 8 * (2 * state * 4 + (80 + 16) * 128 * 2)
    assert flops == 20 * 8 * (3 + 2 * 5) * state
    assert lanes_cost(lanes, model=dict(MODEL, retention_degree=0),
                      engine=ENGINE) == (0, 0)
    # a quantum behind a prefix: every row reads the state (40 heads) and
    # goes into it (8 heads); within a chunk of 16 the causal pairs
    flops, nbytes = chunk.cost(lanes, model=MODEL, engine=ENGINE)
    pairs = 64 * 16 * 17 // 2
    assert flops == 8 * (
        1024 * 48 * 2 * D * 129 + 4 * pairs * 40 * 128)
    assert nbytes == 8 * (2 * state * 4 + 1024 * 96 * 128 * 2)
    assert 101e6 < flops / 8 / 1024 < 104e6   # the issue's 102 MFLOP a row
    # a span that starts the sequence: as ONE chunk (the attention form)
    # nothing reads a state, and that is the fewest below 8,320 rows
    flops0, nbytes0 = chunk.cost([(0, 1024)], model=MODEL, engine=ENGINE)
    assert flops0 == 8 * (
        1024 * 8 * 2 * D * 129 + 4 * (1024 * 1025 // 2) * 40 * 128)
    assert nbytes0 == 8 * (state * 4 + 1024 * 96 * 128 * 2)
    assert chunk.cost([(5, 1)] * 3, model=MODEL, engine=ENGINE) == (0, 0)
    # the count does not follow a chunk length: it is the least over them
    assert chunk.span_flops(0, 4000, h=40, kvh=8, d=128, D=D) <= min(
        4000 * 8 * 2 * D * 129 + 4 * (4000 * 4001 // 2) * 40 * 128,
        chunk.span_flops(1, 4000, h=40, kvh=8, d=128, D=D))


def test_the_traffic_files_moments():
    spec = traffic.load("longdoc-c20")
    assert (spec["clients"], spec["block"], spec["ramp_s"]) == (20, 20, 24)
    reqs = traffic.requests(spec, 2**31 + 5, 40)
    prompts = sorted(r["prompt_tokens"] for r in reqs[:20])
    outputs = sorted(r["output_tokens"] for r in reqs[:20])
    assert sorted(r["prompt_tokens"] for r in reqs[20:]) == prompts
    assert 4096 <= prompts[0] and prompts[-1] <= 28672
    assert 256 <= outputs[0] and outputs[-1] <= 1024
    assert 12000 < np.mean(prompts) < 13200          # about 12.6k
    assert 520 < np.mean(outputs) < 590              # about 554
    assert all(r["think_s"] == 0.0 for r in reqs)
    # the longest request fits the served context with its template
    args = manifest.config(CONFIG)["serve_args"]
    assert prompts[-1] + outputs[-1] + 256 < int(
        args[args.index("--max-model-len") + 1])


def test_whole_run_of_the_new_family_on_the_cpu():
    """The harness path end to end for a model with no paged cache: one
    SSE chunk a token, nothing compiles in the window, the served step is
    the reference's, and the slots gauge is read."""
    proc = _run(
        "chipbench", "--workload", f"{TINY}.rehearsal", "--seed",
        str(2**31 + 4321), "--seconds", "2", "--trace", "1", "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result, _said, _errors = _last_lines(proc)
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compared"]["rel_err_p100"]["value"] < 1e-3
    # (no device trace on the CPU: the three kernel metrics stay out)
    # (a 2 s window holds a poll or two: the gauge is read, often at 0)
    assert 0 <= result["metrics"]["state.slots_used_peak_pct"]["value"] <= 100
    assert "kernel.retention_roofline" not in result["metrics"]


def test_the_state_control_of_the_new_family_comes_out_not_correct():
    """The state table's ``S`` held in bfloat16 against the tiny
    configuration's float32 limit, through ``chipbench.control_state`` as
    it is (it casts the first array of each layer's state)."""
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


@pytest.mark.parametrize("lens,rows,budget", [
    (None, None, 1024), ((5, 18, 41, 70), 9, 32), ((5, 37, 50), 8, 32),
    ((3,), 3, 16),
])
def test_the_steps_plan_compares_a_full_batch_and_repeats_no_row(
        lens, rows, budget):
    """``steps/retention_span.py``: every sequence's spans in order and
    whole, one span a sequence a dispatch, ``rows`` spans a sequence (no
    row is repeated to fill the rectangle); a decode lane beside every
    prefill quantum from the second prompt on; ONE mixed dispatch with a
    lane of every other sequence beside a quantum and ONE decode dispatch
    with every sequence live (the two whose slots are kept on the host),
    every other dispatch two spans or fewer."""
    from chipbench.steps import retention_span

    block = manifest.config(CONFIG)["check"]
    if lens is None:                       # the cell's own sample
        lens = block["prompt_lens"]
        rows = block["step_params"]["rows"]
        assert block["step_params"]["quantum"] == budget
        args = manifest.config(CONFIG)["serve_args"]
        assert len(lens) == int(args[args.index("--max-num-seqs") + 1])
    plan = retention_span.plan_steps(lens, rows, budget)
    seen: dict[int, int] = {}
    spans_of: dict[int, int] = {}
    for spans in plan:
        assert len({b for b, _, _ in spans}) == len(spans)
        assert sum(n for _, _, n in spans) <= budget
        for b, prefix, n in spans:
            assert seen.get(b, 0) == prefix
            seen[b] = prefix + n
            spans_of[b] = spans_of.get(b, 0) + 1
    assert spans_of == dict.fromkeys(range(len(lens)), rows)
    assert seen == {
        b: retention_span.sample_len(n, 0, rows=rows, quantum=budget)
        for b, n in enumerate(lens)}
    wide = [s for s in plan if len(s) > 2]
    if len(lens) > 2:
        mixed, decode = wide
        assert len(mixed) == len(decode) == len(lens)
        assert {n for _, _, n in decode} == {1}
        assert sorted(n for _, _, n in mixed)[-2:] == [1, budget - len(lens) + 1]
    if len(lens) > 1:
        assert [s for s in plan if len(s) == 2 and {n for _, _, n in s} != {1}]
    assert block["step"] == "retention_span"


def test_a_state_lost_between_two_quanta_is_not_correct():
    """What a state carries from dispatch to dispatch reaches the judged
    rows (the seeded gates remember: ``llama.retention_gate_bias``): the
    longest prompt's slot zeroed before its LAST quantum, in the served run
    and in the logits run, moves that row's logits by far more than any
    rounding and the verdict says not correct."""
    import dataclasses

    from chipbench import control
    from chipbench.steps import retention_span
    from dynamo_tpu.engine.runner import ModelRunner

    data = manifest.config(TINY)
    modelcfg.register(data)
    ecfg = control.engine_config(data)
    kw = check.compare_kwargs(data)
    lens, shape = kw["prompt_lens"], kw["step_params"]
    plan = retention_span.plan_steps(lens, shape["rows"], shape["quantum"])
    last = len(lens) - 1
    at = next(i for i, spans in enumerate(plan) if any(
        b == last and prefix + n == lens[last] for b, prefix, n in spans))
    assert any(b == last and prefix > 0 for b, prefix, _ in plan[at])

    def read(lose: bool) -> dict:
        runner = ModelRunner(dataclasses.replace(ecfg, seed=5), rng_seed=5)
        build, calls = runner._unified_operands, []

        def operands(*args, **kwargs):
            calls.append(1)
            # (a dispatch builds its operands twice: served, then logits)
            if lose and len(calls) in (2 * at + 1, 2 * at + 2):
                runner.rec_state = jax.tree.map(
                    lambda a: a.at[last + 1].set(0), runner.rec_state)
            return build(*args, **kwargs)

        runner._unified_operands = operands
        return check.compare(data, 77, runner, weights_seed=5, **kw)

    sound, lost = read(False), read(True)
    assert not check.judge(sound, data["check"])
    assert check.judge(lost, data["check"])
    assert lost["rel_err"] > 0.05 > 100 * sound["rel_err"], (sound, lost)
