"""The block-diffusion family in the benchmark: its reference draws the
program's weights, its step driver holds the served block step to the
reference through prefill in chunks, commit passes and a denoising pass
with planted masks, and the whole harness path runs on the CPU."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, control, manifest, modelcfg, registry
from chipbench.reference import sdar as ref
from chipbench.steps import sdar_block
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from test_chipbench_run import _last_lines, _run

CONFIG = "tiny-sdar-rehearsal"
CELL = "sdar-30b-a3b-l7.chat-c64"


def test_manifest_holds_the_new_cell_and_nothing_is_inconsistent():
    assert manifest.check() == []
    bench = manifest.benchmark_json()
    assert [c["name"] for c in bench["configs"]][-1] == "sdar-30b-a3b-l7"
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        CELL, "chat-c64", 1)
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == [
        "diffusion.tokens_per_lane_pass", "diffusion.commit_pass_pct",
        "kernel.moe_grouped_step_pct", "kernel.moe_grouped_roofline",
    ]
    assert all(m["moves"] == "out_tok_s_chip" for m in new)


def test_published_widths_are_the_catalogs_and_the_presets():
    """Every key of the source's config under its own name, only the
    depth cut, and ``modelcfg`` holds the rest to the program's preset."""
    data = manifest.config("sdar-30b-a3b-l7")
    pub = data["published"]
    assert data["reduced"] == ["num_hidden_layers"]
    # the driver reads the source's keys at the file's top level
    assert {k: data[k] for k in pub} == pub
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["num_experts_per_tok"], pub["moe_intermediate_size"],
            pub["hidden_size"], pub["vocab_size"], pub["head_dim"]) == (
        7, 128, 8, 768, 2048, 151936, 128)
    served = modelcfg.model_config(data)
    preset = ModelConfig.sdar_30b_a3b()
    assert served == preset.scaled(name=data["name"], num_layers=7)
    assert served.diffusion_block_length == 4
    assert data["check"]["step_params"] == {
        "block_length": served.diffusion_block_length,
        "mask_token_id": served.mask_token_id,
    }
    bad = json.loads(json.dumps(data))
    bad["published"]["moe_intermediate_size"] = 512
    with pytest.raises(ValueError, match="moe_intermediate_size"):
        modelcfg.model_config(bad)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_reference_draws_the_programs_weights(dtype):
    cfg = ModelConfig.tiny_sdar_test()
    pub = manifest.config(CONFIG)["published"]
    seed = 7654321
    params = llama.init_params(
        jax.random.PRNGKey(seed), cfg, dtype=jnp.dtype(dtype))
    layer_keys, _ek, _hk = ref.model_keys(seed, cfg.num_layers)
    for li in range(cfg.num_layers):
        mine = ref.layer_weights(layer_keys[li], pub, jnp.dtype(dtype))
        theirs = params["layers"][li]
        # the norms (layer and per-head) are ones on both sides
        norms = {k for k in theirs if k.startswith("ln_")}
        assert sorted(mine) == sorted(set(theirs) - norms)
        assert all(bool(jnp.all(theirs[k] == 1)) for k in norms)
        assert {"ln_q_head", "ln_k_head"} <= norms
        for name in mine:
            np.testing.assert_array_equal(
                np.asarray(mine[name], np.float32),
                np.asarray(theirs[name], np.float32), err_msg=name)


def build_runner(seed, **changes):
    from dynamo_tpu.engine.runner import ModelRunner

    data = manifest.config(CONFIG)
    modelcfg.register(data)
    ecfg = dataclasses.replace(
        control.engine_config(data), seed=seed, **changes)
    return data, ModelRunner(ecfg, rng_seed=seed)


@pytest.mark.parametrize("pallas", ["0", "1"])
@pytest.mark.parametrize("seed", [5, 2**31 + 99])
def test_the_served_block_step_is_the_references(seed, pallas, monkeypatch):
    """Prefill in chunks on block boundaries, the commit passes of the
    blocks behind the prompt and the last block's denoising pass with 1
    to 4 planted masks, through the runner's cache on both attention
    paths: float32 against the reference's one full pass."""
    monkeypatch.setenv("DYNAMO_TPU_PALLAS", pallas)
    weights_seed = seed % (2**31 - 1)
    data, runner = build_runner(weights_seed)
    assert runner.attention_path == ("pallas" if pallas == "1" else "xla")
    kwargs = check.compare_kwargs(data)
    kwargs.update(decode_steps=3, pad_to=56)
    out = check.compare(data, seed, runner, weights_seed=weights_seed,
                        **kwargs)
    assert out["rel_err"] < 1e-5, out
    assert out["rel_err_by_phase"]["prefill"] < 1e-5
    assert out["rel_err_by_phase"]["decode"] < 1e-5
    assert out["token_rows"] >= len(data["check"]["prompt_lens"]) - 1
    assert out["token_mismatches_all_rows"] == 0


def test_driver_plants_masks_in_place_and_judges_only_committed_rows():
    seed = 11
    data, runner = build_runner(seed)
    params = data["check"]["step_params"]
    B, mask = params["block_length"], params["mask_token_id"]
    lens = (5, 8, 18, 41)
    counts = [sdar_block.sample_len(n, 2, **params) for n in lens]
    assert counts == [4 + 8, 8 + 8, 16 + 8, 40 + 8]
    sample = check.sample_tokens(seed, 384, counts, 48)
    sample[sample == mask] = 1
    before = sample.copy()
    out = sdar_block.drive(runner, sample, lens, 2, seed, **params)
    planted = (sample == mask) & (before != mask)
    for b, n in enumerate(lens):
        last = counts[b] - B
        assert 1 <= planted[b, last:counts[b]].sum() <= B
        assert not planted[b, :last].any()
    # judged: only rows fed as masks that the program committed; at the
    # default threshold that is the floor, one row a sequence
    judged = out["judged"]
    assert judged.sum(axis=1).min() >= 1
    rows_judged = out["rows"][judged]
    seqs = np.nonzero(judged)[0]
    assert all(planted[b, r] for b, r in zip(seqs, rows_judged))
    assert (out["served"][judged] >= 0).all()
    # prefill rows: the prompt's whole blocks only; decode rows behind
    for b, n in enumerate(lens):
        whole = n - n % B
        assert (out["rows"][b][~out["decode"][b]] < whole).all()
        assert (out["rows"][b][out["decode"][b]] >= whole).all()


def test_plan_prefill_chunks_on_block_boundaries():
    steps = sdar_block.plan_prefill((5, 41, 18, 3), 4, budget=24, lanes=2)
    flat = [s for step in steps for s in step]
    assert all(n % 4 == 0 and p % 4 == 0 for _, p, n in flat)
    assert all(sum(n for *_, n in step) <= 24 and len(step) <= 2
               for step in steps)
    fed = {}
    for b, p, n in flat:
        assert fed.get(b, 0) == p       # in order, nothing skipped
        fed[b] = p + n
    assert fed == {0: 4, 1: 40, 2: 16}  # 3 tokens hold no whole block


def test_grouped_ffn_cost_and_the_ratio_reader():
    cost = registry.load("costs", "moe_grouped_ffn").cost
    model = {"num_layers": 7, "hidden_size": 2048,
             "moe_intermediate_size": 768, "intermediate_size": 6144,
             "num_experts": 128, "num_experts_per_tok": 8,
             "first_k_dense_replace": 0}
    engine = {"tp": 1, "dtype_bytes": 2}
    flops, nbytes = cost([(100, 4)] * 64, model=model, engine=engine)
    assert flops == 256 * 8 * 6 * 2048 * 768 * 7
    weights = 128 * 3 * 2048 * 768 * 2
    assert nbytes == (weights + 256 * 8 * 2048 * 6) * 7
    # a dispatch of one row touches 8 experts; a dense model costs nothing
    _, small = cost([(0, 1)], model=model, engine=engine)
    assert small == (8 * 3 * 2048 * 768 * 2 + 8 * 2048 * 6) * 7
    assert cost([(0, 4)], model=dict(model, num_experts=0),
                engine=engine) == (0, 0)
    # the program's own count of the experts that had a row, summed over
    # the layers, takes the place of the most the rows could touch
    _, counted = cost([(0, 256)], model=model, engine=engine,
                      experts_hit=7 * 70)
    assert counted == 7 * 70 * 3 * 2048 * 768 * 2 + 256 * 8 * 2048 * 6 * 7
    roofline = registry.load("readers", "flight_kernel_roofline").read

    class Traced:
        trace = {"op_seconds": {"gmm": 0.004, "gmm.1": 0.004, "fusion": 1.0},
                 "host_window": (10.0, 13.0)}
        unix_minus_mono = 1000.0
        device_kind = "TPU v5 lite"
        flight = [
            {"dispatch_ms": 1, "t_unix": 1011.0, "decode_tokens": 256,
             "prefill_tokens": 0, "moe_experts_hit": 7 * 70},
            {"dispatch_ms": 1, "t_unix": 1014.0, "decode_tokens": 256,
             "moe_experts_hit": 7 * 70},               # behind the trace
            {"dispatch_ms": 1, "t_unix": 1012.0, "decode_tokens": 4},
        ]

    Traced.model, Traced.engine = model, engine
    params = dict(kernel="gmm", cost="moe_grouped_ffn",
                  counted="moe_experts_hit", keyword="experts_hit")
    share = roofline(Traced, **params)
    assert share == pytest.approx(100 * (counted / 819e9) / 0.008, rel=1e-3)
    Traced.flight = Traced.flight[2:]     # a program that counts nothing
    assert roofline(Traced, **params) is None
    read = registry.load("readers", "flight_ratio").read

    class Obs:
        flight = [
            {"dispatch_ms": 1, "committed_tokens": 3, "diffusion_lanes": 4},
            {"dispatch_ms": 1, "committed_tokens": 1, "diffusion_lanes": 1},
            {"kind": "fault"},
        ]

    assert read(Obs, over=["committed_tokens"], under=["diffusion_lanes"]
                ) == 0.8
    # a program without the fields (the parent) reads nothing
    Obs.flight = [{"dispatch_ms": 1, "decode_tokens": 5}]
    assert read(Obs, over=["commit_rows"],
                under=["denoise_rows", "commit_rows"], scale=100.0) is None


def test_whole_run_of_the_new_family_on_the_cpu():
    """The harness path of the new family end to end: the server streams
    one SSE chunk a token and ``usage.completion_tokens`` equals
    ``max_tokens`` for every request (lengths that are no multiple of 4
    are cut at delivery), nothing compiles in the window, the block step
    is the reference's, and the flight recorder's diffusion counters
    reach the result line."""
    proc = _run(
        "chipbench", "--workload", f"{CONFIG}.rehearsal", "--seed",
        str(2**31 + 4321), "--seconds", "3", "--trace", "1", "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result, _said, _errors = _last_lines(proc)
    assert result["correct"] is True, proc.stdout[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    per_pass = result["metrics"]["diffusion.tokens_per_lane_pass"]["value"]
    assert 0.5 < per_pass <= 1.0
    commit = result["metrics"]["diffusion.commit_pass_pct"]["value"]
    assert 5.0 < commit <= 25.0
    # no device on the CPU: the kernel's share and roofline read nothing
    assert "kernel.moe_grouped_roofline" not in result["metrics"]


def test_the_control_of_the_new_family_comes_out_not_correct():
    proc = _run(
        "chipbench.control", "--config", CONFIG, "--seeds", "2",
        "--control-seeds", "2", "--allow-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    limit = last["limits_in_file"]["limit"]
    assert last["sound_max"]["rel_err"] < limit / 3
    assert last["sound_not_correct"] == 0
    for path in ("int8_weights", "int8_kv"):
        assert last["control_min"][path]["rel_err"] > 3 * limit, last
        assert last["control_correct"][path] == 0, last
