"""Per-matmul weight-quantization policy (docs/architecture/
weight_quant.md): qdot's exact XLA-twin contract per matmul site,
quantize-on-load parity, engine-vs-oracle exactness, TP-sharded token
equality, the REAL-engine greedy quality gate, config validation, the
calibration weight-bytes term, mocker pricing, the BENCH_WQUANT
equal-budget math, and DT011 gauge-surface parity.

The reference reaches quantized serving through its backend engines
(its headline disagg numbers are FP8-70B via vLLM, reference:
docs/architecture/architecture.md:75-79); our engine is native, so the
per-site weight policy is first-class and tested like any other model
path.
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.llm.protocols.common import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.quant import (
    ATTN_KEYS,
    FP8_DTYPE,
    MLP_KEYS,
    dequantize_weight,
    is_quantized,
    qdot,
    quantize_param_specs_policy,
    quantize_params_policy,
    quantize_weight,
    quant_tree_stats,
)
from dynamo_tpu.parallel.mesh import build_mesh
from dynamo_tpu.parallel.sharding import llama_param_specs
from dynamo_tpu.runtime.engine import Context
from stepdrive import reference_greedy, step_token

pytestmark = pytest.mark.anyio

CFG = ModelConfig.tiny_test()
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)

SITES = ("embedding", "attn", "mlp", "unembed")


def _policy(spec: str) -> llama.WeightQuantPolicy:
    return llama.WeightQuantPolicy.from_string(spec)


# ---------------------------------------------------------------------------
# Policy grammar
# ---------------------------------------------------------------------------


def test_policy_parsing_and_describe():
    p = _policy("int8")
    assert [getattr(p, s) for s in SITES] == ["int8"] * 4
    assert p.active
    assert p.describe() == "embedding=int8,attn=int8,mlp=int8,unembed=int8"
    p = _policy("attn=int8,mlp=fp8")
    assert p.embedding is None and p.unembed is None
    assert p.attn == "int8" and p.mlp == "fp8"
    assert p.describe() == "attn=int8,mlp=fp8"
    assert not llama.WeightQuantPolicy().active
    assert llama.WeightQuantPolicy().describe() == "off"
    with pytest.raises(ValueError, match="site"):
        _policy("router=int8")
    with pytest.raises(ValueError, match="format"):
        _policy("attn=int4")


# ---------------------------------------------------------------------------
# qdot: the one arithmetic contract, exact per site
# ---------------------------------------------------------------------------


def test_qdot_exact_contract():
    """qdot on a quantized operand must be BIT-IDENTICAL to its XLA twin
    (x @ q.astype * s, same association) — the parity the unified
    programs rely on to stay byte-stable under the policy — and the
    identity x @ w on a plain operand."""
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 96), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (96, 160), jnp.float32) * 0.2
    qw = quantize_weight(w)
    twin = (x @ qw["q"].astype(x.dtype)) * qw["s"].astype(x.dtype)
    assert jnp.array_equal(qdot(x, qw), twin)
    assert jnp.array_equal(qdot(x, w), x @ w)
    # and under jit (the form every engine program compiles)
    assert jnp.array_equal(jax.jit(qdot)(x, qw), twin)


def test_qdot_reconstruction_close():
    w = jax.random.normal(jax.random.PRNGKey(3), (96, 160), jnp.float32) * 0.2
    qw = quantize_weight(w)
    rel = float(
        jnp.max(jnp.abs(dequantize_weight(qw) - w)) / jnp.max(jnp.abs(w))
    )
    assert rel < 0.01, rel


def test_fp8_weight_roundtrip():
    if FP8_DTYPE is None:
        pytest.skip("no float8_e4m3fn in this jax")
    w = jax.random.normal(jax.random.PRNGKey(4), (64, 96), jnp.float32) * 0.3
    qw = quantize_weight(w, fmt="fp8")
    assert qw["q"].dtype == FP8_DTYPE
    assert qw["s"].shape == (96,)
    rel = float(
        jnp.max(jnp.abs(dequantize_weight(qw) - w)) / jnp.max(jnp.abs(w))
    )
    assert rel < 0.1, rel  # e4m3: 3 mantissa bits
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 64), jnp.float32)
    twin = (x @ qw["q"].astype(x.dtype)) * qw["s"].astype(x.dtype)
    assert jnp.array_equal(qdot(x, qw), twin)


def test_unknown_format_rejected():
    w = jnp.ones((8, 8), jnp.float32)
    with pytest.raises(ValueError):
        quantize_weight(w, fmt="int4")


# ---------------------------------------------------------------------------
# Per-site engine-vs-oracle exactness (kernel parity per matmul site)
# ---------------------------------------------------------------------------


def _oracle_greedy(qparams, prompt: list[int], n: int) -> list[int]:
    """Greedy continuation through the no-cache oracle over the SAME
    quantized tree — the paged unified engine must match it exactly
    (qdot is exact-contract, so site precision cannot drift between
    the oracle and the budget-ladder programs)."""
    return reference_greedy(CFG, qparams, prompt, n, length=128)


async def _collect(engine, prompt, max_tokens=8):
    pre = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )
    tokens = []
    async for raw in engine.generate(Context(pre.to_wire())):
        tokens.extend(EngineOutput.from_wire(raw).token_ids)
    return tokens


@pytest.mark.parametrize(
    "spec", ["embedding=int8", "attn=int8", "mlp=int8", "unembed=int8", "int8"]
)
async def test_unified_engine_matches_policy_oracle(spec):
    """Each site selected ALONE (then all together) through the real
    unified engine: greedy tokens must equal the same-policy no-cache
    oracle exactly — per-matmul parity of the serving kernels."""
    qparams = quantize_params_policy(
        jax.tree.map(jnp.copy, PARAMS), _policy(spec),
        tie_embed=CFG.tie_word_embeddings,
    )
    cfg = EngineConfig(
        model=CFG, dtype="float32", block_size=4, num_blocks=64,
        max_num_seqs=4, max_model_len=128, weight_quant=spec,
        unified_token_budget=64, unified_prefill_quantum=16,
        sampling_extras=False,
    )
    engine = TpuEngine(cfg, params=jax.tree.map(jnp.copy, PARAMS))
    await engine.start()
    try:
        prompt = [1, 5, 9, 2, 7]
        tokens = await _collect(engine, prompt, max_tokens=10)
        assert tokens == _oracle_greedy(qparams, prompt, 10)
    finally:
        await engine.stop()


def test_policy_tree_structure_and_specs_mirror():
    p = _policy("attn=int8,mlp=int8,unembed=int8")
    q = quantize_params_policy(
        jax.tree.map(jnp.copy, PARAMS), p, tie_embed=CFG.tie_word_embeddings
    )
    layer = q["layers"][0]
    for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert is_quantized(layer[k]), k
    assert not is_quantized(q["embed"])       # embedding site off
    assert not is_quantized(layer["ln_attn"])
    assert is_quantized(q["lm_head"])
    # spec tree mirrors the quantized params tree exactly, and the
    # scale spec drops the contracted axis: wq (None, tp) -> s (tp,)
    specs = quantize_param_specs_policy(
        llama_param_specs(CFG), p, tie_embed=CFG.tie_word_embeddings
    )
    jax.tree.map(lambda a, b: None, q, specs)  # raises on mismatch
    assert tuple(specs["layers"][0]["wq"]["s"]) == ("tp",)
    # partial policy: untouched sites keep their plain specs
    p2 = _policy("attn=int8")
    specs2 = quantize_param_specs_policy(
        llama_param_specs(CFG), p2, tie_embed=CFG.tie_word_embeddings
    )
    q2 = quantize_params_policy(
        jax.tree.map(jnp.copy, PARAMS), p2, tie_embed=CFG.tie_word_embeddings
    )
    jax.tree.map(lambda a, b: None, q2, specs2)


def test_site_key_groups_cover_known_matrices():
    assert set(ATTN_KEYS) >= {"wq", "wk", "wv", "wo"}
    assert set(MLP_KEYS) >= {"w_gate", "w_up", "w_down"}
    assert "w_router" not in ATTN_KEYS + MLP_KEYS  # router stays full


def test_tied_embed_policy_quantizes_table_per_row():
    tcfg = ModelConfig.tiny_test().scaled(tie_word_embeddings=True)
    tparams = llama.init_params(jax.random.PRNGKey(5), tcfg, dtype=jnp.float32)
    q = quantize_params_policy(
        jax.tree.map(jnp.copy, tparams), _policy("unembed=int8"),
        tie_embed=True,
    )
    assert is_quantized(q["embed"])
    assert q["embed"]["s"].shape == (tcfg.vocab_size,)
    ref = llama.reference_forward(
        tcfg, tparams, jnp.arange(2, 34, dtype=jnp.int32)
    )
    qref = llama.reference_forward(
        tcfg, q, jnp.arange(2, 34, dtype=jnp.int32)
    )
    cos = float(
        jnp.sum(ref * qref) / (jnp.linalg.norm(ref) * jnp.linalg.norm(qref))
    )
    assert cos > 0.99, cos


def test_sharded_policy_engine_matches_single_chip():
    ecfg = EngineConfig(
        model=CFG, dtype="float32", block_size=16, num_blocks=32,
        max_num_seqs=2, max_model_len=128, weight_quant="int8",
    )
    blocks = [1, 2, 3, 4]
    prompt = list(range(2, 18))
    single = ModelRunner(ecfg)
    tok_single = step_token(single, prompt, blocks)
    mesh = build_mesh({"tp": 2, "dp": 4})
    sharded = ModelRunner(ecfg, mesh=mesh)
    tok_sharded = step_token(sharded, prompt, blocks)
    assert tok_single == tok_sharded


# ---------------------------------------------------------------------------
# Quantize-on-load (HF checkpoint path)
# ---------------------------------------------------------------------------


def _write_hf_checkpoint(tmp_path, cfg, seed=7):
    """A tiny random llama-layout safetensors shard (HF [out, in])."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    h, inter, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim

    def mat(out_dim, in_dim):
        return (rng.standard_normal((out_dim, in_dim)) * 0.05).astype(
            np.float32
        )

    t = {
        "model.embed_tokens.weight": mat(v, h),
        "model.norm.weight": np.ones((h,), np.float32),
        "lm_head.weight": mat(v, h),
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        t[f"{p}.self_attn.q_proj.weight"] = mat(qd, h)
        t[f"{p}.self_attn.k_proj.weight"] = mat(kvd, h)
        t[f"{p}.self_attn.v_proj.weight"] = mat(kvd, h)
        t[f"{p}.self_attn.o_proj.weight"] = mat(h, qd)
        t[f"{p}.input_layernorm.weight"] = np.ones((h,), np.float32)
        t[f"{p}.post_attention_layernorm.weight"] = np.ones((h,), np.float32)
        t[f"{p}.mlp.gate_proj.weight"] = mat(inter, h)
        t[f"{p}.mlp.up_proj.weight"] = mat(inter, h)
        t[f"{p}.mlp.down_proj.weight"] = mat(h, inter)
    save_file(t, str(tmp_path / "model.safetensors"))


def test_load_hf_weights_quantizes_on_load(tmp_path):
    """load_hf_weights(policy=...) must equal quantize-after-load
    EXACTLY (same eager quantize_weight calls on the same arrays) and
    feed a working reference forward — the bf16 tree never needs to
    exist resident for the quantized load to be correct."""
    pytest.importorskip("safetensors")
    _write_hf_checkpoint(tmp_path, CFG)
    p = _policy("int8")
    plain = llama.load_hf_weights(CFG, str(tmp_path), dtype=jnp.float32)
    fused = llama.load_hf_weights(
        CFG, str(tmp_path), dtype=jnp.float32, policy=p
    )
    want = quantize_params_policy(
        plain, p, tie_embed=CFG.tie_word_embeddings
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        fused,
        want,
    )
    toks = jnp.arange(2, 34, dtype=jnp.int32)
    out = llama.reference_forward(CFG, fused, toks)
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# REAL-engine greedy quality gate (int8 weights vs full precision)
# ---------------------------------------------------------------------------


def test_greedy_stream_quality_gate():
    """Greedy token streams on the REAL tiny model: int8 weights must
    match the full-precision stream at >= the threshold rate
    (tier-1-sized: 2 prompts, short OSL)."""
    _greedy_quality(n_prompts=2, osl=10, threshold=0.7)


def _greedy_quality(n_prompts, osl, threshold):
    async def run(weight_quant):
        cfg = EngineConfig(
            model=ModelConfig.tiny_test(), dtype="float32", num_blocks=64,
            max_num_seqs=4, max_model_len=128, prefill_batch=2,
            unified_token_budget=64,
            unified_prefill_quantum=16, sampling_extras=False,
            weight_quant=weight_quant,
        )
        eng = TpuEngine(cfg)
        await eng.start()

        async def one(seed):
            rng = np.random.default_rng(seed)
            req = PreprocessedRequest(
                token_ids=rng.integers(0, 384, 24).tolist(),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl, ignore_eos=True),
            )
            toks = []
            async for out in eng.generate(Context(req.to_wire())):
                toks += out["token_ids"]
            return toks

        streams = await asyncio.gather(*[one(s) for s in range(n_prompts)])
        ready = eng.readiness()
        gauges = {
            k: ready[k]
            for k in (
                "weight_quant_active",
                "weight_quant_bytes_saved",
                "weight_quant_density",
            )
        }
        await eng.stop()
        return streams, gauges

    base, g_b = asyncio.run(run(None))
    quant, g_q = asyncio.run(run("int8"))
    assert g_b["weight_quant_active"] == 0.0
    assert g_b["weight_quant_bytes_saved"] == 0.0
    assert g_q["weight_quant_active"] == 1.0
    assert g_q["weight_quant_bytes_saved"] > 0
    assert 0.9 < g_q["weight_quant_density"] <= 1.0
    match = sum(
        x == y for s1, s2 in zip(base, quant) for x, y in zip(s1, s2)
    )
    total = sum(len(s) for s in base)
    assert total == n_prompts * osl
    rate = match / total
    assert rate >= threshold, (
        f"greedy token-match rate {rate:.2f} below {threshold} "
        f"({match}/{total}) — int8 weights degraded the stream too far"
    )


def test_weight_quant_composes_with_kv_quant():
    """Both quant axes at once through the real engine: a finite greedy
    stream and both gauge families live on readiness."""
    async def run():
        cfg = EngineConfig(
            model=ModelConfig.tiny_test(), dtype="float32", num_blocks=64,
            max_num_seqs=2, max_model_len=128,
            unified_token_budget=64, unified_prefill_quantum=16,
            sampling_extras=False, weight_quant="int8", kv_quant="int8",
        )
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            toks = await _collect(eng, [1, 5, 9, 2, 7], max_tokens=6)
            ready = eng.readiness()
        finally:
            await eng.stop()
        return toks, ready

    toks, ready = asyncio.run(run())
    assert len(toks) == 6
    assert ready["weight_quant_active"] == 1.0
    assert 0.2 < ready["kvbm_kv_quant_ratio"] < 0.3  # int8 KV over f32


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------


def test_weight_quant_config_validation():
    # A bare policy validates...
    EngineConfig(model=CFG, weight_quant="int8").validate()
    EngineConfig(model=CFG, weight_quant="attn=int8,mlp=fp8").validate()
    # ...composes with kv_quant...
    EngineConfig(model=CFG, weight_quant="int8", kv_quant="int8").validate()
    # ...rejects stacking on the legacy whole-tree quant...
    with pytest.raises(ValueError, match="--quant \\+ --weight-quant"):
        EngineConfig(model=CFG, weight_quant="int8", quant="int8").validate()
    # ...and parse errors surface at validate time.
    with pytest.raises(ValueError, match="format"):
        EngineConfig(model=CFG, weight_quant="int4").validate()
    with pytest.raises(ValueError, match="site"):
        EngineConfig(model=CFG, weight_quant="router=int8").validate()


def test_kv_quant_conflict_messages_name_flag_pairs():
    with pytest.raises(ValueError, match="--kv-quant \\+ --kv-sp"):
        EngineConfig(
            model=CFG, kv_quant="int8", kv_sp=True,
            mesh_shape={"tp": 1, "sp": 2},
        ).validate()


# ---------------------------------------------------------------------------
# Calibration: the weight-bytes term and its artifact contract
# ---------------------------------------------------------------------------


def test_weight_bytes_per_step_rederives_from_artifact():
    """WEIGHT_BYTES_PER_STEP is the r04 decode step priced at the r04
    bandwidth — and the standalone-prefill dispatch base must ROUND-TRIP
    through it exactly (bytes / rate = the measured flat base), so the
    two pricing laws can never drift apart (same contract as the PR 10
    decode constants)."""
    from dynamo_tpu.planner import calibration as cal

    rec = cal.recorded_r04()
    # The artifact's two-point fit (test_xpyd re-derives the published
    # constant the same way): base = b32 step minus 32 lane slopes.
    per_lane_us = (
        (rec["decode_step_ms"] - rec["decode_step_ms_b32"]) * 1000.0 / 32.0
    )
    base_us = rec["decode_step_ms_b32"] * 1000.0 - 32.0 * per_lane_us
    want = base_us * 1e-6 * rec["effective_hbm_gbps"] * 1e9
    assert cal.WEIGHT_BYTES_PER_STEP == pytest.approx(want, rel=0.02)
    assert cal.DECODE_HBM_GBPS == rec["effective_hbm_gbps"]
    # Exact closed forms over the published symbols: the bytes term IS
    # base·rate, and the standalone-prefill base round-trips through it
    # to the SAME flat microseconds — the two pricing laws cannot drift.
    assert cal.WEIGHT_BYTES_PER_STEP == (
        cal.DECODE_TIME_PER_STEP_US * 1e-6 * cal.DECODE_HBM_GBPS * 1e9
    )
    assert (
        cal.PREFILL_DISPATCH_BASE_US
        == cal.WEIGHT_BYTES_PER_STEP / (cal.DECODE_HBM_GBPS * 1e9) * 1e6
        == cal.DECODE_TIME_PER_STEP_US
    )


def test_weight_quant_bytes_ratio_math():
    from dynamo_tpu.planner import calibration as cal

    # int8 data + one f32 scale per output channel over bf16 rows.
    assert cal.weight_quant_bytes_ratio(2048, 2) == (2048 + 4) / 4096
    assert 0.5 < cal.weight_quant_bytes_ratio() < 0.51
    assert cal.weight_bytes_per_step(None) == cal.WEIGHT_BYTES_PER_STEP
    assert (
        cal.weight_bytes_per_step("int8")
        == cal.WEIGHT_BYTES_PER_STEP * cal.weight_quant_bytes_ratio()
    )


def test_mocker_weight_pass_pricing():
    """_weight_pass_us REPLACES the flat base with bytes/rate when both
    terms are armed, scales with the ratio, falls back to base*ratio
    when the bandwidth term is off, and is the identity at defaults —
    every pre-existing scenario stays byte-identical."""
    from dynamo_tpu.mocker.engine import MockerConfig, _SimRunner

    cfg = EngineConfig(model=CFG)
    sim = _SimRunner(cfg, MockerConfig())
    assert sim._weight_pass_us(123.0) == 123.0  # defaults: identity
    sim.sim = MockerConfig(
        weight_bytes_per_step=2e9, decode_hbm_gbps=100.0,
        weight_bytes_ratio=1.0,
    )
    assert abs(sim._weight_pass_us(123.0) - 2e9 / (100e9) * 1e6) < 1e-9
    sim.sim = MockerConfig(
        weight_bytes_per_step=2e9, decode_hbm_gbps=100.0,
        weight_bytes_ratio=0.5,
    )
    assert abs(sim._weight_pass_us(123.0) - 1e9 / (100e9) * 1e6) < 1e-9
    sim.sim = MockerConfig(weight_bytes_ratio=0.5)  # no bandwidth term
    assert sim._weight_pass_us(100.0) == 50.0


def test_calibrated_mocker_config_weight_term_is_inert():
    """calibrated_mocker_config arms weight_bytes_per_step but NOT the
    bandwidth term — the xPyD calibration gate's pricing must stay the
    recorded flat base."""
    from dynamo_tpu.mocker.engine import _SimRunner
    from dynamo_tpu.planner import calibration as cal

    sim_cfg = cal.calibrated_mocker_config()
    assert sim_cfg.weight_bytes_per_step == cal.WEIGHT_BYTES_PER_STEP
    assert sim_cfg.decode_hbm_gbps == 0.0
    sim = _SimRunner(EngineConfig(model=CFG), sim_cfg)
    assert (
        sim._weight_pass_us(cal.DECODE_TIME_PER_STEP_US)
        == cal.DECODE_TIME_PER_STEP_US
    )


def test_simulate_prices_weight_quant():
    """SimConfig.weight_quant scales the decode step's weight pass by
    the calibration ratio (and only that term)."""
    from dynamo_tpu.planner import calibration as cal
    from dynamo_tpu.planner.simulate import SimConfig

    base = SimConfig()
    q = SimConfig(weight_quant="int8")
    lanes = 16
    m = base.mocker
    full = base.decode_step_cost_s(lanes)
    packed = q.decode_step_cost_s(lanes)
    ratio = cal.weight_quant_bytes_ratio()
    shared = (
        base.host_overhead_us + m.decode_time_per_lane_us * lanes
    ) / 1e6
    assert abs(
        (packed - shared) / (full - shared) - ratio
    ) < 1e-9
    # standalone prefill's weight-pass base scales the same way
    pf = base.prefill_batch_cost_s([512])
    pq = q.prefill_batch_cost_s([512])
    assert pf > pq
    assert abs(
        (pf - pq) - m.prefill_dispatch_base_us * (1 - ratio) / 1e6
    ) < 1e-9


def test_wquant_equal_budget_math():
    """The BENCH_WQUANT lane law: freed weight bytes convert to KV
    blocks; lanes scale with blocks but never oversubscribe them."""
    import bench
    from dynamo_tpu.planner import calibration as cal

    wratio = cal.weight_quant_bytes_ratio()
    blocks, lanes = bench.wquant_equal_budget(
        3328, 24, wratio, tokens_per_lane=2048 + 150
    )
    kv_block_bytes = cal.KV_BYTES_PER_TOKEN * 16
    freed = cal.WEIGHT_BYTES_PER_STEP * (1 - wratio)
    assert blocks == 3328 + int(freed // kv_block_bytes)
    per_lane = -(-(2048 + 150) // 16)  # ceil
    assert lanes * per_lane <= blocks
    assert lanes > 24  # the freed HBM actually buys lanes
    # identity leg: ratio 1.0 changes nothing
    b1, l1 = bench.wquant_equal_budget(3328, 24, 1.0, tokens_per_lane=2198)
    assert (b1, l1) == (3328, 24)


# ---------------------------------------------------------------------------
# Gauges: tree stats + DT011 surfaces
# ---------------------------------------------------------------------------


def test_quant_tree_stats_counts_bytes():
    p = _policy("int8")
    q = quantize_params_policy(
        jax.tree.map(jnp.copy, PARAMS), p, tie_embed=CFG.tie_word_embeddings
    )
    saved, density = quant_tree_stats(q, dtype_bytes=4)  # f32 tree
    # int8 + f32 row vs f32: saves just under 3/4 of covered bytes
    assert saved > 0
    assert 0.9 < density <= 1.0
    s0, d0 = quant_tree_stats(PARAMS, dtype_bytes=4)
    assert (s0, d0) == (0.0, 0.0)


def test_weight_quant_gauges_on_wire_and_exporter_surfaces():
    """The weight_quant_* gauges survive the ForwardPassMetrics wire
    roundtrip and are registered on the standalone exporter (DT011's
    dynamic complement)."""
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.llm.metrics_exporter import _GAUGES

    names = {n for n, _ in _GAUGES}
    for g in (
        "weight_quant_active",
        "weight_quant_bytes_saved",
        "weight_quant_density",
    ):
        assert g in names
        assert hasattr(ForwardPassMetrics(), g)
    m = ForwardPassMetrics.from_wire(
        {"weight_quant_active": 1.0, "weight_quant_bytes_saved": 42.0}
    )
    assert m.weight_quant_active == 1.0
    assert m.weight_quant_bytes_saved == 42.0


def test_mocker_exposes_weight_quant_gauges():
    from dynamo_tpu.mocker.engine import MockerConfig, _SimRunner

    cfg = EngineConfig(model=CFG, weight_quant="int8")
    sim = _SimRunner(
        cfg,
        MockerConfig(weight_bytes_per_step=2e9, weight_bytes_ratio=0.5),
    )
    assert sim.weight_quant_density == 1.0
    assert sim.weight_quant_bytes_saved == 1e9
    sim_off = _SimRunner(EngineConfig(model=CFG), MockerConfig())
    assert sim_off.weight_quant_density == 0.0
    assert sim_off.weight_quant_bytes_saved == 0.0
