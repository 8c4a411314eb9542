"""int8 weight-only quantization (ops/quant.py): reconstruction accuracy,
paged-engine-vs-oracle exactness under quant, sharded/single-chip token
equality, and spec-tree mirroring.

The reference reaches quantized serving through its backend engines (its
headline disagg numbers are FP8-70B via vLLM, reference:
docs/architecture/architecture.md:75-79); our engine is native, so the
quantized path is first-class and tested like any other model path.
"""

import asyncio

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
    dequantize_weight,
    is_quantized,
    qmm,
    quantize_param_specs,
    quantize_params,
    quantize_weight,
)
from dynamo_tpu.parallel.mesh import build_mesh
from dynamo_tpu.parallel.sharding import llama_param_specs
from dynamo_tpu.runtime.engine import Context
from stepdrive import reference_greedy, step_token

pytestmark = pytest.mark.anyio

CFG = ModelConfig.tiny_test()
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
QPARAMS = jax.jit(quantize_params)(PARAMS)


def test_weight_roundtrip_error_small():
    w = jax.random.normal(jax.random.PRNGKey(1), (96, 160), jnp.float32) * 0.2
    qw = quantize_weight(w)
    assert qw["q"].dtype == jnp.int8
    assert qw["s"].shape == (160,)
    rel = float(
        jnp.max(jnp.abs(dequantize_weight(qw) - w)) / jnp.max(jnp.abs(w))
    )
    assert rel < 0.01, rel
    # qmm agrees with the dequantized matmul
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 96), jnp.float32)
    got = qmm(x, qw)
    want = x @ dequantize_weight(qw)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-3


def test_quantized_logits_close_to_fp():
    toks = jnp.arange(2, 34, dtype=jnp.int32)
    ref = llama.reference_forward(CFG, PARAMS, toks)
    qref = llama.reference_forward(CFG, QPARAMS, toks)
    cos = float(
        jnp.sum(ref * qref) / (jnp.linalg.norm(ref) * jnp.linalg.norm(qref))
    )
    assert cos > 0.995, cos


def test_quantize_params_structure_and_specs_mirror():
    layer = QPARAMS["layers"][0]
    for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert is_quantized(layer[k]), k
    assert not is_quantized(QPARAMS["embed"])
    assert not is_quantized(layer["ln_attn"])
    assert is_quantized(QPARAMS["lm_head"])
    # spec tree mirrors the quantized params tree exactly
    specs = quantize_param_specs(llama_param_specs(CFG))
    jax.tree.map(lambda p, s: None, QPARAMS, specs)  # raises on mismatch
    # s-spec drops the contraction axis: wq (None, tp) -> s (tp,)
    assert tuple(specs["layers"][0]["wq"]["s"]) == ("tp",)
    assert tuple(specs["layers"][0]["wo"]["s"]) in ((), (None,))  # replicated


def oracle_greedy_quant(prompt: list[int], n: int) -> list[int]:
    """Greedy continuation through the QUANTIZED no-cache oracle — the
    paged int8 engine must match it exactly (same math, fp32 accum)."""
    return reference_greedy(CFG, QPARAMS, prompt, n, length=128)


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


async def test_quantized_engine_matches_quantized_oracle():
    cfg = EngineConfig(
        model=CFG, dtype="float32", block_size=4, num_blocks=64,
        max_num_seqs=4, max_model_len=128, quant="int8",
    )
    engine = TpuEngine(cfg, params=PARAMS)
    await engine.start()
    try:
        prompt = [1, 5, 9, 2, 7]
        tokens = await _collect(engine, prompt, max_tokens=10)
        assert tokens == oracle_greedy_quant(prompt, 10)
    finally:
        await engine.stop()


def test_sharded_quantized_prefill_matches_single_chip():
    ecfg = EngineConfig(
        model=CFG, dtype="float32", block_size=16, num_blocks=32,
        max_num_seqs=2, max_model_len=128, quant="int8",
    )
    blocks = [1, 2, 3, 4]
    prompt = list(range(2, 18))
    single = ModelRunner(ecfg)
    tok_single = step_token(single, prompt, blocks)
    mesh = build_mesh({"tp": 2, "dp": 4})
    sharded = ModelRunner(ecfg, mesh=mesh)
    tok_sharded = step_token(sharded, prompt, blocks)
    assert tok_single == tok_sharded


def test_quantized_moe_forward_finite():
    mcfg = ModelConfig.tiny_moe_test()
    mparams = llama.init_params(jax.random.PRNGKey(3), mcfg, dtype=jnp.float32)
    mq = jax.jit(quantize_params)(mparams)
    out = llama.reference_forward(mcfg, mq, jnp.arange(2, 18, dtype=jnp.int32))
    assert bool(jnp.all(jnp.isfinite(out)))
    ref = llama.reference_forward(mcfg, mparams, jnp.arange(2, 18, dtype=jnp.int32))
    cos = float(
        jnp.sum(ref * out) / (jnp.linalg.norm(ref) * jnp.linalg.norm(out))
    )
    assert cos > 0.99, cos


def test_tied_embed_quantization_roundtrip():
    """tie_word_embeddings models quantize the embed table per-row so the
    tied lm_head matmul streams int8 (ops/quant.py tied_head_mm); greedy
    tokens still match the same-quantized oracle exactly."""
    tcfg = ModelConfig.tiny_test().scaled(tie_word_embeddings=True)
    tparams = llama.init_params(jax.random.PRNGKey(5), tcfg, dtype=jnp.float32)
    from functools import partial

    tq = jax.jit(partial(quantize_params, tie_embed=True))(tparams)
    assert is_quantized(tq["embed"])
    assert tq["embed"]["s"].shape == (tcfg.vocab_size,)
    ref = llama.reference_forward(tcfg, tparams, jnp.arange(2, 34, dtype=jnp.int32))
    qref = llama.reference_forward(tcfg, tq, jnp.arange(2, 34, dtype=jnp.int32))
    cos = float(
        jnp.sum(ref * qref) / (jnp.linalg.norm(ref) * jnp.linalg.norm(qref))
    )
    assert cos > 0.99, cos

    # sharded (tp over the embed feature dim) matches single-chip
    ecfg = EngineConfig(
        model=tcfg, dtype="float32", block_size=16, num_blocks=32,
        max_num_seqs=2, max_model_len=128, quant="int8",
    )
    prompt = list(range(2, 18))
    tok_single = step_token(
        ModelRunner(ecfg, params=tparams), prompt, [1, 2, 3, 4]
    )
    mesh = build_mesh({"tp": 2, "dp": 4})
    tok_sharded = step_token(
        ModelRunner(ecfg, params=tparams, mesh=mesh), prompt, [1, 2, 3, 4]
    )
    assert tok_single == tok_sharded


def test_engine_config_rejects_unknown_quant():
    cfg = EngineConfig(model=CFG, quant="fp4")
    with pytest.raises(ValueError):
        cfg.validate()
