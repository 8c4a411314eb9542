"""The paged decode kernel's numerics (kv_sp's striped scan): the
interpret-mode kernel vs the jnp oracle (ops/attention.py) over ragged
batches, GQA, idle lanes. The ragged kernel, which serves every span, is
held to its oracles in test_ragged_attention.py. The same kernels compile
under Mosaic on real TPU; interpret mode runs the identical kernel code
path on the CPU backend."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.attention import paged_decode_attention
from dynamo_tpu.ops.pallas import paged_decode_attention_pallas

BS = 16  # block size


def _caches(rng, num_blocks, kvH, D, dtype=jnp.float32):
    shape = (num_blocks * BS, kvH, D)
    k = jnp.asarray(rng.standard_normal(shape), dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype)
    return k, v


def _tables(rng, B, max_blocks, num_blocks):
    """Disjoint block tables (block 0 is the trash block, never used)."""
    ids = rng.permutation(np.arange(1, num_blocks))[: B * max_blocks]
    return jnp.asarray(ids.reshape(B, max_blocks), jnp.int32)


@pytest.mark.parametrize("H,kvH,D", [(8, 8, 64), (8, 2, 64), (4, 1, 128)])
def test_decode_kernel_matches_oracle(H, kvH, D):
    rng = np.random.default_rng(0)
    B, max_blocks, num_blocks = 5, 4, 64
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k_cache, v_cache = _caches(rng, num_blocks, kvH, D)
    tables = _tables(rng, B, max_blocks, num_blocks)
    # Ragged: full blocks, partial block, single token, inactive slot.
    ctx = jnp.asarray([64, 37, 1, 16, 0], jnp.int32)

    want = paged_decode_attention(q, k_cache, v_cache, tables, ctx, BS)
    got = paged_decode_attention_pallas(q, k_cache, v_cache, tables, ctx, BS)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[-1]).any()  # inactive slot stays zero


def test_decode_kernel_bf16():
    rng = np.random.default_rng(1)
    B, H, kvH, D, max_blocks, num_blocks = 3, 8, 4, 64, 3, 32
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    k_cache, v_cache = _caches(rng, num_blocks, kvH, D, jnp.bfloat16)
    tables = _tables(rng, B, max_blocks, num_blocks)
    ctx = jnp.asarray([48, 20, 5], jnp.int32)

    want = paged_decode_attention(q, k_cache, v_cache, tables, ctx, BS)
    got = paged_decode_attention_pallas(q, k_cache, v_cache, tables, ctx, BS)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), rtol=2e-2, atol=2e-2
    )


@pytest.mark.anyio
async def test_engine_end_to_end_pallas_interpret(monkeypatch):
    """Full engine (scheduler → padded cache → Pallas interpret kernels)
    must match the no-cache greedy oracle — covers the lane-padding path
    (tiny model D=32 → cache 128) exactly as the TPU runs it."""
    import asyncio

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.protocols.common import (
        EngineOutput,
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.engine import Context
    from stepdrive import reference_greedy

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    cfg = ModelConfig.tiny_test()
    params = llama.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    engine = TpuEngine(
        EngineConfig(
            model=cfg, dtype="float32", block_size=8, num_blocks=32,
            max_num_seqs=2, max_model_len=64,
        ),
        params=params,
    )
    await engine.start()
    try:
        assert engine.runner.cache_head_dim == 128  # padded for the kernel
        prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5], [2, 7, 1]]

        async def run(prompt):
            req = PreprocessedRequest(
                token_ids=prompt,
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=5, ignore_eos=True),
            )
            toks = []
            async for raw in engine.generate(Context(req.to_wire())):
                toks += EngineOutput.from_wire(raw).token_ids
            return toks

        results = await asyncio.gather(*[run(p) for p in prompts])
        for prompt, toks in zip(prompts, results):
            assert toks == reference_greedy(
                cfg, params, prompt, 5, length=64
            ), prompt
    finally:
        await engine.stop()


def test_kernels_sliding_window_matches_oracle():
    """The window-masked decode kernel vs the jnp reference."""
    rng = np.random.default_rng(9)
    B, H, kvH, D, max_blocks, num_blocks, W = 3, 8, 2, 128, 4, 64, 10
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k_cache, v_cache = _caches(rng, num_blocks, kvH, D)
    tables = _tables(rng, B, max_blocks, num_blocks)
    ctx = jnp.asarray([64, 23, 0], jnp.int32)

    want = paged_decode_attention(
        q, k_cache, v_cache, tables, ctx, BS, window=W
    )
    got = paged_decode_attention_pallas(
        q, k_cache, v_cache, tables, ctx, BS, window=W
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # And the window changed the answer vs full attention.
    full = paged_decode_attention_pallas(
        q, k_cache, v_cache, tables, ctx, BS
    )
    assert np.abs(np.asarray(got[0]) - np.asarray(full[0])).max() > 1e-4
