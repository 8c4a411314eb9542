"""JAX engine tests: paged-attention correctness against a no-cache oracle,
continuous batching, prefix caching, stop handling.

All on the CPU backend with fp32 so greedy decoding is exactly reproducible.
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.kv_cache import BlockAllocator
from dynamo_tpu.llm.protocols.common import (
    EngineOutput,
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context
from stepdrive import reference_greedy

pytestmark = pytest.mark.anyio

CFG = ModelConfig.tiny_test()
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)


def oracle_greedy(prompt: list[int], n: int) -> list[int]:
    """Full-recompute greedy continuation — the correctness reference."""
    return reference_greedy(CFG, PARAMS, prompt, n, length=128)


def engine_config(**kw) -> EngineConfig:
    defaults = dict(
        model=CFG,
        dtype="float32",
        block_size=4,
        num_blocks=64,
        max_num_seqs=4,
        max_model_len=128,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


async def collect(engine, prompt, max_tokens=8, **stop_kw):
    pre = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True, **stop_kw),
    )
    tokens, finish = [], None
    async for raw in engine.generate(Context(pre.to_wire())):
        out = EngineOutput.from_wire(raw)
        tokens.extend(out.token_ids)
        if out.finish_reason:
            finish = out.finish_reason
    return tokens, finish


async def test_engine_matches_oracle():
    engine = TpuEngine(engine_config(), params=PARAMS)
    await engine.start()
    try:
        prompt = [1, 5, 9, 2, 7]  # crosses a block boundary (bs=4)
        tokens, finish = await collect(engine, prompt, max_tokens=10)
        assert tokens == oracle_greedy(prompt, 10)
        assert finish is FinishReason.LENGTH
    finally:
        await engine.stop()


async def test_concurrent_requests_batch_correctly():
    engine = TpuEngine(engine_config(), params=PARAMS)
    await engine.start()
    try:
        prompts = [[3, 1, 4, 1, 5], [2, 7, 1, 8], [9, 9, 8, 2, 6, 5, 3]]
        results = await asyncio.gather(
            *[collect(engine, p, max_tokens=6) for p in prompts]
        )
        for prompt, (tokens, _) in zip(prompts, results):
            assert tokens == oracle_greedy(prompt, 6), prompt
    finally:
        await engine.stop()


async def test_prefix_cache_reuse_is_exact():
    engine = TpuEngine(engine_config(), params=PARAMS)
    await engine.start()
    try:
        prompt = list(range(1, 18))  # 17 tokens = 4 full blocks + tail
        first, _ = await collect(engine, prompt, max_tokens=5)
        assert engine.prefix_hit_rate == 0.0
        second, _ = await collect(engine, prompt, max_tokens=5)
        assert second == first == oracle_greedy(prompt, 5)
        assert engine.prefix_hit_rate == 0.5  # 1 hit / 2 lookups
    finally:
        await engine.stop()


async def test_stop_token_and_max_tokens():
    engine = TpuEngine(engine_config(), params=PARAMS)
    await engine.start()
    try:
        prompt = [1, 2, 3]
        expected = oracle_greedy(prompt, 8)
        stop_tok = expected[3]
        pre = PreprocessedRequest(
            token_ids=prompt,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=8, stop_token_ids=[stop_tok]),
        )
        tokens, finish = [], None
        async for raw in engine.generate(Context(pre.to_wire())):
            out = EngineOutput.from_wire(raw)
            tokens.extend(out.token_ids)
            if out.finish_reason:
                finish = out.finish_reason
        assert tokens == expected[: expected.index(stop_tok) + 1]
        assert finish is FinishReason.STOP
    finally:
        await engine.stop()


async def test_oversized_prompt_errors():
    engine = TpuEngine(engine_config(max_model_len=16), params=PARAMS)
    await engine.start()
    try:
        tokens, finish = await collect(engine, list(range(20)), max_tokens=4)
        assert tokens == []
        assert finish is FinishReason.ERROR
    finally:
        await engine.stop()


def test_block_allocator_prefix_lifecycle():
    events = []
    alloc = BlockAllocator(8, 4, on_event=events.append)
    blocks = alloc.allocate_many(3)
    assert alloc.num_free == 4  # 7 usable minus 3
    alloc.register(blocks[0], 111, parent_hash=None, token_ids=[1, 2, 3, 4])
    alloc.register(blocks[1], 222, parent_hash=111)
    assert [e.kind for e in events] == ["stored", "stored"]
    for b in blocks:
        alloc.release(b)
    # Registered blocks stay discoverable; unregistered one went to free list.
    assert alloc.num_free == 7
    matched = alloc.match_prefix([111, 222, 333])
    assert matched == blocks[:2]
    for b in matched:
        alloc.release(b)
    # Pressure evicts LRU reusable blocks and emits removal events.
    _ = alloc.allocate_many(7)
    kinds = [e.kind for e in events]
    assert kinds.count("removed") == 2


async def test_pipeline_depths_agree():
    """Pipelined dispatch (dispatches in flight feeding on device-resident
    tokens) must emit exactly the depth-1 stream (greedy), including at
    the max_model_len boundary."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    outs = []
    for depth in (1, 2, 4):
        engine = TpuEngine(
            engine_config(pipeline_depth=depth, max_model_len=24),
            params=PARAMS,
        )
        await engine.start()
        toks, finish = await collect(engine, prompt, max_tokens=64)
        await engine.stop()
        outs.append((toks, finish))
    assert outs[0] == outs[1] == outs[2]
    assert outs[0][0] == oracle_greedy(prompt, 16)
    # 24-token context limit: 8 prompt + 16 generated, finish=length.
    assert len(outs[0][0]) == 16 and outs[0][1] is FinishReason.LENGTH


async def test_chunked_prefill_matches_oracle():
    """A prompt longer than the token budget is fed in chunks; the result
    must be bit-identical to the unchunked computation."""
    prompt = list(range(1, 41))  # 40 tokens, budget 16 -> 3 chunks
    engine = TpuEngine(
        engine_config(unified_token_budget=16, unified_prefill_quantum=8,
                      num_blocks=64),
        params=PARAMS,
    )
    await engine.start()
    try:
        toks, finish = await collect(engine, prompt, max_tokens=6)
        assert toks == oracle_greedy(prompt, 6)
        assert finish is FinishReason.LENGTH
    finally:
        await engine.stop()


async def test_long_prefill_interleaves_with_short_requests():
    """A long prompt must NOT freeze token streaming for others: a short
    request already decoding finishes its whole generation before the
    long prompt's first token arrives — decode lanes fill every unified
    dispatch first and the prefill quantum bounds how much of the budget
    the long prompt can take per step."""
    events = []
    first_token = asyncio.Event()

    async def run(engine, name, prompt, max_tokens):
        async for raw in engine.generate(
            Context(
                PreprocessedRequest(
                    token_ids=prompt,
                    sampling=SamplingOptions(temperature=0.0),
                    stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
                ).to_wire()
            )
        ):
            out = EngineOutput.from_wire(raw)
            for _ in out.token_ids:
                events.append(name)
                first_token.set()

    engine = TpuEngine(
        engine_config(
            num_blocks=80, max_model_len=256, prefill_batch=2,
            unified_token_budget=32, unified_prefill_quantum=16,
        ),
        params=PARAMS,
    )
    await engine.start()
    try:
        long_p = list(range(1, 101))  # 100 tokens >> the 32-token budget
        short_p = [2, 7, 1]
        short_task = asyncio.create_task(run(engine, "short", short_p, 8))
        await first_token.wait()  # short is decoding before long arrives
        await asyncio.gather(
            run(engine, "long", long_p, 4),
            short_task,
        )
        first_long = events.index("long")
        short_done = len(events) - 1 - events[::-1].index("short")
        assert short_done < first_long, events
    finally:
        await engine.stop()


def test_context_limit_seq_excluded_from_decode_batch():
    """Regression: a sequence speculatively at the context limit (cap
    exhausted, chunks still in flight — sched_len = max_model_len + 1)
    must be excluded from decode batches. Growing its block table would
    overflow the [B, max_blocks_per_seq] buffer in _issue_decode and kill
    the engine thread, failing every request. Reachable on real hardware
    whenever one sequence hits the limit while a shorter one keeps
    decoding (chunks retire too fast on CPU to hit it end-to-end)."""
    from dynamo_tpu.engine.scheduler import Scheduler
    from dynamo_tpu.engine.sequence import Sequence

    cfg = engine_config(max_model_len=12, num_blocks=16)  # bs=4 → 3 blk/seq
    sched = Scheduler(cfg, BlockAllocator(cfg.num_blocks, cfg.block_size))

    noop = lambda tok, reason: None  # noqa: E731
    capped = Sequence(
        "capped", list(range(7)), SamplingOptions(), StopConditions(), noop
    )
    short = Sequence(
        "short", [1, 2, 3], SamplingOptions(), StopConditions(), noop
    )
    assert sched.admit(capped) and sched.admit(short)
    # Simulate in-flight fused chunks having advanced past the cap.
    capped.inflight_chunks = 2
    capped.sched_len = cfg.max_model_len + 1

    batch = sched.decode_batch(lookahead=4)
    assert capped not in batch and short in batch
    assert len(capped.block_ids) <= cfg.max_blocks_per_seq


async def test_moe_model_engine_matches_oracle():
    """Mixtral-style MoE model family through the full engine: routed
    expert MLPs in every layer, greedy continuation identical to the
    no-cache oracle forward."""
    moe_cfg = ModelConfig.tiny_moe_test()
    moe_params = llama.init_params(jax.random.PRNGKey(3), moe_cfg, dtype=jnp.float32)
    engine = TpuEngine(engine_config(model=moe_cfg), params=moe_params)
    await engine.start()
    try:
        prompt = [4, 11, 7, 2, 19, 5]
        tokens, finish = await collect(engine, prompt, max_tokens=8)
        assert tokens == reference_greedy(
            moe_cfg, moe_params, prompt, 8, length=128
        )
        assert finish is FinishReason.LENGTH
    finally:
        await engine.stop()


def test_block_lifecycle_typestate_violations_are_loud():
    """Illegal lifecycle transitions raise BlockStateError instead of
    silently corrupting the pool (SURVEY §5 race discipline — the Python
    answer to the reference's typestate blocks)."""
    from dynamo_tpu.engine.kv_cache import BlockState, BlockStateError

    alloc = BlockAllocator(8, 4)
    b = alloc.allocate()
    assert alloc.state(b) is BlockState.ACTIVE

    alloc.register(b, sequence_hash=111)
    assert alloc.state(b) is BlockState.REGISTERED

    alloc.release(b)
    assert alloc.state(b) is BlockState.REUSABLE
    with pytest.raises(BlockStateError, match="release"):
        alloc.release(b)  # double free
    with pytest.raises(BlockStateError, match="retain"):
        alloc.retain(b)  # retain without ownership (must go via match)

    [b2] = alloc.match_prefix([111])
    assert b2 == b and alloc.state(b) is BlockState.REGISTERED
    alloc.release(b)

    free_block = alloc.allocate()
    alloc.release(free_block)
    assert alloc.state(free_block) is BlockState.FREE
    with pytest.raises(BlockStateError, match="register"):
        alloc.register(free_block, sequence_hash=222)  # not allocated
    with pytest.raises(BlockStateError, match="retain"):
        alloc.retain(0)  # the trash block is never a legal target


def test_rope_scaling_llama3_formula(tmp_path):
    """Llama-3.1 frequency-dependent rope scaling: high-frequency bands
    untouched, low-frequency divided by `factor`, smooth ramp between —
    validated against an independent numpy rendering of the published
    formula, plus HF config parsing."""
    import json
    import math

    from dynamo_tpu.ops.rope import RopeScaling, _scaled_freqs, apply_rope

    s = RopeScaling(
        factor=8.0, low_freq_factor=1.0, high_freq_factor=4.0,
        original_max_position=8192,
    )
    half = 64
    freqs = np.exp(-np.log(500000.0) * (np.arange(half) / half)).astype(
        np.float32
    )
    got = np.asarray(_scaled_freqs(jnp.asarray(freqs), s))

    # Independent reference implementation.
    want = freqs.copy()
    for i, f in enumerate(freqs):
        wl = 2 * math.pi / f
        if wl < 8192 / 4.0:
            pass  # high-frequency: unchanged
        elif wl > 8192 / 1.0:
            want[i] = f / 8.0
        else:
            sm = (8192 / wl - 1.0) / (4.0 - 1.0)
            want[i] = (1 - sm) * f / 8.0 + sm * f
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == freqs[0]          # fastest component untouched
    assert got[-1] == freqs[-1] / 8.0  # slowest fully stretched

    # scaling=None keeps the original rotation bit-for-bit.
    x = jnp.asarray(np.random.default_rng(0).standard_normal((5, 2, 128)),
                    jnp.float32)
    pos = jnp.arange(5)
    np.testing.assert_array_equal(
        np.asarray(apply_rope(x, pos, 500000.0)),
        np.asarray(apply_rope(x, pos, 500000.0, None)),
    )
    # Scaled rotation differs at large positions (the long-context regime).
    far = jnp.arange(20000, 20005)
    a = np.asarray(apply_rope(x, far, 500000.0))
    b = np.asarray(apply_rope(x, far, 500000.0, s))
    assert np.abs(a - b).max() > 1e-3

    # HF config parsing end-to-end.
    cfg_json = {
        "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "rope_theta": 500000.0,
        "max_position_embeddings": 131072,
        "rope_scaling": {
            "factor": 32.0, "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 8192,
            "rope_type": "llama3",
        },
    }
    (tmp_path / "config.json").write_text(json.dumps(cfg_json))
    parsed = ModelConfig.from_hf(str(tmp_path))
    assert parsed.rope_scaling == RopeScaling(
        factor=32.0, low_freq_factor=1.0, high_freq_factor=4.0,
        original_max_position=8192,
    )
    assert ModelConfig.llama31_8b().rope_scaling.factor == 8.0


# -- sampling extras: seed / penalties / logprobs ----------

async def collect_full(engine, prompt, max_tokens=8, sampling=None,
                       logprobs=None):
    """collect() variant returning (tokens, logprob_entries, finish)."""
    pre = PreprocessedRequest(
        token_ids=prompt,
        sampling=sampling or SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        logprobs=logprobs,
    )
    tokens, entries, finish = [], [], None
    async for raw in engine.generate(Context(pre.to_wire())):
        out = EngineOutput.from_wire(raw)
        tokens.extend(out.token_ids)
        if out.logprobs:
            entries.extend(out.logprobs)
        if out.finish_reason:
            finish = out.finish_reason
    return tokens, entries, finish


async def test_seeded_sampling_is_deterministic_across_batching():
    """A seeded request reproduces its tokens regardless of co-scheduled
    traffic or which engine step picked it up (the OpenAI `seed`
    contract)."""
    engine = TpuEngine(engine_config(), params=PARAMS)
    await engine.start()
    try:
        prompt = [3, 1, 4, 1, 5]
        seeded = SamplingOptions(temperature=1.0, seed=42)
        # Run 1: alone.
        t1, _, _ = await collect_full(engine, prompt, 12, sampling=seeded)
        # Run 2: batched with unseeded noise traffic.
        results = await asyncio.gather(
            collect_full(engine, prompt, 12, sampling=seeded),
            collect(engine, [2, 7, 1, 8], max_tokens=12),
            collect(engine, [9, 9, 8], max_tokens=12),
        )
        t2 = results[0][0]
        assert t1 == t2, f"seeded run diverged: {t1} vs {t2}"
        # A different seed gives a different stream (overwhelmingly).
        t3, _, _ = await collect_full(
            engine, prompt, 12,
            sampling=SamplingOptions(temperature=1.0, seed=7),
        )
        assert t3 != t1
    finally:
        await engine.stop()


async def test_frequency_penalty_discourages_repeats():
    engine = TpuEngine(engine_config(), params=PARAMS)
    await engine.start()
    try:
        prompt = [1, 5, 9, 2, 7]
        plain, _, _ = await collect_full(engine, prompt, 16)
        pen, _, _ = await collect_full(
            engine, prompt, 16,
            sampling=SamplingOptions(
                temperature=0.0, frequency_penalty=8.0,
            ),
        )
        assert plain == oracle_greedy(prompt, 16)  # full path == plain greedy
        assert pen != plain
        assert len(set(pen)) > len(set(plain)), (
            f"penalty should widen the token set: {pen} vs {plain}"
        )
    finally:
        await engine.stop()


async def test_logprobs_payload_shape_and_values():
    engine = TpuEngine(engine_config(), params=PARAMS)
    await engine.start()
    try:
        prompt = [1, 5, 9, 2, 7]
        tokens, entries, _ = await collect_full(
            engine, prompt, 6, logprobs=3
        )
        assert tokens == oracle_greedy(prompt, 6)
        assert len(entries) == len(tokens)
        for tok, e in zip(tokens, entries):
            assert e["id"] == tok
            assert e["logprob"] <= 0.0
            assert len(e["top"]) == 3
            lps = [lp for _, lp in e["top"]]
            assert lps == sorted(lps, reverse=True)
            # Greedy: the chosen token IS the top-1 alternative.
            assert e["top"][0][0] == tok
            assert abs(e["top"][0][1] - e["logprob"]) < 1e-5
    finally:
        await engine.stop()


async def test_sampling_extras_rejections():
    # Penalties/logprobs are incompatible with speculative decoding.
    engine = TpuEngine(engine_config(speculative_k=2), params=PARAMS)
    await engine.start()
    try:
        with pytest.raises(ValueError, match="speculative"):
            await collect_full(
                engine, [1, 2, 3], 4,
                sampling=SamplingOptions(presence_penalty=1.0),
            )
        with pytest.raises(ValueError, match="exceeds"):
            await collect_full(engine, [1, 2, 3], 4, logprobs=99)
    finally:
        await engine.stop()


async def test_qwen3_qk_norm_engine_matches_oracle():
    """Qwen3-style per-head q/k RMSNorm (qk_norm): the paged engine must
    match the no-cache oracle, and the norm must actually change the
    function (same weights minus the norm gains gives different logits)."""
    import dataclasses

    q3cfg = dataclasses.replace(
        CFG, name="tiny-qwen3", qk_norm=True, qkv_bias=False
    )
    params = llama.init_params(jax.random.PRNGKey(4), q3cfg, dtype=jnp.float32)
    assert "ln_q_head" in params["layers"][0]

    prompt = [1, 5, 9, 2, 7]
    engine = TpuEngine(engine_config(model=q3cfg), params=params)
    await engine.start()
    try:
        tokens, _ = await collect(engine, prompt, max_tokens=8)
        assert tokens == reference_greedy(q3cfg, params, prompt, 8, length=128)
    finally:
        await engine.stop()

    # The norm is live: zeroing its gains changes the logits.
    import numpy as np

    zeroed = jax.tree.map(lambda x: x, params)
    zeroed["layers"][0] = dict(zeroed["layers"][0])
    zeroed["layers"][0]["ln_q_head"] = jnp.zeros_like(
        params["layers"][0]["ln_q_head"]
    )
    a = np.asarray(llama.reference_forward(q3cfg, params, jnp.asarray(prompt)))
    b = np.asarray(llama.reference_forward(q3cfg, zeroed, jnp.asarray(prompt)))
    assert np.abs(a - b).max() > 1e-3


async def test_sliding_window_engine_matches_oracle():
    """Mistral-style sliding-window attention: the paged engine (window
    masking in every attention path) must match the no-cache oracle with
    the same window, and the window must be live (different tokens than
    the full-attention model once the context exceeds it)."""
    import dataclasses

    import numpy as np

    wcfg = dataclasses.replace(CFG, name="tiny-swa", sliding_window=8)
    params = llama.init_params(jax.random.PRNGKey(6), wcfg, dtype=jnp.float32)
    prompt = [int(t) for t in
              np.random.default_rng(3).integers(1, CFG.vocab_size, 24)]

    engine = TpuEngine(engine_config(model=wcfg), params=params)
    await engine.start()
    try:
        tokens, _ = await collect(engine, prompt, max_tokens=10)
        assert tokens == reference_greedy(wcfg, params, prompt, 10, length=128)
    finally:
        await engine.stop()

    # Window is live: the full-attention model diverges (ctx 24 >> 8).
    full = dataclasses.replace(wcfg, sliding_window=0)
    assert tokens != reference_greedy(full, params, prompt, 10, length=128)


async def test_rolling_buffer_eviction_plateaus_and_is_exact():
    """Rolling-buffer KV eviction: a fully-windowed
    model's long generation must (a) hold only O(window/bs) live blocks —
    behind-window pages are released as decoding advances — and (b)
    produce tokens identical to the same engine with eviction disabled."""
    import dataclasses

    wcfg = dataclasses.replace(CFG, name="tiny-swa", sliding_window=8)
    params = llama.init_params(jax.random.PRNGKey(6), wcfg, dtype=jnp.float32)
    prompt = [int(t) for t in
              np.random.default_rng(4).integers(1, CFG.vocab_size, 20)]
    OUT = 60  # final length 80 >> window 8
    ecfg = engine_config(model=wcfg, max_model_len=128)

    async def run(evict: bool):
        engine = TpuEngine(ecfg, params=params)
        await engine.start()
        if not evict:
            engine.scheduler.evict_behind_window = lambda *a, **k: 0
        peaks = []
        pre = PreprocessedRequest(
            token_ids=prompt,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=OUT, ignore_eos=True),
        )
        toks = []
        async for raw in engine.generate(Context(pre.to_wire())):
            toks.extend(EngineOutput.from_wire(raw).token_ids)
            peaks.append(engine.scheduler.metrics()["kv_active_blocks"])
        await engine.stop()
        return toks, peaks

    toks_off, peaks_off = await run(evict=False)
    toks_on, peaks_on = await run(evict=True)
    assert toks_on == toks_off, "eviction changed generated tokens"
    # Without eviction the live block count grows with the context; with
    # it, the tail of the run must sit at O(window/bs): window 8 / bs 4 =
    # 2 in-window pages + the partially-filled growth page + pipeline
    # slack (dispatches in flight keep sched_len ahead by pipeline_depth).
    bs = ecfg.block_size
    bound = (
        (wcfg.sliding_window + bs - 1) // bs + 1
        + ecfg.pipeline_depth // bs + 1
    )
    assert max(peaks_off) >= (len(prompt) + OUT - 8) // bs  # grew ~O(ctx)
    assert max(peaks_on[len(peaks_on) // 2 :]) <= bound, (
        peaks_on, bound,
    )
