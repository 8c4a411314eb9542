"""JAX engine tests: paged-attention correctness against a no-cache oracle,
continuous batching, prefix caching, stop handling.

All on the CPU backend with fp32 so greedy decoding is exactly reproducible.
"""

import asyncio
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine, _drain_handoff
from dynamo_tpu.engine.kv_cache import BlockAllocator
from dynamo_tpu.llm.protocols.common import (
    EngineOutput,
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.mocker.engine import MockerConfig, MockerEngine
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.utils.deadline import Deadline
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


# -- the hand-off to the frontend's loop (engine.py `_flush_outbox`) --------
# A retired dispatch's frames cross from the engine's thread to the loop in
# ONE `call_soon_threadsafe`; whatever else emits in a pass leaves at its
# end; a request's frames keep their order.


class SpyLoop:
    """The engine's view of the loop, every `call_soon_threadsafe` kept."""

    def __init__(self, loop):
        self._real = loop
        self.calls: list[tuple] = []
        self.hold = False  # keep the calls, run none (a loop that is busy)

    def call_soon_threadsafe(self, fn, *args):
        self.calls.append((fn, args))
        if not self.hold:
            return self._real.call_soon_threadsafe(fn, *args)

    def __getattr__(self, name):
        return getattr(self._real, name)

    def handoffs(self) -> list[list]:
        return [args[0] for fn, args in self.calls if fn is _drain_handoff]

    def frames(self) -> dict[int, list[tuple]]:
        """Each stream's frames as handed over: id(queue) -> [(the
        hand-off's index, (token, finish, lp))], in order."""
        by_stream: dict[int, list[tuple]] = {}
        for n, batch in enumerate(self.handoffs()):
            for out_q, item in batch:
                by_stream.setdefault(id(out_q), []).append((n, item))
        return by_stream


class HandDriven(MockerEngine):
    """The engine without its thread: the test makes the passes of
    `_engine_loop` itself, one `one_pass()` at a time."""

    def _engine_loop(self) -> None:
        return

    async def one_pass(self) -> bool:
        # Off the loop's thread, as the engine's is: a hand-off may wait
        # for the loop to have written the one before it.
        return await asyncio.to_thread(self._pass)


async def spied(engine) -> SpyLoop:
    await engine.start()
    engine._loop = spy = SpyLoop(engine._loop)
    return spy


def watch_retires(engine, spy) -> list[tuple[int, int]]:
    """(loop calls made, frames handed over) by each retire, as they run."""
    seen: list[tuple[int, int]] = []
    inner = engine._process_unified_chunk

    def retire(record):
        calls, items = len(spy.calls), engine._handoff_items
        inner(record)
        seen.append((len(spy.calls) - calls, engine._handoff_items - items))

    engine._process_unified_chunk = retire
    return seen


def mock_request(n=12, max_tokens=6, deadline=None, stop_token_ids=()):
    return PreprocessedRequest(
        token_ids=list(range(1, n + 1)),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(
            max_tokens=max_tokens, stop_token_ids=list(stop_token_ids),
            ignore_eos=not stop_token_ids,
        ),
        deadline=deadline,
    )


async def frames_of(engine, pre=None, ctx=None) -> list[dict]:
    ctx = ctx or Context(pre.to_wire())
    return [raw async for raw in engine.generate(ctx)]


def assert_tokens_then_finish(frames, finish, n_tokens=None):
    """One token a frame, then exactly one finish frame, the last."""
    *toks, last = frames
    assert all(
        len(f["token_ids"]) == 1 and f["finish_reason"] is None for f in toks
    )
    assert [f["cum_tokens"] for f in toks] == list(range(1, len(toks) + 1))
    assert last["token_ids"] == [] and last["finish_reason"] == finish.value
    if n_tokens is not None:
        assert len(toks) == n_tokens


async def test_a_retire_hands_its_tokens_over_in_one_wakeup():
    """Four streams decode side by side on the file's float32 model: a
    retire that delivers four tokens to four streams makes ONE
    `call_soon_threadsafe`, no call carries a single `put_nowait`, and the
    two counters and the flight records count what crossed."""
    engine = TpuEngine(engine_config(), params=PARAMS)
    await engine.start()
    try:
        await engine.warmup()  # no compile parts the four streams
        engine._loop = spy = SpyLoop(engine._loop)
        retires = watch_retires(engine, spy)
        prompts = [[1, 5, 9, 2], [3, 4, 6], [7, 8], [2, 2, 9, 1, 5]]
        # All four are queued before the engine takes the first: one that a
        # busy machine sent a dispatch late would decode in the pipeline's
        # other phase, and no retire would carry all four.
        drain, queued = engine._drain_submissions, engine._submit_q
        all_four = []

        def drain_once_all_four_wait():
            if all_four or queued.qsize() >= len(prompts):
                all_four.append(True)
                drain()

        engine._drain_submissions = drain_once_all_four_wait
        outs = await asyncio.gather(
            *[collect(engine, p, max_tokens=8) for p in prompts]
        )
        for p, (tokens, finish) in zip(prompts, outs):
            assert tokens == oracle_greedy(p, 8)
            assert finish is FinishReason.LENGTH
        assert all(fn is _drain_handoff for fn, _ in spy.calls)
        assert retires and all(
            calls == (1 if items else 0) for calls, items in retires
        )
        widest = max(spy.handoffs(), key=len)
        tokens_in = [q for q, (tok, _f, _lp) in widest if tok is not None]
        assert len(tokens_in) == len(set(map(id, tokens_in))) == 4
        # 4 x (8 tokens + a finish frame), in far fewer wake-ups.
        ready = engine.readiness()
        assert ready["engine_handoff_items_total"] == 36
        assert ready["engine_handoff_wakeups_total"] == len(spy.handoffs())
        assert len(spy.handoffs()) <= len(retires)
        assert sum(r["handoff_items"] for r in engine.debug_steps()) <= 36
        assert max(r["handoff_items"] for r in engine.debug_steps()) >= 4
    finally:
        await engine.stop()


async def test_a_steps_flight_record_counts_the_kernels_folds():
    """``attn_short_folds`` / ``attn_long_folds`` ride a step's flight
    record as the runner counted them for THAT dispatch, and their sums
    are ``attn_folds_total{tile=...}`` on ``readiness()`` (and so on
    ``/metrics``): decode rows walk short folds only, a prompt's quantum
    long ones. The file's model is served by the XLA twin, which counts
    nothing; the test hands the runner a plan, two layers of 16-key folds."""
    from collections import Counter
    from functools import partial

    from dynamo_tpu.llm.metrics import Metrics
    from dynamo_tpu.ops.pallas.ragged_attention import fold_counts

    engine = TpuEngine(engine_config(), params=PARAMS)
    assert engine.readiness()['attn_folds_total{tile="short"}'] == 0
    await engine.start()
    engine.runner._fold_plan = dict(
        count=partial(fold_counts, long_rows=16, fold_keys=16),
        layers=Counter({0: 2}),
    )
    try:
        for prompt in (list(range(1, 40)), [3, 4, 6]):
            await collect(engine, prompt, max_tokens=8)
        steps = [r for r in engine.debug_steps() if "dispatch_ms" in r]
        decode_only = [r for r in steps if not r["prefill_tokens"]]
        assert decode_only and all(
            r["attn_long_folds"] == 0 < r["attn_short_folds"]
            for r in decode_only
        )
        # 39 rows from 0: tiles of 16 rows over 1, 2 and 3 folds, two layers
        first = next(r for r in steps if r["prefill_tokens"] >= 39)
        assert first["attn_long_folds"] == 2 * (1 + 2 + 3)
        ready = engine.readiness()
        for tile in ("short", "long"):
            total = ready[f'attn_folds_total{{tile="{tile}"}}']
            assert total == sum(r[f"attn_{tile}_folds"] for r in steps) > 0
    finally:
        await engine.stop()
    # a name that carries labels: its family is typed once on /metrics
    m = Metrics()
    for tile in ("short", "long"):
        key = f'attn_folds_total{{tile="{tile}"}}'
        m.set_gauge(key, ready[key])
    text = m.render()
    assert text.count("_attn_folds_total counter") == 1
    assert f'_attn_folds_total{{tile="long"}} {ready[key]}\n' in text


@pytest.mark.parametrize("ending", ["stop", "max_model_len", "deadline"])
async def test_a_token_and_its_finish_frame_arrive_in_order(ending):
    """What ends a request inside `_deliver` emits the token and then the
    finish in one pass: both leave in the same hand-off, the token first,
    and the consumer reads tokens, then the one finish frame."""
    cfg = engine_config(max_model_len=24 if ending == "max_model_len" else 128)
    sim = MockerConfig(decode_time_per_step_us=2000.0)
    if ending == "stop":
        # The mocker's greedy stream is a pure function of the prompt:
        # stop on its third token.
        probe = MockerEngine(cfg, sim)
        await probe.start()
        try:
            third = (await frames_of(probe, mock_request()))[2]["token_ids"]
        finally:
            await probe.stop()
        pre, finish, n = mock_request(
            max_tokens=32, stop_token_ids=third
        ), FinishReason.STOP, 3
    elif ending == "max_model_len":
        pre, finish, n = (
            mock_request(n=20, max_tokens=64), FinishReason.LENGTH, 4
        )
    else:
        pre, finish, n = mock_request(
            max_tokens=4096, deadline=Deadline.after(0.05)
        ), FinishReason.DEADLINE, None
    engine = MockerEngine(cfg, sim)
    spy = await spied(engine)
    try:
        frames = await asyncio.wait_for(frames_of(engine, pre), 30.0)
        assert_tokens_then_finish(frames, finish, n)
        (handed,) = spy.frames().values()
        (at_tok, (tok, f0, _)), (at_fin, (none, f1, _)) = handed[-2:]
        assert at_tok == at_fin and tok is not None and f0 is None
        assert none is None and f1 is finish
        assert sum(f is not None for _, (_t, f, _lp) in handed) == 1
    finally:
        await engine.stop()


async def test_an_abort_ends_a_stream_behind_its_tokens():
    """A consumer that goes away: the engine's CANCELLED frame is the last
    thing handed to that stream, behind every token, though the abort is
    drained in the same pass as a retire that holds its next token."""
    engine = MockerEngine(
        engine_config(), MockerConfig(decode_time_per_step_us=2000.0)
    )
    spy = await spied(engine)
    try:
        ctx = Context(mock_request(max_tokens=4096).to_wire())
        stream = engine.generate(ctx)
        got = [await stream.__anext__() for _ in range(3)]
        assert [f["token_ids"] != [] for f in got] == [True] * 3
        await stream.aclose()  # `_stream`'s finally submits the abort
        for _ in range(200):
            if engine.drained:
                break
            await asyncio.sleep(0.01)
        assert engine.drained
        (handed,) = spy.frames().values()
        *toks, (_, last) = handed
        assert last == (None, FinishReason.CANCELLED, None)
        assert len(toks) >= 3 and all(
            tok is not None and f is None for _, (tok, f, _lp) in toks
        )
    finally:
        await engine.stop()


@pytest.mark.parametrize("ending", ["expiry", "shed", "abort"])
async def test_a_finish_outside_a_retire_leaves_within_its_pass(ending):
    """Admission is held (no warmup ran), so nothing is ever dispatched
    and no retire flushes: a finish from the waiting queue's expiry, from
    a shed or from the abort of a waiting request still reaches its stream
    in the pass that emitted it."""
    engine = HandDriven(
        engine_config(warmup_gate="hold", max_waiting=1), MockerConfig()
    )
    spy = await spied(engine)
    try:
        deadline = Deadline.after(0.02) if ending == "expiry" else None
        ctx = Context(mock_request(deadline=deadline).to_wire())
        victim = asyncio.ensure_future(frames_of(engine, ctx=ctx))
        await asyncio.sleep(0.03)  # submitted; past its deadline if it has one
        await engine.one_pass()
        finish = FinishReason.DEADLINE
        if ending != "expiry":
            assert spy.calls == [] and len(engine.scheduler.waiting) == 1
        if ending == "shed":
            # A second waiter over max_waiting=1 sheds the oldest.
            other = asyncio.ensure_future(frames_of(engine, mock_request()))
            await asyncio.sleep(0)
            await engine.one_pass()
            finish = FinishReason.SHED
        elif ending == "abort":
            ctx.stop_generating()
            engine._submit_q.put(("abort", engine.scheduler.waiting[0]))
            await engine.one_pass()
            finish = FinishReason.CANCELLED
        (batch,) = spy.handoffs()
        assert [item for _q, item in batch] == [(None, finish, None)]
        assert engine.flight.total_steps == 0
        frames = await asyncio.wait_for(victim, 5.0)
        assert [f["finish_reason"] for f in frames] == [finish.value]
        if ending == "shed":
            other.cancel()
    finally:
        await engine.stop()


async def test_a_dead_engine_fails_running_waiting_and_queued_requests():
    """The step raises with two requests running, two waiting and one
    still in the submission queue: each stream ends in an ERROR frame,
    all five in the death path's one hand-off."""
    engine = MockerEngine(
        engine_config(max_num_seqs=2),
        MockerConfig(decode_time_per_step_us=1000.0),
    )
    spy = await spied(engine)
    reached, go = threading.Event(), threading.Event()
    inner, calls = engine.runner.unified_step, []

    def failing_step(*a, **kw):
        calls.append(1)
        if len(calls) < 4:
            return inner(*a, **kw)
        reached.set()
        go.wait(10.0)
        raise RuntimeError("boom")

    engine.runner.unified_step = failing_step
    try:
        early = [
            asyncio.ensure_future(
                frames_of(engine, mock_request(max_tokens=4096))
            )
            for _ in range(4)
        ]
        assert await asyncio.to_thread(reached.wait, 10.0)
        assert len(engine.scheduler.running) == 2
        assert len(engine.scheduler.waiting) == 2
        late = asyncio.ensure_future(frames_of(engine, mock_request()))
        while engine._submit_q.empty():
            await asyncio.sleep(0)
        go.set()
        outs = await asyncio.wait_for(asyncio.gather(*early, late), 10.0)
        assert [o[-1]["finish_reason"] for o in outs] == ["error"] * 5
        assert [len(o) for o in outs[2:]] == [1, 1, 1]  # never ran
        assert isinstance(engine._dead, RuntimeError)
        errors = [
            item for _q, item in spy.handoffs()[-1]
            if item == (None, FinishReason.ERROR, None)
        ]
        assert len(errors) == 5
    finally:
        await engine.stop()


async def test_a_step_leaves_when_the_loop_has_written_the_one_before():
    """One step is in the channel at a time: with the loop still busy on
    the batch it was handed, the engine's thread waits with the next one
    (and counts the wait), then hands it over behind it, in order."""
    engine = HandDriven(engine_config(), MockerConfig())
    spy = await spied(engine)
    try:
        out_q: asyncio.Queue = asyncio.Queue()
        emit = engine._emitter(out_q)
        spy.hold = True
        emit(1, None)
        engine._flush_outbox()  # nothing before it: leaves at once
        emit(2, None)
        emit(None, FinishReason.LENGTH)
        second = asyncio.ensure_future(
            asyncio.to_thread(engine._flush_outbox)
        )
        await asyncio.sleep(0.05)
        assert len(spy.calls) == 1 and not second.done()
        spy.hold = False
        (fn, args), = spy.calls
        fn(*args)  # the loop gets to the first batch at last
        await asyncio.wait_for(second, 5.0)
        assert [len(b) for b in spy.handoffs()] == [1, 2]
        await asyncio.sleep(0)
        assert [out_q.get_nowait() for _ in range(3)] == [
            (1, None, None), (2, None, None),
            (None, FinishReason.LENGTH, None),
        ]
        ready = engine.readiness()
        assert ready["engine_handoff_wait_seconds_total"] >= 0.04
        assert ready["engine_handoff_wakeups_total"] == 2
    finally:
        await engine.stop()


@pytest.mark.parametrize("family", ["block_diffusion", "draft_verify"])
async def test_a_lane_that_yields_three_tokens_yields_three_frames(family):
    """A block-diffusion lane that commits three tokens in a pass and a
    draft-verify lane whose span accepts three: three items in the one
    hand-off, three frames of one token each for the consumer."""
    if family == "block_diffusion":
        model = ModelConfig.tiny_sdar_test().scaled(confidence_threshold=0.02)
        engine = TpuEngine(engine_config(
            model=model, block_size=8, seed=3, unified_token_budget=32,
            unified_prefill_quantum=16,
        ))
    else:
        model = CFG.scaled(num_layers=0)  # greedy decoding enters a cycle
        engine = TpuEngine(
            engine_config(model=model, num_blocks=128, speculative_k=3),
            params=llama.init_params(
                jax.random.PRNGKey(0), model, dtype=jnp.float32
            ),
        )
    spy = await spied(engine)
    try:
        pre = PreprocessedRequest(
            token_ids=[1, 5, 9, 2, 7],
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=40, ignore_eos=True),
        )
        frames = await frames_of(engine, pre)
        assert_tokens_then_finish(frames, FinishReason.LENGTH, 40)
        most = max(
            sum(tok is not None for _q, (tok, _f, _lp) in batch)
            for batch in spy.handoffs()
        )
        assert most >= 3, most
    finally:
        await engine.stop()
