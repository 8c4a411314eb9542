"""Prompt-lookup speculative decoding (engine/runner.py unified_spec_fn):
greedy output must be EXACTLY the sequential greedy output (same model,
same cache — acceptance only keeps drafts the verify pass would have
produced anyway), sampled lanes must degrade to plain decode, and
acceptance must actually exceed 1 token/step on repetitive text.

The reference has no native engine to put this in (it delegates decode to
vLLM, which ships the same technique as "prompt lookup / n-gram
speculation") — here draft and verify run inside the one ragged step:
drafts come from a device-resident history buffer, so no host round trip
per step.
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

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

pytestmark = pytest.mark.anyio

CFG = ModelConfig.tiny_test()
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)


def _cfg(**kw) -> EngineConfig:
    defaults = dict(
        model=CFG,
        dtype="float32",
        block_size=4,
        num_blocks=128,
        max_num_seqs=4,
        max_model_len=128,
        speculative_k=3,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


async def _generate(engine, prompt, max_tokens=24, temperature=0.0, seed=None):
    pre = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(temperature=temperature, seed=seed),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )
    tokens = []
    async for raw in engine.generate(Context(pre.to_wire())):
        tokens.extend(EngineOutput.from_wire(raw).token_ids)
    return tokens


async def _run(cfg, prompt, **kw):
    engine = TpuEngine(cfg, params=PARAMS)
    await engine.start()
    try:
        return await _generate(engine, prompt, **kw), engine
    finally:
        await engine.stop()


async def test_speculative_greedy_equals_sequential():
    """The headline invariant: spec on/off produce IDENTICAL greedy
    tokens. (A deep random model rarely accepts drafts — full-context
    attention makes repeated bigrams continue differently — so this is
    purely the correctness check; acceptance is proven below.)"""
    prompt = [1, 5, 9, 2, 7, 9, 2, 7]
    seq_tokens, _ = await _run(_cfg(speculative_k=0), prompt, max_tokens=32)
    spec_tokens, _ = await _run(_cfg(), prompt, max_tokens=32)
    assert spec_tokens == seq_tokens
    assert len(spec_tokens) == 32


async def test_speculative_accepts_on_cyclic_continuation():
    """Acceptance > 1 token/step where it must happen: a 0-layer model
    predicts from the last token alone, so greedy generation enters a
    cycle and prompt-lookup drafts are exactly what the verifier
    reproduces. Output must still equal the sequential rollout."""
    cfg0 = ModelConfig.tiny_test().scaled(num_layers=0)
    params0 = llama.init_params(jax.random.PRNGKey(0), cfg0, dtype=jnp.float32)

    async def run(spec_k):
        engine = TpuEngine(
            EngineConfig(
                model=cfg0, dtype="float32", block_size=4, num_blocks=128,
                max_num_seqs=2, max_model_len=128,
                speculative_k=spec_k,
            ),
            params=params0,
        )
        await engine.start()
        try:
            toks = await _generate(engine, [1, 5, 9], max_tokens=48)
        finally:
            await engine.stop()
        return toks, engine

    seq_tokens, _ = await run(0)
    spec_tokens, engine = await run(3)
    assert spec_tokens == seq_tokens
    assert engine.spec_tokens_per_step > 1.5, engine.spec_tokens_per_step


async def test_speculative_concurrent_lanes_match_oracle():
    engine = TpuEngine(_cfg(), params=PARAMS)
    await engine.start()
    try:
        prompts = [[3, 1, 4, 1, 5], [2, 7, 1, 8, 2, 7], [9, 9, 8, 2, 6]]
        results = await asyncio.gather(
            *[_generate(engine, p, max_tokens=16) for p in prompts]
        )
        for p, got in zip(prompts, results):
            assert got == reference_greedy(CFG, PARAMS, p, 16, length=128), p
    finally:
        await engine.stop()


async def test_speculative_sampled_lane_is_reproducible():
    """Non-greedy lanes accept zero drafts and sample from the same
    logits as plain decode. Chunk partitioning differs between the two
    modes (spec divides its step budget by K+1), so the sampling-key
    stream — and thus the exact tokens — legitimately differ from plain;
    the invariants are reproducibility under a fixed seed and a full-
    length stream."""
    prompt = [1, 5, 9, 2, 7]
    kw = dict(max_tokens=16, temperature=0.8, seed=7)
    a, _ = await _run(_cfg(seed=3), prompt, **kw)
    b, _ = await _run(_cfg(seed=3), prompt, **kw)
    assert a == b
    assert len(a) == 16
    plain, _ = await _run(_cfg(speculative_k=0, seed=3), prompt, **kw)
    assert len(plain) == 16  # same budget either mode


async def test_speculative_respects_stops_and_limits():
    cfg = _cfg(max_model_len=32)
    engine = TpuEngine(cfg, params=PARAMS)
    await engine.start()
    try:
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        pre = PreprocessedRequest(
            token_ids=prompt,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=64, ignore_eos=True),
        )
        tokens = []
        finish = None
        async for raw in engine.generate(Context(pre.to_wire())):
            out = EngineOutput.from_wire(raw)
            tokens.extend(out.token_ids)
            finish = out.finish_reason or finish
        # capped by context, never past it
        assert len(prompt) + len(tokens) <= cfg.max_model_len
        assert finish is not None
    finally:
        await engine.stop()


def test_speculative_config_validation():
    with pytest.raises(ValueError):
        EngineConfig(model=CFG, speculative_k=-1).validate()
    with pytest.raises(ValueError):
        EngineConfig(model=CFG, block_size=4, speculative_k=5).validate()


async def test_speculative_auto_gates_below_break_even_and_reprobes():
    """sampled lanes accept zero drafts (exactly 1.0
    delivered token/step < break-even 1.4), so the engine must disable
    speculation after a window, serve plain decode correctly, then
    re-probe after speculative_probe_steps plain steps."""
    cfg = _cfg(speculative_window=8, speculative_probe_steps=16)
    engine = TpuEngine(cfg, params=PARAMS)
    await engine.start()
    try:
        prompt = [1, 5, 9, 2]
        assert engine.spec_active
        await _generate(
            engine, prompt, max_tokens=16, temperature=1.0, seed=11
        )
        assert not engine.spec_active, (
            f"gate should disable at {engine.spec_tokens_per_step:.2f} "
            f"tok/step"
        )
        assert engine.spec_tokens_per_step < cfg.speculative_break_even

        # The plain fallback must still produce correct greedy output.
        gated_tokens = await _generate(engine, prompt, max_tokens=8)
        plain_tokens, _ = await _run(
            _cfg(speculative_k=0), prompt, max_tokens=8
        )
        assert gated_tokens == plain_tokens

        # Enough plain steps re-arm the probe. (The probe may measure the
        # greedy stream below break-even and disable AGAIN within the same
        # run — correct behavior — so assert the re-probe EVENT, not the
        # final gate state.)
        await _generate(engine, prompt, max_tokens=16)
        assert engine.spec_probe_count >= 1, (
            "probe should have re-enabled speculation at least once"
        )
    finally:
        await engine.stop()


async def test_spec_flight_records_and_metric_surfaces():
    """Unified spec observability (DT011-clean): accepting-draft
    dispatches leave kind="spec" flight records carrying the
    drafted/accepted split, and the cumulative twins reach the metrics
    callback, the readiness snapshot, and ForwardPassMetrics."""
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.mocker import MockerConfig, MockerEngine, det_next_token

    # Position-free deterministic chain on a tiny vocab: an 11-cycle, so
    # a chain prompt's bigrams repeat and prompt-lookup drafts verify
    # (built through the sim's own closed-form helper).
    vocab = 23
    prompt = [3]
    for _ in range(47):
        prompt.append(int(det_next_token(prompt[-1], 0, vocab, positional=False)))
    eng = MockerEngine(
        EngineConfig(
            model=ModelConfig.tiny_test(), num_blocks=128, max_num_seqs=2,
            max_model_len=256, speculative_k=4, unified_token_budget=64,
        ),
        MockerConfig(
            vocab_size=vocab, deterministic_tokens=True, det_positional=False
        ),
    )
    metrics: list[dict] = []
    eng._on_metrics = metrics.append
    await eng.start()
    try:
        toks = await _generate(eng, prompt, max_tokens=32)
        assert len(toks) == 32
        assert eng.spec_tokens_per_step > 1.5  # drafts actually accepted
        recs = [r for r in eng.debug_steps() if r.get("kind") == "spec"]
        assert recs, "no spec flight records"
        assert any(r["drafted"] > 0 for r in recs)
        assert any(r["accepted"] > 0 for r in recs)
        assert sum(r["drafted"] for r in recs) == eng._spec_drafted
        assert sum(r["accepted"] for r in recs) == eng._spec_accepted
        # All three metric surfaces carry the cumulative twins.
        m = metrics[-1]
        assert m["spec_drafted_tokens_total"] == eng._spec_drafted
        assert m["spec_accepted_tokens_total"] == eng._spec_accepted
        r = eng.readiness()
        assert r["spec_drafted_tokens_total"] == eng._spec_drafted
        assert r["spec_accepted_tokens_total"] == eng._spec_accepted
        fpm = ForwardPassMetrics.from_wire(m)
        assert fpm.spec_drafted_tokens_total == eng._spec_drafted
        assert fpm.spec_accepted_tokens_total == eng._spec_accepted
    finally:
        await eng.stop()


async def test_spec_reprobe_recovers_on_accepting_traffic():
    """Regression (review round 3): a re-probe must measure DRAFT-VERIFY
    dispatches, not plain dispatches already in flight when the gate
    flipped — counting those judged every probe at 1.0 tok/step and
    speculation could never re-enable. Drive sampled traffic to disable
    the gate, then accepting greedy chain traffic: the probe must
    recover (spec active again, drafts accepted)."""
    from dynamo_tpu.mocker import MockerConfig, MockerEngine, det_next_token

    vocab = 23
    eng = MockerEngine(
        EngineConfig(
            model=ModelConfig.tiny_test(), num_blocks=256, max_num_seqs=2,
            max_model_len=512, speculative_k=4, unified_token_budget=64,
            speculative_window=8, speculative_probe_window=2,
            speculative_probe_steps=8,
        ),
        MockerConfig(
            vocab_size=vocab, deterministic_tokens=True, det_positional=False
        ),
    )
    await eng.start()
    try:
        # Sampled traffic: accepts nothing → the gate disables.
        await _generate(
            eng, [1, 5, 9, 2], max_tokens=16, temperature=1.0, seed=3
        )
        assert not eng.spec_active
        # Accepting greedy chain traffic: the re-probe must measure real
        # draft-verify dispatches and re-commit to speculation.
        prompt = [3]
        for _ in range(47):
            prompt.append(
                int(det_next_token(prompt[-1], 0, vocab, positional=False))
            )
        await _generate(eng, prompt, max_tokens=96)
        assert eng.spec_probe_count >= 1
        assert eng._spec_drafted > 0, (
            "re-probe never issued a draft-verify dispatch — the probe "
            "window was judged on plain dispatches"
        )
        assert eng.spec_active, (
            f"speculation never recovered on accepting traffic "
            f"({eng.spec_tokens_per_step:.2f} tok/step measured)"
        )
    finally:
        await eng.stop()


async def test_spec_gate_is_free_when_losing_mocker_ab():
    """Narrow scope: once the gate has disabled
    speculation, plain decode must pay ~0% overhead — each RE-probe runs
    only speculative_probe_window spec steps (not a full measurement
    window), so the steady-state loss is probe_window/probe_steps. The
    mocker's unified_step never accepts drafts (1.0 tok/step, a
    guaranteed loss) and charges the verify width per step — the exact
    regime the gate must make free. A/B'd against a plain mocker engine
    on the same workload (the BENCH_SPEC_AB path, mocker mode)."""
    from dynamo_tpu.mocker import MockerConfig, MockerEngine

    def mocker_cfg(**kw):
        defaults = dict(
            model=ModelConfig.tiny_test(),
            dtype="float32",
            num_blocks=128,
            max_num_seqs=2,
            max_model_len=512,
        )
        defaults.update(kw)
        return EngineConfig(**defaults)

    window, probe_window, probe_steps = 8, 2, 32
    spec = MockerEngine(
        mocker_cfg(
            speculative_k=3,
            speculative_window=window,
            speculative_probe_window=probe_window,
            speculative_probe_steps=probe_steps,
        ),
        MockerConfig(seed=5),
    )
    plain = MockerEngine(mocker_cfg(), MockerConfig(seed=5))
    await spec.start()
    await plain.start()
    try:
        prompt = list(range(24))
        n_tokens = 360
        spec_toks = await _generate(spec, prompt, max_tokens=n_tokens)
        plain_toks = await _generate(plain, prompt, max_tokens=n_tokens)
        assert len(spec_toks) == len(plain_toks) == n_tokens
        # The gate disabled after the initial window and every re-probe
        # cost only probe_window steps: total losing (spec) work is
        # bounded by window + probes * probe_window — NOT window per
        # probe (the old ladder, which would be ~4x this bound here).
        assert not spec.spec_active
        assert spec.spec_probe_count >= 1, "re-probe never fired"
        budget = window + spec.spec_probe_count * probe_window
        assert spec._spec_steps <= budget + probe_window, (
            f"{spec._spec_steps} spec steps run; free-when-losing bound "
            f"is {budget}"
        )
        # Steady-state overhead ratio: losing steps over total steps —
        # must be single-digit percent, not the old ~window/probe_steps.
        overhead = spec._spec_steps / n_tokens
        assert overhead < 0.10, f"gated-off overhead {overhead:.1%}"
    finally:
        await spec.stop()
        await plain.stop()
